"""What the OLMoE cells' readers count from shapes: the FLOPs a token needs
with its 8 active experts, and the least time the chip could take for a
step's grouped matmuls. Keys are the published config.json's."""

from __future__ import annotations


def active_flops_per_token(config: dict, sequence_length: int) -> float:
    """Forward and backward FLOPs a token needs, no recomputation, counted
    on the experts it is routed to: six per matmul parameter it touches
    (attention projections, router, num_experts_per_tok gated experts of
    three matrices, the head; the embedding is a gather) and causal
    attention's scores and values."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    per_layer = (4 * d * d + d * config["num_experts"]
                 + config["num_experts_per_tok"] * 3 * d
                 * config["intermediate_size"])
    matmul_params = layers * per_layer + config["vocab_size"] * d
    return 6.0 * matmul_params + layers * 12.0 * d * sequence_length / 2


def grouped_matmul_least_seconds(config: dict, tokens: int, peaks: dict):
    """(seconds, what bounds it) the chip needs at the least for one step's
    grouped matmuls, all layers: gate, up and down over tokens x
    num_experts_per_tok rows, each forward, dX and dW (nine matmuls of
    2 x rows x hidden x width FLOPs), against each matmul reading its row
    operand and every expert's matrix once and writing its result once,
    in bf16 (HBM operands: nothing here is small enough to be parked in
    VMEM, the row operands are 256-512 MB)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    rows = tokens * config["num_experts_per_tok"]
    layers = config["num_hidden_layers"]
    flops = layers * 9 * 2.0 * rows * d * f
    moved = layers * 9 * 2.0 * (rows * (d + f)
                                + config["num_experts"] * d * f)
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes
                                     else "bytes")
