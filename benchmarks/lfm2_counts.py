"""What the readers of `lfm2-train-8k` count from shapes: parameters, the
FLOPs a token needs on the experts that are chosen AND held, and the least
time the chip could take for a step's short-convolution projections, its
grouped matmuls over the held rows and its grouped attention. Keys are the
published config.json's (`model_type: lfm2_moe`), with the cut's
`experts_held`."""

from __future__ import annotations


def _kinds(config: dict):
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return kinds.count("conv"), kinds.count("full_attention")


def _expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["num_dense_layers"]


def conv_mixer_params(config: dict) -> int:
    """in_proj (hidden -> 3 x hidden) and out_proj: the six projection
    matmuls' two matrices (the taps, 3 a channel, are no matmul)."""
    d = config["hidden_size"]
    return 3 * d * d + d * d


def attention_mixer_params(config: dict) -> int:
    d, h = config["hidden_size"], config["num_attention_heads"]
    kv = config["num_key_value_heads"] * (d // h)
    return 2 * d * d + 2 * d * kv


def expert_params(config: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def param_count(config: dict) -> int:
    """Trainable parameters of the cut configuration as the program holds
    it: mixers, the dense MLP, the held experts with their router and its
    bias, the tied embedding, every norm, the taps and the head norms."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    conv, attn = _kinds(config)
    held = config["experts_held"][1]
    routed = config.get("experts_routed", config["num_experts"])
    moe = _expert_layers(config)
    return (conv * (conv_mixer_params(config) + config["conv_L_cache"] * d)
            + attn * (attention_mixer_params(config) + 2 * (d // h))
            + config["num_dense_layers"] * 3 * d * config["intermediate_size"]
            + moe * (held * expert_params(config) + d * routed
                     + (routed if config["use_expert_bias"] else 0))
            + config["vocab_size"] * d
            + (2 * config["num_hidden_layers"] + 1) * d)


def held_flops_per_token(config: dict, sequence_length: int,
                         held_assignments_a_token: float) -> float:
    """Forward and backward FLOPs a token needs, no recomputation, counted
    on the experts it chose that are held here: six per matmul parameter it
    touches (the mixers by kind, the dense MLP, each router, the held
    experts at `held_assignments_a_token` summed over the expert layers,
    the head over the vocabulary's slice; the embedding is a gather) and
    causal attention's scores and values in the layers that have them."""
    d = config["hidden_size"]
    conv, attn = _kinds(config)
    routed = config.get("experts_routed", config["num_experts"])
    matmul_params = (
        conv * conv_mixer_params(config)
        + attn * attention_mixer_params(config)
        + config["num_dense_layers"] * 3 * d * config["intermediate_size"]
        + _expert_layers(config) * d * routed
        + held_assignments_a_token * expert_params(config)
        + config["vocab_size"] * d)
    return 6.0 * matmul_params + attn * 12.0 * d * sequence_length / 2


def _least(flops: float, moved: float, peaks: dict):
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes
                                     else "bytes")


def short_conv_least_seconds(config: dict, tokens: int, peaks: dict):
    """(seconds, what bounds it) the chip needs at the least for one
    step's short-convolution projections, all `conv` layers: in_proj and
    out_proj, each forward, dX and dW (six matmuls a layer, 6 x tokens x
    their parameters FLOPs), against each matmul reading its two operands
    and writing its result once, in bf16."""
    d = config["hidden_size"]
    conv, _ = _kinds(config)
    flops = conv * 6.0 * tokens * conv_mixer_params(config)
    # in_proj: (tokens, d) x (d, 3d) -> (tokens, 3d); out_proj: d -> d;
    # forward, dX and dW each touch the same three arrays once
    moved = conv * 3 * 2.0 * ((tokens * d + d * 3 * d + tokens * 3 * d)
                              + (tokens * d + d * d + tokens * d))
    return _least(flops, moved, peaks)


def held_matmul_least_seconds(config: dict, rows: float, peaks: dict):
    """(seconds, what bounds it) the chip needs at the least for one
    step's grouped matmuls over `rows` assignments to held experts, summed
    over the expert layers: gate, up and down, each forward, dX and dW
    (nine matmuls of 2 x rows x hidden x width FLOPs), against each
    matmul reading its row operand and every held expert's matrix once and
    writing its result once, in bf16."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    held = config["experts_held"][1]
    flops = 9 * 2.0 * rows * d * f
    moved = 9 * 2.0 * (rows * (d + f)
                       + _expert_layers(config) * held * d * f)
    return _least(flops, moved, peaks)


def grouped_attention_least_seconds(config: dict, sequence_length: int,
                                    batch: int, peaks: dict):
    """(seconds, what bounds it) the chip needs at the least for one
    step's causal attention calls in the `full_attention` layers, forward
    and backward: six matmuls of 2 s^2 head_dim a query head (QK^T and PV
    forward; dV, dP, dQ, dK backward; the backward's recomputation of the
    scores is not counted), halved by the mask, against q and o read or
    written once forward and q, o, do, dq backward at the query heads'
    width, k and v forward and k, v, dk, dv backward at the KV heads'
    width, in bf16: counted from the work, whether the kernel reads a KV
    head once or once a query head."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    kv = config["num_key_value_heads"] * (d // h)
    _, attn = _kinds(config)
    s = sequence_length
    flops = attn * 6.0 * batch * s * s * d
    moved = attn * 6.0 * batch * s * (d + kv) * 2
    return _least(flops, moved, peaks)
