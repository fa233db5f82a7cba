"""Transformer models.

`build_transformer` reproduces the reference benchmark model
(examples/cpp/Transformer/transformer.cc:33-45,112-160): a stack of
`create_attention_encoder` blocks — MHA(hidden, heads) followed by
dense(hidden, relu, no bias) → dense(hidden, no bias) — on a
(batch, seq, hidden) float input, head dense(1), MSE loss. Defaults match
TransformerConfig (transformer.cc:79-85): hidden 1024, heads 16, layers 12,
seq 512.

`build_transformer_lm` is the TPU-native flagship: token embedding, pre-LN
causal blocks with residuals (flash-attention Pallas kernel), GELU MLP, and a
vocab head — the model bench.py measures, designed so megatron TP + data
parallel + optional seq-parallel shardings apply cleanly.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from ..fftype import ActiMode, DataType


@dataclass
class TransformerConfig:
    """Parity with transformer.cc:79-85."""

    hidden_size: int = 1024
    embedding_size: int = 1024
    num_heads: int = 16
    num_layers: int = 12
    sequence_length: int = 512


def create_attention_encoder(ff, input, hidden_dim, num_heads, kdim, vdim,
                             prefix=""):
    """transformer.cc:33-45 (no residuals, no layernorm — faithful)."""
    t = ff.multihead_attention(input, input, input, hidden_dim, num_heads,
                               kdim, vdim, name=f"{prefix}attn")
    t = ff.dense(t, hidden_dim, ActiMode.AC_MODE_RELU, use_bias=False,
                 name=f"{prefix}ffn1")
    return ff.dense(t, hidden_dim, use_bias=False, name=f"{prefix}ffn2")


def build_transformer(ff, config: TransformerConfig | None = None,
                      batch_size: int | None = None):
    """Returns (input_tensor, output_tensor). Loss should be
    LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE (transformer.cc:163)."""
    c = config or TransformerConfig()
    bs = batch_size or ff.config.batch_size
    input = ff.create_tensor((bs, c.sequence_length, c.hidden_size),
                             name="input")
    t = input
    for i in range(c.num_layers):
        t = create_attention_encoder(
            ff, t, c.hidden_size, c.num_heads,
            c.hidden_size // c.num_heads, c.hidden_size // c.num_heads,
            prefix=f"l{i}_",
        )
    t = ff.dense(t, 1, use_bias=False, name="head")
    return input, t


# `layer_pattern`'s kinds: {kind: (the TransformerLMConfig field that holds
# what `_lm_trunk` builds the kind from, what that is)}; (None, ..) = the
# model's own fields. `__post_init__` checks against it and `_lm_trunk`
# dispatches on it, so a kind the trunk does not build cannot pass the
# check and a new kind is one entry
LAYER_KINDS = {"mha": (None, "the model's attention"),
               "swa": ("swa", "the window layers' arguments, a window"),
               "delta": ("delta", "a DeltaFrontEnd"),
               "mamba": ("mamba", "a MambaFrontEnd"),
               "conv": ("conv", "a ShortConvFrontEnd")}


@dataclass
class TransformerLMConfig:
    """Flagship decoder-only LM (TPU-native; exceeds reference capability —
    the reference has no positional handling, residuals, or causal mask in
    its benchmark model)."""

    vocab_size: int = 32000
    hidden_size: int = 1024
    num_heads: int = 16
    num_layers: int = 12
    mlp_ratio: int = 4
    sequence_length: int = 512
    dtype: DataType = DataType.DT_FLOAT
    attention_impl: str = "flash"  # xla | flash | ring
    # The block as data. The defaults are the GPT-2 block (pre-LN, learned
    # positions, biased attention, GELU MLP of mlp_ratio x); OLMoE is
    # norm="rmsnorm", position="rope", attention_bias=False, qk_norm=True,
    # mlp="moe" (`olmoe_lm_config`).
    norm: str = "layernorm"        # layernorm | rmsnorm
    norm_eps: float = 1e-5
    # learned (a wpe table) | rope | none (no position enters anywhere)
    position: str = "learned"
    rope_theta: float = 10000.0
    attention_bias: bool = True
    # False | "projection" (True: OLMoE's, over the whole q and k) |
    # "head" (over each head): ops/attention.AttentionFrontEnd.qk_norm
    qk_norm: object = False
    # gelu | swiglu (SiLU-gated, bias-free, of `intermediate_size`) | moe
    # (SiLU-gated routed experts)
    mlp: str = "gelu"
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    router_aux_loss_coef: float = 0.0
    # DeepSeek-V3.2 (`deepseek_v32_lm_config`) and Mistral-Small-4
    # (`mistral_small4_lm_config`): attention="latent" builds latent
    # attention, with the lightning indexer or with none, from `latent` (an
    # ops.latent_attention.LatentFrontEnd, positions go to it); the first
    # `first_k_dense` layers of an mlp="moe" stack are swiglu ones of
    # `intermediate_size`; `moe_routing` holds the further fields of
    # ops.moe.MoEMLPParams (the sigmoid group-limited router, the shared
    # expert, the experts held here); `initializer_range` > 0 draws every
    # matrix from N(0, that), and `embedding_range` > 0 the embedding from
    # N(0, that) instead
    attention: str = "mha"         # mha | latent
    latent: Optional[object] = None
    intermediate_size: int = 0
    first_k_dense: int = 0
    moe_routing: Optional[dict] = None
    initializer_range: float = 0.0
    embedding_range: float = 0.0
    # Solar-Open2 (`solar_open2_lm_config`): grouped keys and values, a
    # head's size apart from hidden / heads and a sigmoid output gate on
    # the softmax layers (ops/attention.AttentionFrontEnd's fields);
    # `layer_pattern`, one kind a layer ("mha" | "delta"; None = every
    # layer is `attention`), "delta" = gated delta-rule linear attention
    # built from `delta` (an ops.delta_attention.DeltaFrontEnd)
    num_kv_heads: int = 0
    head_dim: int = 0
    attention_gate: bool = False
    layer_pattern: Optional[tuple] = None
    delta: Optional[object] = None
    # Keye-VL-2.0 (`keye_vl2_lm_config`): `indexer` (an
    # ops.attention.Indexer) gives the "mha" layers a learned top-k
    # selection of the positions a row attends; needs position "rope"
    indexer: Optional[object] = None
    # MiMo-V2-Flash (`mimo_v2_flash_lm_config`): a value head's size apart
    # from the key head's, RoPE over a head's first `rope_dim` lanes, the
    # values' scale; `layer_pattern` "swa" is an "mha" layer with the
    # arguments of FFModel.multihead_attention in `swa` in place of the
    # model's (its window, its sink, its KV heads, its theta: what differs
    # by kind is the layer's front end's, ops/attention.AttentionFrontEnd);
    # `sink_range` > 0 draws a sink from N(0, that)
    v_head_dim: int = 0
    rope_dim: int = 0
    value_scale: float = 1.0
    swa: Optional[dict] = None
    sink_range: float = 0.0
    # the sigmoid router's correction bias (ops/moe.py `router_bias`) is
    # drawn from N(0, that), 0.0 = zeros; None leaves the layer's own
    # draw, N(0, 0.02)
    router_bias_range: Optional[float] = None
    # Command A+ (`command_a_plus_lm_config`): `parallel_block`: one norm a
    # layer, n = norm(h), and h + attention(n) + mlp(n), where the default
    # is h + attention(norm1(h)) and then + mlp(norm2(.)); `norm_bias`
    # False: LayerNorm with a learned scale and no bias; position is the
    # layer kind's: `swa` may carry a `rope_theta` of its own (the window
    # layers then take positions whatever `position` says, and under
    # position "none" the global layers take none) and the form,
    # `rope_interleaved` (lanes 2j and 2j + 1 a pair), both keys of `swa`;
    # `tie_embeddings`: the head reads the embedding's table (one array,
    # no `lm_head` weight)
    parallel_block: bool = False
    norm_bias: bool = True
    tie_embeddings: bool = False
    # the embedding is drawn around this mean (a residual stream of zero
    # mean, which a random stack's is, cannot tell LayerNorm from RMSNorm)
    embedding_mean: float = 0.0
    # EvaByte (`evabyte_lm_config`): `swa` may carry a `summary_chunk`
    # (EVA attention: the window is then aligned, and a row attends one
    # learned summary a chunk of the windows before it, `phi` and `mu_k`
    # drawn uniformly within head_dim^-0.5); `norm_unit_offset`: an
    # RMSNorm's scale is 1 + g, g from zeros; `fp32_residual`: the
    # embedding's rows and every residual add are float32 under a narrower
    # compute dtype, and a norm hands the matmuls that dtype;
    # `fp32_logits`: the head's output is float32 likewise
    norm_unit_offset: bool = False
    fp32_residual: bool = False
    fp32_logits: bool = False
    # Jamba (`jamba_lm_config`): `layer_pattern` "mamba" = a selective
    # state-space layer built from `mamba` (an ops.ssm.MambaFrontEnd)
    mamba: Optional[object] = None
    # LFM2 (`lfm2_moe_lm_config`): `layer_pattern` "conv" = a gated short
    # convolution built from `conv` (an ops.short_conv.ShortConvFrontEnd)
    conv: Optional[object] = None

    def layer_kind(self, i: int) -> str:
        return self.layer_pattern[i] if self.layer_pattern else self.attention

    def __post_init__(self):
        for field, allowed in (("norm", ("layernorm", "rmsnorm")),
                               ("position", ("learned", "rope", "none")),
                               ("mlp", ("gelu", "swiglu", "moe")),
                               ("attention", ("mha", "latent"))):
            if getattr(self, field) not in allowed:
                raise ValueError(
                    f"TransformerLMConfig.{field} must be one of "
                    f"{allowed}, got {getattr(self, field)!r}")
        if self.attention == "latent" and (self.latent is None
                                           or self.position != "rope"):
            raise ValueError(
                "TransformerLMConfig.attention 'latent' needs `latent` (a "
                "LatentFrontEnd) and position 'rope'")
        if self.layer_pattern is not None:
            self.layer_pattern = tuple(self.layer_pattern)
            if (len(self.layer_pattern) != self.num_layers
                    or set(self.layer_pattern) - set(LAYER_KINDS)):
                raise ValueError(
                    f"TransformerLMConfig.layer_pattern names one kind "
                    f"({' | '.join(map(repr, LAYER_KINDS))}) for each of the "
                    f"{self.num_layers} layers, got {self.layer_pattern!r}")
            if "swa" in self.layer_pattern and not (self.swa or {}).get(
                    "window"):
                raise ValueError(
                    "TransformerLMConfig.layer_pattern 'swa' needs `swa` "
                    "with a window")
            for kind in set(self.layer_pattern) - {"swa"}:
                field, what = LAYER_KINDS[kind]
                if field and getattr(self, field) is None:
                    raise ValueError(
                        f"TransformerLMConfig.layer_pattern {kind!r} needs "
                        f"`{field}` ({what})")


def olmoe_lm_config(**sizes) -> TransformerLMConfig:
    """The OLMoE block (arXiv:2409.02060, transformers' modeling_olmoe):
    RMSNorm, RoPE, bias-free attention with QK-norm over the whole
    projections, routed SiLU-gated experts; `sizes` are the widths."""
    return TransformerLMConfig(
        norm="rmsnorm", position="rope", attention_bias=False, qk_norm=True,
        mlp="moe", **sizes)


def _latent_widths(config: dict) -> dict:
    """LatentFrontEnd's widths from the keys the latent-attention family
    publishes them under."""
    return dict(
        embed_dim=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"])


def _yarn(scaling):
    """LatentFrontEnd.rope_scaling of a config's YaRN group, if any."""
    return None if not scaling else (
        scaling["factor"], scaling["original_max_position_embeddings"],
        scaling["beta_fast"], scaling["beta_slow"],
        scaling["mscale_all_dim"])


def deepseek_v32_lm_config(config: dict, *, sequence_length: int,
                           attention_impl: str = "xla",
                           initializer_range: float = 0.006
                           ) -> TransformerLMConfig:
    """The DeepSeek-V3.2 block from the keys of its published config.json
    (`model_type: deepseek_v32`; models/deepseek_v32_reference.py writes
    the equations out). A cut configuration states the experts one chip
    holds as `experts_held` = [first id, count] beside `experts_routed`,
    the router's width (both default to all of `n_routed_experts`)."""
    from ..ops.latent_attention import LatentFrontEnd, LatentIndexer

    front = LatentFrontEnd(
        **_latent_widths(config),
        index=LatentIndexer(
            n_heads=config["index_n_heads"],
            head_dim=config["index_head_dim"], topk=config["index_topk"],
            norm_eps=config.get("index_norm_eps", 1e-6)),
        rope_theta=float(config["rope_theta"]),
        rope_scaling=_yarn(config.get("rope_scaling")),
        norm_eps=config["rms_norm_eps"])
    held = config.get("experts_held")
    routed = config.get("experts_routed", config["n_routed_experts"])
    return TransformerLMConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        sequence_length=sequence_length, attention_impl=attention_impl,
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], position="rope",
        rope_theta=float(config["rope_theta"]), attention_bias=False,
        attention="latent", latent=front, mlp="moe",
        intermediate_size=config["intermediate_size"],
        first_k_dense=config["first_k_dense_replace"],
        num_experts=routed,
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_routing=dict(
            scoring=config["scoring_func"], n_group=config["n_group"],
            topk_group=config["topk_group"],
            norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=config["routed_scaling_factor"],
            shared_intermediate_size=(config["n_shared_experts"]
                                      * config["moe_intermediate_size"]),
            experts_held=None if held is None else tuple(held)),
        initializer_range=initializer_range)


def mistral_small4_lm_config(config: dict, *, sequence_length: int,
                             attention_impl: str = "xla",
                             initializer_range: float = 0.02,
                             embedding_range: float = 1.0
                             ) -> TransformerLMConfig:
    """The language model of Mistral-Small-4 from the keys of its
    published config.json (`model_type: mistral4`;
    models/mistral_small4_reference.py writes the equations out): latent
    attention with NO selection (a row attends its whole past), YaRN's
    numbers and theta under `rope_parameters`, the query of position t
    scaled by 1 + `llama_4_scaling_beta` ln(1 + floor(t / original)),
    every layer with routed experts under a renormalised softmax router
    (no groups, no correction bias) and a shared expert. A cut
    configuration states `experts_held` / `experts_routed` as
    DeepSeek-V3.2's does. `embedding_range` 1.0: the embedding alone is
    N(0, 1), as an untrained stack needs for rows that differ (PERF.md
    section 6, PR 38)."""
    from ..ops.latent_attention import LatentFrontEnd

    if config["first_k_dense_replace"] or config["n_group"] != 1:
        raise NotImplementedError(
            "mistral_small4_lm_config builds the published block: "
            "first_k_dense_replace 0, n_group 1")
    rope = config["rope_parameters"]
    front = LatentFrontEnd(
        **_latent_widths(config),
        rope_theta=float(rope["rope_theta"]), rope_scaling=_yarn(rope),
        norm_eps=config["rms_norm_eps"],
        query_scale=(float(rope["llama_4_scaling_beta"]),
                     int(rope["original_max_position_embeddings"])))
    held = config.get("experts_held")
    return TransformerLMConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        sequence_length=sequence_length, attention_impl=attention_impl,
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], position="rope",
        rope_theta=float(rope["rope_theta"]), attention_bias=False,
        attention="latent", latent=front, mlp="moe", first_k_dense=0,
        num_experts=config.get("experts_routed", config["n_routed_experts"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_routing=dict(
            scoring="softmax", norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            shared_intermediate_size=(config["n_shared_experts"]
                                      * config["moe_intermediate_size"]),
            experts_held=None if held is None else tuple(held)),
        initializer_range=initializer_range,
        embedding_range=embedding_range)


def solar_open2_lm_config(config: dict, *, sequence_length: int,
                          attention_impl: str = "xla",
                          initializer_range: float = 0.02
                          ) -> TransformerLMConfig:
    """The Solar-Open2 block from the keys of its published config.json
    (`model_type: solar_open2`; models/solar_open2_reference.py writes the
    equations out): layers `gqa_layers` are gated softmax attention with
    grouped keys and values and no positions, the others gated delta-rule
    linear attention (`linear_attn_config`), every layer has routed
    experts under a renormalised softmax router and a shared expert. A
    cut configuration states `experts_held` / `experts_routed` as
    DeepSeek-V3.2's does."""
    from ..ops.delta_attention import DeltaFrontEnd

    if config.get("use_rope") or config["first_k_dense_replace"]:
        raise NotImplementedError(
            "solar_open2_lm_config builds the published block: use_rope "
            "false, first_k_dense_replace 0")
    lin = config["linear_attn_config"]
    layers = config["num_hidden_layers"]
    gqa = set(config["gqa_layers"])
    held = config.get("experts_held")
    return TransformerLMConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"], num_layers=layers,
        sequence_length=sequence_length, attention_impl=attention_impl,
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], position="none",
        attention_bias=False,
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        attention_gate=config["use_gqa_gate"],
        layer_pattern=tuple("mha" if i in gqa else "delta"
                            for i in range(layers)),
        delta=DeltaFrontEnd(
            embed_dim=config["hidden_size"], num_heads=lin["num_heads"],
            head_dim=lin["head_dim"],
            conv_kernel=lin["short_conv_kernel_size"],
            neg_eigval=config["kda_allow_neg_eigval"],
            norm_eps=config["rms_norm_eps"]),
        mlp="moe",
        num_experts=config.get("experts_routed", config["n_routed_experts"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_routing=dict(
            norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=config["routed_scaling_factor"],
            shared_intermediate_size=(config["n_shared_experts"]
                                      * config["moe_intermediate_size"]),
            experts_held=None if held is None else tuple(held)),
        initializer_range=initializer_range)


def keye_vl2_lm_config(config: dict, *, sequence_length: int,
                       attention_impl: str = "xla",
                       initializer_range: float = 0.02,
                       embedding_range: float = 0.0
                       ) -> TransformerLMConfig:
    """The language model of Keye-VL-2.0 from the keys of its published
    config.json (`model_type: KeyeVL2`; models/keye_vl2_reference.py
    writes the equations out and says what the keys leave open): every
    layer is softmax attention with grouped keys and values, RMSNorm of
    each q and k head, RoPE, and over it the `sa_config` indexer's top-k
    selection; then routed experts under a softmax router renormalised
    over the chosen, no shared expert. Text positions only (the three
    components of `mrope_section` are then equal: 1-D RoPE); the vision
    tower is not built. Random weights are N(0, `initializer_range`), the
    embedding N(0, `embedding_range`) where that is given: at the
    matrices' 0.02 a token's embedding is a tenth of the first attention's
    output, the residual stream of an untrained stack is its context's mean
    from there on, and every row of a sequence then routes alike."""
    from ..ops.attention import Indexer

    if (config.get("mlp_only_layers") or config["decoder_sparse_step"] != 1
            or config.get("use_sliding_window")
            or config.get("attention_bias")):
        raise NotImplementedError(
            "keye_vl2_lm_config builds the published block: every layer "
            "an expert layer, no sliding window, no attention bias")
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise NotImplementedError(
            "keye_vl2_lm_config: the indexer has one key a token")
    experts = config["num_experts"]
    return TransformerLMConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        sequence_length=sequence_length, attention_impl=attention_impl,
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], position="rope",
        rope_theta=float(config["rope_theta"]), attention_bias=False,
        qk_norm="head", num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        indexer=Indexer(
            n_heads=sa["indexer_num_heads"],
            head_dim=sa["indexer_head_dim"], topk=sa["topk"],
            rope_dim=sa["indexer_head_dim"]),
        mlp="moe", num_experts=experts,
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        # every expert is held: said so that the layer keeps the counts a
        # serving graph's `moe_assignments` / `moe_dropped` read
        moe_routing=dict(norm_topk_prob=config["norm_topk_prob"],
                         experts_held=(0, experts)),
        initializer_range=initializer_range,
        embedding_range=embedding_range)


def mimo_v2_flash_lm_config(config: dict, *, sequence_length: int,
                            attention_impl: str = "xla",
                            initializer_range: float = 0.02,
                            embedding_range: float = 0.0,
                            sink_range: float = 0.0) -> TransformerLMConfig:
    """MiMo-V2-Flash from the keys of its published config.json
    (`model_type: mimo_v2_flash`; models/mimo_v2_flash_reference.py writes
    the equations out and says what the keys leave open): layers where
    `hybrid_layer_pattern` is 0 are global softmax attention, where 1
    attention over a window of `sliding_window` keys with a learned sink a
    head, each kind with its own KV heads and RoPE theta; heads of
    `head_dim` for q and k and `v_head_dim` for v, RoPE over the first
    int(head_dim x `partial_rotary_factor`) lanes, the values scaled by
    `attention_value_scale`; a gated MLP where `moe_layer_freq` is 0, else
    DeepSeek-V3's sigmoid router (`noaux_tc`) over SiLU-gated experts, no
    shared expert. A cut configuration states `experts_held` /
    `experts_routed` as DeepSeek-V3.2's does. The three MTP layers are not
    built. Random weights are N(0, `initializer_range`), the embedding
    N(0, `embedding_range`) and the sinks N(0, `sink_range`) where those
    are given; the routers' correction bias is zeros (no key gives the
    trained one, and a seeded one of the layer's default spread, 0.02
    beside scores 0.005 apart, decides which experts are loaded: a held
    sixteenth's share of the assignments then swings by a fifth with the
    seed)."""
    layers = config["num_hidden_layers"]
    pattern = config["hybrid_layer_pattern"][:layers]
    moe = config["moe_layer_freq"][:layers]
    dense = moe.index(1) if 1 in moe else layers
    if (any(moe[:dense]) or not all(moe[dense:])
            or config.get("attention_bias") or config.get("n_shared_experts")
            or config["hidden_act"] != "silu"
            or config["swa_num_attention_heads"]
            != config["num_attention_heads"]
            or (config["swa_head_dim"], config["swa_v_head_dim"])
            != (config["head_dim"], config["v_head_dim"])):
        raise NotImplementedError(
            "mimo_v2_flash_lm_config builds the published block: leading "
            "dense layers then expert layers, no attention bias, no shared "
            "expert, SiLU, both kinds of layer with the same query heads "
            "and head sizes")
    held = config.get("experts_held")
    return TransformerLMConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"], num_layers=layers,
        sequence_length=sequence_length, attention_impl=attention_impl,
        norm="rmsnorm", norm_eps=config["layernorm_epsilon"],
        position="rope", rope_theta=float(config["rope_theta"]),
        attention_bias=False,
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], v_head_dim=config["v_head_dim"],
        rope_dim=int(config["head_dim"] * config["partial_rotary_factor"]),
        value_scale=float(config["attention_value_scale"]),
        layer_pattern=tuple("swa" if kind else "mha" for kind in pattern),
        swa=dict(num_kv_heads=config["swa_num_key_value_heads"],
                 rope_theta=float(config["swa_rope_theta"]),
                 window=config["sliding_window"],
                 sink=bool(config["add_swa_attention_sink_bias"])),
        mlp="moe", intermediate_size=config["intermediate_size"],
        first_k_dense=dense,
        num_experts=config.get("experts_routed", config["n_routed_experts"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_routing=dict(
            scoring=config["scoring_func"], n_group=config["n_group"],
            topk_group=config["topk_group"],
            norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=float(
                config["routed_scaling_factor"] or 1.0),
            experts_held=None if held is None else tuple(held)),
        initializer_range=initializer_range,
        embedding_range=embedding_range, sink_range=sink_range,
        router_bias_range=0.0)


def command_a_plus_lm_config(config: dict, *, sequence_length: int,
                             attention_impl: str = "xla",
                             initializer_range: float = 0.02,
                             embedding_range: float = 0.0,
                             embedding_mean: float = 0.0
                             ) -> TransformerLMConfig:
    """The language model of Command A+ from the keys of its published
    config.json (`model_type: cohere2_moe`;
    models/command_a_plus_reference.py writes the equations out and says
    what the keys leave open): a parallel block under one LayerNorm with a
    scale and no bias; `layer_types` three `sliding_attention` layers (a
    window of `sliding_window` keys, interleaved RoPE over the whole head)
    to one `full_attention` layer that takes no position; grouped keys and
    values; every layer with routed experts under a sigmoid router (no
    correction bias, no groups) renormalised over the chosen, beside
    `num_shared_experts` shared experts whose outputs are averaged; the
    head tied to the embedding. A cut configuration states `experts_held`
    / `experts_routed` as DeepSeek-V3.2's does (`num_experts` then counts
    the experts held). The vision tower is not built. The embedding is
    N(`embedding_mean`, `embedding_range` or `initializer_range`): under a
    tied head an embedding of N(0, 1) beside matrices of 0.02 makes a
    row's logit of the token it read its largest by far, and a greedy
    reply that token repeated."""
    layers = config["num_hidden_layers"]
    kinds = tuple(config["layer_types"][:layers])
    period = ("sliding_attention",) * 3 + ("full_attention",)
    if kinds != (period * -(-layers // 4))[:layers]:
        raise NotImplementedError(
            f"command_a_plus_lm_config: layer_types is three "
            f"sliding_attention layers to one full_attention layer, got "
            f"{kinds}")
    for key, built in (("use_qk_norm", False), ("attention_bias", False),
                       ("first_k_dense_replace", 0),
                       ("shared_expert_combination_strategy", "average"),
                       ("expert_selection_fn", "sigmoid"),
                       ("position_embedding_type", "rope_gptj"),
                       ("rotary_pct", 1), ("logit_scale", 1),
                       ("hidden_act", "silu"),
                       ("use_gated_activation", True)):
        if config.get(key, built) != built:
            raise NotImplementedError(
                f"command_a_plus_lm_config builds {key} {built!r}, got "
                f"{config[key]!r}")
    held = config.get("experts_held")
    shared = config["num_shared_experts"]
    return TransformerLMConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"], num_layers=layers,
        sequence_length=sequence_length, attention_impl=attention_impl,
        norm="layernorm", norm_eps=config["layer_norm_eps"],
        norm_bias=False, parallel_block=bool(config["use_parallel_block"]),
        position="none", attention_bias=False,
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        layer_pattern=tuple("swa" if kind == "sliding_attention" else "mha"
                            for kind in kinds),
        swa=dict(window=config["sliding_window"],
                 rope_theta=float(config["rope_theta"]),
                 rope_interleaved=True),
        mlp="moe",
        num_experts=config.get("experts_routed", config["num_experts"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["intermediate_size"],
        moe_routing=dict(
            scoring="sigmoid", correction_bias=False,
            norm_topk_prob=config["norm_topk_prob"],
            # the shared experts side by side as one gated MLP, its
            # output times 1 / their number: their average
            shared_intermediate_size=shared * config["intermediate_size"],
            shared_scale=1.0 / shared if shared else 1.0,
            experts_held=None if held is None else tuple(held)),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        initializer_range=initializer_range,
        embedding_range=embedding_range, embedding_mean=embedding_mean)


def evabyte_lm_config(config: dict, *, sequence_length: int,
                      attention_impl: str = "xla") -> TransformerLMConfig:
    """EvaByte from the keys of its published config.json (`model_type:
    evabyte`; models/evabyte_reference.py writes the equations out and says
    what the keys leave open): a byte vocabulary, every layer EVA attention
    (`attention_class: eva`: an aligned window of `window_size` exact keys
    beside one learned summary for every `chunk_size` keys of the windows
    already closed, one softmax) over as many KV heads as query heads,
    RoPE over the whole head, SwiGLU, RMSNorm scaled by 1 + g
    (`norm_add_unit_offset`), the residual stream in float32
    (`fp32_skip_add`) and float32 logits (`fp32_logits`), an untied head:
    head 0 of the `num_pred_heads` prediction heads, the others read the
    same final state and are not built. Every matrix and the embedding
    N(0, `init_std`), `phi` and `mu_k` uniform within head_dim^-0.5."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    if config.get("num_chunks") is not None:
        raise NotImplementedError(
            f"evabyte_lm_config: num_chunks {config['num_chunks']!r} (a "
            f"fixed number of chunks a window) is not built: chunks are "
            f"chunk_size keys each")
    if config["window_size"] % config["chunk_size"]:
        raise NotImplementedError(
            f"evabyte_lm_config: chunk_size {config['chunk_size']} does not "
            f"divide window_size {config['window_size']}")
    if config.get("rope_scaling") is not None:
        raise NotImplementedError(
            f"evabyte_lm_config: rope_scaling {config['rope_scaling']!r} is "
            f"not built")
    for key, built in (("attention_class", "eva"), ("attention_bias", False),
                       ("num_key_value_heads", heads),
                       ("hidden_act", "silu"),
                       ("tie_word_embeddings", False)):
        if config.get(key, built) != built:
            raise NotImplementedError(
                f"evabyte_lm_config builds {key} {built!r}, got "
                f"{config[key]!r}")
    return TransformerLMConfig(
        vocab_size=config["vocab_size"], hidden_size=hidden,
        num_heads=heads, num_layers=config["num_hidden_layers"],
        sequence_length=sequence_length, attention_impl=attention_impl,
        norm="rmsnorm", norm_eps=config["rms_norm_eps"],
        norm_unit_offset=bool(config.get("norm_add_unit_offset", False)),
        position="rope", rope_theta=float(config["rope_theta"]),
        attention_bias=False,
        layer_pattern=("swa",) * config["num_hidden_layers"],
        swa=dict(window=config["window_size"],
                 summary_chunk=config["chunk_size"]),
        mlp="swiglu", intermediate_size=config["intermediate_size"],
        fp32_residual=bool(config.get("fp32_skip_add", False)),
        fp32_logits=bool(config.get("fp32_logits", False)),
        initializer_range=float(config["init_std"]))


def jamba_lm_config(config: dict, *, sequence_length: int,
                    attention_impl: str = "xla",
                    initializer_range: float = 0.02) -> TransformerLMConfig:
    """Jamba from the keys of its published config.json (`model_type:
    jamba`; models/jamba2_reference.py writes the equations out and says
    what the keys leave open): layer i is causal softmax attention where i
    mod `attn_layer_period` = `attn_layer_offset` (grouped keys and
    values, no bias, no position anywhere) and a selective state-space
    (Mamba-1) layer elsewhere, with RMSNorms inside it over dt, B and C;
    every layer a SiLU-gated MLP of `intermediate_size` (`num_experts` 1:
    no routed experts), RMSNorm before each, the head tied to the
    embedding where `tie_word_embeddings` says so."""
    from ..ops.ssm import MambaFrontEnd

    for key, built in (("num_experts", 1), ("mamba_proj_bias", False),
                       ("hidden_act", "silu")):
        if config.get(key, built) != built:
            raise NotImplementedError(
                f"jamba_lm_config builds {key} {built!r}, got "
                f"{config[key]!r}")
    layers, hidden = config["num_hidden_layers"], config["hidden_size"]
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    heads = config["num_attention_heads"]
    return TransformerLMConfig(
        vocab_size=config["vocab_size"], hidden_size=hidden,
        num_heads=heads, num_layers=layers,
        sequence_length=sequence_length, attention_impl=attention_impl,
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], position="none",
        attention_bias=False,
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim") or hidden // heads,
        layer_pattern=tuple("mha" if i % period == offset else "mamba"
                            for i in range(layers)),
        mamba=MambaFrontEnd(
            embed_dim=hidden, inner=config["mamba_expand"] * hidden,
            state_size=config["mamba_d_state"],
            dt_rank=config["mamba_dt_rank"],
            conv_kernel=config["mamba_d_conv"],
            conv_bias=bool(config["mamba_conv_bias"]),
            norm_eps=config["rms_norm_eps"]),
        mlp="swiglu", intermediate_size=config["intermediate_size"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        initializer_range=initializer_range)


def lfm2_moe_lm_config(config: dict, *, sequence_length: int,
                       attention_impl: str = "xla",
                       initializer_range: float = 0.02,
                       embedding_range: float = 0.0) -> TransformerLMConfig:
    """LFM2-MoE from the keys of its published config.json (`model_type:
    lfm2_moe`; models/lfm2_moe_reference.py writes the equations out and
    says what the keys leave open): `layer_types` "conv" is a gated short
    convolution of `conv_L_cache` taps a channel, "full_attention" causal
    softmax attention with grouped keys and values, an RMSNorm over each q
    and k head and RoPE; the first `num_dense_layers` layers carry a
    SiLU-gated MLP of `intermediate_size`, the others `num_experts` routed
    experts of `moe_intermediate_size` under a sigmoid router (a bias for
    the choice only where `use_expert_bias`, gates renormalised over the
    chosen with 1e-6 in the sum where `norm_topk_prob`), no shared expert;
    the head tied to the embedding (`tie_word_embeddings`, true where the
    key is left out, as the family's default). A cut configuration states
    `experts_held` / `experts_routed` as DeepSeek-V3.2's does
    (`num_experts` then counts the experts held). The router's bias is
    zeros, as the family initialises it."""
    from ..ops.short_conv import ShortConvFrontEnd

    layers, hidden = config["num_hidden_layers"], config["hidden_size"]
    kinds = tuple(config["layer_types"][:layers])
    known = {"conv": "conv", "full_attention": "mha"}
    if len(kinds) != layers or set(kinds) - set(known):
        raise NotImplementedError(
            f"lfm2_moe_lm_config: layer_types names 'conv' or "
            f"'full_attention' for each of the {layers} layers, got {kinds}")
    for key, built in (("conv_bias", False), ("hidden_act", "silu"),
                       ("attention_bias", False)):
        if config.get(key, built) != built:
            raise NotImplementedError(
                f"lfm2_moe_lm_config builds {key} {built!r}, got "
                f"{config[key]!r}")
    heads = config["num_attention_heads"]
    held = config.get("experts_held")
    return TransformerLMConfig(
        vocab_size=config["vocab_size"], hidden_size=hidden,
        num_heads=heads, num_layers=layers,
        sequence_length=sequence_length, attention_impl=attention_impl,
        norm="rmsnorm", norm_eps=config["norm_eps"], position="rope",
        rope_theta=float(config["rope_theta"]), attention_bias=False,
        qk_norm="head", num_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim") or hidden // heads,
        layer_pattern=tuple(known[kind] for kind in kinds),
        conv=ShortConvFrontEnd(embed_dim=hidden,
                               conv_kernel=config["conv_L_cache"]),
        mlp="moe", intermediate_size=config["intermediate_size"],
        first_k_dense=config["num_dense_layers"],
        num_experts=config.get("experts_routed", config["num_experts"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_routing=dict(
            scoring="sigmoid", n_group=1, topk_group=1,
            norm_topk_prob=bool(config["norm_topk_prob"]),
            norm_topk_eps=1e-6,
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            correction_bias=bool(config["use_expert_bias"]),
            experts_held=None if held is None else tuple(held)),
        tie_embeddings=bool(config.get("tie_word_embeddings", True)),
        initializer_range=initializer_range,
        embedding_range=embedding_range, router_bias_range=0.0)


def _norm_initializer(stddev: float, mean: float = 0.0):
    from ..initializer import NormInitializer

    return NormInitializer(stddev=stddev, mean=mean)


def _lm_norm(ff, c: TransformerLMConfig, h, name: str):
    if c.norm == "rmsnorm":
        return ff.rms_norm(h, c.norm_eps, name=name,
                           unit_offset=c.norm_unit_offset,
                           narrow_out=c.fp32_residual)
    return ff.layer_norm(h, [2], eps=c.norm_eps, name=name,
                         bias=c.norm_bias)


def _lm_trunk(ff, c: TransformerLMConfig, h, pos, wte=None):
    """The pre-norm block stack + final norm + vocab head. What the block
    is made of is the config's (norm, position, attention_bias, qk_norm,
    mlp, parallel_block). The decode graph is this graph replayed with
    the same layer names (serving/decode_graph.py), so trained parameters
    transfer to it by name. `wte`: the embedding's output, which a tied
    head reads the table of."""
    rope = c.position == "rope"
    init = (_norm_initializer(c.initializer_range)
            if c.initializer_range else None)
    # the kinds built from a front end of their own; "mha" and "swa" are
    # the model's attention under the kind's arguments
    recurrent = {"delta": ff.gated_delta_attention, "mamba": ff.mamba,
                 "conv": ff.short_conv}
    assert set(recurrent) | {"mha", "swa"} == set(LAYER_KINDS)
    for i in range(c.num_layers):
        p = f"l{i}_"
        n = _lm_norm(ff, c, h, f"{p}ln1")
        kind = c.layer_kind(i)
        if kind in recurrent:
            a = recurrent[kind](n, getattr(c, LAYER_KINDS[kind][0]),
                                kernel_initializer=init, name=f"{p}attn")
        elif c.attention == "latent":
            a = ff.latent_attention(n, pos, c.latent, kernel_initializer=init,
                                    name=f"{p}attn")
        else:
            front = dict(
                rope_theta=c.rope_theta if rope else 0.0,
                num_kv_heads=c.num_kv_heads, head_dim=c.head_dim,
                v_head_dim=c.v_head_dim, rope_dim=c.rope_dim,
                value_scale=c.value_scale)
            if kind == "swa":
                front.update(c.swa)
            a = ff.multihead_attention(
                n, n, n, c.hidden_size, c.num_heads, bias=c.attention_bias,
                causal=True, impl=c.attention_impl, name=f"{p}attn",
                # position is the layer kind's: a kind with a theta
                positions=pos if front["rope_theta"] else None,
                qk_norm=c.qk_norm, qk_norm_eps=c.norm_eps,
                output_gate=c.attention_gate, index=c.indexer,
                kernel_initializer=init,
                sink_initializer=(_norm_initializer(c.sink_range)
                                  if c.sink_range else None),
                **front,
            )
        if c.parallel_block:
            # the fork: the MLP reads what the attention read, and the
            # two join before the residual add
            m = n
        else:
            h = ff.add(h, a, name=f"{p}res1")
            m = _lm_norm(ff, c, h, f"{p}ln2")
        if c.mlp == "moe" and i >= c.first_k_dense:
            # the objective carries the mean over the layers of each
            # router's load-balancing term, so a layer adds coef / layers
            m = ff.moe_mlp(m, c.num_experts, c.num_experts_per_tok,
                           c.moe_intermediate_size,
                           c.router_aux_loss_coef / c.num_layers,
                           name=f"{p}moe", kernel_initializer=init,
                           router_bias_initializer=(
                               None if c.router_bias_range is None
                               else _norm_initializer(c.router_bias_range)),
                           **(c.moe_routing or {}))
        elif c.mlp in ("swiglu", "moe"):
            g = ff.dense(m, c.intermediate_size, use_bias=False,
                         kernel_initializer=init, name=f"{p}ffn_gate")
            u = ff.dense(m, c.intermediate_size, use_bias=False,
                         kernel_initializer=init, name=f"{p}ffn_up")
            g = ff.multiply(g, ff.sigmoid(g, name=f"{p}ffn_sigmoid"),
                            name=f"{p}ffn_silu")
            m = ff.dense(ff.multiply(g, u, name=f"{p}ffn_gated"),
                         c.hidden_size, use_bias=False,
                         kernel_initializer=init, name=f"{p}ffn_down")
        else:
            m = ff.dense(m, c.mlp_ratio * c.hidden_size, name=f"{p}ffn1")
            m = ff.gelu(m, name=f"{p}gelu")
            m = ff.dense(m, c.hidden_size, name=f"{p}ffn2")
        if c.parallel_block:
            m = ff.add(a, m, name=f"{p}join")
        h = ff.add(h, m, name=f"{p}res2")
    h = _lm_norm(ff, c, h, "ln_f")
    if c.tie_embeddings:
        return ff.dense(h, c.vocab_size, use_bias=False, name="lm_head",
                        shared_op=wte)
    return ff.dense(h, c.vocab_size, use_bias=False, name="lm_head",
                    kernel_initializer=init, float32_out=c.fp32_logits)


def build_transformer_lm(ff, config: TransformerLMConfig | None = None,
                         batch_size: int | None = None):
    """Returns (tokens_input, logits). Loss:
    LOSS_SPARSE_CATEGORICAL_CROSSENTROPY over shifted labels."""
    c = config or TransformerLMConfig()
    bs = batch_size or ff.config.batch_size
    tokens = ff.create_tensor((bs, c.sequence_length), DataType.DT_INT32,
                              name="tokens")
    embedding_range = c.embedding_range or c.initializer_range
    h = wte = ff.embedding(
        tokens, c.vocab_size, c.hidden_size, name="wte",
        kernel_initializer=(None if not embedding_range else
                            _norm_initializer(embedding_range,
                                              c.embedding_mean)),
        float32_out=c.fp32_residual)
    pos = ff.create_tensor((bs, c.sequence_length), DataType.DT_INT32,
                           name="positions")
    if c.position == "learned":  # rotary positions go to the attention ops
        hp = ff.embedding(pos, c.sequence_length, c.hidden_size, name="wpe")
        h = ff.add(h, hp, name="embed_add")
    return tokens, _lm_trunk(ff, c, h, pos, wte=wte)


def build_transformer_lm_pipelined(ff, config: TransformerLMConfig | None = None,
                                   batch_size: int | None = None,
                                   num_microbatches: int = 0):
    """The flagship LM with its block stack as ONE PipelineBlocks op: the
    layer dim shards over the `pipe` mesh axis (ppermute fill/drain
    pipeline, parallel/pipeline.py) — pipeline-parallel capability the
    reference's enum-only OP_PIPELINE never implements. Identical numerics
    to a sequential block stack by construction (same op, pipe axis 1)."""
    c = config or TransformerLMConfig()
    if (c.norm, c.position, c.mlp, c.qk_norm) != ("layernorm", "learned",
                                                  "gelu", False):
        raise NotImplementedError(
            f"build_transformer_lm_pipelined builds the GPT-2 block only "
            f"(ops/pipeline_blocks.py has its own block); got "
            f"norm={c.norm!r} position={c.position!r} mlp={c.mlp!r} "
            f"qk_norm={c.qk_norm}")
    bs = batch_size or ff.config.batch_size
    tokens = ff.create_tensor((bs, c.sequence_length), DataType.DT_INT32,
                              name="tokens")
    h = ff.embedding(tokens, c.vocab_size, c.hidden_size, name="wte")
    pos = ff.create_tensor((bs, c.sequence_length), DataType.DT_INT32,
                           name="positions")
    hp = ff.embedding(pos, c.sequence_length, c.hidden_size, name="wpe")
    h = ff.add(h, hp, name="embed_add")
    h = ff.pipeline_blocks(h, c.num_layers, c.num_heads, c.mlp_ratio,
                           num_microbatches=num_microbatches, causal=True,
                           attention_impl=c.attention_impl, name="blocks")
    h = ff.layer_norm(h, [2], name="ln_f")
    logits = ff.dense(h, c.vocab_size, use_bias=False, name="lm_head")
    return tokens, logits


def transformer_lm_param_count(c: TransformerLMConfig) -> int:
    """Trainable parameter count of the flagship LM (embeddings + blocks
    + final norm + head) — the zoo sizing / FSDP-capacity arithmetic."""
    d, L, v = c.hidden_size, c.num_layers, c.vocab_size
    per_layer = (4 * d * d + 4 * d          # attention qkv+o (+ biases)
                 + 2 * c.mlp_ratio * d * d  # mlp up + down
                 + c.mlp_ratio * d + d      # mlp biases
                 + 4 * d)                   # 2× layernorm scale+bias
    return (v * d + c.sequence_length * d   # wte + wpe
            + L * per_layer
            + 2 * d                         # final norm
            + v * d)                        # lm_head


def transformer_lm_state_bytes_per_chip(c: TransformerLMConfig,
                                        opt_slots: int = 2,
                                        update_stage: int = 0,
                                        shards: int = 1) -> float:
    """Resident fp32 training-state bytes per chip — master + grad +
    `opt_slots` optimizer entries per parameter — under a given
    weight-update stage. Stage 2 shards masters/grads/slots 1/shards but
    keeps one gathered compute copy resident per weight; stage 3
    (ZeRO-3/FSDP) shards the weights at rest too, so per-chip model
    state shrinks ~1/shards and the zoo grows past what one chip can
    hold replicated."""
    n = float(transformer_lm_param_count(c)) * 4.0
    state = n * (2 + opt_slots)
    if update_stage >= 3 and shards > 1:
        return state / shards
    if update_stage >= 2 and shards > 1:
        return n + state / shards
    return state


# The model zoo bench.py / the smokes draw from, ordered by scale. The
# `-fsdp` tiers are sized so their REPLICATED training state (masters +
# grads + Adam slots ≈ 16 bytes/param) exceeds a single chip of the
# named HBM class while the 1/shards stage-3 layout fits — the ZeRO-3
# enabler for growing the zoo past one replicated chip (ROADMAP item 5).
TRANSFORMER_LM_ZOO: dict = {
    # CPU-smoke scale: tiny, runs everywhere
    "lm-smoke": TransformerLMConfig(
        vocab_size=512, hidden_size=128, num_heads=4, num_layers=2,
        sequence_length=128, attention_impl="xla"),
    # speculative-decoding drafter for lm-smoke: same vocab + positional
    # extent (a drafter must share the target's tokenizer and reach
    # every position it decodes at — serving/speculative.py), a quarter
    # the width and half the depth
    "lm-smoke-draft": TransformerLMConfig(
        vocab_size=512, hidden_size=32, num_heads=2, num_layers=1,
        sequence_length=128, attention_impl="xla"),
    # the reference benchmark scale (transformer.cc:79-85)
    "lm-base": TransformerLMConfig(
        vocab_size=32000, hidden_size=1024, num_heads=16, num_layers=12,
        sequence_length=512),
    # drafter tier for lm-base: SpecInfer-style ~20x-smaller LM sharing
    # the 32k vocab and 512-token extent
    "lm-base-draft": TransformerLMConfig(
        vocab_size=32000, hidden_size=256, num_heads=4, num_layers=4,
        sequence_length=512),
    # ~1.3B params: replicated Adam state ≈ 21 GB — over one 16 GB chip,
    # under it at 1/4 stage-3 shards
    "lm-xl-fsdp": TransformerLMConfig(
        vocab_size=32000, hidden_size=2048, num_heads=32, num_layers=24,
        sequence_length=1024),
    # ~6.7B params: replicated Adam state ≈ 107 GB — needs stage 3 even
    # on 95 GB-class chips once activations are counted
    "lm-xxl-fsdp": TransformerLMConfig(
        vocab_size=32000, hidden_size=4096, num_heads=32, num_layers=32,
        sequence_length=2048),
}


def transformer_lm_flops_per_token(c: TransformerLMConfig) -> float:
    """Analytic fwd+bwd FLOPs/token for MFU accounting (6N_matmul + attn).
    The wte/wpe lookups are gathers (no matmul FLOPs); only the lm_head's
    v×d projection counts among the embedding-sized params."""
    d, L, s, v = c.hidden_size, c.num_layers, c.sequence_length, c.vocab_size
    params_per_layer = 4 * d * d + 2 * c.mlp_ratio * d * d
    n_matmul_params = L * params_per_layer + v * d  # lm_head only
    flops = 6.0 * n_matmul_params
    flops += L * 12.0 * d * s / 2  # causal attention scores+values fwd+bwd
    return flops
