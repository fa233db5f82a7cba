"""The plain reference of Mistral-Small-4's language model as
`build_transformer_lm` builds it from `mistral_small4_lm_config`: the
forward pass of one sequence. The benchmark's own copy of
`flexflow_tpu/models/mistral_small4_reference.py` (a later PR cannot move
the yardstick by editing the program's), with the comparison that decides
`correct` at its end.

float32, `jax.default_matmul_precision("highest")`, jax.numpy only: no
kernel, no cache, no batching, the EXPANDED form of latent attention (every
head's keys and values made from c_kv; nothing absorbed). Scores are
computed in blocks of query rows and of heads, the experts one after
another and the head in blocks of the vocabulary, so that a sequence of
seventeen or forty thousand tokens at the published widths fits beside the
program on one chip; the blocks change no number. A forward is five jitted
programs a length (`lowerings`).

The model (config.json of mistralai/Mistral-Small-4-119B-2603,
`model_type: mistral4`; what it leaves open is listed as `assumed` in
benchmarks/configs/mistral-small-4-119b.json):

- Block: h = h + MLA(RMSNorm(h)); h = h + MoE(RMSNorm(h)); eps
  `rms_norm_eps`, no biases. Final RMSNorm, untied head (over the slice of
  the vocabulary held).
- MLA (DeepSeek-V2's, arXiv:2405.04434): c_q = RMSNorm(x W_dq)
  (`q_lora_rank`); q = c_q W_uq, a head [q_C (`qk_nope_head_dim`) ; q_R
  (`qk_rope_head_dim`)]; [c_kv (`kv_lora_rank`) ; k_R] = x W_dkv, c_kv =
  RMSNorm(c_kv); q_R and k_R rotated in interleaved pairs (x[2i], x[2i+1])
  (`rope_interleave`) at YaRN's frequencies (`rope_parameters`: factor,
  original_max_position_embeddings, beta_fast / beta_slow, rope_theta; the
  cos / sin factor mscale / mscale_all_dim is 1), one k_R for all heads;
  [k_C,h ; v_h (`v_head_dim`)] = c_kv W_ukv; score_h(t, s) = a(t) scale
  (q_C,h(t) . k_C,h(s) + q_R,h(t) . k_R(s)), causal softmax over ALL s <=
  t (no selection), o_h = sum_s p v_h(s), out = concat(o_h) W_o.
  scale = (d_nope + d_rope)^-0.5 m^2, m = 0.1 mscale_all_dim ln(factor) + 1.
  a(t) = 1 + `llama_4_scaling_beta` ln(1 + floor(t /
  original_max_position_embeddings)) multiplies the query of position t
  (assumed: as transformers' get_llama_4_attn_scale applies it in the
  ministral3 model, to the whole query).
- MoE: p = softmax(x W_g) over all `n_routed_experts` in float32, the
  `num_experts_per_tok` largest, gates p_e / sum of the chosen
  (`norm_topk_prob`) times `routed_scaling_factor`; no groups (`n_group` =
  `topk_group` = 1), no correction bias (assumed: the config has neither
  `scoring_func` nor a bias; softmax is the family's convention). y = sum
  over the chosen experts HELD HERE of gate_e E_e(x) + S(x), E(x) =
  (SiLU(x W_gate) * x W_up) W_down at `moe_intermediate_size`, S the same
  at `n_shared_experts` x `moe_intermediate_size`. `intermediate_size` is
  read by no layer (`first_k_dense_replace` 0).

Every departure from the published model is a comment that starts with
"departure:". `get(node, weight)` returns the program's own array of that
name (wte.kernel, l<i>_ln1.scale, l<i>_attn.{wq_a, q_norm, wq_b, wkv_a,
kv_norm, wkv_b, wo}, l<i>_ln2.scale, l<i>_moe.{router, gate, up, down,
shared_gate, shared_up, shared_down}, ln_f.scale, lm_head.kernel). Linear
weights are stored (in, out).

Routing is discontinuous. Where the reference's k-th and (k+1)-th
probabilities of a token lie within a margin, a program in lower precision
may rightly pick otherwise: `forward` takes the program's choice
(`program`: per layer `experts` for the rows it names, a slot's decoded
rows or a chunk's) at exactly those tokens.

`spoil` computes one part of the model wrongly, for the controls that fix
the comparison's limits: "query_scale_off" leaves a(t) at 1, "yarn_off"
rotates at the plain frequencies and drops m^2 from the scale,
"rope_half_pairing" rotates pairs (x[i], x[i + d/2]), "renorm_off" leaves
the gates as the softmax gave them, "shared_off" leaves the shared expert
out, "e4m3" rounds every matrix to float8_e4m3fn. Beside them "bf16"
(no entry of SPOILS: it changes nothing off a TPU) runs every matmul at
the TPU's default precision, one bfloat16 pass.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import deepseek_v32_reference as dsa

SPOILS = (None, "query_scale_off", "yarn_off", "rope_half_pairing",
          "renorm_off", "shared_off", "e4m3")
_f32 = dsa._f32


def e4m3(a):
    """`a` rounded to float8_e4m3fn's 3 bits of mantissa, in float32
    arithmetic (on the chip XLA folds a cast there and back away)."""
    return jax.lax.reduce_precision(_f32(a), exponent_bits=4,
                                    mantissa_bits=3)


def _mat(a, spoil):
    return e4m3(a) if spoil == "e4m3" else _f32(a)


class Dims(NamedTuple):
    """What the attention's programs are compiled for."""
    heads: int
    nope: int
    rope: int
    value: int
    latent: int
    eps: float
    scale: float
    beta: float      # of a(t); 0 = none
    original: int
    interleaved: bool


def _scaling(cfg, spoil=None):
    """YaRN's numbers (`rope_parameters`), None under "yarn_off"."""
    return None if spoil == "yarn_off" else cfg["rope_parameters"]


def dims(cfg, spoil=None) -> Dims:
    rope = cfg["rope_parameters"]
    scaling = _scaling(cfg, spoil)
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if scaling:
        m = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1
        scale *= m * m
    return Dims(cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                cfg["kv_lora_rank"], cfg["rms_norm_eps"], scale,
                0.0 if spoil == "query_scale_off"
                else float(rope["llama_4_scaling_beta"]),
                int(rope["original_max_position_embeddings"]),
                spoil != "rope_half_pairing")


def inv_freq(cfg, spoil=None):
    # departure: YaRN at every position (the deployment's
    # max_position_embeddings 1,048,576 is over the original 8,192)
    return jnp.asarray(dsa.yarn_inv_freq(
        cfg["qk_rope_head_dim"], cfg["rope_parameters"]["rope_theta"],
        _scaling(cfg, spoil)))


def query_scale(positions, d: Dims):
    """a(t) (s,) float32."""
    return 1.0 + d.beta * jnp.log1p(
        (positions // d.original).astype(jnp.float32))


def _rope(x, angles, d: Dims):
    return (dsa.rope_interleaved if d.interleaved else dsa.rope_half)(
        x, angles)


ATTENTION_WEIGHTS = ("wq_a", "q_norm", "wkv_a", "kv_norm")


@functools.partial(jax.jit, static_argnames=("d", "spoil"))
def _attention_inputs(x, scale, w, positions, freqs, d, spoil=None):
    """What the attention of x (s, hidden) starts from: the query's latent
    c_q, the cached row (c_kv, k_R), the rotary angles and a(t). `scale`:
    the norm before the layer."""
    x = dsa.rms_norm(x, _f32(scale), d.eps)
    angles = positions.astype(jnp.float32)[:, None] * freqs  # (s, dr / 2)
    cq = dsa.rms_norm(x @ _mat(w["wq_a"], spoil), _f32(w["q_norm"]), d.eps)
    kv = x @ _mat(w["wkv_a"], spoil)
    ckv = dsa.rms_norm(kv[:, :d.latent], _f32(w["kv_norm"]), d.eps)
    kr = _rope(kv[:, d.latent:], angles, d)
    return cq, ckv, kr, angles, query_scale(positions, d)


def _attend_rows(q, k, v, mask, scale):
    """softmax(scale q . k) v over the masked positions: q (tb, h, dq), k
    (s, h, dq), v (s, h, dv), mask (tb, s) -> (tb, h, dv)."""
    scores = jnp.einsum("thd,shd->hts", q, k) * scale
    scores = jnp.where(mask[None], scores, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)


@functools.partial(jax.jit, static_argnames=("d", "row_block", "head_block",
                                             "spoil"))
def _attend(cq, ckv, kr, angles, a, wq_b, wkv_b, wo, d, row_block,
            head_block, spoil=None):
    """concat(o_h) W_o (s, hidden) of the expanded form, a block of heads
    after the other and, within it, a block of rows after the other, each
    row over every position up to its own."""
    s, dn, dr = cq.shape[0], d.nope, d.rope
    hb = min(head_block, d.heads)
    wq_b = wq_b.reshape(-1, d.heads // hb, hb, dn + dr).swapaxes(0, 1)
    wkv_b = wkv_b.reshape(d.latent, d.heads // hb, hb,
                          dn + d.value).swapaxes(0, 1)
    wo = wo.reshape(d.heads // hb, hb, d.value, -1)
    starts = jnp.arange(-(-s // row_block)) * row_block
    at = jnp.arange(s)

    def heads(u, w):
        q = jnp.einsum("tc,chd->thd", cq, _mat(w[0], spoil))
        q = jnp.concatenate(
            [q[..., :dn], _rope(q[..., dn:], angles[:, None], d)], axis=-1)
        q = q * a[:, None, None]
        kvh = jnp.einsum("sc,chd->shd", ckv, _mat(w[1], spoil))
        k = jnp.concatenate(
            [kvh[..., :dn], jnp.broadcast_to(kr[:, None], (s, hb, dr))],
            axis=-1)
        v = kvh[..., dn:]
        o = jax.lax.map(
            lambda part: _attend_rows(
                part[0], k, v,
                at[None] <= (part[1] + jnp.arange(row_block))[:, None],
                d.scale),
            (dsa._row_blocks(q, row_block), starts))
        o = o.reshape(-1, hb, d.value)[:s]
        return u + jnp.einsum("thd,hde->te", o, _mat(w[2], spoil)), None

    return jax.lax.scan(heads, jnp.zeros((s, wo.shape[-1]), jnp.float32),
                        (wq_b, wkv_b, wo))[0]


class Routing(NamedTuple):
    """What the router's program is compiled for: its width, the experts
    a token, whether the gates are renormalised, and their scale."""
    experts: int
    k: int
    norm: bool
    scale: float


def routing(cfg, spoil=None) -> Routing:
    """The router from the published keys: `n_routed_experts` in a cut
    configuration file counts the experts held (`reduced`),
    `experts_routed` the router's width."""
    return Routing(cfg.get("experts_routed", cfg["n_routed_experts"]),
                   cfg["num_experts_per_tok"],
                   bool(cfg["norm_topk_prob"]) and spoil != "renorm_off",
                   float(cfg["routed_scaling_factor"]))


def held_experts(cfg) -> tuple:
    return tuple(cfg.get("experts_held", (0, cfg["n_routed_experts"])))


def route(x, router, program_ids, margin, r: Routing):
    """(gates (t, k), ids used (t, k), near-tie mask (t,), the reference's
    own ids (t, k), gap (t,)) of tokens x (t, d): softmax over all the
    experts, the k largest, renormalised over the chosen. A token's gap is
    how far its choice is from another: the k-th probability less the
    next as a share of the k-th; a near-tie is a gap under `margin`, and
    there the program's ids (t, k) are used (a row of -1: not known)."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    top, own = jax.lax.top_k(probs, r.k + 1)
    gap = (top[:, r.k - 1] - top[:, r.k]) / top[:, r.k - 1]
    own = own[:, :r.k]
    tie = (gap < margin) & jnp.all(program_ids >= 0, axis=-1)
    ids = jnp.where(tie[:, None], program_ids, own)
    picked = jnp.take_along_axis(probs, ids, axis=-1)
    if r.norm:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return picked * r.scale, ids, tie, own, gap


EXPERT_WEIGHTS = ("router", "gate", "up", "down", "shared_gate",
                  "shared_up", "shared_down")


def _gated_mlp(x, gate, up, down, spoil):
    return (jax.nn.silu(x @ _mat(gate, spoil))
            * (x @ _mat(up, spoil))) @ _mat(down, spoil)


def _expert_layer(x, w, program_ids, margin, r, first, spoil):
    """S(x) + the sum over the chosen experts that are held here (ids
    `first` .. `first` + the experts in w["gate"]).
    departure: the published code gathers the rows routed to each expert;
    here every held expert runs on every token and a mask of gate weights
    picks: the same sum."""
    gates, ids, tie, own, gap = route(x, _f32(w["router"]), program_ids,
                                      margin, r)
    y = (jnp.zeros_like(x) if spoil == "shared_off" else _gated_mlp(
        x, w["shared_gate"], w["shared_up"], w["shared_down"], spoil))

    def expert(y, held):
        j, gate, up, down = held
        g = jnp.sum(jnp.where(ids == first + j, gates, 0.0), axis=-1)
        return y + g[:, None] * _gated_mlp(x, gate, up, down, spoil), None

    y = jax.lax.scan(expert, y, (jnp.arange(w["gate"].shape[0]), w["gate"],
                                 w["up"], w["down"]))[0]
    return y, {"ids": ids, "tie": tie, "own_ids": own, "gap": gap}


_expert_layer_jit = jax.jit(_expert_layer,
                            static_argnames=("r", "first", "spoil"))


def expert_layer(x, w, cfg, *, held, program_ids=None, margin=0.0,
                 spoil=None):
    """`_expert_layer` on its own: `held` = (first expert id, count),
    w["gate"], w["up"], w["down"] hold those experts only, in order."""
    r = routing(cfg, spoil)
    if w["gate"].shape[0] != held[1]:
        raise ValueError("the weights are not those of the experts held")
    return _expert_layer_jit(
        x, {name: w[name] for name in EXPERT_WEIGHTS},
        dsa._program_ids(program_ids, x.shape[0], r.k), margin, r=r,
        first=held[0], spoil=spoil)


@functools.partial(jax.jit, static_argnames=("eps", "r", "first", "spoil"))
def _expert_tail(x, u, scale, w, program_ids, margin, eps, r, first,
                 spoil=None):
    """A layer from its attention's output on: x + u, the norm, the
    experts, the residual."""
    x = x + u
    y, routed = _expert_layer(dsa.rms_norm(x, _f32(scale), eps), w,
                              program_ids, margin, r, first, spoil)
    return x + y, routed


@functools.partial(jax.jit, static_argnames=("eps", "spoil", "blocks"))
def _head(x, scale, lm_head, rows, eps, spoil=None, blocks=4):
    """The logits of `rows`, the vocabulary in `blocks` parts."""
    # departure: the vision tower is not the language model and is not held
    h = dsa.rms_norm(x[rows], _f32(scale), eps)
    step = -(-lm_head.shape[1] // blocks)
    return jnp.concatenate(
        [h @ _mat(lm_head[:, lo:lo + step], spoil)
         for lo in range(0, lm_head.shape[1], step)], axis=-1)


def _layer_attention(get, i: int, x, positions, freqs, d: Dims, row_block,
                     head_block, spoil):
    """(layer i's attention output on x (s, hidden), its cache rows (c_kv,
    k_R)) through the two jitted programs."""
    p = f"l{i}_"
    cq, ckv, kr, angles, a = _attention_inputs(
        x, get(p + "ln1", "scale"),
        {name: get(p + "attn", name) for name in ATTENTION_WEIGHTS},
        positions, freqs, d=d, spoil=spoil)
    u = _attend(cq, ckv, kr, angles, a, get(p + "attn", "wq_b"),
                get(p + "attn", "wkv_b"), get(p + "attn", "wo"), d=d,
                row_block=row_block, head_block=head_block, spoil=spoil)
    return u, (ckv, kr)


def forward(get, tokens, config, *, program=None, route_margin=0.0,
            row_block=128, head_block=8, rows=None, spoil=None):
    """(logits (s, vocab) float32 numpy, notes) of the causal forward over
    one sequence `tokens` (s,) at positions 0 .. s - 1; with `rows`, the
    logits of those positions only. `program`: per layer {"experts": {row:
    ids (k,)}} of the program's own routing at the rows it names, used at
    near-ties only. notes: per layer, `route`'s readings and `attended`,
    the attention's output (after W_o) at `rows`. The weights stay as the
    program holds them and are upcast where they are used."""
    if spoil not in (*SPOILS, "bf16"):
        raise ValueError(f"spoil is one of {SPOILS} or 'bf16', got {spoil!r}")
    d, r = dims(config, spoil), routing(config, spoil)
    eps, first = config["rms_norm_eps"], held_experts(config)[0]
    tokens = jnp.asarray(tokens, jnp.int32).reshape(-1)
    s = tokens.shape[0]
    positions = jnp.arange(s, dtype=jnp.int32)
    rows = positions if rows is None else jnp.asarray(rows, jnp.int32)
    freqs = inv_freq(config, spoil)
    notes = []
    with jax.default_matmul_precision(
            "default" if spoil == "bf16" else "highest"):
        x = dsa._embed(get("wte", "kernel"), tokens)
        for i in range(config["num_hidden_layers"]):
            p = f"l{i}_"
            u, _ = _layer_attention(get, i, x, positions, freqs, d,
                                    min(row_block, s), head_block, spoil)
            ids = None
            chosen = (program or {}).get(i, {}).get("experts")
            if chosen:
                ids = np.full((s, r.k), -1, np.int32)
                for row, mine in chosen.items():
                    ids[row] = mine
            x, routed = _expert_tail(
                x, u, get(p + "ln2", "scale"),
                {name: get(p + "moe", name) for name in EXPERT_WEIGHTS},
                dsa._program_ids(ids, s, r.k), route_margin, eps=eps, r=r,
                first=first, spoil=spoil)
            notes.append({**routed, "attended": u[rows]})
        logits = _head(x, get("ln_f", "scale"), get("lm_head", "kernel"),
                       rows, eps=eps, spoil=spoil)
    return np.asarray(logits, np.float32), notes


def first_layer(get, tokens, config, rows, *, row_block=128, head_block=8,
                spoil=None):
    """(the first layer's attention output at positions `rows` (n, hidden),
    its cache rows [c_kv ; k_R] of every position (s, latent + rope)),
    float32 numpy: what a layer gives whose input no other layer has
    touched (the embedding's row of each token), so that a comparison with
    it is the attention's alone: the programs are `forward`'s own."""
    d = dims(config, spoil)
    tokens = jnp.asarray(tokens, jnp.int32).reshape(-1)
    s = tokens.shape[0]
    with jax.default_matmul_precision(
            "default" if spoil == "bf16" else "highest"):
        u, cached = _layer_attention(
            get, 0, dsa._embed(get("wte", "kernel"), tokens),
            jnp.arange(s, dtype=jnp.int32), inv_freq(config, spoil), d,
            min(row_block, s), head_block, spoil)
    return (np.asarray(u[jnp.asarray(rows, jnp.int32)], np.float32),
            np.concatenate([np.asarray(a) for a in cached], axis=-1))


def lowerings(get, config, length, *, named=128, row_block=128,
              head_block=8) -> list:
    """[(name, jax.stages.Lowered)]: the programs `forward` runs over
    `length` tokens with `named` rows asked for, lowered and not compiled,
    for a caller that compiles them ahead of the forward and beside other
    work (they are the forward's own jitted functions at its own shapes,
    so the forward finds them in the compile cache). Every layer is of one
    kind and shares its programs."""
    d, r, eps = dims(config), routing(config), config["rms_norm_eps"]
    s, block = length, min(row_block, length)
    out = []

    def like(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    # what a program returns lies where the weights lie, and is committed
    # there: a program lowered for an argument that is not is another one
    placed = getattr(get("wte", "kernel"), "sharding", None)
    if isinstance(placed, jax.sharding.NamedSharding):
        placed = jax.sharding.NamedSharding(
            placed.mesh, jax.sharding.PartitionSpec())

    def add(fn, *args, **static):
        out.append((f"{fn.__name__}@{s}", fn.lower(*args, **static)))
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=placed),
            jax.eval_shape(functools.partial(fn, **static), *args))

    def attn(name):
        return get("l0_attn", name)

    whole = -(-named // block) * block
    with jax.default_matmul_precision("highest"):
        x = add(dsa._embed, get("wte", "kernel"), like(s, dtype=jnp.int32))
        cq, ckv, kr, angles, a = add(
            _attention_inputs, x, get("l0_ln1", "scale"),
            {name: attn(name) for name in ATTENTION_WEIGHTS},
            like(s, dtype=jnp.int32), like(d.rope // 2), d=d, spoil=None)
        u = add(_attend, cq, ckv, kr, angles, a, attn("wq_b"), attn("wkv_b"),
                attn("wo"), d=d, row_block=block, head_block=head_block,
                spoil=None)
        add(_expert_tail, x, u, get("l0_ln2", "scale"),
            {name: get("l0_moe", name) for name in EXPERT_WEIGHTS},
            like(s, r.k, dtype=jnp.int32), 0.0, eps=eps, r=r,
            first=held_experts(config)[0], spoil=None)
        add(_head, x, get("ln_f", "scale"), get("lm_head", "kernel"),
            like(whole, dtype=jnp.int32) if named
            else like(s, dtype=jnp.int32), eps=eps, spoil=None)
    return out


# What decides `correct` in `ms4-serve-longctx` (jobs/serve_longctx.py), at
# the decoded rows of the pre-window check (a context of 2,300 tokens,
# a(t) = 1) and of two streams the loop served (contexts near 17 k and
# 41 k, where a(t) has taken two and four or five steps; all 16 slots live,
# through the engine's own block manager, radix match and copy-on-write):
# the logits, and EVERY layer's attention output there, which the step's
# own program keeps (the decode op's `attended`), because in this seeded
# model the attention is under a hundredth of the residual stream at such
# contexts and the logits hardly see it; the reference evaluated under the
# program's routing where its own lies at a near-tie, at the decoded rows
# and at the prompt's (a chunk's rows' `chunk_expert_ids`). Beside them,
# as a second witness, the FIRST layer's attention output and cache rows
# of those two sessions' prompts, read alone (`first_layer`; the job's
# `first_layer_probe`). Each limit stands between two readings (my chip
# runs, PR 46; PERF.md section 6 has the table): the largest of the sound
# runs, and the controls, which have to come out not correct: every entry
# of SPOILS, and the streams replayed with one cached block of 256 rows of
# their history zeroed in every layer.
#
# LOGIT_TOL: max |program logit - reference logit| over the compared rows
# as a share of the largest |reference logit| there (bf16 against
# float32). Sound 0.0080-0.0128 (some 100 checks and streams of 35 runs, at
# 2.3 k, 17 k and 41 k alike); YaRN off 0.030-0.087, the rotation's pairing
# 0.034-0.080, the gates not renormalised 0.28-0.49, e4m3 weights
# 0.78-1.05, the shared expert left out 1.04-1.30. NOT told here: the
# query scale left out 0.0091-0.0152 and a lost block 0.0085-0.0111
# (LAYER_ATTEND_TOL, ATTEND_TOL and CACHE_TOL tell them), and a reference
# at the TPU's default matmul precision, one bfloat16 pass, 0.0107-0.0125:
# a second bf16 computation is as far from the float32 one as the program
# is, and no limit that passes the program refuses it. 0.02 is 1.6 times
# the one and two thirds of the smallest other.
#
# LAYER_ATTEND_TOL: a layer's attention output (after W_o) at ALL the
# compared rows of a sequence, as the replayed step's own program kept it,
# less the reference's, norm over norm (`compare`'s `attend_errors`; the
# largest entry's share swings with one entry of two million: 0.006-0.033
# over the same runs). Sound 0.0055-0.0174 over 282 readings (47 sequences
# of 17 runs x 6 layers), growing with depth as the input's rounding does
# (layer 0 0.0055-0.0074, layer 5 0.0116-0.0174), within 5 % of itself
# from seed to seed; a lost block 0.0332-0.0363 at 41 k and 0.0652-0.0718
# at 17 k in every layer; the query scale left out 0.157-0.168 at 17 k and
# 0.207-0.220 at 41 k in every layer (and nothing at 2.3 k, where a(t) is
# 1); the gates not renormalised 0.13-0.35 and the shared expert left out
# 0.88-1.18 from layer 1 on; the pairing 0.68-1.06, e4m3 0.83-1.26, YaRN
# off 1.01-1.34. Not told: the bf16 reference, 0.0060-0.0195. WITHOUT the
# prompt rows' experts handed to the reference the sound readings of
# layers 2-5 were 0.02-0.06 by the largest entry (two runs): 3 % of a
# history's rows a layer are routed otherwise by the bf16 program, and a
# decoded row's attention averages over them. 0.025 is 1.44 times the one
# and 0.75 of the smallest other.
#
# ATTEND_TOL: max |program - reference| over the largest |reference| entry
# of the first layer's attention output (after W_o) at the last 16 cached
# positions of a compared session's prompt, the program's decode op (on
# the chip the paged latent kernel over the 17 k or 41 k rows the loop
# left in the pool) called once more outside any step, against the
# expanded form. Sound 0.0042-0.0092 (60 readings); a lost block
# 0.033-0.048 (41 k) and 0.067-0.086 (17 k), the query scale left out
# 0.16-0.24, the pairing 0.69-0.77, e4m3 0.69-0.94, YaRN off 0.95-1.40;
# the router's spoils do not reach the first layer's attention. 0.02 is
# 2.2 times the one and 0.61 of the smallest other.
#
# CACHE_TOL: the same of the pool's first-layer rows [c_kv ; k_R] of the
# whole prompt. A row is a function of its own token and position, so the
# reading is rounding: sound 0.0048-0.0065; e4m3 0.32-0.37, a lost block
# 0.74-0.92, YaRN off 1.73-1.93, the pairing 1.65-1.92. 0.02 is three
# times the one and a sixteenth of the smallest other.
#
# ROUTE_MARGIN: keye_vl2_reference.route's rule: the reference takes the
# program's experts at a token whose gap (the 4th probability less the
# 5th, as a share of the 4th) is under this; a decoded row the program
# routed otherwise at a larger gap makes the run not correct. The reading
# is the largest gap at which the two chose otherwise: sound 0.000-0.061
# over some 140,000 routings of 33 runs (0.050 the largest of the last
# nine; the two choose otherwise at 2.4-3.7 % of the decoded rows'
# routings); YaRN off 0.090-0.136 (told in four sequences of six, by 1-3
# rows), the pairing 0.097-0.165 (in five of six, by 1-8 rows), the gates
# not renormalised 0.26-0.50 (289-407 rows at 17-41 k), e4m3 0.29-0.71,
# the shared expert left out 0.45-0.70; the query scale left out, at most
# 0.045, is not told here. At
# 0.10 the reference takes the program's experts at 51-53 % of the
# routings (softmax probabilities of a seeded router lie a few per cent
# apart), where 0.15 took two thirds. 0.10 is 1.64 times the one and 1.1
# of the smallest it tells at all.
LOGIT_TOL = 0.02
ATTEND_TOL = 0.02
LAYER_ATTEND_TOL = 0.025
CACHE_TOL = 0.02
ROUTE_MARGIN = 0.10
# no layer selects: the keys the session job's report reads of a selection
SEL_MARGIN = 0.0
MAX_OUTSIDE = 0
# the compared rows of a sequence come in whole blocks of this many: a
# reply is at most 512 tokens, so every comparison is one shape a length
ROWS = 512
logit_error = dsa.logit_error


def compare(get, tokens, config, rows, program, pad_to=None,
            spoil=None) -> dict:
    """The program's logits `rows` {position: (vocab,)} of one sequence
    against the reference's full forward over `tokens`, under the program's
    routing `program` (`forward`) at near-ties; the interface of
    deepseek_v32_reference.compare, whose selection readings are empty
    here, and `attend_errors`: a layer, the norm of (program's attention
    output - reference's) over all the compared rows as a share of the
    reference's norm there (`program[layer]["attended"]`;
    LAYER_ATTEND_TOL). The forward runs over the tokens padded to `pad_to`, or to a
    whole number of 256 (causal: no compared row sees the padding)."""
    tokens = list(tokens)
    length = pad_to or len(tokens) + -len(tokens) % 256
    if length < len(tokens):
        raise ValueError(f"{len(tokens)} tokens do not fit {pad_to}")
    at = sorted(rows)
    full, notes = forward(get, tokens + [0] * (length - len(tokens)), config,
                          program=program, route_margin=ROUTE_MARGIN,
                          rows=at + at[-1:] * (-len(at) % ROWS), spoil=spoil)
    mine = np.stack([np.asarray(rows[t], np.float32) for t in at])
    ref = full[:len(at)]
    by_row = (np.max(np.abs(mine - ref), axis=-1) / np.max(np.abs(ref)))
    ties = [np.asarray(note["tie"])[at] for note in notes]
    gaps = []  # of the tokens the program routed otherwise
    for i, note in enumerate(notes):
        chosen = program.get(i, {}).get("experts", {})
        if chosen:
            own, gap = np.asarray(note["own_ids"]), np.asarray(note["gap"])
            gaps += [float(gap[t]) for t in at if t in chosen
                     and set(np.asarray(chosen[t]).tolist())
                     != set(own[t].tolist())]
    # each layer's attention output at the compared rows, as the step's own
    # program kept it (the state leaf `attended`), where it kept them all
    attend = []
    for i, note in enumerate(notes):
        kept = program.get(i, {}).get("attended", {})
        if all(t in kept for t in at):
            want = np.asarray(note["attended"], np.float32)[:len(at)]
            attend.append(round(float(
                np.linalg.norm(np.stack([kept[t] for t in at]) - want)
                / np.linalg.norm(want)), 5))
    return {
        "error": logit_error(mine, ref),
        "error_by_row": by_row.round(4).tolist(),
        "attend_errors": attend,
        "sel_bad": 0, "sel_taken": 0, "sel_rows": 0, "outside_max": 0,
        "shortfall_max": 0.0,
        "route_rows": sum(t.size for t in ties),
        "route_taken": int(sum(t.sum() for t in ties)),
        "route_differs": len(gaps),
        "route_gap_max": max(gaps, default=0.0),
        "route_bad": sum(g >= ROUTE_MARGIN for g in gaps),
    }
