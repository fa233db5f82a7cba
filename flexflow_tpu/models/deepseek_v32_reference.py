"""The plain reference of the DeepSeek-V3.2 model as `build_transformer_lm`
builds it from `deepseek_v32_lm_config`: the forward pass of one sequence.

float32, `jax.default_matmul_precision("highest")`, jax.numpy only: no
kernel, no cache, no batching, the expanded form of latent attention, the
indexer and its selection as dense masks. Scores are computed in blocks of
query rows and of heads so that a sequence of some ten thousand tokens at
the published widths fits beside the program on one chip; the blocks change
no number. A forward is nine jitted programs a length (`lowerings`), the
blocks steps of `lax.map` and `lax.scan` inside them; only the rows the
program names come to the host (`select`).

The model, as published (DeepSeek-V2, arXiv:2405.04434, for latent
attention; DeepSeek-V3, arXiv:2412.19437, for the router; the
DeepSeek-V3.2-Exp report and its reference inference code for the indexer):

- Block: h = h + Attn(RMSNorm(h)); h = h + FFN(RMSNorm(h)); eps 1e-6, no
  biases. Final RMSNorm, untied head.
- Latent attention: cQ = RMSNorm(W_dq x); q_i = W_uq,i cQ = [q_i^nope ;
  q_i^rope], q_i^rope rotated. [cKV ; k^r] = W_dkv x, cKV = RMSNorm(cKV),
  k^R = RoPE(k^r), one rotary key for all heads. [k_i^nope ; v_i] =
  W_ukv,i cKV, k_i = [k_i^nope ; k^R], p = softmax(scale q_i . k_i) over
  the selected positions, o_i = sum p v, u = W_o [o_1 .. o_H].
  scale = (d_nope + d_rope)^-0.5 m^2, m = 0.1 mscale_all_dim ln(factor) + 1.
  RoPE: interleaved pairs, YaRN frequencies.
- Lightning indexer: qI_t,j = W_iq,j cQ_t; kI_s = LayerNorm(W_ik x_s); the
  first d_rope of each head rotated (YaRN frequencies, half-rotation
  pairing); w_t = W_iw x_t n_heads^-0.5 head_dim^-0.5; I_t,s = sum_j w_t,j
  ReLU(qI_t,j . kI_s); S_t = the index_topk positions s <= t of largest
  I_t,s, all of them while t < index_topk. Attention of t runs over S_t.
- Expert layer: s = sigmoid(x R) over all routed experts; selection score
  s + b; groups scored by the sum of their two largest s + b, the
  topk_group best kept; the k largest s + b among them chosen; gates g_e =
  routed_scaling_factor s_e / sum_chosen s. y = Shared(x) + sum_chosen g_e
  E_e(x), E(x) = W_down(SiLU(W_gate x) * W_up x). On one chip of a
  deployment the layer holds experts e0 .. e0 + n - 1 and the sum runs
  over the chosen experts that are held; what the others would add is left
  out.
- Dense layers: SiLU-gated MLP.

Every departure from the published model is a comment that starts with
"departure:". `get(node, weight)` returns the program's own array of that
name (wte.kernel, l<i>_ln1.scale, l<i>_attn.{wq_a, q_norm, wq_b, wkv_a,
kv_norm, wkv_b, wo, wi_q, wi_k, wi_k_norm, wi_k_bias, wi_w},
l<i>_ln2.scale, l<i>_ffn_{gate, up, down}.kernel or l<i>_moe.{router,
router_bias, gate, up, down, shared_gate, shared_up, shared_down},
ln_f.scale, lm_head.kernel). Linear weights are stored (in, out).

Selection and routing are discontinuous. Where the reference's k-th and
(k+1)-th scores of a position lie within a margin, a program in lower
precision may rightly pick otherwise: `forward` takes the program's choice
(`program`: per layer `sel` and `experts` for the rows it names) and uses
it at exactly those positions, and says where the program chose an index or
an expert the reference scores lower than its k-th less the margin.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def yarn_inv_freq(dim, theta, scaling):
    """The rotary frequencies (dim / 2,) under YaRN (precompute_freqs_cis
    of the reference inference code): each frequency divided by `factor`
    where its wavelength is longer than the original context, kept where
    it turns more than beta_fast times in it, blended in between."""
    freqs = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    if not scaling:
        return freqs.astype(np.float32)
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    smooth = 1.0 - ramp
    return (freqs / scaling["factor"] * (1 - smooth)
            + freqs * smooth).astype(np.float32)


def softmax_scale(cfg):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scaling = cfg.get("rope_scaling")
    if scaling:
        m = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1
        scale *= m * m
    return scale


def rope_interleaved(x, angles):
    """x (.., d) rotated in pairs (x[2i], x[2i+1]) by angles (.., d / 2)."""
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def rope_half(x, angles):
    """x (.., d) rotated in pairs (x[i], x[i + d/2]) by angles (.., d / 2)."""
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _blocks(n, size):
    return [(lo, min(lo + size, n)) for lo in range(0, n, size)]


def _row_blocks(a, size):
    """a (s, ..) as (blocks, size, ..), the last block filled up with
    copies of the last row (a caller drops them again)."""
    pad = -a.shape[0] % size
    a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1), mode="edge")
    return a.reshape(-1, size, *a.shape[1:])


class Dims(NamedTuple):
    """The widths the attention's programs are compiled for."""
    heads: int
    nope: int
    rope: int
    value: int
    latent: int
    eps: float
    index_heads: int
    index_dim: int
    index_eps: float
    topk: int
    scale: float


def _dims(cfg) -> Dims:
    # LayerNorm's eps is `assumed` (1e-6, the reference code's default)
    return Dims(cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                cfg["kv_lora_rank"], cfg["rms_norm_eps"],
                cfg["index_n_heads"], cfg["index_head_dim"],
                cfg.get("index_norm_eps", 1e-6), cfg["index_topk"],
                softmax_scale(cfg))


def _index_block(qi, wt, ki, rows, topk):
    """Index scores I (tb, s) of query rows at positions `rows` (tb,)
    against all keys, the causal mask applied; the positions each row
    attends as a mask (tb, s); the k-th and (k+1)-th largest score of
    each row (NEG where a row has no more candidates)."""
    scores = jnp.einsum("tjd,sd->tjs", qi, ki)
    index = jnp.sum(wt[:, :, None] * jax.nn.relu(scores), axis=1)
    s = ki.shape[0]
    causal = jnp.arange(s)[None, :] <= rows[:, None]
    index = jnp.where(causal, index, NEG)
    if s > topk:
        # exactly topk positions, ties to the lower position (a ReLU makes
        # exact zeros where indexer heads are few)
        top, sel = jax.lax.top_k(index, topk + 1)
        kth, nxt = top[:, topk - 1], top[:, topk]
        mask = causal & jnp.zeros_like(causal).at[
            jnp.arange(sel.shape[0])[:, None], sel[:, :topk]].set(True)
    else:
        kth = nxt = jnp.full((qi.shape[0],), NEG, jnp.float32)
        mask = causal
    return index, mask, kth, nxt


@functools.partial(jax.jit, static_argnames=("topk", "row_block"))
def _select_all(qi, wt, ki, topk, row_block):
    """The mask (s, s) of the positions every row attends: `_index_block`
    over one block of rows after the other."""
    s = ki.shape[0]

    def block(part):
        return _index_block(part[0], part[1], ki, part[2], topk)[1]

    mask = jax.lax.map(block, tuple(
        _row_blocks(a, row_block) for a in (qi, wt, jnp.arange(s))))
    return mask.reshape(-1, s)[:s]


@functools.partial(jax.jit, static_argnames=("topk",))
def _named_rows(qi, wt, ki, at, topk):
    """`_index_block` of the rows at positions `at` (n,)."""
    return _index_block(qi[at], wt[at], ki, at, topk)


@jax.jit
def _set_rows(mask, at, rows):
    return mask.at[at].set(rows)


def select(qi, wt, ki, topk, *, program_sel=None, margin=0.0,
           max_outside=0, row_block=128):
    """(mask (s, s) bool of the positions each row attends, rows where the
    program's set was taken, rows where the program's set is not one the
    reference allows, {row: (outside, shortfall)}). `program_sel`: {row:
    indices (topk,) int, -1 where the program had fewer}. The program's
    set is allowed where it has topk distinct positions of the row's past
    of which at most `max_outside` score, in the reference, below its k-th
    less the margin (a key whose own hidden state the program computed
    under another choice of experts scores otherwise in both); it is
    taken where it is allowed and either the k-th and the next score are
    within the margin or some of its positions are outside. The two
    readings of a row say how far its set is from refused: `outside`, how
    many of its positions score below the k-th less the margin, and
    `shortfall`, by what share of the row's largest |score| the
    (max_outside + 1)-th lowest of them lies under the k-th (the set is
    allowed iff outside <= max_outside iff shortfall <= margin)."""
    s = ki.shape[0]
    block = min(row_block, s)
    mask = _select_all(qi, wt, ki, topk=topk, row_block=block)
    named = sorted(t for t in (program_sel or {}) if topk <= t < s)
    taken, bad, readings = [], [], {}
    if not named:
        return mask, taken, bad, readings
    # only these rows come to the host, as a whole number of blocks (the
    # last of them repeated) so that their programs compile for one shape
    at = jnp.asarray(named + named[-1:] * (-len(named) % block), jnp.int32)
    index, rows, kth, nxt = (np.array(a) for a in _named_rows(
        qi, wt, ki, at, topk=topk))
    for r, t in enumerate(named):
        row = index[r]
        largest = float(np.max(np.abs(row[:t + 1])))
        span = margin * largest
        chosen = np.asarray(program_sel[t])
        chosen = chosen[chosen >= 0]
        short = np.sort(kth[r] - row[chosen])[::-1]  # lowest first
        outside = int(np.sum(short > span))
        readings[t] = (outside, max(
            float(short[max_outside]) / max(largest, 1e-30), 0.0)
            if short.size > max_outside else 0.0)
        if (len(set(chosen.tolist())) != topk or chosen.max() > t
                or outside > max_outside):
            bad.append(t)
        elif outside or kth[r] - nxt[r] < span:
            rows[r] = False
            rows[r, chosen] = True
            taken.append(t)
    rows[len(named):] = rows[len(named) - 1]
    return _set_rows(mask, at, jnp.asarray(rows)), taken, bad, readings


def _attend_rows(q, k, v, mask, scale):
    """softmax(scale q . k) v over the masked positions: q (tb, h, dq), k
    (s, h, dq), v (s, h, dv), mask (tb, s) -> (tb, h, dv)."""
    scores = jnp.einsum("thd,shd->hts", q, k) * scale
    scores = jnp.where(mask[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hts,shd->thd", probs, v)


ATTENTION_WEIGHTS = ("wq_a", "q_norm", "wkv_a", "kv_norm", "wi_q", "wi_k",
                     "wi_k_norm", "wi_k_bias", "wi_w")


@functools.partial(jax.jit, static_argnames=("d",))
def _attention_inputs(x, scale, w, positions, inv_freq, d):
    """What the selection and the attention of x (s, hidden) start from:
    the query's latent cQ, the cached row (cKV, k^R), the rotary angles,
    and the indexer's queries, keys and head weights. `scale`: the norm
    before the layer, None where x has been through it."""
    if scale is not None:
        x = rms_norm(x, _f32(scale), d.eps)
    w = {name: _f32(a) for name, a in w.items()}
    angles = positions.astype(jnp.float32)[:, None] * inv_freq  # (s, dr/2)
    cq = rms_norm(x @ w["wq_a"], w["q_norm"], d.eps)
    kv = x @ w["wkv_a"]
    ckv = rms_norm(kv[:, :d.latent], w["kv_norm"], d.eps)
    kr = rope_interleaved(kv[:, d.latent:], angles)
    # the indexer
    # departure: bfloat16 in the program, FP8 in the published system; the
    # Hadamard rotation before its quantisation is orthogonal on both
    # sides of q . k and is left out
    s, dr = x.shape[0], d.rope
    qi = (cq @ w["wi_q"]).reshape(s, d.index_heads, d.index_dim)
    qi = jnp.concatenate([rope_half(qi[..., :dr], angles[:, None]),
                          qi[..., dr:]], axis=-1)
    ki = layer_norm(x @ w["wi_k"], w["wi_k_norm"], w["wi_k_bias"],
                    d.index_eps)
    ki = jnp.concatenate([rope_half(ki[:, :dr], angles), ki[:, dr:]],
                         axis=-1)
    wt = (x @ w["wi_w"]) * (d.index_heads ** -0.5) * (d.index_dim ** -0.5)
    return cq, ckv, kr, angles, qi, ki, wt


@functools.partial(jax.jit, static_argnames=("d", "row_block", "head_block"))
def _attend(cq, ckv, kr, angles, mask, wq_b, wkv_b, wo, d, row_block,
            head_block):
    """W_o [o_1 .. o_H] (s, hidden) of the expanded form, a block of heads
    after the other and, within it, a block of rows after the other."""
    s, dn, dr = cq.shape[0], d.nope, d.rope
    hb = min(head_block, d.heads)
    wq_b = wq_b.reshape(-1, d.heads // hb, hb, dn + dr).swapaxes(0, 1)
    wkv_b = wkv_b.reshape(d.latent, d.heads // hb, hb,
                          dn + d.value).swapaxes(0, 1)
    wo = wo.reshape(d.heads // hb, hb, d.value, -1)
    masks = _row_blocks(mask, row_block)

    def heads(u, w):
        q = jnp.einsum("tc,chd->thd", cq, _f32(w[0]))
        q = jnp.concatenate(
            [q[..., :dn], rope_interleaved(q[..., dn:], angles[:, None])],
            axis=-1)
        kvh = jnp.einsum("sc,chd->shd", ckv, _f32(w[1]))
        k = jnp.concatenate(
            [kvh[..., :dn], jnp.broadcast_to(kr[:, None], (s, hb, dr))],
            axis=-1)
        v = kvh[..., dn:]
        o = jax.lax.map(
            lambda part: _attend_rows(part[0], k, v, part[1], d.scale),
            (_row_blocks(q, row_block), masks))
        o = o.reshape(-1, hb, d.value)[:s]
        return u + jnp.einsum("thd,hde->te", o, _f32(w[2])), None

    return jax.lax.scan(heads, jnp.zeros((s, wo.shape[-1]), jnp.float32),
                        (wq_b, wkv_b, wo))[0]


def latent_attention(x, w, positions, cfg, *, scale=None, program_sel=None,
                     sel_margin=0.0, max_outside=0, row_block=128,
                     head_block=8):
    """Latent attention with the indexer's selection on x (s, d) at
    `positions` (s,): (output (s, d), rows taken, rows bad, the rows'
    readings: `select`). `scale`: the norm x goes through first, if any."""
    d = _dims(cfg)
    inv_freq = jnp.asarray(yarn_inv_freq(d.rope, cfg["rope_theta"],
                                         cfg.get("rope_scaling")))
    cq, ckv, kr, angles, qi, ki, wt = _attention_inputs(
        x, scale, {name: w[name] for name in ATTENTION_WEIGHTS},
        jnp.asarray(positions, jnp.int32), inv_freq, d=d)
    mask, taken, bad, readings = select(
        qi, wt, ki, d.topk, program_sel=program_sel, margin=sel_margin,
        max_outside=max_outside, row_block=row_block)
    u = _attend(cq, ckv, kr, angles, mask, w["wq_b"], w["wkv_b"], w["wo"],
                d=d, row_block=min(row_block, x.shape[0]),
                head_block=head_block)
    return u, taken, bad, readings


def _gated_mlp(x, gate, up, down, col_block):
    y = 0.0
    for c0, c1 in _blocks(gate.shape[1], col_block):
        g, u, d = _f32(gate[:, c0:c1]), _f32(up[:, c0:c1]), _f32(down[c0:c1])
        y = y + (jax.nn.silu(x @ g) * (x @ u)) @ d
    return y


@functools.partial(jax.jit, static_argnames=("col_block",))
def gated_mlp(x, gate, up, down, col_block=4608):
    """W_down(SiLU(W_gate x) * W_up x), in blocks of the hidden width (the
    sum over the width's blocks is the same sum): only a block's weights
    are ever held in float32."""
    return _gated_mlp(x, gate, up, down, col_block)


class Routing(NamedTuple):
    """What the router's program is compiled for: its width, the experts
    a token, the groups and how many are kept, whether the gates are
    renormalised, and their scale."""
    experts: int
    k: int
    groups: int
    keep: int
    norm: bool
    scale: float


def _routing(cfg) -> Routing:
    return Routing(cfg["n_routed_experts_total"], cfg["num_experts_per_tok"],
                   cfg["n_group"], cfg["topk_group"],
                   bool(cfg["norm_topk_prob"]), cfg["routed_scaling_factor"])


def route(x, router, bias, program_ids, margin, r):
    """(gates (t, k), ids used (t, k), near-tie mask (t,), the reference's
    own ids (t, k), gap (t,)) of tokens x (t, d): DeepSeek-V3's Gate with
    sigmoid scores, the correction bias in the selection only,
    group-limited. A token's gap is how far its choice is from another:
    the k-th selection score less the next as a share of the k-th, or the
    last kept group's score less the first dropped one's as a share of
    it, whichever is smaller; a near-tie is a gap under `margin`, and
    there the program's ids (t, k) are used (a row of -1: not known)."""
    n, k, groups, keep = r.experts, r.k, r.groups, r.keep
    scores = jax.nn.sigmoid(x @ router)
    biased = scores + bias
    grouped = biased.reshape(-1, groups, n // groups)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    gtop, gidx = jax.lax.top_k(group_score, min(keep + 1, groups))
    kept = jnp.any(jax.nn.one_hot(gidx[:, :keep], groups, dtype=bool), axis=1)
    masked = jnp.where(jnp.repeat(kept, n // groups, axis=1), biased,
                       -jnp.inf)
    top, own = jax.lax.top_k(masked, k + 1)
    gap = (top[:, k - 1] - top[:, k]) / jnp.abs(top[:, k - 1])
    if keep < groups:
        gap = jnp.minimum(gap, (gtop[:, keep - 1] - gtop[:, keep])
                          / jnp.abs(gtop[:, keep - 1]))
    own = own[:, :k]
    # rows of -1: the program's choice there is not known
    tie = (gap < margin) & jnp.all(program_ids >= 0, axis=-1)
    ids = jnp.where(tie[:, None], program_ids, own)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    if r.norm:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return picked * r.scale, ids, tie, own, gap


def _program_ids(program_ids, tokens: int, k: int):
    if program_ids is None:
        return jnp.full((tokens, k), -1, jnp.int32)
    return jnp.asarray(program_ids, jnp.int32).reshape(tokens, k)


def _expert_layer(x, w, program_ids, margin, r, first, col_block):
    gates, ids, tie, own, gap = route(
        x, _f32(w["router"]), _f32(w["router_bias"]), program_ids, margin, r)
    y = _gated_mlp(x, w["shared_gate"], w["shared_up"], w["shared_down"],
                   col_block)

    def expert(y, held):
        j, gate, up, down = held
        g = jnp.sum(jnp.where(ids == first + j, gates, 0.0), axis=-1)
        return y + g[:, None] * _gated_mlp(x, gate, up, down, col_block), None

    y = jax.lax.scan(expert, y, (jnp.arange(w["gate"].shape[0]), w["gate"],
                                 w["up"], w["down"]))[0]
    return y, {"ids": ids, "tie": tie, "own_ids": own, "gap": gap}


_expert_layer_jit = jax.jit(
    _expert_layer, static_argnames=("r", "first", "col_block"))
EXPERT_WEIGHTS = ("router", "router_bias", "gate", "up", "down",
                  "shared_gate", "shared_up", "shared_down")


def expert_layer(x, w, cfg, *, held, program_ids=None, margin=0.0):
    """Shared(x) + the sum over the chosen experts that are held here,
    `held` = (first expert id, count): w["gate"], w["up"], w["down"] hold
    those experts only, in order.
    departure: the published code gathers the rows routed to each expert;
    here every held expert runs on every token and a mask of gate weights
    picks: the same sum."""
    r = _routing(cfg)
    if w["gate"].shape[0] != held[1]:
        raise ValueError("the weights are not those of the experts held")
    return _expert_layer_jit(
        x, {name: w[name] for name in EXPERT_WEIGHTS},
        _program_ids(program_ids, x.shape[0], r.k), margin, r=r,
        first=held[0], col_block=4608)


@jax.jit
def _embed(wte, tokens):
    return _f32(wte)[tokens]


@functools.partial(jax.jit, static_argnames=("eps", "col_block"))
def _dense_tail(x, u, scale, gate, up, down, eps, col_block=4608):
    """A dense layer from its attention's output on: x + u, the norm, the
    gated MLP, the residual."""
    x = x + u
    return x + _gated_mlp(rms_norm(x, _f32(scale), eps), gate, up, down,
                          col_block)


@functools.partial(jax.jit,
                   static_argnames=("eps", "r", "first", "col_block"))
def _expert_tail(x, u, scale, w, program_ids, margin, eps, r, first,
                 col_block=4608):
    """An expert layer from its attention's output on."""
    x = x + u
    y, routing = _expert_layer(rms_norm(x, _f32(scale), eps), w, program_ids,
                               margin, r, first, col_block)
    return x + y, routing


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, lm_head, rows, eps):
    # departure: the multi-token-prediction module changes no logit of the
    # model and is not held
    return rms_norm(x[rows], _f32(scale), eps) @ _f32(lm_head)


def model_cfg(config: dict) -> dict:
    """The published keys the reference reads, with the experts this chip
    holds: `n_routed_experts` in a cut configuration file counts the
    experts held (`reduced`), `experts_routed` the router's width."""
    cfg = dict(config)
    cfg["n_routed_experts_total"] = config.get(
        "experts_routed", config["n_routed_experts"])
    cfg.setdefault("experts_held", [0, config["n_routed_experts"]])
    return cfg


def forward(get, tokens, config, *, program=None, sel_margin=0.0,
            max_outside=0, route_margin=0.0, row_block=128, head_block=8,
            rows=None):
    """(logits (s, vocab) float32 numpy, notes) of the causal forward over
    one sequence `tokens` (s,) at positions 0 .. s - 1; with `rows`, the
    logits of those positions only. `program`: per layer {"sel": {row:
    indices}, "experts": {row: ids (k,)}} of the program's own choices at
    the rows it names, used at near-ties only. notes: per layer, the rows
    where the program's selection or routing was taken or not allowed, and
    the readings of `select` and `route`. The weights stay as the program
    holds them and are upcast where they are used, a block at a time."""
    cfg = model_cfg(config)
    r, eps = _routing(cfg), cfg["rms_norm_eps"]
    tokens = jnp.asarray(tokens, jnp.int32).reshape(-1)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    rows = positions if rows is None else jnp.asarray(rows, jnp.int32)
    notes = []
    with jax.default_matmul_precision("highest"):
        x = _embed(get("wte", "kernel"), tokens)
        for i in range(cfg["num_hidden_layers"]):
            p = f"l{i}_"
            prog = (program or {}).get(i, {})
            u, taken, bad, readings = latent_attention(
                x, {name: get(p + "attn", name) for name in (
                    *ATTENTION_WEIGHTS, "wq_b", "wkv_b", "wo")},
                positions, cfg, scale=get(p + "ln1", "scale"),
                program_sel=prog.get("sel"), sel_margin=sel_margin,
                max_outside=max_outside, row_block=row_block,
                head_block=head_block)
            note = {"sel_taken": taken, "sel_bad": bad,
                    "sel_readings": readings}
            if i < cfg["first_k_dense_replace"]:
                x = _dense_tail(
                    x, u, get(p + "ln2", "scale"),
                    *(get(p + "ffn_" + name, "kernel")
                      for name in ("gate", "up", "down")), eps=eps)
            else:
                ids = None
                if prog.get("experts"):
                    ids = np.full((tokens.shape[0], r.k), -1, np.int32)
                    for row, chosen in prog["experts"].items():
                        ids[row] = chosen
                x, routing = _expert_tail(
                    x, u, get(p + "ln2", "scale"),
                    {name: get(p + "moe", name) for name in EXPERT_WEIGHTS},
                    _program_ids(ids, tokens.shape[0], r.k), route_margin,
                    eps=eps, r=r, first=cfg["experts_held"][0])
                note.update(routing)
            notes.append(note)
        logits = _head(x, get("ln_f", "scale"), get("lm_head", "kernel"),
                       rows, eps=eps)
    return np.asarray(logits, np.float32), notes


def lowerings(get, config, length, *, named=128, row_block=128,
              head_block=8) -> list:
    """[(name, jax.stages.Lowered)]: the programs `forward` runs over
    `length` tokens, `named` of them named by the program and as many asked
    for, lowered and not compiled, for a caller that compiles them ahead
    of the forward and beside other work (they are the forward's own
    jitted functions at its own shapes, so the forward finds them in the
    compile cache). The layers of one kind share their programs."""
    cfg = model_cfg(config)
    d, r, eps = _dims(cfg), _routing(cfg), cfg["rms_norm_eps"]
    s, block = length, min(row_block, length)
    dense = cfg["first_k_dense_replace"]
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
    at = like(whole, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = add(_embed, get("wte", "kernel"), like(s, dtype=jnp.int32))
        cq, ckv, kr, angles, qi, ki, wt = add(
            _attention_inputs, x, get("l0_ln1", "scale"),
            {name: attn(name) for name in ATTENTION_WEIGHTS},
            like(s, dtype=jnp.int32), like(d.rope // 2), d=d)
        mask = add(_select_all, qi, wt, ki, topk=d.topk, row_block=block)
        if named and s > d.topk:
            add(_named_rows, qi, wt, ki, at, topk=d.topk)
            add(_set_rows, mask, at, like(whole, s, dtype=jnp.bool_))
        u = add(_attend, cq, ckv, kr, angles, mask, attn("wq_b"),
                attn("wkv_b"), attn("wo"), d=d, row_block=block,
                head_block=head_block)
        if dense:
            add(_dense_tail, x, u, get("l0_ln2", "scale"),
                *(get("l0_ffn_" + name, "kernel")
                  for name in ("gate", "up", "down")), eps=eps)
        if dense < cfg["num_hidden_layers"]:
            p = f"l{dense}_"
            add(_expert_tail, x, u, get(p + "ln2", "scale"),
                {name: get(p + "moe", name) for name in EXPERT_WEIGHTS},
                like(s, r.k, dtype=jnp.int32), 0.0, eps=eps, r=r,
                first=cfg["experts_held"][0])
        add(_head, x, get("ln_f", "scale"), get("lm_head", "kernel"),
            at if named else like(s, dtype=jnp.int32), eps=eps)
    return out

