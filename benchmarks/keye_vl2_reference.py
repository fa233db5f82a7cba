"""The plain reference of Keye-VL-2.0's language model as
`build_transformer_lm` builds it from `keye_vl2_lm_config`: the forward
pass of one sequence of text. The benchmark's own copy of
`flexflow_tpu/models/keye_vl2_reference.py` (a later PR cannot move the
yardstick by editing the program's), with the comparison that decides
`correct` at its end.

float32, `jax.default_matmul_precision("highest")`, jax.numpy only: no
kernel, no cache, no batching, the indexer's selection as a dense mask.
Scores are computed in blocks of query rows, the experts one after another
and the head in blocks of the vocabulary, so that a sequence of some
sixteen thousand tokens at the published widths fits beside the program on
one chip; the blocks change no number. A forward is eight jitted programs
a length (`lowerings`); only the rows the program names come to the host.

The model (config.json of Kwai-Keye/Keye-VL-2.0-30B-A3B, `model_type:
KeyeVL2`; what it leaves open is listed as `assumed` in
benchmarks/configs/keye-vl-2.0-30b-a3b.json):

- Block, every layer: h = h + Attn(RMSNorm(h)); h = h + MoE(RMSNorm(h));
  eps 1e-6, no biases. Final RMSNorm, untied head.
- Attention: q = x W_q (H heads of d), k = x W_k, v = x W_v (G heads of
  d); q_i = RMSNorm_d(q_i; gamma_q), k_g = RMSNorm_d(k_g; gamma_k), each
  head on its own, one scale of d for all heads; then RoPE at theta, the
  half-rotation form over the whole head (text: the three position
  components of `mrope_section` are equal, which is 1-D RoPE);
  p = softmax(d^-0.5 q_i . k_g) over the selected positions, query head i
  reading KV head i // (H / G); y = concat_i(sum p v) W_o.
- The indexer (DeepSeek-Sparse-Attention, as DeepSeek-V3.2 published it
  and models/deepseek_v32_reference.py has it, at `sa_config`'s sizes,
  its queries projected from the hidden state: this model has no query
  latent): qI_t,j = W_iq,j x_t (n heads of dI); kI_s = LayerNorm(W_ik
  x_s), scale and bias; both rotated whole at theta (half-rotation,
  frequencies over dI lanes); w_t = W_iw x_t n^-0.5 dI^-0.5; I_t,s =
  sum_j w_t,j ReLU(qI_t,j . kI_s); S_t = the `topk` positions s <= t of
  largest I_t,s, all of them while t < topk. `q_chunk_size` and
  `kv_chunk_size` are the published implementation's tile sizes and change
  no number.
- Expert layer: p = softmax(x R) over all experts in float32, the k
  largest, gates g_e = p_e / sum_chosen p (`norm_topk_prob`); y =
  sum_chosen g_e E_e(x), E(x) = W_down(SiLU(W_gate x) * W_up x). No shared
  expert.

Every departure from the published model is a comment that starts with
"departure:". `get(node, weight)` returns the program's own array of that
name (wte.kernel, l<i>_ln1.scale, l<i>_attn.{wq, wk, wv, wo, q_norm,
k_norm, wi_q, wi_k, wi_k_norm, wi_k_bias, wi_w}, l<i>_ln2.scale,
l<i>_moe.{router, gate, up, down}, ln_f.scale, lm_head.kernel). Linear
weights are stored (in, out).

Selection and routing are discontinuous. Where the reference's k-th and
(k+1)-th scores of a position lie within a margin, a program in lower
precision may rightly pick otherwise: `forward` takes the program's choice
(`program`: per layer `sel` and `experts` for the rows it names) and uses
it at exactly those positions, and says where the program chose an index
or an expert the reference scores lower than its k-th less the margin
(the selection's rule is deepseek_v32_reference.select, shared).

`spoil` computes one part of the model wrongly, for the controls that fix
the comparison's limits (benchmarks/jobs/serve_mediaqa.py): "e4m3" rounds
every matrix to float8_e4m3fn, "topk_half" selects half of `topk`,
"norm_projection" normalises q and k over the whole projection, not a
head, "no_rope" leaves the rotation of q and k out.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import deepseek_v32_reference as dsa

SPOILS = (None, "e4m3", "topk_half", "norm_projection", "no_rope")
_f32 = dsa._f32


def e4m3(a):
    """`a` rounded to float8_e4m3fn's 3 bits of mantissa, in float32
    arithmetic (on the chip XLA folds a cast there and back away)."""
    return jax.lax.reduce_precision(_f32(a), exponent_bits=4,
                                    mantissa_bits=3)


def _mat(a, spoil):
    return e4m3(a) if spoil == "e4m3" else _f32(a)


class Dims(NamedTuple):
    """The widths the attention's programs are compiled for."""
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float
    index_heads: int
    index_dim: int
    index_eps: float
    topk: int


def _dims(cfg, spoil=None) -> Dims:
    sa = cfg["sa_config"]
    # LayerNorm's eps is `assumed` (1e-6, DeepSeek-V3.2's)
    return Dims(cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], cfg["rms_norm_eps"],
                float(cfg["rope_theta"]), sa["indexer_num_heads"],
                sa["indexer_head_dim"], 1e-6,
                sa["topk"] // 2 if spoil == "topk_half" else sa["topk"])


ATTENTION_WEIGHTS = ("wq", "wk", "wv", "q_norm", "k_norm", "wi_q", "wi_k",
                     "wi_k_norm", "wi_k_bias", "wi_w")


@functools.partial(jax.jit, static_argnames=("d", "spoil"))
def _attention_inputs(x, scale, w, positions, d, spoil=None):
    """What the selection and the attention of x (s, hidden) start from: q
    (s, H, hd) and k (s, G, hd) normalised and rotated, v (s, G, hd), and
    the indexer's queries, key and head weights. `scale`: the norm before
    the layer."""
    x = dsa.rms_norm(x, _f32(scale), d.eps)
    s, hd = x.shape[0], d.head_dim
    mats = ("wq", "wk", "wv", "wi_q", "wi_k", "wi_w")
    w = {n: _mat(a, spoil) if n in mats else _f32(a) for n, a in w.items()}
    pos = positions.astype(jnp.float32)[:, None]

    def angles(dim):
        return pos * d.theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32)
                                 / dim)

    q, k = x @ w["wq"], x @ w["wk"]
    if spoil == "norm_projection":
        gq, gk = jnp.tile(w["q_norm"], d.heads), jnp.tile(w["k_norm"],
                                                          d.kv_heads)
        q, k = dsa.rms_norm(q, gq, d.eps), dsa.rms_norm(k, gk, d.eps)
    q, k = q.reshape(s, d.heads, hd), k.reshape(s, d.kv_heads, hd)
    if spoil != "norm_projection":
        q = dsa.rms_norm(q, w["q_norm"], d.eps)
        k = dsa.rms_norm(k, w["k_norm"], d.eps)
    if spoil != "no_rope":
        q = dsa.rope_half(q, angles(hd)[:, None])
        k = dsa.rope_half(k, angles(hd)[:, None])
    v = (x @ w["wv"]).reshape(s, d.kv_heads, hd)
    # the indexer
    # departure: bfloat16 in the program, FP8 in DeepSeek's published
    # system; its Hadamard rotation is orthogonal on both sides of q . k
    # and is left out
    qi = dsa.rope_half((x @ w["wi_q"]).reshape(s, d.index_heads,
                                               d.index_dim),
                       angles(d.index_dim)[:, None])
    ki = dsa.rope_half(
        dsa.layer_norm(x @ w["wi_k"], w["wi_k_norm"], w["wi_k_bias"],
                       d.index_eps), angles(d.index_dim))
    wt = (x @ w["wi_w"]) * (d.index_heads ** -0.5) * (d.index_dim ** -0.5)
    return q, k, v, qi, ki, wt


def _attend_rows(q, k, v, mask, scale):
    """softmax(scale q . k) v over the masked positions, query head i
    reading KV head i // group: q (tb, G, group, hd), k and v (s, G, hd),
    mask (tb, s) -> (tb, G, group, hd)."""
    scores = jnp.einsum("tgqd,sgd->gqts", q, k) * scale
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("gqts,sgd->tgqd", probs, v)


@functools.partial(jax.jit, static_argnames=("d", "row_block", "spoil"))
def _attend(q, k, v, mask, wo, d, row_block, spoil=None):
    """concat_i(o_i) W_o (s, hidden), a block of rows after the other."""
    s = q.shape[0]
    q = q.reshape(s, d.kv_heads, d.heads // d.kv_heads, d.head_dim)
    o = jax.lax.map(
        lambda part: _attend_rows(part[0], k, v, part[1],
                                  d.head_dim ** -0.5),
        (dsa._row_blocks(q, row_block), dsa._row_blocks(mask, row_block)))
    return o.reshape(-1, d.heads * d.head_dim)[:s] @ _mat(wo, spoil)


def attention(x, w, positions, cfg, *, scale, program_sel=None,
              sel_margin=0.0, max_outside=0, row_block=128, spoil=None):
    """Attention under the indexer's selection on x (s, hidden) at
    `positions` (s,): (output (s, hidden), rows taken, rows bad, the
    rows' readings: deepseek_v32_reference.select). `scale`: the norm x
    goes through first."""
    d = _dims(cfg, spoil)
    q, k, v, qi, ki, wt = _attention_inputs(
        x, scale, {name: w[name] for name in ATTENTION_WEIGHTS},
        jnp.asarray(positions, jnp.int32), d=d, spoil=spoil)
    mask, taken, bad, readings = dsa.select(
        qi, wt, ki, d.topk, program_sel=program_sel, margin=sel_margin,
        max_outside=max_outside, row_block=row_block)
    u = _attend(q, k, v, mask, w["wo"], d=d,
                row_block=min(row_block, x.shape[0]), spoil=spoil)
    return u, taken, bad, readings


class Routing(NamedTuple):
    """What the router's program is compiled for: its width, the experts
    a token, whether the gates are renormalised."""
    experts: int
    k: int
    norm: bool


def _routing(cfg) -> Routing:
    return Routing(cfg["num_experts"], cfg["num_experts_per_tok"],
                   bool(cfg["norm_topk_prob"]))


def route(x, router, program_ids, margin, r):
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
    return picked, ids, tie, own, gap


EXPERT_WEIGHTS = ("router", "gate", "up", "down")


def _expert_layer(x, w, program_ids, margin, r, spoil):
    """departure: the published code gathers the rows routed to each
    expert; here every expert runs on every token and a mask of gate
    weights picks: the same sum."""
    gates, ids, tie, own, gap = route(x, _f32(w["router"]), program_ids,
                                      margin, r)

    def expert(y, held):
        j, gate, up, down = held
        g = jnp.sum(jnp.where(ids == j, gates, 0.0), axis=-1)
        h = jax.nn.silu(x @ _mat(gate, spoil)) * (x @ _mat(up, spoil))
        return y + g[:, None] * (h @ _mat(down, spoil)), None

    y = jax.lax.scan(expert, jnp.zeros_like(x),
                     (jnp.arange(r.experts), w["gate"], w["up"],
                      w["down"]))[0]
    return y, {"ids": ids, "tie": tie, "own_ids": own, "gap": gap}


@jax.jit
def _embed(wte, tokens):
    return _f32(wte[tokens])


@functools.partial(jax.jit, static_argnames=("eps", "r", "spoil"))
def _expert_tail(x, u, scale, w, program_ids, margin, eps, r, spoil=None):
    """A layer from its attention's output on: x + u, the norm, the
    experts, the residual."""
    x = x + u
    y, routing = _expert_layer(dsa.rms_norm(x, _f32(scale), eps), w,
                               program_ids, margin, r, spoil)
    return x + y, routing


@functools.partial(jax.jit, static_argnames=("eps", "spoil", "blocks"))
def _head(x, scale, lm_head, rows, eps, spoil=None, blocks=8):
    """The logits of `rows`, the vocabulary in `blocks` parts (only a
    part of the head is ever held in float32)."""
    h = dsa.rms_norm(x[rows], _f32(scale), eps)
    step = -(-lm_head.shape[1] // blocks)
    return jnp.concatenate(
        [h @ _mat(lm_head[:, lo:lo + step], spoil)
         for lo in range(0, lm_head.shape[1], step)], axis=-1)


def _program_ids(ids, tokens: int, k: int):
    if ids is None:
        return jnp.full((tokens, k), -1, jnp.int32)
    return jnp.asarray(ids, jnp.int32).reshape(tokens, k)


def forward(get, tokens, config, *, program=None, sel_margin=0.0,
            max_outside=0, route_margin=0.0, row_block=128, rows=None,
            spoil=None):
    """(logits (s, vocab) float32 numpy, notes) of the causal forward over
    one sequence `tokens` (s,) at positions 0 .. s - 1; with `rows`, the
    logits of those positions only. `program`: per layer {"sel": {row:
    indices}, "experts": {row: ids (k,)}} of the program's own choices at
    the rows it names, used at near-ties only. notes: per layer, the rows
    where the program's selection or routing was taken or not allowed,
    and the readings of `select` and `route`. The weights stay as the
    program holds them and are upcast where they are used."""
    if spoil not in SPOILS:
        raise ValueError(f"spoil is one of {SPOILS}, got {spoil!r}")
    r, eps = _routing(config), config["rms_norm_eps"]
    tokens = jnp.asarray(tokens, jnp.int32).reshape(-1)
    s = tokens.shape[0]
    positions = jnp.arange(s, dtype=jnp.int32)
    rows = positions if rows is None else jnp.asarray(rows, jnp.int32)
    notes = []
    with jax.default_matmul_precision("highest"):
        x = _embed(get("wte", "kernel"), tokens)
        for i in range(config["num_hidden_layers"]):
            p = f"l{i}_"
            prog = (program or {}).get(i, {})
            u, taken, bad, readings = attention(
                x, {name: get(p + "attn", name)
                    for name in (*ATTENTION_WEIGHTS, "wo")},
                positions, config, scale=get(p + "ln1", "scale"),
                program_sel=prog.get("sel"), sel_margin=sel_margin,
                max_outside=max_outside, row_block=row_block, spoil=spoil)
            note = {"sel_taken": taken, "sel_bad": bad,
                    "sel_readings": readings}
            ids = None
            if prog.get("experts"):
                ids = np.full((s, r.k), -1, np.int32)
                for row, chosen in prog["experts"].items():
                    ids[row] = chosen
            x, routing = _expert_tail(
                x, u, get(p + "ln2", "scale"),
                {name: get(p + "moe", name) for name in EXPERT_WEIGHTS},
                _program_ids(ids, s, r.k), route_margin, eps=eps, r=r,
                spoil=spoil)
            note.update(routing)
            notes.append(note)
        # departure: the vision tower and its projector are not held: a
        # sequence of text never passes through them
        logits = _head(x, get("ln_f", "scale"), get("lm_head", "kernel"),
                       rows, eps=eps, spoil=spoil)
    return np.asarray(logits, np.float32), notes


def cache_rows(get, tokens, config, *, spoil=None):
    """What a cache holds of `tokens` (s,) in the first layer, where every
    row is a function of its own token and position alone (no selection
    and no routing stands before it, so a program's rows agree with these
    to rounding): ([k ; v] (s, 2 x G x d), the indexer's key (s, dI)),
    float32 numpy. The programs are `forward`'s own at that length."""
    tokens = jnp.asarray(tokens, jnp.int32).reshape(-1)
    s = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        x = _embed(get("wte", "kernel"), tokens)
        _, k, v, _, ki, _ = _attention_inputs(
            x, get("l0_ln1", "scale"),
            {name: get("l0_attn", name) for name in ATTENTION_WEIGHTS},
            jnp.arange(s, dtype=jnp.int32), d=_dims(config, spoil),
            spoil=spoil)
    return (np.concatenate([np.asarray(k).reshape(s, -1),
                            np.asarray(v).reshape(s, -1)], axis=-1),
            np.asarray(ki))


def lowerings(get, config, length, *, named=128, row_block=128) -> list:
    """[(name, jax.stages.Lowered)]: the programs `forward` runs over
    `length` tokens, `named` of them named by the program and as many
    asked for, lowered and not compiled, for a caller that compiles them
    ahead of the forward and beside other work (they are the forward's
    own jitted functions at its own shapes, so the forward finds them in
    the compile cache). The layers share their programs."""
    d, r, eps = _dims(config), _routing(config), config["rms_norm_eps"]
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
    at = like(whole, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = add(_embed, get("wte", "kernel"), like(s, dtype=jnp.int32))
        q, k, v, qi, ki, wt = add(
            _attention_inputs, x, get("l0_ln1", "scale"),
            {name: attn(name) for name in ATTENTION_WEIGHTS},
            like(s, dtype=jnp.int32), d=d, spoil=None)
        mask = add(dsa._select_all, qi, wt, ki, topk=d.topk, row_block=block)
        if named and s > d.topk:
            add(dsa._named_rows, qi, wt, ki, at, topk=d.topk)
            add(dsa._set_rows, mask, at, like(whole, s, dtype=jnp.bool_))
        u = add(_attend, q, k, v, mask, attn("wo"), d=d, row_block=block,
                spoil=None)
        add(_expert_tail, x, u, get("l0_ln2", "scale"),
            {name: get("l0_moe", name) for name in EXPERT_WEIGHTS},
            like(s, r.k, dtype=jnp.int32), 0.0, eps=eps, r=r, spoil=None)
        add(_head, x, get("ln_f", "scale"), get("lm_head", "kernel"),
            at if named else like(s, dtype=jnp.int32), eps=eps, spoil=None)
    return out


# What decides `correct` in `keye2-serve-mediaqa` (jobs/serve_mediaqa.py):
# logits, at the decoded rows of the pre-window check (a context of 2,300
# tokens) and of two streams the loop served (contexts near 9 k and 16 k,
# all 16 slots live), the reference evaluated under the program's choices
# where its own lie at a near-tie; and the first layer's cache rows of
# those two sessions' prompts. Each limit stands between two readings (my
# chip runs, PR 38; PERF.md section 6 has the table): the largest of the
# sound runs (45 checks and streams over fifteen seeds), and the controls, which have to come out not correct: the reference
# over weights rounded to e4m3, with a selection of 1,024, with QK-norm
# over the whole projection, without RoPE, and the stream replayed with
# one block of 256 indexer keys of its history zeroed.
#
# Every expert is held here, so the reference is given the experts the
# program chose at EVERY position, the prompt's too (the job's
# `ChunkChoices`): a token the two route apart has another hidden state in
# both for the rest of the sequence, its keys score apart in the next
# layers, and a decoded row's selection then differs by a hundred
# positions for no fault of the program's (without them the same sound
# program read 0.020-0.079, 136-483 outside, gaps to 0.16).
#
# LOGIT_TOL: max |program logit - reference logit| over the compared rows
# as a share of the largest |reference logit| there (bf16 against
# float32). Sound 0.0045-0.0075; QK-norm over the projection 0.054-0.058,
# a selection of 1,024 0.12-0.27, no RoPE 0.19-0.31, e4m3 0.80-1.13; a lost
# block of indexer keys 0.0060-0.0068 (1.5 % of a row's candidates: the
# logits do not tell it, MAX_OUTSIDE and CACHE_TOL do). Those readings are
# of weights with the embedding at N(0, 0.02); with it at N(0, 1), the
# cell's since the benchmark check refused the first (PERF.md section 6,
# PR 38): sound 0.0050-0.0069 over 21 checks of seven seeds, e4m3
# 0.42-0.56, a selection of 1,024 0.088-0.100, a lost block 0.037-0.040,
# QK-norm over the projection 0.021-0.026 (a row's state is mostly its own
# token's embedding there: CACHE_TOL tells that one, the logits only just).
#
# SEL_MARGIN, MAX_OUTSIDE: deepseek_v32_reference.select's rule. At these
# contexts the topk-th and the next index score of a row lie within
# rounding of each other, so the reference attends the program's set
# wherever that set is allowed: topk distinct positions of the row's past,
# of which at most MAX_OUTSIDE score, in the reference, lower than its
# topk-th by more than SEL_MARGIN of the row's largest |index score| (a
# history row's own selection, made in a chunk step, is recorded nowhere,
# and where the two chose otherwise there its keys in the next layers
# score apart by more than rounding). Sound rows (some 80,000 at 8.6-15.8
# k, a row in a layer): at most 46 outside, 99th percentile 10-27; a lost block
# 224-237, QK-norm over the projection 308-402, no RoPE 1,535-1,814, e4m3
# 1,784-1,946, and a selection of 1,024 is refused by its count. With the
# embedding at N(0, 1): sound at most 1, a lost block 237-240, e4m3
# 965-1,113, QK-norm over the projection 0-3 (not told here).
#
# ROUTE_MARGIN: the reference takes the program's experts at a token whose
# gap (`route`: the k-th probability less the next, as a share of the
# k-th) is under this; a token the program routed otherwise than the
# reference at a larger gap makes the run not correct. The reading is the
# largest gap at which the two chose otherwise (`route_gap_max`): sound
# 0.019-0.066 over 80,000 routings (softmax probabilities of a seeded
# router lie a few per cent apart: most tokens are near-ties at any margin
# that clears rounding); QK-norm over the projection 0.21-0.24, a selection
# of 1,024 0.23-0.28, no RoPE 0.15-0.31, e4m3 0.22-0.31. With the embedding
# at N(0, 1): sound 0.000-0.021; a selection of 1,024 0.19-0.20, e4m3
# 0.17-0.30, a lost block 0.061-0.154, QK-norm over the projection
# 0.024-0.044 (not told here).
#
# CACHE_TOL (jobs/serve_mediaqa.py `cache_check`): the first layer's cache
# rows of a served prompt, [k ; v] and the indexer's key, against
# `cache_rows`, max |difference| over the largest |reference| entry. A
# first-layer row is a function of its own token and position alone, so
# the reading is rounding and nothing else: sound 0.0032-0.0048 ([k ; v])
# and 0.0037-0.0061 (the key); QK-norm over the projection 0.130-0.150,
# e4m3 0.32-0.42, a lost block of keys 0.78-0.94, no RoPE 1.83-1.91 (a
# row is a function of its token's direction, so these readings are the
# same at either spread of the embedding, and were read at both).
LOGIT_TOL = 0.02
CACHE_TOL = 0.02
SEL_MARGIN = 0.03
MAX_OUTSIDE = 128
ROUTE_MARGIN = 0.15
# the compared rows of a sequence come in whole blocks of this many: a
# reply is at most 512 tokens, so every comparison is one shape
ROWS = 512
logit_error = dsa.logit_error


def compare(get, tokens, config, rows, program, pad_to=None,
            spoil=None) -> dict:
    """The program's logits `rows` {position: (vocab,)} of one sequence
    against the reference's full forward over `tokens`, under the
    program's choices `program` (`forward`) at near-ties. The forward runs
    over the tokens padded to `pad_to`, or to a whole number of 256
    (causal: no compared row sees the padding), and gives the logits of
    the compared rows in whole blocks of ROWS, so that its programs
    compile for few shapes and `lowerings` knows them. The keys are
    deepseek_v32_reference.compare's: error (to hold against LOGIT_TOL),
    sel_bad (rows whose selection the reference does not allow), route_bad
    (tokens routed otherwise than the reference at no near-tie), and the
    readings beside them."""
    tokens = list(tokens)
    length = pad_to or len(tokens) + -len(tokens) % 256
    if length < len(tokens):
        raise ValueError(f"{len(tokens)} tokens do not fit {pad_to}")
    at = sorted(rows)
    full, notes = forward(get, tokens + [0] * (length - len(tokens)), config,
                          program=program, sel_margin=SEL_MARGIN,
                          max_outside=MAX_OUTSIDE, route_margin=ROUTE_MARGIN,
                          rows=at + at[-1:] * (-len(at) % ROWS), spoil=spoil)
    mine = np.stack([np.asarray(rows[t], np.float32) for t in at])
    ref = full[:len(at)]
    by_row = (np.max(np.abs(mine - ref), axis=-1) / np.max(np.abs(ref)))
    readings = [r for note in notes for r in note["sel_readings"].values()]
    ties = [np.asarray(note["tie"])[at] for note in notes]
    gaps = []  # of the tokens the program routed otherwise
    for layer, note in enumerate(notes):
        chosen = program.get(layer, {}).get("experts", {})
        own, gap = np.asarray(note["own_ids"]), np.asarray(note["gap"])
        gaps += [float(gap[t]) for t in at if t in chosen
                 and set(np.asarray(chosen[t]).tolist())
                 != set(own[t].tolist())]
    return {
        "error": logit_error(mine, ref),
        "error_by_row": by_row.round(4).tolist(),
        "sel_bad": sum(len(note["sel_bad"]) for note in notes),
        "sel_taken": sum(len(note["sel_taken"]) for note in notes),
        "sel_rows": len(readings),
        "outside_max": max((r[0] for r in readings), default=0),
        "shortfall_max": max((r[1] for r in readings), default=0.0),
        "route_rows": sum(t.size for t in ties),
        "route_taken": int(sum(t.sum() for t in ties)),
        "route_differs": len(gaps),
        "route_gap_max": max(gaps, default=0.0),
        "route_bad": sum(g >= ROUTE_MARGIN for g in gaps),
    }
