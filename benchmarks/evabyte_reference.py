"""The plain reference of EvaByte as `build_transformer_lm` builds it from
`evabyte_lm_config`: the forward pass of one sequence of bytes.
The benchmark's own copy of `flexflow_tpu/models/evabyte_reference.py` (a
later PR cannot move the yardstick by editing the program's), with the
comparison that decides `correct` at its end.

float32, `jax.default_matmul_precision("highest")`, jax.numpy only: no
kernel, no cache, no batching. Every chunk's summary is computed from the
whole sequence; scores and outputs are computed in blocks of query rows (a
block reads the exact keys from its first row's window on and every
summary, under a mask), so that a sequence of some thirty thousand bytes at
the published widths fits beside the program and its cache on one chip; the
blocks change no number.

The model (config.json of EvaByte/EvaByte, `model_type: evabyte`, with the
form of the chunk summary from its published modelling code, `eva.py`,
`eva_prep_kv_kernel.py`, `eva_agg_kernel.py`, and from Zheng et al.,
"Efficient Attention via Control Variates", arXiv:2302.04542), for a byte
at position t, W = `window_size`, C = `chunk_size`, w = floor(t / W):

- h_0 = E[x_t], float32 from here on (`fp32_skip_add`): h <- h + Attn(N_1(h)),
  h <- h + MLP(N_2(h)). N(x) = x / sqrt(mean(x^2) + `rms_norm_eps`) * (1 + g)
  (`norm_add_unit_offset`). MLP(n) = W_down(SiLU(W_gate n) * W_up n) at
  `intermediate_size`, no bias. After the last layer N_f, then
  logits = N_f(h) W_head (`fp32_logits`), `vocab_size` wide.
- Attn: q, k, v = n W_q, n W_k, n W_v as `num_attention_heads` heads of
  hidden / heads each (as many KV heads), no bias, no QK-norm; RoPE in the
  half-rotation form, `rope_theta`, over the whole head, on q and k at t.
  For each head:
  - the exact set S_t = { u : W w <= u <= t } with keys k_u, values v_u;
  - chunk c (bytes C c .. C c + C - 1): a_{c,m} = softmax_m(k_m . phi) over
    the chunk's C rotated keys, ksum_c = sum_m a_{c,m} k_m + mu_k,
    vsum_c = sum_m a_{c,m} v_m, phi and mu_k learned vectors a head; the
    summary set C_t = { c : C (c + 1) <= W w }: the W / C chunks of each
    closed window and none of the current one;
  - scores q_t . k_u / sqrt(d) over S_t and q_t . ksum_c / sqrt(d) over
    C_t, ONE softmax over the union, applied to v_u and vsum_c; then W_o.
  A row in window 0 is plain causal attention.

ASSUMED lists what the published keys leave open, DEPARTURES where the
program leaves the published model; benchmarks/configs/evabyte-6.5b.json
carries both. `get(node, weight)` returns the program's own array of that
name (wte.kernel, l<i>_ln1.scale, l<i>_attn.{wq, wk, wv, wo, phi, mu_k},
l<i>_ln2.scale, l<i>_ffn_{gate, up, down}.kernel, ln_f.scale,
lm_head.kernel). Linear weights are stored (in, out), the embedding
(vocabulary, hidden), phi and mu_k (heads, head size).

`spoil` computes one part of the model wrongly, for the controls that fix
a comparison's limits (SPOILS): "summaries_early" shows a summary as soon
as its chunk closes, "sliding_window" slides the window of W keys,
"no_mu_k" leaves mu_k out, "chunk_mean" takes a chunk's mean in place of
the phi-weighted sum, "unrotated_summaries" summarises the keys before
RoPE, "two_softmaxes" normalises the two sets apart and adds, "no_summaries"
attends the window only, "full_causal" every earlier key and no summary,
"norm_no_offset" scales a norm by g, "bf16_residual" rounds the residual
stream to bfloat16 after every add, "e4m3" rounds every matrix to
float8_e4m3fn.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

SPOILS = (None, "summaries_early", "sliding_window", "no_mu_k", "chunk_mean",
          "unrotated_summaries", "two_softmaxes", "no_summaries",
          "full_causal", "norm_no_offset", "bf16_residual", "e4m3")

ASSUMED = {
    "summary_logit": "a chunk's weights are softmax_m(k_m . phi) with no "
                     "further scale, and mu_k is added to the key summary "
                     "only (the value summary has no offset)",
    "summary_position": "keys are rotated before they are summarised, and a "
                        "summary carries no position of its own",
    "summary_visibility": "a chunk's summary becomes visible when its WINDOW "
                          "closes, not when the chunk does: a row attends "
                          "the summaries of the windows before its own",
    "norm_statistics": "norm statistics in float32 though fp32_ln is false: "
                       "the stream they read is float32 (fp32_skip_add)",
}

DEPARTURES = {
    "layers": "8 of 32 layers: one pipeline stage of four",
    "prediction_heads": "head 0 of the num_pred_heads prediction heads is "
                        "built (hidden x vocabulary); heads 1-7 read the "
                        "same final state and change no logit of head 0, "
                        "and multi-byte drafting is not run",
    "weights": "random from the seed: init_std for every matrix and the "
               "embedding, phi and mu_k uniform within head_dim^-0.5, norm "
               "gains zeros",
    "cache_precision": "summaries and exact rows are stored in the cache's "
                       "bf16; a summary's sums are taken in float32 and "
                       "rounded once",
    "training_shape": "the training-shaped EVA op is XLA (windows as a batch "
                      "dimension), with no kernel and no training cell",
}


class Forward(NamedTuple):
    logits: jax.Array  # (rows, vocabulary) float32
    k: jax.Array       # the kept layer's rotated keys (tokens, heads * d)
    v: jax.Array
    ksum: jax.Array    # its whole chunks' summaries (tokens // C, heads * d)
    vsum: jax.Array


def e4m3(a):
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _mat(a, spoil):
    a = jnp.asarray(a, jnp.float32)
    return e4m3(a) if spoil == "e4m3" else a


def norm(x, g, eps, spoil=None):
    g = jnp.asarray(g, jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (g if spoil == "norm_no_offset" else 1.0 + g)


def rope(x, positions, theta):
    """x (tokens, heads, d) rotated in the half-rotation form: lanes j and
    j + d / 2 a pair, frequency theta^(-2j / d)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def summaries(k, v, phi, mu_k, chunk, spoil=None):
    """(ksum, vsum) (chunks, heads, d) of the whole chunks of k, v (tokens,
    heads, d)."""
    n = k.shape[0] // chunk
    kc = k[:n * chunk].reshape(n, chunk, *k.shape[1:])
    vc = v[:n * chunk].reshape(n, chunk, *v.shape[1:])
    a = jax.nn.softmax(jnp.einsum("cmhd,hd->cmh", kc, phi), axis=1)
    if spoil == "chunk_mean":
        a = jnp.full_like(a, 1.0 / chunk)
    ksum = jnp.einsum("cmh,cmhd->chd", a, kc)
    if spoil != "no_mu_k":
        ksum = ksum + mu_k
    return ksum, jnp.einsum("cmh,cmhd->chd", a, vc)


def _settle(h, spoil):
    """The residual stream after an add."""
    if spoil == "bf16_residual":
        # (not a cast there and back, which a TPU compile may elide as
        # excess precision it is allowed to keep)
        return jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
    return h


@functools.partial(jax.jit, static_argnames=("spoil",))
def _embed(table, tokens, spoil=None):
    return _settle(jnp.asarray(table, jnp.float32)[tokens], spoil)


@functools.partial(jax.jit, static_argnames=(
    "heads", "chunk", "theta", "eps", "spoil"))
def _project(h, g, w, positions, *, heads, chunk, theta, eps, spoil=None):
    """q, rotated k, v (tokens, heads, d) of norm(h) and the whole chunks'
    summaries (chunks, heads, d)."""
    x = norm(h, g, eps, spoil)
    T = x.shape[0]
    d = w["wq"].shape[1] // heads
    q, k, v = ((x @ _mat(w[name], spoil)).reshape(T, heads, d)
               for name in ("wq", "wk", "wv"))
    q, k_rot = rope(q, positions, theta), rope(k, positions, theta)
    ksum, vsum = summaries(
        k if spoil == "unrotated_summaries" else k_rot, v,
        jnp.asarray(w["phi"], jnp.float32),
        jnp.asarray(w["mu_k"], jnp.float32), chunk, spoil)
    return q, k_rot, v, ksum, vsum


@functools.partial(jax.jit, static_argnames=("window", "chunk", "spoil"))
def _block(qb, t, kb, vb, u, ksum, vsum, *, window, chunk, spoil=None):
    """A block of query rows qb (rows, heads, d) at positions t over the
    candidate keys kb, vb (keys, heads, d) at positions u and every
    summary, each under its mask."""
    scale = qb.shape[-1] ** -0.5
    closes = (jnp.arange(ksum.shape[0]) + 1) * chunk  # where a chunk closes
    start = (t // window * window)[:, None]
    seen = u[None] <= t[:, None]
    if spoil == "sliding_window":
        seen &= u[None] > t[:, None] - window
    elif spoil != "full_causal":
        seen &= u[None] >= start
    if spoil in ("no_summaries", "full_causal"):
        shown = jnp.zeros((t.shape[0], ksum.shape[0]), bool)
    elif spoil == "summaries_early":
        shown = closes[None] <= t[:, None]
    else:
        shown = closes[None] <= start
    sx = jnp.einsum("thd,uhd->htu", qb, kb) * scale
    ss = jnp.einsum("thd,chd->htc", qb, ksum) * scale
    sx = jnp.where(seen[None], sx, -jnp.inf)
    ss = jnp.where(shown[None], ss, -jnp.inf)
    if spoil == "two_softmaxes":
        px = jax.nn.softmax(sx, axis=-1)
        ps = jnp.where(shown.any(axis=-1)[None, :, None],
                       jax.nn.softmax(ss, axis=-1), 0.0)
    else:
        p = jax.nn.softmax(jnp.concatenate([sx, ss], axis=-1), axis=-1)
        px, ps = p[..., :sx.shape[-1]], p[..., sx.shape[-1]:]
    o = (jnp.einsum("htu,uhd->thd", px, vb)
         + jnp.einsum("htc,chd->thd", ps, vsum))
    return o.reshape(t.shape[0], -1)


def _span(length, window, row_block, spoil):
    """Candidate keys of a block of query rows: its first row's window and
    the block itself (the window before it, the whole sequence, under the
    spoils that read further back). Every block has one shape."""
    if spoil == "full_causal":
        return length
    return window + row_block - (0 if spoil == "sliding_window" else 1)


def _attend(q, k, v, ksum, vsum, *, window, chunk, row_block, spoil):
    """The core over the whole sequence, block of rows by block of rows.
    Padded rows and keys lie past every real row's position."""
    T = q.shape[0]
    span = _span(T, window, row_block, spoil)
    pad = -(-T // row_block) * row_block + span - T
    qp, kp, vp = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
    at = jnp.arange(T + pad, dtype=jnp.int32)
    out = []
    for first in range(0, T, row_block):
        lo = (0 if spoil == "full_causal" else
              max(0, first - window + 1) if spoil == "sliding_window" else
              first // window * window)
        out.append(_block(
            qp[first:first + row_block], at[first:first + row_block],
            kp[lo:lo + span], vp[lo:lo + span], at[lo:lo + span], ksum, vsum,
            window=window, chunk=chunk, spoil=spoil))
    return jnp.concatenate(out)[:T]


@functools.partial(jax.jit, static_argnames=("spoil",))
def _attn_out(h, o, wo, spoil=None):
    return _settle(h + o @ _mat(wo, spoil), spoil)


@functools.partial(jax.jit, static_argnames=("eps", "spoil"))
def _mlp(h, g, gate, up, down, *, eps, spoil=None):
    """h + MLP(N_2(h)) of some rows."""
    n = norm(h, g, eps, spoil)
    m = (jax.nn.silu(n @ _mat(gate, spoil)) * (n @ _mat(up, spoil))
         ) @ _mat(down, spoil)
    return _settle(h + m, spoil)


@functools.partial(jax.jit, static_argnames=("eps", "spoil"))
def _head(h, g, w, rows, *, eps, spoil=None):
    return norm(h[rows], g, eps, spoil) @ _mat(w, spoil)


MLP_ROWS = 4096  # rows of the MLP at a time: the widest intermediate held


def forward(get, tokens, config, *, rows=None, keep_layer=-1, spoil=None,
            row_block=256) -> Forward:
    """The forward pass of `tokens` (a sequence of byte ids): the logits of
    `rows` (every row by default) and the keys, values and summaries of
    layer `keep_layer`, each (.., heads * d)."""
    if spoil not in SPOILS:
        raise ValueError(f"spoil is one of {SPOILS}, got {spoil!r}")
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        T = tokens.shape[0]
        positions = jnp.arange(T, dtype=jnp.int32)
        layers, eps = config["num_hidden_layers"], config["rms_norm_eps"]
        kept = None
        h = _embed(get("wte", "kernel"), tokens, spoil=spoil)
        for i in range(layers):
            p = f"l{i}_"
            w = {name: get(f"{p}attn", name)
                 for name in ("wq", "wk", "wv", "phi", "mu_k")}
            q, *cache = _project(
                h, get(f"{p}ln1", "scale"), w, positions,
                heads=config["num_attention_heads"],
                chunk=config["chunk_size"],
                theta=float(config["rope_theta"]), eps=eps, spoil=spoil)
            o = _attend(q, *cache, window=config["window_size"],
                        chunk=config["chunk_size"], row_block=row_block,
                        spoil=spoil)
            if i == keep_layer % layers:
                kept = [a.reshape(a.shape[0], -1) for a in cache]
            del q, cache
            h = _attn_out(h, o, get(f"{p}attn", "wo"), spoil=spoil)
            mlp = [get(f"{p}ffn_{name}", "kernel")
                   for name in ("gate", "up", "down")]
            h = jnp.concatenate([
                _mlp(h[lo:lo + MLP_ROWS], get(f"{p}ln2", "scale"), *mlp,
                     eps=eps, spoil=spoil)
                for lo in range(0, T, MLP_ROWS)])
        rows = positions if rows is None else jnp.asarray(rows, jnp.int32)
        logits = _head(h, get("ln_f", "scale"), get("lm_head", "kernel"),
                       rows, eps=eps, spoil=spoil)
        return Forward(logits, *kept)


# What decides `correct` in `evabyte-serve-bytedocs` (jobs/serve_bytedocs.py):
# logits, at the decoded rows of the pre-window check (a prompt of 4,700
# bytes, two closed windows, 256 summaries and 604 exact rows, prefilled in
# chunks of 256 and 8 rows decoded through tables that hold every block of
# both groups) and of two streams the loop served (histories of 7,996
# bytes, 196 short of a window boundary, which the turn's chunk or the
# first decoded rows cross, and of 18,732, 300 into a window, crossed deep
# in a reply; all 16 slots live, through the engine's own block manager:
# the closed window's blocks given back at the boundary, a history matched
# over both groups, its shared tail block copied in both); and what the two
# pools hold of those two streams' prompts in the LAST layer held after the
# replay: the exact keys and values of the prompt's current window (the
# window group, through the blocks the prefix cache pins) and the key and
# value summaries of every whole chunk of the prompt (the global group),
# against the reference's k, v, ksum, vsum. Layer 7's rows are a function
# of seven EVA layers' outputs, so a window, a summary, a norm or a residual
# stream computed wrongly when the prompt was prefilled shows there at full
# size, in rows no decoded row's logits are compared at. Each limit stands
# between two readings (my chip runs, PR 53; PERF.md section 6 has the
# table): the largest of the sound runs, and the controls, which have to
# come out not correct: every entry of SPOILS, the streams replayed with
# the window blocks, or the summary blocks, of their cached history zeroed,
# and the program built with a bfloat16 residual stream.
#
# LOGIT_TOL: max |program logit - reference logit| over the compared rows
# as a share of the largest |reference logit| there (bf16 matmuls under a
# float32 residual stream against float32). Sound 0.0061-0.0105 at the
# check and 0.0076-0.0108 at the streams (33 comparisons of eleven seeds);
# the controls, on the same recorded logits (scripts/evabyte_controls.py,
# four seeds): a summary shown when its chunk closes 0.0232-0.0393, mu_k
# left out 0.0126-0.0151 at the check (two closed windows: it passes there)
# and 0.0188-0.0468 at the streams, a chunk's mean 0.136-0.187, summaries
# of unrotated keys 0.198-0.415, e4m3 weights 0.232-0.270, a sliding window
# 0.594-0.659, two softmaxes 0.625-0.921, full causal attention
# 0.645-0.981, summaries dropped 0.760-1.228, a norm scaled by g infinite
# (the gains are zeros). 0.02 is 1.85 times the largest sound reading and
# 0.86 of the smallest other that no other limit catches (a summary shown
# early; mu_k left out is caught by CACHE_TOL wherever its logits pass).
#
# CACHE_TOL: max |pool row - reference row| over the largest |reference|
# entry, each of the four leaves of layer 7 (bf16 through eight layers).
# Sound: k, v 0.0064-0.0099, ksum, vsum 0.0047-0.0073 (22 streams); mu_k
# left out 0.0308-0.0356 (ksum), a summary shown early 0.0342-0.0419, a
# chunk's mean 0.23-0.31, e4m3 0.24-0.31, a sliding window 0.42-0.66, full
# causal 0.44-0.73, two softmaxes 0.62-0.86, summaries dropped 1.1-1.4,
# unrotated keys 1.7-1.9; a lost window block 1.0 (the rows are zeros), a
# lost summary block 1.0-1.6. 0.02 is twice the largest sound reading and
# 0.65 of the smallest other.
#
# STREAM_SHARE: whether the program's residual stream is float32. The size
# of an error cannot say: a bfloat16 stream moves the logits by less than
# bf16 matmuls already do (the reference with its stream rounded after
# every add lies 0.0058-0.0062 of the logits' root mean square off the
# float32 one, the sound program 0.0068-0.0075 off it, the program built
# with `fp32_skip_add` false 0.0088-0.0097: 1.52-1.56 times the move where
# the sound one reads 1.18-1.28, and the largest difference of either is
# inside LOGIT_TOL and inside other seeds' sound range). The direction
# does. With e = program - reference and d = the reference with a bfloat16
# stream - reference over every compared logit of a run (the check's 8 rows
# and the two streams' 1,300-1,900 each), the share of the move that the
# error carries is e . d / d . d. The sound program's error knows nothing
# of d: -0.0027 to 0.0017 a run (ten seeds; a block of 100 rows scatters
# by 0.011, a run of 3,000 by 0.002). The program built with
# `fp32_skip_add` false (the job's control `bf16_stream`: under --dtype
# bf16 its stream is rounded after every add, nothing else differs) rounds,
# early in the stack where its stream and the reference's are still within
# an ulp, where the reference rounds: 0.1184, 0.1051 and 0.0998 a run,
# 0.090-0.128 a comparison (three seeds, two of them among the sound
# ones). 0.05 stands 25 scatters over 0 and at half the smallest control. (The `bf16_residual`
# spoil of the reference reads 1 under a sound program by construction, e =
# its error - d, and shows nothing; the spoil rounds with
# lax.reduce_precision because a cast to bfloat16 and back is excess
# precision a TPU compile may keep.)
LOGIT_TOL = 0.02
CACHE_TOL = 0.02
STREAM_SHARE = 0.05
# no layer selects and none routes: the keys the session job's report reads
SEL_MARGIN = 0.0
MAX_OUTSIDE = 0
ROUTE_MARGIN = 0.0
# the compared rows of a sequence come in whole blocks of this many
ROWS = 512


def logit_error(program, reference) -> float:
    """max |program - reference| as a share of max |reference|; infinite
    where the program's numbers are not all finite."""
    program = np.asarray(program, np.float32)
    reference = np.asarray(reference, np.float32)
    if program.shape != reference.shape:
        raise ValueError(f"shapes differ: {program.shape} against "
                         f"{reference.shape}")
    if not np.all(np.isfinite(program)):
        return float("inf")
    return float(np.max(np.abs(program - reference))
                 / np.max(np.abs(reference)))


def stream_reading(readings) -> tuple:
    """(share, ratio) over every compared logit of a run, whose
    comparisons' `stream` sums `readings` are: the share of what a
    residual stream of the other precision moves the reference by that the
    program's error carries (its projection on the move, e . d / d . d: to
    hold against STREAM_SHARE), and the error's size as a multiple of the
    move's."""
    ee, dd, ed = (sum(r[i] for r in readings) for i in range(3))
    dd = max(dd, 1e-300)
    return float(ed / dd), float(np.sqrt(ee / dd))


def padded(n: int) -> int:
    return n + -n % 256


def compare(get, tokens, config, rows, program=None, pad_to=None, spoil=None,
            pool_rows=None) -> dict:
    """The program's logits `rows` {position: (vocab,)} of one sequence
    against the reference's full forward over `tokens`; the interface of
    deepseek_v32_reference.compare, whose selection and routing readings
    are empty here (`program` is not read). `pool_rows` = (first, k, v,
    ksum, vsum): what the program's pools hold of the sequence in the last
    layer, exact rows of positions first .. first + len(k) - 1 and the
    summaries of chunks 0 .. len(ksum) - 1, held against the reference's
    -> cache_error (to hold against CACHE_TOL) and cache_errors by leaf.
    `stream`: with e = program - reference and d = the reference whose
    residual stream is of the other precision (bfloat16 or float32) -
    reference, the sums (e . e, d . d, e . d) over the compared logits, for
    `stream_reading` over a run's comparisons (None under a spoil that is
    not of the stream)."""
    tokens = list(tokens)
    length = pad_to or padded(len(tokens))
    if length < len(tokens):
        raise ValueError(f"{len(tokens)} tokens do not fit {pad_to}")
    at = sorted(rows)
    full = forward(get, tokens + [0] * (length - len(tokens)), config,
                   rows=at + at[-1:] * (-len(at) % ROWS), spoil=spoil)
    mine = np.stack([np.asarray(rows[t], np.float32) for t in at])
    ref = np.asarray(full.logits[:len(at)], np.float32)
    by_row = np.max(np.abs(mine - ref), axis=-1) / np.max(np.abs(ref))
    out = {
        "error": logit_error(mine, ref), "stream": None,
        "error_by_row": by_row.round(4).tolist(),
        "sel_bad": 0, "sel_taken": 0, "sel_rows": 0, "outside_max": 0,
        "shortfall_max": 0.0, "route_rows": 0, "route_taken": 0,
        "route_differs": 0, "route_gap_max": 0.0, "route_bad": 0,
    }
    if spoil in (None, "bf16_residual"):
        other = np.asarray(forward(
            get, tokens + [0] * (length - len(tokens)), config,
            rows=at + at[-1:] * (-len(at) % ROWS),
            spoil=None if spoil else "bf16_residual").logits[:len(at)],
            np.float64)
        e, d = mine - ref.astype(np.float64), other - ref
        out["stream"] = tuple(float(np.sum(a * b))
                              for a, b in ((e, e), (d, d), (e, d)))
    if pool_rows is not None:
        first, k, v, ksum, vsum = pool_rows
        want = (full.k[first:first + len(k)], full.v[first:first + len(v)],
                full.ksum[:len(ksum)], full.vsum[:len(vsum)])
        out["cache_errors"] = [
            logit_error(have, np.asarray(ref_rows, np.float32))
            if len(have) else 0.0
            for have, ref_rows in zip((k, v, ksum, vsum), want)]
        out["cache_error"] = max(out["cache_errors"])
    return out


def lowerings(get, config, length, *, named=ROWS, row_block=256,
              spoil=None) -> list:
    """[(name, jax.stages.Lowered)]: the programs `forward` runs over
    `length` tokens with `named` rows asked for, lowered and not compiled,
    for a caller that compiles them ahead of the forward and beside other
    work (they are the forward's own jitted functions at its own shapes,
    so the forward finds them in the compile cache). Every layer is alike
    and shares its programs. `spoil`: the forward's under that spoil."""
    eps, heads = config["rms_norm_eps"], config["num_attention_heads"]
    window, chunk = config["window_size"], config["chunk_size"]
    hidden = config["hidden_size"]
    s, out = length, []

    def like(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    # what a program returns lies where the weights lie, and is committed
    # there: a program lowered for an argument that is not is another one
    placed = getattr(get("wte", "kernel"), "sharding", None)
    if isinstance(placed, jax.sharding.NamedSharding):
        placed = jax.sharding.NamedSharding(
            placed.mesh, jax.sharding.PartitionSpec())

    def add(fn, *args, **static):
        out.append((f"{fn.__name__}@{s}.{len(out)}",
                    fn.lower(*args, **static)))
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=placed),
            jax.eval_shape(functools.partial(fn, **static), *args))

    ints = functools.partial(like, dtype=jnp.int32)
    span = _span(s, window, row_block, spoil)
    with jax.default_matmul_precision("highest"):
        h = add(_embed, get("wte", "kernel"), ints(s), spoil=spoil)
        q, k, v, ksum, vsum = add(
            _project, h, get("l0_ln1", "scale"),
            {name: get("l0_attn", name)
             for name in ("wq", "wk", "wv", "phi", "mu_k")},
            ints(s), heads=heads, chunk=chunk,
            theta=float(config["rope_theta"]), eps=eps, spoil=spoil)
        d = q.shape[1:]
        add(_block, like(row_block, *d), ints(row_block), like(span, *d),
            like(span, *d), ints(span), ksum, vsum, window=window,
            chunk=chunk, spoil=spoil)
        add(_attn_out, h, like(s, hidden), get("l0_attn", "wo"), spoil=spoil)
        mlp = [get(f"l0_ffn_{name}", "kernel")
               for name in ("gate", "up", "down")]
        for rows in sorted({min(MLP_ROWS, s), s % MLP_ROWS or MLP_ROWS}):
            add(_mlp, like(rows, hidden), get("l0_ln2", "scale"), *mlp,
                eps=eps, spoil=spoil)
        add(_head, h, get("ln_f", "scale"), get("lm_head", "kernel"),
            ints(-(-named // ROWS) * ROWS if named else s), eps=eps,
            spoil=spoil)
    return out
