"""The plain reference of EvaByte as `build_transformer_lm` builds it from
`evabyte_lm_config`: the forward pass of one sequence of bytes.

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
