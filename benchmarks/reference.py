"""The plain reference of the GPT-2 block as this repo builds it
(`models/transformer._lm_trunk`), and the comparison that decides `correct`.

float32, `jax.default_matmul_precision("highest")`, jax.numpy only: no
kernel, no cache, no batching tricks. Token and learned position
embeddings, then per layer pre-LN -> full causal multi-head attention with
biases -> residual -> pre-LN -> dense 4x -> GELU -> dense -> residual, a
final LayerNorm and an untied, bias-free head. Departures from the
published GPT-2 block follow the program and are listed in each
configuration's `departures`: the head is not tied to `wte`, and GELU is
the exact erf form, not GPT-2's tanh approximation.

One jitted block is applied layer by layer with that layer's weights fetched
by name through `get(node, weight)`, so the reference never holds more than
the program's own arrays and 1.4 B parameters are no obstacle.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5

# max |program logit - reference logit| over the compared positions, as a
# share of the largest |reference logit| there. The cells compute in bf16
# (eps 2^-8 = 0.0039) through 24 layers against this float32 reference;
# measured on the chip (PR 24): 0.0077-0.0091 for the training graphs of
# both configurations, 0.0105-0.0135 for cerebras-gpt-1.3b's decode graph
# through the paged cache. 0.03 is a little over twice the worst reading:
# a float32 program passes with room, and a step down in precision (an
# 8-bit float has eps 2^-4, sixteen times bf16's) cannot.
LOGIT_TOL = 0.03


def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


@functools.partial(jax.jit, static_argnames=("num_heads",))
def block(x, w, num_heads: int):
    """One pre-LN block on x (batch, seq, hidden); w is the layer's
    weights keyed `<op>.<weight>` (ln1, attn, ln2, ffn1, ffn2)."""
    b, s, d = x.shape
    hd = d // num_heads
    a = _layer_norm(x, w["ln1.scale"], w["ln1.bias"])

    def heads(t):
        return t.reshape(b, s, num_heads, hd).transpose(0, 2, 1, 3)

    q = heads(a @ w["attn.wq"] + w["attn.bq"])
    k = heads(a @ w["attn.wk"] + w["attn.bk"])
    v = heads(a @ w["attn.wv"] + w["attn.bv"])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + ctx @ w["attn.wo"] + w["attn.bo"]
    m = _layer_norm(x, w["ln2.scale"], w["ln2.bias"])
    m = jax.nn.gelu(m @ w["ffn1.kernel"] + w["ffn1.bias"], approximate=False)
    return x + m @ w["ffn2.kernel"] + w["ffn2.bias"]


@jax.jit
def _embed(tokens, wte, wpe):
    return wte[tokens] + wpe[jnp.arange(tokens.shape[1])][None]


@jax.jit
def _head(x, scale, bias, kernel):
    return _layer_norm(x, scale, bias) @ kernel


LAYER_WEIGHTS = (
    "ln1.scale", "ln1.bias", "attn.wq", "attn.wk", "attn.wv", "attn.wo",
    "attn.bq", "attn.bk", "attn.bv", "attn.bo", "ln2.scale", "ln2.bias",
    "ffn1.kernel", "ffn1.bias", "ffn2.kernel", "ffn2.bias")


def forward_logits(get, tokens, *, num_layers: int, num_heads: int):
    """float32 logits (batch, seq, vocab) of the full causal forward over
    `tokens` (batch, seq) int. `get(node, weight)` returns the program's
    array of that name (wte.kernel, l3_attn.wq, lm_head.kernel, ...)."""
    def f32(node, weight):
        return jnp.asarray(get(node, weight), jnp.float32)

    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _embed(tokens, f32("wte", "kernel"), f32("wpe", "kernel"))
        for i in range(num_layers):
            w = {name: f32(f"l{i}_{name.split('.')[0]}", name.split(".")[1])
                 for name in LAYER_WEIGHTS}
            x = block(x, w, num_heads)
        logits = _head(x, f32("ln_f", "scale"), f32("ln_f", "bias"),
                       f32("lm_head", "kernel"))
    return np.asarray(logits, np.float32)


def loss(logits, labels) -> float:
    """Mean next-token cross entropy of float32 logits (.., vocab)."""
    logits = np.asarray(logits, np.float64)
    labels = np.asarray(labels).reshape(logits.shape[:-1])
    top = logits.max(axis=-1, keepdims=True)
    logz = top[..., 0] + np.log(np.exp(logits - top).sum(axis=-1))
    picked = np.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return float(np.mean(logz - picked))


def logit_error(program, reference) -> float:
    """max |program - reference| as a share of max |reference|; infinite
    where the program's logits are not all finite."""
    program = np.asarray(program, np.float32)
    reference = np.asarray(reference, np.float32)
    if program.shape != reference.shape:
        raise ValueError(f"logit shapes differ: {program.shape} against "
                         f"{reference.shape}")
    if not np.all(np.isfinite(program)):
        return float("inf")
    return float(np.max(np.abs(program - reference))
                 / np.max(np.abs(reference)))


def stream_agrees(logits, tokens) -> bool:
    """Whether a greedy stream is one the reference could have written:
    `logits` (n, vocab) are the reference's rows at the positions the
    stream's `tokens` (n) were sampled from, prompt and the stream's own
    earlier tokens before them. A program whose logits are within
    LOGIT_TOL of these picks, at each position, a token whose reference
    logit is within twice that of the row's largest: both logits may be
    off by the tolerance. With some tens of thousands of tokens to pick
    from, a few at most lie that close to the top, so a stream from a
    wrong cache, page table or position does not pass by chance."""
    logits = np.asarray(logits, np.float32)
    tokens = np.asarray(tokens).reshape(-1)
    if logits.shape[0] != tokens.shape[0] or not tokens.size:
        return False
    margin = 2.0 * LOGIT_TOL * float(np.max(np.abs(logits)))
    picked = logits[np.arange(tokens.size), tokens]
    return bool(np.all(picked >= logits.max(axis=-1) - margin))
