"""The plain reference of Jamba2-3B as `build_transformer_lm` builds it
from `jamba_lm_config`: the forward pass of one sequence.

float32, `jax.default_matmul_precision("highest")`, jax.numpy only: no
kernel, no cache, no batching, a plain `lax.scan` a token for the
state-space recurrence. A forward is four jitted programs a length, and a
layer's weights are upcast when the layer runs (3.03 B float32 parameters
are 12.1 GB and do not fit beside the program on one chip).

The model (config.json of ai21labs/AI21-Jamba2-3B; what it leaves open is
listed as `assumed` in benchmarks/configs/jamba2-3b.json):

- Block, every layer: h = h + Mix(RMSNorm(h)); h = h + MLP(RMSNorm(h));
  eps `rms_norm_eps`, MLP(m) = (SiLU(m W_g) . m W_u) W_d, no biases. A
  final RMSNorm; logits against the embedding's table
  (`tie_word_embeddings`). No position enters anywhere.
- Layer i is a softmax layer where i mod `attn_layer_period` =
  `attn_layer_offset`: q = x W_q (H heads of d), k = x W_k, v = x W_v (G
  heads of d); causal softmax attention at scale d^-0.5, query head i
  reading KV head i // (H / G); y = o W_o.
- Every other layer is a selective state-space (Mamba-1) layer on a row
  x_t, E = `mamba_expand` x hidden channels, a state of N = `mamba_d_state`
  a channel, R = `mamba_dt_rank`:
    [u_t | z_t] = x_t W_in
    c_t = SiLU(b_conv + sum_{j<K} w_conv[j] . u_{t-K+1+j}), a causal
      depthwise convolution of K = `mamba_d_conv` taps a channel
    [r_t | B_t | C_t] = c_t W_x, of R, N and N
    r_t, B_t, C_t <- RMSNorm(r_t; g_dt), RMSNorm(B_t; g_B), RMSNorm(C_t; g_C)
    dt_t = softplus(r_t W_dt + b_dt), a channel
    A = -exp(A_log), (E, N)
    h_t = exp(dt_t (x) A) . h_{t-1} + (dt_t . c_t) (x) B_t, h (E, N)
      float32, h_{-1} = 0
    y_t = h_t C_t + D . c_t
    out = (y_t . SiLU(z_t)) W_out

Every departure from the published model is a comment that starts with
"departure:". `get(node, weight)` returns the program's own array of that
name (wte.kernel, l<i>_ln1.scale, l<i>_attn.{wq, wk, wv, wo} or
l<i>_attn.{w_in, conv, conv_bias, w_x, dt_norm, b_norm, c_norm, w_dt,
dt_bias, a_log, d, w_out}, l<i>_ln2.scale, l<i>_ffn_{gate, up,
down}.kernel, ln_f.scale). Linear weights are stored (in, out).
departure: the program keeps a_log and h with the state's N before the
channels, (N, E): the transpose of the published layout, the same numbers.

`spoil` computes one part of the model wrongly, for the controls that fix
the comparison's limits (scripts/jamba2_controls.py): "bf16_state" keeps h
in bfloat16 between two tokens, "no_inner_norms" leaves the three inner
RMSNorms out, "no_conv_bias" the convolution's bias, "no_d" the D . c
term, "no_dt_bias" dt's bias; "e4m3" rounds every matrix to float8_e4m3fn
(the nearest precision below the bfloat16 the configuration's cell
computes in).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
SPOILS = (None, "e4m3", "bf16_state", "no_inner_norms", "no_conv_bias",
          "no_d", "no_dt_bias")


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def e4m3(a):
    """float32 values rounded to the nearest float8_e4m3fn (three bits of
    mantissa, subnormal under 2^-6, largest 448), in float32 arithmetic:
    a conversion there and back is the compiler's to fold."""
    a = _f32(a)
    _, e = jnp.frexp(a)                         # a = m 2^e, |m| in [0.5, 1)
    quantum = jnp.exp2(jnp.maximum(e - 1, -6).astype(jnp.float32) - 3)
    return jnp.clip(jnp.round(a / quantum) * quantum, -448.0, 448.0)


def _mm(x, w, spoil):
    """x W as the reference multiplies."""
    return x @ (e4m3(w) if spoil == "e4m3" else _f32(w))


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(scale)


def silu(x):
    return x * jax.nn.sigmoid(x)


def softmax_attention(x, w, cfg, spoil=None):
    """A softmax layer's Mix of x (t, hidden)."""
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // H
    t = x.shape[0]
    q = _mm(x, w["wq"], spoil).reshape(t, G, H // G, d)
    k = _mm(x, w["wk"], spoil).reshape(t, G, d)
    v = _mm(x, w["wv"], spoil).reshape(t, G, d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.einsum("tgjd,sgd->gjts", q, k) * d ** -0.5
    p = jax.nn.softmax(jnp.where(causal, s, NEG), axis=-1)
    o = jnp.einsum("gjts,sgd->tgjd", p, v).reshape(t, H * d)
    return _mm(o, w["wo"], spoil)


def ssm_parameters(c, w, cfg, spoil=None):
    """(dt (t, E), B (t, N), C (t, N)) of the convolved rows c (t, E)."""
    N, R = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    eps = cfg["rms_norm_eps"]
    rbc = _mm(c, w["w_x"], spoil)
    r, B, C = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]
    if spoil != "no_inner_norms":
        r, B, C = (rms_norm(r, w["dt_norm"], eps),
                   rms_norm(B, w["b_norm"], eps),
                   rms_norm(C, w["c_norm"], eps))
    dt = _mm(r, w["w_dt"], spoil)
    if spoil != "no_dt_bias":
        dt = dt + _f32(w["dt_bias"])
    return jax.nn.softplus(dt), B, C


def ssm_recurrence(dt, c, B, C, A, D, state_dtype=jnp.float32, state=None,
                   at=None):
    """(y (t, E), h (E, N) after token `at`: the last where None) of the
    selective recurrence over t tokens of dt, c (t, E), B, C (t, N), with
    A (E, N) and D (E,), a `lax.scan` a token, from `state` (zeros): h =
    exp(dt_t (x) A) . h + (dt_t . c_t) (x) B_t; y_t = h C_t + D . c_t.
    `state_dtype` is what h is kept in between two tokens (float32;
    bfloat16 is the control)."""
    t, E = dt.shape
    at = t - 1 if at is None else at

    def token(carry, xs):
        h, kept = carry
        i, dt_t, c_t, b_t, c_out = xs
        h = (jnp.exp(dt_t[:, None] * A) * h.astype(jnp.float32)
             + (dt_t * c_t)[:, None] * b_t[None, :])
        h = h.astype(state_dtype)
        y = h.astype(jnp.float32) @ c_out + D * c_t
        return (h, jnp.where(i == at, h, kept)), y

    if state is None:
        state = jnp.zeros((E, A.shape[1]), jnp.float32)
    state = state.astype(state_dtype)
    (_, kept), y = jax.lax.scan(token, (state, state),
                                (jnp.arange(t), dt, c, B, C))
    return y, kept.astype(jnp.float32)


def mamba_layer(x, w, cfg, spoil=None, state_at=None):
    """(a state-space layer's Mix of x (t, hidden), its h (E, N) after
    token `state_at` and the convolution's inputs u of the K - 1 tokens up
    to it (K - 1, E), zeros before the sequence: after the last token
    where None)."""
    E = cfg["mamba_expand"] * cfg["hidden_size"]
    K = cfg["mamba_d_conv"]
    t = x.shape[0]
    uz = _mm(x, w["w_in"], spoil)
    u, z = uz[:, :E], uz[:, E:]
    taps = _f32(w["conv"])
    padded = jnp.concatenate([jnp.zeros((K - 1, E)), u])
    c = sum(taps[j] * padded[j:j + t] for j in range(K))
    if cfg["mamba_conv_bias"] and spoil != "no_conv_bias":
        c = c + _f32(w["conv_bias"])
    c = silu(c)
    dt, B, C = ssm_parameters(c, w, cfg, spoil)
    D = _f32(w["d"])
    y, h = ssm_recurrence(
        dt, c, B, C, -jnp.exp(_f32(w["a_log"])).T,
        jnp.zeros_like(D) if spoil == "no_d" else D,
        jnp.bfloat16 if spoil == "bf16_state" else jnp.float32, at=state_at)
    at = t - 1 if state_at is None else state_at
    tail = jax.lax.dynamic_slice_in_dim(padded, at + 1, K - 1, axis=0)
    return _mm(y * silu(z), w["w_out"], spoil), h, tail


class _Static(dict):
    """The configuration as a static argument of a jitted program."""

    def __hash__(self):
        return hash(repr(sorted(self.items(), key=lambda kv: kv[0])))


@jax.jit
def _embed(wte, tokens):
    return _f32(wte[tokens])


@functools.partial(jax.jit, static_argnames=("cfg", "softmax", "spoil"))
def _mix(x, scale, w, state_at, cfg, softmax, spoil):
    a = rms_norm(x, scale, cfg["rms_norm_eps"])
    if softmax:
        return x + softmax_attention(a, w, cfg, spoil), None, None
    y, h, tail = mamba_layer(a, w, cfg, spoil, state_at)
    return x + y, h, tail


@functools.partial(jax.jit, static_argnames=("eps", "spoil"))
def _mlp(x, scale, gate, up, down, eps, spoil):
    m = rms_norm(x, scale, eps)
    return x + _mm(silu(_mm(m, gate, spoil)) * _mm(m, up, spoil), down,
                   spoil)


@functools.partial(jax.jit, static_argnames=("eps", "spoil"))
def _head(x, scale, wte, rows, eps, spoil):
    # the tied head: logits against the embedding's table
    return _mm(rms_norm(x[rows], scale, eps), jnp.asarray(wte).T, spoil)


def model_cfg(config: dict) -> dict:
    """The published keys the reference reads."""
    return _Static({k: v for k, v in config.items()
                    if isinstance(v, (int, float, bool, str)) or v is None})


def layer_kinds(config: dict) -> list:
    """"attention" or "mamba" a layer, by the family's published rule."""
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return ["attention" if i % period == offset else "mamba"
            for i in range(config["num_hidden_layers"])]


ATTENTION = ("wq", "wk", "wv", "wo")
MAMBA = ("w_in", "conv", "conv_bias", "w_x", "dt_norm", "b_norm", "c_norm",
         "w_dt", "dt_bias", "a_log", "d", "w_out")


def forward(get, tokens, config, *, rows=None, spoil=None, state_at=None):
    """(logits (len(rows), vocabulary) float32 of one sequence `tokens`
    (t,), report): every row where `rows` is None. `report` holds the
    state-space layers' h (N, E) (`states`, in the layers' order and in
    the program's layout) and their convolution's last K - 1 inputs (K -
    1, E) (`tails`) after token `state_at` (after the last token where
    None)."""
    if spoil not in SPOILS:
        raise ValueError(f"spoil must be one of {SPOILS}")
    if not config.get("tie_word_embeddings", True):
        raise NotImplementedError("the reference builds the tied head")
    cfg = model_cfg(config)
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    rows = jnp.arange(t) if rows is None else jnp.asarray(rows, jnp.int32)
    state_at = jnp.asarray(t - 1 if state_at is None else state_at,
                           jnp.int32)
    eps = cfg["rms_norm_eps"]
    report = {"states": [], "tails": []}
    with jax.default_matmul_precision("highest"):
        x = _embed(get("wte", "kernel"), tokens)
        for i, kind in enumerate(layer_kinds(config)):
            softmax = kind == "attention"
            names = ATTENTION if softmax else tuple(
                n for n in MAMBA
                if n != "conv_bias" or cfg["mamba_conv_bias"])
            x, h, tail = _mix(x, get(f"l{i}_ln1", "scale"),
                              {n: get(f"l{i}_attn", n) for n in names},
                              state_at, cfg, softmax, spoil)
            if not softmax:
                report["states"].append(np.asarray(h).T)
                report["tails"].append(np.asarray(tail))
            x = _mlp(x, get(f"l{i}_ln2", "scale"),
                     get(f"l{i}_ffn_gate", "kernel"),
                     get(f"l{i}_ffn_up", "kernel"),
                     get(f"l{i}_ffn_down", "kernel"), eps, spoil)
        logits = _head(x, get("ln_f", "scale"), get("wte", "kernel"), rows,
                       eps, spoil)
    return np.asarray(logits), report


def logit_error(program, reference) -> float:
    """max |difference| over max |reference logit|."""
    reference = np.asarray(reference, np.float32)
    return float(np.max(np.abs(np.asarray(program, np.float32) - reference))
                 / np.max(np.abs(reference)))
