"""The plain reference of LFM2-MoE (LFM2-8B-A1B) as `build_transformer_lm`
builds it from `lfm2_moe_lm_config`: forward, loss, and `jax.grad` of the
loss.

float32, `jax.default_matmul_precision("highest")`, jax.numpy only: no
kernel, no sort, no dispatch (the held experts are a loop under a dense
mask), the attention in blocks of query rows so that 8,192 positions fit
beside a training state. The short convolution, the attention, the norms
and the layer follow transformers' `modeling_lfm2.py` (4.57: Lfm2ShortConv,
Lfm2Attention, Lfm2RMSNorm, Lfm2DecoderLayer); the expert layer follows
`modeling_lfm2_moe.py` as the published config.json's keys describe it
(that file is not in this installation: see "assumed" below). Every
departure is a comment that starts with "departure:".

`params` is the program's own nested dict `{node: {weight: array}}`
(`FFModel._params`): wte.kernel (vocab, hidden; the head is tied to it),
l<i>_ln1.scale (operator_norm), l<i>_attn.{w_in (hidden, 3, hidden), conv
(taps, hidden), w_out} on a `conv` layer or l<i>_attn.{wq, wk, wv, wo,
q_norm, k_norm} on a `full_attention` layer, l<i>_ln2.scale (ffn_norm),
l<i>_ffn_{gate, up, down}.kernel on a dense layer or l<i>_moe.{router,
router_bias, gate, up, down} on an expert layer, ln_f.scale
(embedding_norm). Linear weights are stored (in, out), the transpose of
torch's; `w_in[:, 0]`, `[:, 1]`, `[:, 2]` are in_proj's three chunks B, C,
x; `conv[j, c]` is torch's conv.weight[c, 0, j].

assumed (what only `lfm2_moe` defines): the router scores are sigmoid(n
W_r); the k largest of scores + expert_bias are chosen (`use_expert_bias`);
the gates are the scores at the chosen, divided by (their sum + 1e-6) where
`norm_topk_prob`, times `routed_scaling_factor`; the dense layers' width is
`intermediate_size` as written; the head is tied to the embedding.

A cut configuration holds `experts_held` = (first id, count) of a layer's
experts: the router keeps its width and its k, and what the experts held
elsewhere would add is left out of the result, and so of every gradient.

Routing is discontinuous: where the k-th and (k+1)-th of a position's
scores + bias are closer than `tie_margin` (as a share of the k-th), a
program in lower precision may rightly pick the other expert. `forward`
takes the program's choice (`program_ids`) and uses it at exactly those
positions; everywhere else the choice is its own.

`spoil` names a control that must NOT pass a comparison with the program:
"bf16_taps" rounds the convolution's input, products and sum to bfloat16;
"kv_shift" has query head i read KV head i // group + 1; "e4m3" rounds
every matrix to an 8-bit float (4 exponent bits, 3 of mantissa).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 256     # query rows a block of the attention


def rms_norm(x, scale, eps):
    # Lfm2RMSNorm: x * rsqrt(mean(x^2) + eps), then the learned scale
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope_cos_sin(positions, head_dim, theta):
    # Lfm2RotaryEmbedding: inv_freq = 1 / theta^(2i / head_dim), a
    # position's angles repeated for both halves of the head
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def short_conv(x, w, *, spoil=None):
    """Lfm2ShortConv on x (b, s, d): [B | C | x'] = in_proj(x); u = B x';
    c[t] = sum_j w[j] u[t - (taps - 1) + j] (torch's Conv1d with padding
    taps - 1, cut to s: causal, depthwise, no activation, no bias);
    out_proj(C c)."""
    s = x.shape[1]
    bcx = jnp.einsum("bsd,dge->bsge", x, w["w_in"])
    B, C, xs = bcx[..., 0, :], bcx[..., 1, :], bcx[..., 2, :]
    u = B * xs
    taps = w["conv"].shape[0]
    if spoil == "bf16_taps":
        u = _bf16(u)
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    c = jnp.zeros_like(u)
    for j in range(taps):
        term = w["conv"][j] * padded[:, j:j + s]
        c = c + term if spoil != "bf16_taps" else _bf16(c + _bf16(term))
    return (C * c) @ w["w_out"]


def attention(x, w, positions, *, num_heads, num_kv_heads, eps, theta,
              spoil=None):
    """Lfm2Attention on x (b, s, d): causal softmax attention, query head
    i reading KV head i // (num_heads // num_kv_heads), an RMSNorm over
    each q and k head's lanes (q_layernorm, k_layernorm) before RoPE.
    departure: none in the mathematics; the scores are computed for
    ROW_BLOCK query rows at a time (each row's softmax is whole)."""
    b, s, _ = x.shape
    hd = w["wq"].shape[1] // num_heads
    group = num_heads // num_kv_heads

    def heads(t, n):
        return t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    q = rms_norm(heads(x @ w["wq"], num_heads), w["q_norm"], eps)
    k = rms_norm(heads(x @ w["wk"], num_kv_heads), w["k_norm"], eps)
    v = heads(x @ w["wv"], num_kv_heads)
    cos, sin = rope_cos_sin(positions, hd, theta)
    cos, sin = cos[:, None], sin[:, None]
    q = q * cos + rotate_half(q) * sin
    k = k * cos + rotate_half(k) * sin
    if spoil == "kv_shift":
        k, v = jnp.roll(k, -1, axis=1), jnp.roll(v, -1, axis=1)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = min(ROW_BLOCK, s)
    pad = -s % block
    qb = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qb = qb.reshape(b, num_heads, -1, block, hd).transpose(2, 0, 1, 3, 4)
    keys = jnp.arange(s)

    def rows(args):
        qi, first = args
        scores = jnp.einsum("bhqd,bhkd->bhqk", qi, k) / math.sqrt(hd)
        at = first + jnp.arange(block)
        scores = jnp.where(keys[None, :] <= at[:, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(rows, (qb, jnp.arange(qb.shape[0]) * block))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, num_heads, -1, hd)[:, :, :s]
    return out.transpose(0, 2, 1, 3).reshape(b, s, num_heads * hd) @ w["wo"]


def route(x, router, bias, k, *, norm_topk_prob, routed_scaling_factor,
          program_ids=None, tie_margin=0.0):
    """(gates (t, k), expert ids used (t, k), scores (t, n), near-tie mask
    (t,), the reference's own choice (t, k)) of tokens x (t, d)."""
    scores = jax.nn.sigmoid(x @ router)
    biased = scores if bias is None else scores + bias
    top, own = jax.lax.top_k(biased, k + 1)
    tie = (top[:, k - 1] - top[:, k]) < tie_margin * top[:, k - 1]
    own = ids = own[:, :k]
    if program_ids is not None:
        ids = jnp.where(tie[:, None], program_ids.reshape(ids.shape), own)
    else:
        tie = jnp.zeros_like(tie)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    if norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    return gates * routed_scaling_factor, ids, scores, tie, own


def experts(x, gates, ids, w, held):
    """sum_j gates_j * (silu(x gate_e) * (x up_e)) down_e over each token's
    chosen experts e = ids_j that are held here.
    departure: the published block loops over the experts and index_adds
    the rows routed to each; here every held expert runs on every token
    and a dense (t, n) mask of gate weights picks: the same sum."""
    first, count = held
    mask = jnp.sum(jax.nn.one_hot(ids, w["router"].shape[1], dtype=x.dtype)
                   * gates[..., None], axis=1)
    y = jnp.zeros_like(x)
    for e in range(count):
        h = jax.nn.silu(x @ w["gate"][e]) * (x @ w["up"][e])
        y = y + mask[:, first + e:first + e + 1] * (h @ w["down"][e])
    return y


def _e4m3(a):
    return jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3)


def forward(params, tokens, positions, *, layer_types, num_dense_layers,
            num_heads, num_kv_heads, num_experts_per_tok, eps=1e-5,
            rope_theta=1000000.0, norm_topk_prob=True,
            routed_scaling_factor=1.0, experts_held=None, program_ids=None,
            tie_margin=0.0, spoil=None, mixers=None):
    """(logits (b, s, vocab) float32, routing) of the causal forward over
    tokens (b, s) at positions (b, s); the vocabulary is the embedding's
    rows (a slice of the published one in a cut configuration). routing:
    per expert layer the scores, the experts used, the near-tie mask and
    the reference's own choice; `program_ids` is a per-expert-layer list of
    the program's choice of experts. `mixers`, where a dict, receives
    {layer index: (the mixer's input n, its output)} of the
    `full_attention` layers, for a comparison of that layer alone."""
    def f32(t):
        return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)

    def matrices(w):
        if spoil != "e4m3":
            return w
        return {k: _e4m3(a) if a.ndim >= 2 else a for k, a in w.items()}

    with jax.default_matmul_precision("highest"):
        wte = matrices(f32(params["wte"]))["kernel"]
        x = wte[jnp.asarray(tokens, jnp.int32)]
        b, s, d = x.shape
        routing = []
        for i, kind in enumerate(layer_types):
            p = f"l{i}_"
            n = rms_norm(x, f32(params[p + "ln1"]["scale"]), eps)
            w = matrices(f32(params[p + "attn"]))
            if kind == "conv":
                x = x + short_conv(n, w, spoil=spoil)
            elif kind == "full_attention":
                a = attention(n, w, jnp.asarray(positions),
                              num_heads=num_heads, num_kv_heads=num_kv_heads,
                              eps=eps, theta=rope_theta, spoil=spoil)
                if mixers is not None:
                    mixers[i] = (n, a)
                x = x + a
            else:
                raise ValueError(f"layer_types[{i}] = {kind!r}")
            m = rms_norm(x, f32(params[p + "ln2"]["scale"]), eps)
            if i < num_dense_layers:
                # Lfm2MLP: w2(silu(w1 x) * w3 x)
                g, u, dn = (matrices(f32(params[p + "ffn_" + t]))["kernel"]
                            for t in ("gate", "up", "down"))
                x = x + (jax.nn.silu(m @ g) * (m @ u)) @ dn
                continue
            w = matrices(f32(params[p + "moe"]))
            m = m.reshape(b * s, d)
            at = len(routing)
            gates, ids, scores, tie, own = route(
                m, w["router"], w.get("router_bias"), num_experts_per_tok,
                norm_topk_prob=norm_topk_prob,
                routed_scaling_factor=routed_scaling_factor,
                program_ids=None if program_ids is None else program_ids[at],
                tie_margin=tie_margin)
            held = experts_held or (0, w["router"].shape[1])
            x = x + experts(m, gates, ids, w, tuple(held)).reshape(b, s, d)
            routing.append({"scores": scores, "ids": ids, "tie": tie,
                            "own_ids": own,
                            "biased": scores if "router_bias" not in w
                            else scores + w["router_bias"]})
        x = rms_norm(x, f32(params["ln_f"]["scale"]), eps)
        # the head is tied: the embedding's table, transposed
        logits = x @ wte.T
    return logits, routing


def loss(params, tokens, positions, labels, **model):
    """Mean next-token cross entropy over the vocabulary's slice.
    departure: `labels` (b, s) are the next tokens already (the traffic
    generator shifts), where Lfm2MoeForCausalLM shifts inside.
    departure: no load-balancing term (the published config.json carries
    no coefficient for one)."""
    logits, _ = forward(params, tokens, positions, **model)
    logp = jax.nn.log_softmax(logits, axis=-1)
    labels = jnp.asarray(labels, jnp.int32).reshape(logits.shape[:-1])
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


grad = jax.grad(loss)
