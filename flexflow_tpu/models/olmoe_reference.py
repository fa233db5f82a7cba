"""The plain reference of the OLMoE model as `build_transformer_lm` builds
it from `olmoe_lm_config`: forward, loss, and `jax.grad` of the loss.

float32, `jax.default_matmul_precision("highest")`, jax.numpy only: no
kernel, no sort, no dispatch (the experts are a loop over all of them under
a dense mask). It follows transformers' `modeling_olmoe.py` (the
`model_type: olmoe` of the published config.json); every departure from it
is a comment that starts with "departure:".

`params` is the program's own nested dict `{node: {weight: array}}`
(`FFModel._params`): wte.kernel, l<i>_ln1.scale, l<i>_attn.{wq, wk, wv, wo,
q_norm, k_norm}, l<i>_ln2.scale, l<i>_moe.{router, gate, up, down},
ln_f.scale, lm_head.kernel. Linear weights are stored (in, out), the
transpose of torch's, so `x @ w` here is `F.linear(x, w.T)` there.

Routing is discontinuous: where the k-th and (k+1)-th router probabilities
of a position are closer than `tie_margin` (as a share of the k-th), a
program in lower precision may rightly pick the other expert. `forward`
takes the program's choice (`program_ids`) and uses it at exactly those
positions; everywhere else the choice is its own. The routing it returns
holds the near-tie mask and its own choice, so a caller can count where
the program's choice was taken over it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    # OlmoeRMSNorm: x * rsqrt(mean(x^2) + eps), then the learned scale
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope_cos_sin(positions, head_dim, theta):
    # OlmoeRotaryEmbedding: inv_freq = 1 / theta^(2i / head_dim), the
    # angles of a position repeated for both halves of the head
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def attention(x, w, positions, *, num_heads, eps, theta):
    """OlmoeAttention on x (b, s, d), full causal multi-head attention."""
    b, s, d = x.shape
    hd = d // num_heads
    # QK-norm over the whole projection (all heads together), before the
    # split in heads; clip_qkv is null in the published config
    q = rms_norm(x @ w["wq"], w["q_norm"], eps)
    k = rms_norm(x @ w["wk"], w["k_norm"], eps)
    v = x @ w["wv"]

    def heads(t):
        return t.reshape(b, s, num_heads, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    cos, sin = rope_cos_sin(positions, hd, theta)
    cos, sin = cos[:, None], sin[:, None]
    q = q * cos + rotate_half(q) * sin
    k = k * cos + rotate_half(k) * sin
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, d) @ w["wo"]


def route(x, router, k, *, program_ids=None, tie_margin=0.0):
    """(gate weights (t, k), expert ids used (t, k), router probabilities
    (t, n), near-tie mask (t,), the reference's own choice (t, k)) of
    tokens x (t, d). OlmoeSparseMoeBlock: softmax over all experts in
    float32, the k largest, not renormalised (norm_topk_prob false)."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    top, own = jax.lax.top_k(probs, k + 1)
    tie = (top[:, k - 1] - top[:, k]) < tie_margin * top[:, k - 1]
    own = ids = own[:, :k]
    if program_ids is not None:
        ids = jnp.where(tie[:, None], program_ids.reshape(ids.shape), own)
    else:
        tie = jnp.zeros_like(tie)
    return jnp.take_along_axis(probs, ids, axis=-1), ids, probs, tie, own


def experts(x, gates, ids, w):
    """sum_j gates_j * (silu(x @ gate_e) * (x @ up_e)) @ down_e over each
    token's chosen experts e = ids_j.
    departure: modeling_olmoe loops over the experts and index_adds the
    rows routed to each; here every expert runs on every token and a dense
    (t, n) mask of gate weights picks: the same sum, no gather."""
    n = w["gate"].shape[0]
    mask = jnp.sum(jax.nn.one_hot(ids, n, dtype=x.dtype) * gates[..., None],
                   axis=1)
    y = jnp.zeros_like(x)
    for e in range(n):
        h = jax.nn.silu(x @ w["gate"][e]) * (x @ w["up"][e])
        y = y + mask[:, e:e + 1] * (h @ w["down"][e])
    return y


def load_balancing_loss(probs, ids, num_experts):
    """transformers' load_balancing_loss_func on one router's probs (t, n)
    and chosen ids (t, k): num_experts x the sum over experts of (share of
    the tokens that chose it, per choice slot) x (its mean probability)."""
    expert_mask = jax.nn.one_hot(ids, num_experts, dtype=probs.dtype)
    tokens_per_expert = jnp.mean(expert_mask, axis=0)          # (k, n)
    router_prob_per_expert = jnp.mean(probs, axis=0)           # (n,)
    return num_experts * jnp.sum(tokens_per_expert
                                 * router_prob_per_expert[None])


def forward(params, tokens, positions, *, num_layers, num_heads,
            num_experts_per_tok, eps=1e-5, rope_theta=10000.0,
            program_ids=None, tie_margin=0.0):
    """(logits (b, s, vocab) float32, routing) of the causal forward over
    tokens (b, s) at positions (b, s). routing: per layer the router's
    probabilities, the experts used, the near-tie mask and the choice the
    reference would have made; `program_ids` is a per-layer list of the
    program's choice of experts."""
    def f32(t):
        return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)

    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"]["kernel"])[jnp.asarray(tokens, jnp.int32)]
        b, s, d = x.shape
        routing = []
        for i in range(num_layers):
            p = f"l{i}_"
            a = rms_norm(x, f32(params[p + "ln1"]["scale"]), eps)
            x = x + attention(a, f32(params[p + "attn"]),
                              jnp.asarray(positions), num_heads=num_heads,
                              eps=eps, theta=rope_theta)
            m = rms_norm(x, f32(params[p + "ln2"]["scale"]),
                         eps).reshape(b * s, d)
            w = f32(params[p + "moe"])
            gates, ids, probs, tie, own = route(
                m, w["router"], num_experts_per_tok,
                program_ids=None if program_ids is None else program_ids[i],
                tie_margin=tie_margin)
            x = x + experts(m, gates, ids, w).reshape(b, s, d)
            routing.append({"probs": probs, "ids": ids, "tie": tie,
                            "own_ids": own})
        x = rms_norm(x, f32(params["ln_f"]["scale"]), eps)
        # departure: none for the head (untied, bias-free, as published)
        logits = x @ f32(params["lm_head"]["kernel"])
    return logits, routing


def loss(params, tokens, positions, labels, *, router_aux_loss_coef,
         **model):
    """Mean next-token cross entropy + router_aux_loss_coef x the
    load-balancing term.
    departure: `labels` (b, s) are the next tokens already (the traffic
    generator shifts), where OlmoeForCausalLM shifts inside.
    departure: the paper's router z-loss is a training-recipe term that
    config.json does not carry; it is left out.
    departure: the term is the mean over the layers of each router's own
    load_balancing_loss (the paper's per-layer N_E sum_i f_i P_i).
    OlmoeForCausalLM hands the function all layers' router logits
    concatenated, which multiplies one layer's shares with another's
    probabilities as well; with one layer the two are the same number."""
    logits, routing = forward(params, tokens, positions, **model)
    logp = jax.nn.log_softmax(logits, axis=-1)
    labels = jnp.asarray(labels, jnp.int32).reshape(logits.shape[:-1])
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    aux = sum(load_balancing_loss(r["probs"], r["ids"], r["probs"].shape[-1])
              for r in routing) / len(routing)
    return ce + router_aux_loss_coef * aux


grad = jax.grad(loss)
