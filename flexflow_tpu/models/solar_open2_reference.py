"""The plain reference of the Solar-Open2 model as `build_transformer_lm`
builds it from `solar_open2_lm_config`: the forward pass of one sequence.

float32, `jax.default_matmul_precision("highest")`, jax.numpy only: no
kernel, no cache, no batching, a plain `lax.scan` a token for the delta
rule. Softmax attention is computed a KV head at a time and the experts
one after another so that a sequence of some four thousand tokens at the
published widths fits beside the program on one chip; the blocks change
no number. A forward is five jitted programs a length.

The model (config.json of upstage/Solar-Open2-250B; what it leaves open is
listed as `assumed` in benchmarks/configs/solar-open2-250b.json):

- Block, every layer: h = h + Mix(RMSNorm(h)); h = h + MoE(RMSNorm(h));
  eps 1e-5, no biases but one (below). Final RMSNorm, untied head. No
  position enters anywhere (`use_rope: false`).
- Softmax layers (`gqa_layers`): q = x W_q (H heads of d), k = x W_k, v =
  x W_v (G heads of d); causal softmax attention at scale d^-0.5, query
  head i reading KV head i // (H / G); o = o * sigmoid(x W_g),
  elementwise; y = o W_o.
- Delta-rule layers (the others), H heads of d:
    [q~, k~, v~] = x [W_q, W_k, W_v]
    u'_t = SiLU(sum_{i<K} w_i u~_{t-K+1+i}), a causal depthwise
      convolution of K = 4 taps a channel, on each of the three
    q_t = l2norm(q'_t) d^-0.5, k_t = l2norm(k'_t), v_t = v'_t, a head
    alpha_t = exp(-exp(A_log_h) softplus(W_fb (W_fa x_t) + dt_bias)), in
      (0, 1)^d: a decay a channel of the key
    beta_t = 2 sigmoid(x_t W_beta), a head (`kda_allow_neg_eigval`)
    S' = Diag(alpha_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t, S (d x d) float32 a head, S_0 = 0
    o_t = RMSNorm_d(o_t; gamma) * sigmoid(W_gb (W_ga x_t) + b_gb)
    y = concat_h(o_t) W_o
- Expert layer: p = softmax(x R) over all routed experts, the k largest,
  gates g_e = routed_scaling_factor p_e / sum_chosen p
  (`norm_topk_prob`). y = Shared(x) + sum_chosen g_e E_e(x), E(x) =
  W_down(SiLU(W_gate x) * W_up x). On one chip of a deployment the layer
  holds experts e0 .. e0 + n - 1 and the sum runs over the chosen experts
  that are held; what the others would add is left out.

Every departure from the published model is a comment that starts with
"departure:". `get(node, weight)` returns the program's own array of that
name (wte.kernel, l<i>_ln1.scale, l<i>_attn.{wq, wk, wv, wg, wo} or
l<i>_attn.{wq, wk, wv, conv, w_fa, w_fb, a_log, dt_bias, w_beta, o_norm,
w_ga, w_gb, b_gb, wo}, l<i>_ln2.scale, l<i>_moe.{router, gate, up, down,
shared_gate, shared_up, shared_down}, ln_f.scale, lm_head.kernel). Linear
weights are stored (in, out).

Routing is discontinuous: a program in lower precision may rightly pick
an expert the reference scores a little under its k-th. `forward` takes
the program's experts (`program_experts`: (layers, tokens, k), a row of -1
where they are not known) and tests every one of them: a token's shortfall
is how far the lowest-scored of the program's experts lies under the
reference's k-th probability, as a share of the k-th (0 where the program
chose the reference's own). Where the shortfall is within the margin the
reference computes the token with the program's experts (`ties` counts
those that differ from its own); where it is not, with its own, and the
token is counted in `route_bad`.

`spoil` computes one part of the model wrongly, for the controls that fix
the comparison's limits (benchmarks/jobs/serve_reason.py): "e4m3" rounds
every matrix to float8_e4m3fn, "bf16_state" keeps the delta rule's state
in bfloat16 between two tokens, "no_conv" leaves the convolution out (SiLU of the
projection), "beta1" drops beta's factor 2.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
SPOILS = (None, "e4m3", "bf16_state", "no_conv", "beta1")


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def e4m3(a):
    """float32 values rounded to the nearest float8_e4m3fn (three bits of
    mantissa, subnormal under 2^-6, largest 448), in float32 arithmetic:
    a conversion there and back is the compiler's to fold."""
    a = jnp.asarray(a).astype(jnp.float32)
    _, e = jnp.frexp(a)                         # a = m 2^e, |m| in [0.5, 1)
    quantum = jnp.exp2(jnp.maximum(e - 1, -6).astype(jnp.float32) - 3)
    return jnp.clip(jnp.round(a / quantum) * quantum, -448.0, 448.0)


def _mat(a, spoil):
    """A matrix as the reference multiplies by it."""
    return e4m3(a) if spoil == "e4m3" else _f32(a)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def gqa_attention(x, w, cfg, spoil=None):
    """A softmax layer's Mix of x (t, hidden)."""
    H, G, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    t = x.shape[0]
    q = (x @ _mat(w["wq"], spoil)).reshape(t, G, H // G, d)
    k = (x @ _mat(w["wk"], spoil)).reshape(t, G, d)
    v = (x @ _mat(w["wv"], spoil)).reshape(t, G, d)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def kv_head(qkv):
        qg, kg, vg = qkv                       # (t, H / G, d), (t, d), (t, d)
        s = jnp.einsum("tjd,sd->jts", qg, kg) * d ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, NEG), axis=-1)
        return jnp.einsum("jts,sd->tjd", p, vg)

    # departure: a KV head at a time (the scores of all heads of a long
    # sequence do not fit); the same numbers
    o = jax.lax.map(kv_head, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                              jnp.moveaxis(v, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(t, H * d)
    if cfg["use_gqa_gate"]:
        o = o * jax.nn.sigmoid(x @ _mat(w["wg"], spoil))
    return o @ _mat(w["wo"], spoil)


def delta_attention(x, w, cfg, spoil=None, state_at=None):
    """(a delta-rule layer's Mix of x (t, hidden), its state (H, d, d)
    after token `state_at`: after the last where None)."""
    lin = cfg["linear_attn_config"]
    H, d, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    t = x.shape[0]
    u = jnp.concatenate([x @ _mat(w[n], spoil) for n in ("wq", "wk", "wv")],
                        axis=-1)
    if spoil != "no_conv":
        taps = _f32(w["conv"])
        padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1])), u])
        u = sum(taps[i] * padded[i:i + t] for i in range(K))
    u = u * jax.nn.sigmoid(u)
    q, k, v = (a.reshape(t, H, d) for a in jnp.split(u, 3, axis=-1))

    def l2norm(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                                 + 1e-6)

    q, k = l2norm(q) * d ** -0.5, l2norm(k)
    f = (x @ _mat(w["w_fa"], spoil)) @ _mat(w["w_fb"], spoil)
    f = jax.nn.softplus(f + _f32(w["dt_bias"])).reshape(t, H, d)
    alpha = jnp.exp(-jnp.exp(_f32(w["a_log"]))[:, None] * f)
    beta = jax.nn.sigmoid(x @ _mat(w["w_beta"], spoil))
    if cfg["kda_allow_neg_eigval"] and spoil != "beta1":
        beta = 2.0 * beta
    o, state = delta_recurrence(
        q, k, v, alpha, beta,
        jnp.bfloat16 if spoil == "bf16_state" else jnp.float32, at=state_at)
    o = rms_norm(o, _f32(w["o_norm"]), cfg["rms_norm_eps"])
    gate = ((x @ _mat(w["w_ga"], spoil)) @ _mat(w["w_gb"], spoil)
            + _f32(w["b_gb"]))
    y = (o.reshape(t, H * d) * jax.nn.sigmoid(gate)) @ _mat(w["wo"], spoil)
    return y, state


def delta_recurrence(q, k, v, alpha, beta, state_dtype=jnp.float32,
                     state=None, at=None):
    """(o (t, H, d), the state (H, d, d) after token `at`: the last where
    None) of the gated delta rule over t tokens of q, k, v, alpha (t, H,
    d) and beta (t, H), a `lax.scan` a token, from `state` (zeros): S' =
    Diag(alpha_t) S; S = S' + beta_t k_t (v_t - S'^T k_t)^T; o_t = S^T
    q_t. `state_dtype` is what the state is kept in between two tokens
    (float32; bfloat16 is the control)."""
    t, H, d = q.shape
    at = t - 1 if at is None else at

    def token(carry, xs):
        S, kept = carry
        i, qt, kt, vt, alpha_t, bt = xs
        S = S.astype(jnp.float32) * alpha_t[..., None]
        pred = jnp.einsum("hkv,hk->hv", S, kt)
        S = S + (bt[:, None] * kt)[..., None] * (vt - pred)[:, None, :]
        S = S.astype(state_dtype)
        return ((S, jnp.where(i == at, S, kept)),
                jnp.einsum("hkv,hk->hv", S.astype(jnp.float32), qt))

    if state is None:
        state = jnp.zeros((H, d, d), jnp.float32)
    state = state.astype(state_dtype)
    (_, kept), o = jax.lax.scan(token, (state, state),
                                (jnp.arange(t), q, k, v, alpha, beta))
    return o, kept.astype(jnp.float32)


def route(x, router, program_ids, margin, k, norm, scale):
    """(gates (t, k), ids used (t, k), within (t,), the reference's own
    ids (t, k), shortfall (t,)): softmax over all the experts, the k
    largest. A token's shortfall is the reference's k-th probability less
    the lowest it gives an expert of the program's, as a share of the k-th
    (0 where every one of them is among its own k). `within`: the
    program's ids are known (no -1) and the shortfall is at most `margin`;
    there the program's ids are used, elsewhere the reference's own."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    kth, own = jax.lax.top_k(probs, k)
    kth = kth[:, k - 1]
    known = jnp.all(program_ids >= 0, axis=-1)
    theirs = jnp.take_along_axis(probs, jnp.maximum(program_ids, 0), axis=-1)
    shortfall = jnp.where(
        known, jnp.maximum(kth - jnp.min(theirs, axis=-1), 0.0) / kth, 0.0)
    within = known & (shortfall <= margin)
    ids = jnp.where(within[:, None], program_ids, own)
    gates = jnp.take_along_axis(probs, ids, axis=-1)
    if norm:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates * scale, ids, within, own, shortfall


def _gated_mlp(x, gate, up, down, spoil):
    g = x @ _mat(gate, spoil)
    return (g * jax.nn.sigmoid(g) * (x @ _mat(up, spoil))) @ _mat(down, spoil)


def expert_layer(x, w, cfg, *, held, program_ids=None, margin=0.0,
                 spoil=None):
    """Shared(x) + the sum over the chosen experts that are held here,
    `held` = (first expert id, count): w["gate"], w["up"], w["down"] hold
    those experts only, in order. Returns (y, routing).
    departure: the published code gathers the rows routed to each expert;
    here every held expert runs on every token and a mask of gate weights
    picks: the same sum."""
    k = cfg["num_experts_per_tok"]
    if w["gate"].shape[0] != held[1]:
        raise ValueError("the weights are not those of the experts held")
    if program_ids is None:
        program_ids = jnp.full((x.shape[0], k), -1, jnp.int32)
    gates, ids, within, own, shortfall = route(
        x, _f32(w["router"]), jnp.asarray(program_ids, jnp.int32), margin, k,
        cfg["norm_topk_prob"], cfg["routed_scaling_factor"])
    y = jnp.zeros_like(x)
    if "shared_gate" in w:
        y = _gated_mlp(x, w["shared_gate"], w["shared_up"], w["shared_down"],
                       spoil)

    def expert(y, e):
        j, gate, up, down = e
        g = jnp.sum(jnp.where(ids == held[0] + j, gates, 0.0), axis=-1)
        return y + g[:, None] * _gated_mlp(x, gate, up, down, spoil), None

    y = jax.lax.scan(expert, y, (jnp.arange(held[1]), w["gate"], w["up"],
                                 w["down"]))[0]
    return y, {"ids": ids, "within": within, "own_ids": own,
               "shortfall": shortfall}


class _Static(dict):
    """The configuration as a static argument of a jitted program."""

    def __hash__(self):
        return hash(repr(sorted(self.items(), key=lambda kv: kv[0])))


@jax.jit
def _embed(wte, tokens):
    return _f32(wte)[tokens]


@functools.partial(jax.jit, static_argnames=("cfg", "softmax", "spoil"))
def _mix(x, scale, w, state_at, cfg, softmax, spoil):
    a = rms_norm(x, _f32(scale), cfg["rms_norm_eps"])
    if softmax:
        return x + gqa_attention(a, w, cfg, spoil), None
    y, state = delta_attention(a, w, cfg, spoil, state_at)
    return x + y, state


@functools.partial(jax.jit, static_argnames=("cfg", "held", "spoil"))
def _experts(x, scale, w, program_ids, margin, cfg, held, spoil):
    y, routing = expert_layer(
        rms_norm(x, _f32(scale), cfg["rms_norm_eps"]), w, cfg, held=held,
        program_ids=program_ids, margin=margin, spoil=spoil)
    return x + y, routing


@functools.partial(jax.jit, static_argnames=("eps", "spoil"))
def _head(x, scale, lm_head, rows, eps, spoil):
    return rms_norm(x[rows], _f32(scale), eps) @ _mat(lm_head, spoil)


def model_cfg(config: dict) -> dict:
    """The published keys the reference reads, with the experts this chip
    holds: `n_routed_experts` in a cut configuration file counts the
    experts held (`reduced`), `experts_routed` the router's width."""
    cfg = _Static(config)
    for key in ("reduced", "reduced_from", "assumed", "departures"):
        cfg.pop(key, None)
    cfg.setdefault("experts_held", [0, config["n_routed_experts"]])
    for key, value in list(cfg.items()):
        if isinstance(value, list):
            cfg[key] = tuple(value)
        elif isinstance(value, dict):
            cfg[key] = _Static(value)
    return cfg


ATTENTION = ("wq", "wk", "wv", "wg", "wo")
DELTA = ("wq", "wk", "wv", "conv", "w_fa", "w_fb", "a_log", "dt_bias",
         "w_beta", "o_norm", "w_ga", "w_gb", "b_gb", "wo")
EXPERTS = ("router", "gate", "up", "down", "shared_gate", "shared_up",
           "shared_down")


def forward(get, tokens, config, *, rows=None, program_experts=None,
            route_margin=0.0, spoil=None, state_at=None):
    """(logits (len(rows), vocabulary) float32 of one sequence `tokens`
    (t,), report): every row where `rows` is None. `report` counts, over
    the layers, the tokens whose experts the program named (`routings`),
    those among them computed with experts of the program's that are not
    the reference's own (`ties`) and those where an expert of the
    program's lies under the reference's k-th by more than the margin
    (`route_bad`), gives the largest shortfall seen (`worst_shortfall`:
    a number with no limit of its own) and the delta-rule layers' states
    (H, d, d) after token `state_at` (`states`, in the layers' order;
    after the last token where None)."""
    if spoil not in SPOILS:
        raise ValueError(f"spoil must be one of {SPOILS}")
    cfg = model_cfg(config)
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    rows = jnp.arange(t) if rows is None else jnp.asarray(rows, jnp.int32)
    held = tuple(cfg["experts_held"])
    k = cfg["num_experts_per_tok"]
    report = {"ties": 0, "route_bad": 0, "worst_shortfall": 0.0,
              "routings": 0, "states": []}
    state_at = jnp.asarray(t - 1 if state_at is None else state_at,
                           jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _embed(get("wte", "kernel"), tokens)
        for i in range(cfg["num_hidden_layers"]):
            softmax = i in cfg["gqa_layers"]
            names = ATTENTION if softmax else DELTA
            x, state = _mix(x, get(f"l{i}_ln1", "scale"),
                            {n: get(f"l{i}_attn", n) for n in names
                             if n != "wg" or cfg["use_gqa_gate"]},
                            state_at, cfg, softmax, spoil)
            if not softmax:
                report["states"].append(np.asarray(state))
            ids = (jnp.full((t, k), -1, jnp.int32) if program_experts is None
                   else jnp.asarray(program_experts[i], jnp.int32))
            x, routing = _experts(
                x, get(f"l{i}_ln2", "scale"),
                {n: get(f"l{i}_moe", n) for n in EXPERTS
                 if n[:6] != "shared" or cfg["n_shared_experts"]},
                ids, route_margin, cfg, held, spoil)
            known = np.asarray(jnp.all(ids >= 0, axis=-1))
            within = np.asarray(routing["within"])
            differs = np.asarray(jnp.any(
                jnp.sort(ids, -1) != jnp.sort(routing["own_ids"], -1), -1))
            report["routings"] += int(known.sum())
            report["ties"] += int((within & differs).sum())
            report["route_bad"] += int((known & ~within).sum())
            report["worst_shortfall"] = max(
                report["worst_shortfall"],
                float(np.asarray(routing["shortfall"]).max()))
        logits = _head(x, get("ln_f", "scale"), get("lm_head", "kernel"),
                       rows, cfg["rms_norm_eps"], spoil)
    return np.asarray(logits), report


def logit_error(program, reference) -> float:
    """max |difference| over max |reference logit|."""
    reference = np.asarray(reference, np.float32)
    return float(np.max(np.abs(np.asarray(program, np.float32) - reference))
                 / np.max(np.abs(reference)))
