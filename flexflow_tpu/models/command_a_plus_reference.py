"""The plain reference of Command A+'s language model as
`build_transformer_lm` builds it from `command_a_plus_lm_config`: the
forward pass of one sequence.

float32, `jax.default_matmul_precision("highest")`, jax.numpy only: no
kernel, no cache, no batching. Queries, scores and the output projection
are computed in blocks of query rows (a window layer's block reads the
keys of its own rows and the window before them, nothing else), the held
experts one after another and the four shared experts one after another,
so that a sequence of some twelve thousand tokens at the published widths
fits beside the program and its cache on one chip; the blocks change no
number.

The model (config.json of CohereLabs/command-a-plus-05-2026, `model_type:
cohere2_moe`; what it leaves open is listed as `assumed` in
benchmarks/configs/command-a-plus-05-2026.json). Layer i is a window layer
where `layer_types[i]` is `sliding_attention` and a global layer where it
is `full_attention`; G = `num_attention_heads` / `num_key_value_heads`
query heads a KV head:

- n = LN(h): mean and variance over the hidden size, eps `layer_norm_eps`,
  a learned scale, NO bias. One norm a layer (`use_parallel_block`):
  h <- h + Attn_i(n) + FFN_i(n).
- Attn_i: q = n W_q as H heads of `head_dim`, k = n W_k and v = n W_v as KV
  heads of `head_dim`; no bias, no QK-norm. A window layer turns lanes 2j
  and 2j + 1 of every q and k head by t x `rope_theta`^(-2j / head_dim)
  (`position_embedding_type: rope_gptj`, the interleaved form; `rotary_pct`
  1: the whole head) and row t attends keys t - `sliding_window` < u <= t;
  a global layer takes no position anywhere and attends every u <= t.
  s[t,u,i] = q[t,i] . k[u, i // G] / sqrt(head_dim), softmax over the
  attended keys, o[t,i] = sum_u p v[u, i // G], a = concat_i(o) W_o.
- FFN_i: s = sigmoid(n R) over all `num_experts` in float32
  (`expert_selection_fn`), the `num_experts_per_tok` largest, gates
  s_e / sum of the chosen s (`norm_topk_prob`); routed = sum over the
  chosen experts HELD HERE of gate_e E_e(n), E(n) = W_down(SiLU(W_gate n) *
  W_up n) at `intermediate_size`; shared = (1 / `num_shared_experts`) x the
  sum of the shared experts' E_j(n), each of `intermediate_size`
  (`shared_expert_combination_strategy: average`); FFN = routed + shared.
- After the last layer LN_f, logits = `logit_scale` x LN_f(h) E^T with E
  the embedding (`tie_word_embeddings`), over the slice of the vocabulary
  held.

Every departure from the published model is a comment that starts with
"departure:". `get(node, weight)` returns the program's own array of that
name (wte.kernel, l<i>_ln1.scale, l<i>_attn.{wq, wk, wv, wo}, l<i>_moe.
{router, gate, up, down, shared_gate, shared_up, shared_down}, ln_f.scale;
there is no lm_head weight). Linear weights are stored (in, out), the
embedding (vocabulary, hidden). The program holds the four shared experts
as one gated MLP: shared expert j is columns j f .. (j + 1) f of
`shared_gate` and `shared_up` and the same rows of `shared_down`.

Routing is discontinuous. Where the reference's k-th and (k+1)-th scores of
a token lie within a margin, a program in lower precision may rightly pick
otherwise: `forward` takes the program's choice (`program`: per layer
`experts` for the rows it names) at exactly those tokens (the rule of
deepseek_v32_reference.route, without its groups and its bias).

`spoil` computes one part of the model wrongly, for the controls that fix
the comparison's limits (benchmarks/jobs/serve_agentmix.py):
"sequential_block" adds the attention before the FFN reads (the FFN then
norms h + a, by the layer's one scale), "rope_on_global" rotates the
global layers too, "rope_half" pairs lanes j and j + d / 2, "window_off"
lets a window layer attend its whole past, "shared_summed" leaves the
average's 1 / 4 out, "rmsnorm" leaves the mean in, "softmax_scores" takes
the gates from a softmax over the experts, "e4m3" rounds every matrix to
float8_e4m3fn.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import deepseek_v32_reference as dsa

SPOILS = (None, "sequential_block", "rope_on_global", "rope_half",
          "window_off", "shared_summed", "rmsnorm", "softmax_scores", "e4m3")
_f32 = dsa._f32


def e4m3(a):
    """`a` rounded to float8_e4m3fn's 3 bits of mantissa, in float32
    arithmetic (on the chip XLA folds a cast there and back away)."""
    return jax.lax.reduce_precision(_f32(a), exponent_bits=4,
                                    mantissa_bits=3)


def _mat(a, spoil):
    return e4m3(a) if spoil == "e4m3" else _f32(a)


def norm(x, scale, eps, spoil=None):
    """LayerNorm with a scale and no bias."""
    if spoil == "rmsnorm":
        return dsa.rms_norm(x, scale, eps)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale


class Dims(NamedTuple):
    """What a layer's attention programs are compiled for."""
    heads: int
    kv_heads: int
    head_dim: int
    theta: float      # 0 = no position enters
    window: int       # keys a row attends, its own among them; 0 = all
    eps: float


def is_window(cfg, layer: int) -> bool:
    return cfg["layer_types"][layer] == "sliding_attention"


def layer_dims(cfg, layer: int, spoil=None) -> Dims:
    """The attention of layer `layer` from the published keys."""
    window = is_window(cfg, layer)
    return Dims(
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        theta=(float(cfg["rope_theta"])
               if window or spoil == "rope_on_global" else 0.0),
        window=(cfg["sliding_window"]
                if window and spoil != "window_off" else 0),
        eps=cfg["layer_norm_eps"])


ATTENTION_WEIGHTS = ("wq", "wk", "wv", "wo")


def _attend_rows(q, k, v, mask, scale):
    """sum_u p v over the masked keys, query head i reading KV head
    i // group: q (tb, G, group, dk), k and v (u, G, dk), mask (tb, u) ->
    (tb, G, group, dk)."""
    scores = jnp.einsum("tgqd,sgd->gqts", q, k) * scale
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("gqts,sgd->tgqd", p, v)


@functools.partial(jax.jit, static_argnames=("d", "row_block", "spoil"))
def _attention(x, scale, w, positions, d, row_block, spoil=None):
    """(concat_i(o_i) W_o (s, hidden), keys (s, G, dk), values (s, G, dk))
    of x (s, hidden); `scale`: the norm before the layer. Keys and values
    are made whole; the queries, the scores and the output projection a
    block of rows after the other (128 query heads of a long sequence are
    not held at once). A window layer's block of rows [t0, t0 + B) reads
    keys [t0 - window + 1, t0 + B) and no others."""
    n = norm(x, _f32(scale), d.eps, spoil)
    s = n.shape[0]
    group = d.heads // d.kv_heads
    wq, wo = _mat(w["wq"], spoil), _mat(w["wo"], spoil)
    k = (n @ _mat(w["wk"], spoil)).reshape(s, d.kv_heads, d.head_dim)
    v = (n @ _mat(w["wv"], spoil)).reshape(s, d.kv_heads, d.head_dim)
    turn = dsa.rope_half if spoil == "rope_half" else dsa.rope_interleaved
    inv_freq = (d.theta ** (-jnp.arange(0, d.head_dim, 2, dtype=jnp.float32)
                            / d.head_dim) if d.theta else None)

    def rotated(t, at):
        if inv_freq is None:
            return t
        return turn(t, at.astype(jnp.float32)[:, None, None] * inv_freq)

    k = rotated(k, positions)
    scale_ = d.head_dim ** -0.5
    blocks = dsa._row_blocks(n, row_block)
    places = dsa._row_blocks(positions, row_block)
    starts = jnp.arange(blocks.shape[0]) * row_block
    rows = jnp.arange(row_block)
    if d.window:
        # keys before the sequence are rows of zeros under the mask
        reach = row_block + d.window
        pad = ((d.window, row_block), (0, 0), (0, 0))
        kp, vp = jnp.pad(k, pad), jnp.pad(v, pad)
    at_all = jnp.arange(s)

    def block(part):
        nb, pb, t0 = part
        qb = rotated((nb @ wq).reshape(row_block, d.heads, d.head_dim), pb)
        qb = qb.reshape(row_block, d.kv_heads, group, d.head_dim)
        t = t0 + rows
        if d.window:
            at = t0 - d.window + jnp.arange(reach)  # the keys' positions
            mask = ((at[None] <= t[:, None]) & (at[None] > t[:, None]
                                                - d.window) & (at[None] >= 0))
            kb = jax.lax.dynamic_slice_in_dim(kp, t0, reach)
            vb = jax.lax.dynamic_slice_in_dim(vp, t0, reach)
        else:
            mask, kb, vb = at_all[None] <= t[:, None], k, v
        ob = _attend_rows(qb, kb, vb, mask, scale_)
        return ob.reshape(row_block, d.heads * d.head_dim) @ wo

    u = jax.lax.map(block, (blocks, places, starts))
    return u.reshape(-1, u.shape[-1])[:s], k, v


def attention(x, w, positions, d: Dims, *, scale, row_block=128,
              spoil=None):
    """The attention of one layer on x (s, hidden) at `positions` (s,);
    `scale`: the norm x goes through first."""
    return _attention(
        x, scale, {name: w[name] for name in ATTENTION_WEIGHTS},
        jnp.asarray(positions, jnp.int32), d=d,
        row_block=min(row_block, x.shape[0]), spoil=spoil)[0]


class Routing(NamedTuple):
    """What the router's program is compiled for: its width, the experts
    a token, whether the gates are renormalised, and how many shared
    experts are averaged."""
    experts: int
    k: int
    norm: bool
    shared: int


def routing(cfg) -> Routing:
    """The router from the published keys: `num_experts` in a cut
    configuration file counts the experts held (`reduced`),
    `experts_routed` the router's width."""
    return Routing(cfg.get("experts_routed", cfg["num_experts"]),
                   cfg["num_experts_per_tok"], bool(cfg["norm_topk_prob"]),
                   cfg["num_shared_experts"])


def held_experts(cfg) -> tuple:
    return tuple(cfg.get("experts_held", (0, cfg["num_experts"])))


def route(x, router, program_ids, margin, r: Routing, spoil=None):
    """(gates (t, k), ids used (t, k), near-tie mask (t,), the reference's
    own ids (t, k), gap (t,)) of tokens x (t, d): sigmoid scores over all
    the experts, the k largest, no correction bias and no groups (no key
    names either), gates renormalised over the chosen. A token's gap is
    the k-th score less the next as a share of the k-th; a near-tie is a
    gap under `margin`, and there the program's ids (t, k) are used (a row
    of -1: not known)."""
    logits = x @ router
    scores = (jax.nn.softmax(logits, axis=-1) if spoil == "softmax_scores"
              else jax.nn.sigmoid(logits))
    top, own = jax.lax.top_k(scores, r.k + 1)
    gap = (top[:, r.k - 1] - top[:, r.k]) / jnp.abs(top[:, r.k - 1])
    own = own[:, :r.k]
    tie = (gap < margin) & jnp.all(program_ids >= 0, axis=-1)
    ids = jnp.where(tie[:, None], program_ids, own)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    if r.norm:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return picked, ids, tie, own, gap


EXPERT_WEIGHTS = ("router", "gate", "up", "down", "shared_gate",
                  "shared_up", "shared_down")


def _gated(x, gate, up, down, spoil):
    return ((jax.nn.silu(x @ _mat(gate, spoil)) * (x @ _mat(up, spoil)))
            @ _mat(down, spoil))


def _expert_layer(x, w, program_ids, margin, r, first, spoil,
                  parts=("routed", "shared")):
    """routed: the sum over the chosen experts that are held here (ids
    `first` .. `first` + the experts in w["gate"]); shared: the average of
    the shared experts, each `intermediate_size` wide.
    departure: the published code gathers the rows routed to each expert;
    here every held expert runs on every token and a mask of gate weights
    picks: the same sum."""
    gates, ids, tie, own, gap = route(x, _f32(w["router"]), program_ids,
                                      margin, r, spoil)
    y = jnp.zeros_like(x)
    if "routed" in parts:
        def expert(y, held):
            j, gate, up, down = held
            g = jnp.sum(jnp.where(ids == first + j, gates, 0.0), axis=-1)
            return y + g[:, None] * _gated(x, gate, up, down, spoil), None

        y = jax.lax.scan(expert, y,
                         (jnp.arange(w["gate"].shape[0]), w["gate"], w["up"],
                          w["down"]))[0]
    if "shared" in parts and r.shared:
        f = w["shared_gate"].shape[1] // r.shared
        total = 0.0
        for j in range(r.shared):
            cols = slice(j * f, (j + 1) * f)
            total = total + _gated(x, w["shared_gate"][:, cols],
                                   w["shared_up"][:, cols],
                                   w["shared_down"][cols], spoil)
        y = y + (total if spoil == "shared_summed" else total / r.shared)
    return y, {"ids": ids, "tie": tie, "own_ids": own, "gap": gap}


@functools.partial(jax.jit,
                   static_argnames=("r", "first", "spoil", "parts"))
def expert_layer(x, w, program_ids, margin, r, first, spoil=None,
                 parts=("routed", "shared")):
    """`_expert_layer` on its own (the test that adds the shares up)."""
    return _expert_layer(x, w, program_ids, margin, r, first, spoil, parts)


@functools.partial(jax.jit, static_argnames=("eps", "r", "first", "spoil"))
def _expert_tail(x, u, scale, w, program_ids, margin, eps, r, first,
                 spoil=None):
    """A layer from its attention's output `u` on: the FFN reads the norm
    the attention read, and both join the residual stream at once."""
    if spoil == "sequential_block":
        x, u = x + u, 0.0
    y, routed = _expert_layer(norm(x, _f32(scale), eps, spoil), w,
                              program_ids, margin, r, first, spoil)
    return x + u + y, routed


@functools.partial(jax.jit,
                   static_argnames=("eps", "logit_scale", "spoil", "blocks"))
def _head(x, scale, wte, rows, eps, logit_scale=1.0, spoil=None, blocks=4):
    """The logits of `rows`: the final norm against the embedding's table
    (the tied head), the vocabulary in `blocks` parts."""
    # departure: the vision tower of the model's description is not in
    # config.json and is not built
    h = norm(x[rows], _f32(scale), eps, spoil)
    step = -(-wte.shape[0] // blocks)
    return logit_scale * jnp.concatenate(
        [h @ _mat(wte[lo:lo + step], spoil).T
         for lo in range(0, wte.shape[0], step)], axis=-1)


def forward(get, tokens, config, *, program=None, route_margin=0.0,
            row_block=128, rows=None, spoil=None, cache_layer=None):
    """(logits (s, vocab) float32 numpy, notes) of the causal forward over
    one sequence `tokens` (s,) at positions 0 .. s - 1; with `rows`, the
    logits of those positions only. `program`: per layer {"experts": {row:
    ids (k,)}} of the program's own routing at the rows it names, used at
    near-ties only. notes: per layer, `route`'s readings; with
    `cache_layer`, that layer's note holds what a cache holds of the
    sequence there, "cache": (keys (s, G x dk), values (s, G x dk)). The
    weights stay as the program holds them and are upcast where they are
    used."""
    if spoil not in SPOILS:
        raise ValueError(f"spoil is one of {SPOILS}, got {spoil!r}")
    r, eps = routing(config), config["layer_norm_eps"]
    first = held_experts(config)[0]
    tokens = jnp.asarray(tokens, jnp.int32).reshape(-1)
    s = tokens.shape[0]
    positions = jnp.arange(s, dtype=jnp.int32)
    rows = positions if rows is None else jnp.asarray(rows, jnp.int32)
    notes = []
    with jax.default_matmul_precision("highest"):
        x = dsa._embed(get("wte", "kernel"), tokens)
        for i in range(config["num_hidden_layers"]):
            p = f"l{i}_"
            d = layer_dims(config, i, spoil)
            scale = get(p + "ln1", "scale")
            u, k, v = _attention(
                x, scale,
                {name: get(p + "attn", name) for name in ATTENTION_WEIGHTS},
                positions, d=d, row_block=min(row_block, s), spoil=spoil)
            kept = {} if i != cache_layer else {"cache": (
                np.asarray(k).reshape(s, -1), np.asarray(v).reshape(s, -1))}
            del k, v
            ids = None
            chosen = (program or {}).get(i, {}).get("experts")
            if chosen:
                ids = np.full((s, r.k), -1, np.int32)
                for row, mine in chosen.items():
                    ids[row] = mine
            x, routed = _expert_tail(
                x, u, scale,
                {name: get(p + "moe", name) for name in EXPERT_WEIGHTS},
                dsa._program_ids(ids, s, r.k), route_margin, eps=eps, r=r,
                first=first, spoil=spoil)
            notes.append({**routed, **kept})
        logits = _head(x, get("ln_f", "scale"), get("wte", "kernel"), rows,
                       eps=eps, logit_scale=float(config["logit_scale"]),
                       spoil=spoil)
    return np.asarray(logits, np.float32), notes


def lowerings(get, config, length, *, named=128, row_block=128) -> list:
    """[(name, jax.stages.Lowered)]: the programs `forward` runs over
    `length` tokens with `named` rows asked for, lowered and not compiled,
    for a caller that compiles them ahead of the forward and beside other
    work (they are the forward's own jitted functions at its own shapes,
    so the forward finds them in the compile cache). Layers of one kind
    share their programs."""
    r, eps = routing(config), config["layer_norm_eps"]
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
        out.append((f"{fn.__name__}@{s}.{len(out)}",
                    fn.lower(*args, **static)))
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=placed),
            jax.eval_shape(functools.partial(fn, **static), *args))

    whole = -(-named // block) * block
    kinds = [is_window(config, i)
             for i in range(config["num_hidden_layers"])]
    with jax.default_matmul_precision("highest"):
        x = add(dsa._embed, get("wte", "kernel"), like(s, dtype=jnp.int32))
        u = x
        for kind in sorted(set(kinds)):
            i = kinds.index(kind)
            d, p = layer_dims(config, i), f"l{i}_"
            u, _, _ = add(
                _attention, x, get(p + "ln1", "scale"),
                {name: get(p + "attn", name) for name in ATTENTION_WEIGHTS},
                like(s, dtype=jnp.int32), d=d, row_block=block, spoil=None)
        add(_expert_tail, x, u, get("l0_ln1", "scale"),
            {name: get("l0_moe", name) for name in EXPERT_WEIGHTS},
            like(s, r.k, dtype=jnp.int32), 0.0, eps=eps, r=r,
            first=held_experts(config)[0], spoil=None)
        add(_head, x, get("ln_f", "scale"), get("wte", "kernel"),
            like(whole, dtype=jnp.int32) if named
            else like(s, dtype=jnp.int32), eps=eps,
            logit_scale=float(config["logit_scale"]), spoil=None)
    return out
