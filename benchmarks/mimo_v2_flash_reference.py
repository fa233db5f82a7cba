"""The plain reference of MiMo-V2-Flash as `build_transformer_lm` builds it
from `mimo_v2_flash_lm_config`: the forward pass of one sequence. The
benchmark's own copy of `flexflow_tpu/models/mimo_v2_flash_reference.py` (a
later PR cannot move the yardstick by editing the program's), with the
comparison that decides `correct` at its end.

float32, `jax.default_matmul_precision("highest")`, jax.numpy only: no
kernel, no cache, no batching. Scores are computed in blocks of query rows
(a window layer's block reads the keys of its own rows and the window
before them, nothing else), the experts one after another and the head in
blocks of the vocabulary, so that a sequence of some seventeen thousand
tokens at the published widths fits beside the program on one chip; the
blocks change no number.

The model (config.json of XiaomiMiMo/MiMo-V2-Flash, `model_type:
mimo_v2_flash`; what it leaves open is listed as `assumed` in
benchmarks/configs/mimo-v2-flash.json). Layer i is a global layer where
`hybrid_layer_pattern[i]` is 0 and a window layer where it is 1; a global
layer has `num_key_value_heads` KV heads and rotates at `rope_theta`, a
window layer `swa_num_key_value_heads` and `swa_rope_theta`; G = heads a
KV head:

- x = RMSNorm(h) (eps `layernorm_epsilon`); q = x W_q as H heads of
  `head_dim` (192), k = x W_k as KV heads of 192, v = x W_v as KV heads of
  `v_head_dim` (128); no bias, no QK-norm.
- RoPE, half-rotation form, on the first int(192 x `partial_rotary_factor`)
  = 64 lanes of every q and k head, frequencies theta^(-2j/64); the other
  128 lanes pass.
- s[t,u,i] = q[t,i] . k[u, i // G] / sqrt(192) for u <= t (global) or
  t - `sliding_window` < u <= t (window: 128 keys, the row's own among
  them); p = exp(s) / (sum_u exp(s) + [window] exp(b_i)), b one learned
  scalar a head: the sink takes weight and gives no value
  (`add_swa_attention_sink_bias`); o[t,i] = `attention_value_scale` x
  sum_u p v[u, i // G]; a = concat_i(o) W_o (H x 128 -> hidden); h = h + a.
- y = RMSNorm(h). Where `moe_layer_freq[i]` is 0: m = W_down(SiLU(W_gate
  y) * W_up y) at `intermediate_size`. Else r = sigmoid(y R) over all
  `n_routed_experts` in float32, the `num_experts_per_tok` largest of
  r + router_bias (`noaux_tc`; `n_group` 1: no group limit), gates
  r_e / sum_chosen r (`norm_topk_prob`; `routed_scaling_factor` null = 1),
  m = sum over the chosen experts HELD HERE of gate_e E_e(y), each
  W_down(SiLU(W_gate y) * W_up y) at `moe_intermediate_size`; no shared
  expert. h = h + m.
- Final RMSNorm, untied head (over the slice of the vocabulary held).

Every departure from the published model is a comment that starts with
"departure:". `get(node, weight)` returns the program's own array of that
name (wte.kernel, l<i>_ln1.scale, l<i>_attn.{wq, wk, wv, wo, sink},
l<i>_ln2.scale, l0_ffn_{gate, up, down}.kernel, l<i>_moe.{router,
router_bias, gate, up, down}, ln_f.scale, lm_head.kernel). Linear weights
are stored (in, out).

Routing is discontinuous. Where the reference's k-th and (k+1)-th scores of
a token lie within a margin, a program in lower precision may rightly pick
otherwise: `forward` takes the program's choice (`program`: per layer
`experts` for the rows it names) at exactly those tokens
(deepseek_v32_reference.route, shared: the two routers are one).

`spoil` computes one part of the model wrongly, for the controls that fix
the comparison's limits (benchmarks/jobs/serve_longdoc.py): "window_off"
lets a window layer attend its whole past, "sink_off" leaves the sink out
of the denominator, "value_scale_off" leaves the values unscaled,
"thetas_swapped" rotates global layers at the window layers' theta and the
other way round, "rope_whole" rotates the whole head, "e4m3" rounds every
matrix to float8_e4m3fn.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import deepseek_v32_reference as dsa

SPOILS = (None, "window_off", "sink_off", "value_scale_off",
          "thetas_swapped", "rope_whole", "e4m3")
_f32 = dsa._f32


def e4m3(a):
    """`a` rounded to float8_e4m3fn's 3 bits of mantissa, in float32
    arithmetic (on the chip XLA folds a cast there and back away)."""
    return jax.lax.reduce_precision(_f32(a), exponent_bits=4,
                                    mantissa_bits=3)


def _mat(a, spoil):
    return e4m3(a) if spoil == "e4m3" else _f32(a)


class Dims(NamedTuple):
    """What a layer's attention programs are compiled for."""
    heads: int
    kv_heads: int
    head_dim: int
    v_head_dim: int
    rope_dim: int
    theta: float
    window: int       # keys a row attends, its own among them; 0 = all
    sink: bool
    value_scale: float
    eps: float


def layer_dims(cfg, layer: int, spoil=None) -> Dims:
    """The attention of layer `layer` from the published keys."""
    window = bool(cfg["hybrid_layer_pattern"][layer])
    kind = "swa_" if window else ""
    theta = float(cfg["swa_rope_theta" if window != (spoil ==
                                                     "thetas_swapped")
                      else "rope_theta"])
    head_dim = cfg[kind + "head_dim"]
    return Dims(
        heads=cfg[kind + "num_attention_heads"],
        kv_heads=cfg[kind + "num_key_value_heads"],
        head_dim=head_dim, v_head_dim=cfg[kind + "v_head_dim"],
        rope_dim=(head_dim if spoil == "rope_whole"
                  else int(head_dim * cfg["partial_rotary_factor"])),
        theta=theta,
        window=(cfg["sliding_window"]
                if window and spoil != "window_off" else 0),
        sink=bool(cfg["add_swa_attention_sink_bias" if window
                      else "add_full_attention_sink_bias"])
        and spoil != "sink_off",
        value_scale=(1.0 if spoil == "value_scale_off"
                     else float(cfg["attention_value_scale"])),
        eps=cfg["layernorm_epsilon"])


ATTENTION_WEIGHTS = ("wq", "wk", "wv")


@functools.partial(jax.jit, static_argnames=("d", "spoil"))
def _attention_inputs(x, scale, w, positions, d, spoil=None):
    """q (s, H, dk) and k (s, G, dk), their first `rope_dim` lanes rotated,
    and v (s, G, dv) scaled, of x (s, hidden); `scale`: the norm before
    the layer."""
    x = dsa.rms_norm(x, _f32(scale), d.eps)
    s, dr = x.shape[0], d.rope_dim
    q = (x @ _mat(w["wq"], spoil)).reshape(s, d.heads, d.head_dim)
    k = (x @ _mat(w["wk"], spoil)).reshape(s, d.kv_heads, d.head_dim)
    v = (x @ _mat(w["wv"], spoil)).reshape(s, d.kv_heads, d.v_head_dim)
    angles = (positions.astype(jnp.float32)[:, None, None]
              * d.theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))

    def rotated(t):
        return jnp.concatenate(
            [dsa.rope_half(t[..., :dr], angles), t[..., dr:]], axis=-1)

    return rotated(q), rotated(k), v * d.value_scale


def _attend_rows(q, k, v, mask, sink, scale):
    """sum_u p v over the masked keys, query head i reading KV head
    i // group, the sink in the denominator only: q (tb, G, group, dk), k
    (u, G, dk), v (u, G, dv), mask (tb, u), sink (G, group) or None ->
    (tb, G, group, dv)."""
    scores = jnp.einsum("tgqd,sgd->gqts", q, k) * scale
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink[:, :, None, None])
    e = jnp.exp(scores - m)
    total = jnp.sum(e, axis=-1, keepdims=True)
    if sink is not None:
        total = total + jnp.exp(sink[:, :, None, None] - m)
    return jnp.einsum("gqts,sgd->tgqd", e / total, v)


@functools.partial(jax.jit, static_argnames=("d", "row_block", "spoil"))
def _attend(q, k, v, sink, wo, d, row_block, spoil=None):
    """concat_i(o_i) W_o (s, hidden), a block of rows after the other. A
    window layer's block of rows [t0, t0 + B) reads keys [t0 - window + 1,
    t0 + B) and no others."""
    s = q.shape[0]
    group = d.heads // d.kv_heads
    q = q.reshape(s, d.kv_heads, group, d.head_dim)
    sink = _f32(sink).reshape(d.kv_heads, group) if d.sink else None
    scale = d.head_dim ** -0.5
    blocks = dsa._row_blocks(q, row_block)
    starts = jnp.arange(blocks.shape[0]) * row_block
    rows = jnp.arange(row_block)

    if d.window:
        # keys before the sequence are rows of zeros under the mask
        reach = row_block + d.window
        pad = ((d.window, row_block), (0, 0), (0, 0))
        kp, vp = jnp.pad(k, pad), jnp.pad(v, pad)

        def block(part):
            qb, t0 = part
            at = t0 - d.window + jnp.arange(reach)  # the keys' positions
            t = t0 + rows
            mask = ((at[None] <= t[:, None]) & (at[None] > t[:, None]
                                                - d.window) & (at[None] >= 0))
            kb = jax.lax.dynamic_slice_in_dim(kp, t0, reach)
            vb = jax.lax.dynamic_slice_in_dim(vp, t0, reach)
            return _attend_rows(qb, kb, vb, mask, sink, scale)
    else:
        at = jnp.arange(s)

        def block(part):
            qb, t0 = part
            return _attend_rows(qb, k, v, at[None] <= (t0 + rows)[:, None],
                                sink, scale)

    o = jax.lax.map(block, (blocks, starts))
    return o.reshape(-1, d.heads * d.v_head_dim)[:s] @ _mat(wo, spoil)


def _no_sink(get, layer: int, d: Dims):
    """What a layer without a sink hands `_attend` in the sink's place
    (unread): an array that lies where the weights lie, as a sink would,
    so that `lowerings` and `forward` ask for one program."""
    return get(f"l{layer}_ln1", "scale")[:d.heads]


def attention(x, w, positions, d: Dims, *, scale, row_block=128,
              spoil=None):
    """The attention of one layer on x (s, hidden) at `positions` (s,);
    `scale`: the norm x goes through first."""
    q, k, v = _attention_inputs(
        x, scale, {name: w[name] for name in ATTENTION_WEIGHTS},
        jnp.asarray(positions, jnp.int32), d=d, spoil=spoil)
    return _attend(q, k, v, w["sink"] if d.sink else jnp.zeros((d.heads,)),
                   w["wo"], d=d, row_block=min(row_block, x.shape[0]),
                   spoil=spoil)


def routing(cfg) -> dsa.Routing:
    """The router from the published keys: `n_routed_experts` in a cut
    configuration file counts the experts held (`reduced`),
    `experts_routed` the router's width."""
    return dsa.Routing(
        cfg.get("experts_routed", cfg["n_routed_experts"]),
        cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"],
        bool(cfg["norm_topk_prob"]),
        float(cfg["routed_scaling_factor"] or 1.0))


def held_experts(cfg) -> tuple:
    return tuple(cfg.get("experts_held", (0, cfg["n_routed_experts"])))


EXPERT_WEIGHTS = ("router", "router_bias", "gate", "up", "down")


def _expert_layer(x, w, program_ids, margin, r, first, spoil):
    """The sum over the chosen experts that are held here (ids `first` ..
    `first` + the experts in w["gate"]).
    departure: the published code gathers the rows routed to each expert;
    here every held expert runs on every token and a mask of gate weights
    picks: the same sum."""
    gates, ids, tie, own, gap = dsa.route(
        x, _f32(w["router"]), _f32(w["router_bias"]), program_ids, margin, r)

    def expert(y, held):
        j, gate, up, down = held
        g = jnp.sum(jnp.where(ids == first + j, gates, 0.0), axis=-1)
        h = jax.nn.silu(x @ _mat(gate, spoil)) * (x @ _mat(up, spoil))
        return y + g[:, None] * (h @ _mat(down, spoil)), None

    y = jax.lax.scan(expert, jnp.zeros_like(x),
                     (jnp.arange(w["gate"].shape[0]), w["gate"], w["up"],
                      w["down"]))[0]
    return y, {"ids": ids, "tie": tie, "own_ids": own, "gap": gap}


@functools.partial(jax.jit, static_argnames=("r", "first", "spoil"))
def expert_layer(x, w, program_ids, margin, r, first, spoil=None):
    """`_expert_layer` on its own (the test that adds the shares up)."""
    return _expert_layer(x, w, program_ids, margin, r, first, spoil)


@functools.partial(jax.jit, static_argnames=("eps", "spoil", "col_block"))
def _dense_tail(x, u, scale, gate, up, down, eps, spoil=None,
                col_block=4096):
    """A dense layer from its attention's output on: x + u, the norm, the
    gated MLP in blocks of its width, the residual."""
    x = x + u
    y = dsa.rms_norm(x, _f32(scale), eps)
    m = 0.0
    for c0, c1 in dsa._blocks(gate.shape[1], col_block):
        g, p = _mat(gate[:, c0:c1], spoil), _mat(up[:, c0:c1], spoil)
        m = m + (jax.nn.silu(y @ g) * (y @ p)) @ _mat(down[c0:c1], spoil)
    return x + m


@functools.partial(jax.jit, static_argnames=("eps", "r", "first", "spoil"))
def _expert_tail(x, u, scale, w, program_ids, margin, eps, r, first,
                 spoil=None):
    """An expert layer from its attention's output on."""
    x = x + u
    y, routed = _expert_layer(dsa.rms_norm(x, _f32(scale), eps), w,
                              program_ids, margin, r, first, spoil)
    return x + y, routed


@functools.partial(jax.jit, static_argnames=("eps", "spoil", "blocks"))
def _head(x, scale, lm_head, rows, eps, spoil=None, blocks=4):
    """The logits of `rows`, the vocabulary in `blocks` parts."""
    # departure: the three multi-token-prediction layers change no logit
    # of the model and are not held
    h = dsa.rms_norm(x[rows], _f32(scale), eps)
    step = -(-lm_head.shape[1] // blocks)
    return jnp.concatenate(
        [h @ _mat(lm_head[:, lo:lo + step], spoil)
         for lo in range(0, lm_head.shape[1], step)], axis=-1)


def forward(get, tokens, config, *, program=None, route_margin=0.0,
            row_block=128, rows=None, spoil=None, cache_layer=None):
    """(logits (s, vocab) float32 numpy, notes) of the causal forward over
    one sequence `tokens` (s,) at positions 0 .. s - 1; with `rows`, the
    logits of those positions only. `program`: per layer {"experts": {row:
    ids (k,)}} of the program's own routing at the rows it names, used at
    near-ties only. notes: per expert layer, `route`'s readings; with
    `cache_layer`, that layer's note holds what a cache holds of the
    sequence there, "cache": (keys (s, G x dk), values (s, G x dv)). The
    weights stay as the program holds them and are upcast where they are
    used."""
    if spoil not in SPOILS:
        raise ValueError(f"spoil is one of {SPOILS}, got {spoil!r}")
    r, eps = routing(config), config["layernorm_epsilon"]
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
            names = (*ATTENTION_WEIGHTS, "wo") + (("sink",) if d.sink
                                                   else ())
            w = {name: get(p + "attn", name) for name in names}
            q, k, v = _attention_inputs(
                x, get(p + "ln1", "scale"),
                {name: w[name] for name in ATTENTION_WEIGHTS}, positions,
                d=d, spoil=spoil)
            u = _attend(q, k, v,
                        w["sink"] if d.sink else _no_sink(get, i, d),
                        w["wo"], d=d, row_block=min(row_block, s),
                        spoil=spoil)
            kept = {} if i != cache_layer else {"cache": (
                np.asarray(k).reshape(s, -1), np.asarray(v).reshape(s, -1))}
            del q, k, v
            if not config["moe_layer_freq"][i]:
                x = _dense_tail(
                    x, u, get(p + "ln2", "scale"),
                    *(get(p + "ffn_" + name, "kernel")
                      for name in ("gate", "up", "down")), eps=eps,
                    spoil=spoil)
                notes.append(kept)
                continue
            ids = None
            chosen = (program or {}).get(i, {}).get("experts")
            if chosen:
                ids = np.full((s, r.k), -1, np.int32)
                for row, mine in chosen.items():
                    ids[row] = mine
            x, routed = _expert_tail(
                x, u, get(p + "ln2", "scale"),
                {name: get(p + "moe", name) for name in EXPERT_WEIGHTS},
                dsa._program_ids(ids, s, r.k), route_margin, eps=eps, r=r,
                first=first, spoil=spoil)
            notes.append({**routed, **kept})
        logits = _head(x, get("ln_f", "scale"), get("lm_head", "kernel"),
                       rows, eps=eps, spoil=spoil)
    return np.asarray(logits, np.float32), notes


def lowerings(get, config, length, *, named=128, row_block=128) -> list:
    """[(name, jax.stages.Lowered)]: the programs `forward` runs over
    `length` tokens with `named` rows asked for, lowered and not compiled,
    for a caller that compiles them ahead of the forward and beside other
    work (they are the forward's own jitted functions at its own shapes,
    so the forward finds them in the compile cache). Layers of one kind
    share their programs."""
    r, eps = routing(config), config["layernorm_epsilon"]
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
    pattern = config["hybrid_layer_pattern"][:config["num_hidden_layers"]]
    with jax.default_matmul_precision("highest"):
        x = add(dsa._embed, get("wte", "kernel"), like(s, dtype=jnp.int32))
        u = x
        for kind in sorted(set(pattern)):
            i = pattern.index(kind)
            d, p = layer_dims(config, i), f"l{i}_"
            q, k, v = add(
                _attention_inputs, x, get(p + "ln1", "scale"),
                {name: get(p + "attn", name) for name in ATTENTION_WEIGHTS},
                like(s, dtype=jnp.int32), d=d, spoil=None)
            u = add(_attend, q, k, v,
                    get(p + "attn", "sink") if d.sink
                    else _no_sink(get, i, d),
                    get(p + "attn", "wo"), d=d, row_block=block, spoil=None)
        moe = config["moe_layer_freq"][:config["num_hidden_layers"]]
        if 0 in moe:
            p = f"l{moe.index(0)}_"
            add(_dense_tail, x, u, get(p + "ln2", "scale"),
                *(get(p + "ffn_" + name, "kernel")
                  for name in ("gate", "up", "down")), eps=eps, spoil=None)
        if 1 in moe:
            p = f"l{moe.index(1)}_"
            add(_expert_tail, x, u, get(p + "ln2", "scale"),
                {name: get(p + "moe", name) for name in EXPERT_WEIGHTS},
                like(s, r.k, dtype=jnp.int32), 0.0, eps=eps, r=r,
                first=held_experts(config)[0], spoil=None)
        add(_head, x, get("ln_f", "scale"), get("lm_head", "kernel"),
            like(whole, dtype=jnp.int32) if named
            else like(s, dtype=jnp.int32), eps=eps, spoil=None)
    return out


# What decides `correct` in `mimo2f-serve-longdoc` (jobs/serve_longdoc.py):
# logits, at the decoded rows of the pre-window check (a context of 2,300
# tokens: eighteen windows) and of two streams the loop served (contexts
# near 8.4 k and 16.3 k, all 32 slots live, through the engine's own block
# manager: window blocks freed as the slots advance, a history matched over
# both groups, its shared tail block copied in both), the reference
# evaluated under the program's routing where its own lies at a near-tie;
# and what the global pool holds of those two streams' questions in the
# LAST GLOBAL layer (layer 5: its keys and values are a function of four
# window layers' outputs, and the global group keeps them whole). Each
# limit stands between two readings (my chip runs, PR 41; PERF.md section 6
# has the table): the largest of the sound runs, and the controls, which
# have to come out not correct: every entry of SPOILS, and the streams
# replayed with one window block of their cached history zeroed.
#
# LOGIT_TOL: max |program logit - reference logit| over the compared rows
# as a share of the largest |reference logit| there (bf16 against float32).
# Sound 0.0066-0.0080 (30 checks and streams of ten runs at contexts 2.3 k,
# 8.8 k and 16.6 k); a dropped sink 0.046-0.050, the thetas swapped
# 0.26-0.29, window off 0.30-0.35, RoPE over the whole head 0.35-0.41, the
# values unscaled 0.37-0.39, e4m3 weights 0.73-0.90, a lost window block
# 0.29-0.33. 0.02 is 2.5 times the one and 0.44 of the smallest other.
#
# CACHE_TOL: max |pool row - reference row| over the largest |reference|
# entry, keys and values of the question's positions in layer 5 as the
# global pool holds them after the replay. Sound 0.008-0.065 (bf16 through
# five layers; the reference is given the program's experts at decoded
# rows only, and a prompt token routed apart moves its state by an
# expert's share); window off 0.31-0.33, the values unscaled 0.31-0.35,
# e4m3 0.68-0.77, the thetas swapped 1.65-1.72, RoPE over the whole head
# 1.75. A dropped sink (0.058-0.061) and a lost window block (0.027-0.037:
# the rows were cached when the loop served the question, before the block
# was lost) are not told here: the logits tell them.
#
# ROUTE_MARGIN: deepseek_v32_reference.route's rule: the reference takes
# the program's experts at a token whose gap (the 8th selection score less
# the 9th, as a share of the 8th) is under this; a decoded row the program
# routed otherwise at a larger gap makes the run not correct. Sound: the
# largest gap at which the two routed apart 0.0000-0.0030, none beyond the
# margin; the controls 0.017-0.046 with 2-377 rows beyond it (a dropped
# sink 0-2).
LOGIT_TOL = 0.02
CACHE_TOL = 0.15
ROUTE_MARGIN = 0.012
# no layer selects: the keys the session job's report reads of a selection
SEL_MARGIN = 0.0
MAX_OUTSIDE = 0
# the compared rows of a sequence come in whole blocks of this many
ROWS = 512


def last_global_layer(config) -> int:
    pattern = config["hybrid_layer_pattern"][:config["num_hidden_layers"]]
    return max(i for i, kind in enumerate(pattern) if not kind)


def compare(get, tokens, config, rows, program, pad_to=None, spoil=None,
            pool_rows=None) -> dict:
    """The program's logits `rows` {position: (vocab,)} of one sequence
    against the reference's full forward over `tokens`, under the program's
    routing `program` (`forward`) at near-ties; the interface of
    deepseek_v32_reference.compare, whose selection readings are empty
    here. `pool_rows` = (first position, keys (n, G x dk), values (n, G x
    dv)): what the program's pool holds of positions first .. first + n - 1
    in the last global layer, held against the reference's -> cache_error
    (to hold against CACHE_TOL)."""
    tokens = list(tokens)
    length = pad_to or len(tokens) + -len(tokens) % 256
    if length < len(tokens):
        raise ValueError(f"{len(tokens)} tokens do not fit {pad_to}")
    at = sorted(rows)
    layer = last_global_layer(config) if pool_rows is not None else None
    full, notes = forward(get, tokens + [0] * (length - len(tokens)), config,
                          program=program, route_margin=ROUTE_MARGIN,
                          rows=at + at[-1:] * (-len(at) % ROWS), spoil=spoil,
                          cache_layer=layer)
    mine = np.stack([np.asarray(rows[t], np.float32) for t in at])
    ref = full[:len(at)]
    by_row = (np.max(np.abs(mine - ref), axis=-1) / np.max(np.abs(ref)))
    ties = [np.asarray(note["tie"])[at] for note in notes if "tie" in note]
    gaps = []  # of the tokens the program routed otherwise
    for i, note in enumerate(notes):
        chosen = program.get(i, {}).get("experts", {})
        if "gap" in note and chosen:
            own, gap = np.asarray(note["own_ids"]), np.asarray(note["gap"])
            gaps += [float(gap[t]) for t in at if t in chosen
                     and set(np.asarray(chosen[t]).tolist())
                     != set(own[t].tolist())]
    out = {
        "error": dsa.logit_error(mine, ref),
        "error_by_row": by_row.round(4).tolist(),
        "sel_bad": 0, "sel_taken": 0, "sel_rows": 0, "outside_max": 0,
        "shortfall_max": 0.0,
        "route_rows": sum(t.size for t in ties),
        "route_taken": int(sum(t.sum() for t in ties)),
        "route_differs": len(gaps),
        "route_gap_max": max(gaps, default=0.0),
        "route_bad": sum(g >= ROUTE_MARGIN for g in gaps),
    }
    if pool_rows is not None:
        first, *held = pool_rows
        out["cache_error"] = max(
            float(np.max(np.abs(np.asarray(have, np.float32)
                                - want[first:first + len(have)]))
                  / np.max(np.abs(want[first:first + len(have)])))
            for have, want in zip(held, notes[layer]["cache"]))
    return out
