"""A held share's expert layer runs its sorted-order passes over the live
prefix of the sort (PR 62): `ops/moe.py`'s `_held_live` against the layer as it was, every
pass over all tokens x k rows, kept here as a plain function under JAX's
own reverse mode. Sorted rows of five slabs, or of four and a half, at
hidden 16 and experts of 8; the grouped matmul is `ragged_dot` on the CPU,
which writes zeros past its groups, so what the chip leaves unwritten is
poisoned by hand.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.fftype import OperatorType as OT
from flexflow_tpu.ops import moe as moe_ops
from flexflow_tpu.ops.base import OpContext, get_op_def

SLAB = moe_ops.SLAB
D, F, N, K = 16, 8, 8, 4
T = 5 * SLAB // K
M = T * K
# SLAB does not divide these rows: the fifth slab starts inside the fourth
T_RAGGED = (4 * SLAB + SLAB // 2) // K
OP = get_op_def(OT.OP_MOE_MLP)
MATRICES = ("router", "gate", "up", "down")
# float32 against float32: the slab's fused passes and the whole length's
# round their products apart in the last bit (XLA contracts a multiply and
# an add where it fuses them), nothing more
TOL = {jnp.float32: (2e-6, 2e-6), jnp.bfloat16: (0.03, 0.15)}


def params(held):
    return moe_ops.MoEMLPParams(N, K, F, scoring="sigmoid",
                                norm_topk_prob=True, experts_held=held)


def case(load, dtype=jnp.float32, seed=6, tokens=T):
    """(params, x, weights, the assignments that fall on held experts) of
    a layer whose router sends `load` of the tokens x k here."""
    rng = np.random.default_rng(seed)
    held = {"none": (2, 2), "quarter": (2, 2), "all_but_one_row": (0, N - 1),
            "all": (0, K)}[load]
    w = {"router": rng.normal(0, 0.1, (D, N)), "router_bias": np.zeros(N),
         "gate": rng.normal(0, 0.3, (held[1], D, F)),
         "up": rng.normal(0, 0.3, (held[1], D, F)),
         "down": rng.normal(0, 0.3, (held[1], F, D))}
    x = rng.normal(0, 1, (tokens, D))
    first, count = held
    if load == "none":
        w["router_bias"][first:first + count] = -10.0
    elif load == "all":
        w["router_bias"][first:first + count] = 10.0
    elif load == "all_but_one_row":
        # the last expert is held elsewhere and answers to feature 0 alone,
        # which token 0 alone has: its score there is 1 - 0.4 over the
        # others' 0.5, every other token's 0.5 - 0.4 under every other
        # expert's (logits of spread 0.4, a score over 0.2)
        w["router"][:, N - 1] = 0.0
        w["router"][0, :] = 0.0
        w["router"][0, N - 1] = 1.0
        w["router_bias"][N - 1] = -0.4
        x[:, 0] = 0.0
        x[0] = 0.0
        x[0, 0] = 50.0
    want = {"none": 0, "all_but_one_row": tokens * K - 1,
            "all": tokens * K}.get(load)
    return (params(held), jnp.asarray(x, dtype),
            {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}, want)


def whole_length(p, x, w):
    """The layer before PR 62, every pass in sorted order over all tokens
    x k rows: plain jax.numpy, no hand-written backward."""
    k, (first, held) = p.num_experts_per_tok, p.held
    gates, ids, _ = moe_ops.moe_route_sigmoid(
        x, w["router"], w.get("router_bias"), p)
    here = (ids >= first) & (ids < first + held)
    gates = jnp.where(here, gates, 0.0)
    order, position, sizes = moe_ops.moe_sort(
        jnp.where(here, ids - first, held), held + 1)
    sizes = sizes[:held]
    rows = x[order // k]
    gate, up = (jax.lax.ragged_dot(rows, w[name].astype(x.dtype), sizes)
                for name in ("gate", "up"))
    hidden = (jax.nn.silu(gate.astype(jnp.float32))
              * up.astype(jnp.float32)).astype(x.dtype)
    out = jax.lax.ragged_dot(hidden, w["down"].astype(x.dtype), sizes)
    picked = jnp.where(here[..., None], out[position], 0.0)
    y = jnp.sum(gates[..., None] * picked.astype(jnp.float32), axis=1)
    return y.astype(x.dtype)


def layer(p, x, w, built=None):
    """The layer's output and state as the executor calls it: the counters
    the layer built at `built` (x's shape where None) keeps come in beside
    its weights."""
    kept = {spec.name: jnp.zeros((), jnp.int32)
            for spec in OP.weights(p, [built or x.shape])
            if spec.name in ("assignments_total", "dropped_total",
                             "slabs_run")}
    (y,), state = OP.forward(p, [x], {**w, **kept}, {},
                             OpContext(training=True))
    return y, state


def with_grads(fn, x, w, seed=3):
    """((weighted sum of fn's output, what else it returns), (dx, dw)) of
    one jitted program."""
    weight = jnp.asarray(
        np.random.default_rng(seed).normal(0, 1, x.shape), jnp.float32)

    def loss(x, w):
        y, *more = fn(x, w)
        return jnp.sum(y.astype(jnp.float32) * weight), (y, *more)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(
        x, w)


def close(a, b, tol):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape
    scale = max(float(np.max(np.abs(b))), 1e-30)
    assert float(np.max(np.abs(a - b))) <= tol * scale, (
        float(np.max(np.abs(a - b))) / scale)


def same_as(got, want, dtype):
    (_, (y, *_)), (dx, dw) = got
    (_, (want_y,)), (want_dx, want_dw) = want
    tol, grad_tol = TOL[dtype]
    close(y, want_y, tol)
    close(dx, want_dx, grad_tol)
    for name in MATRICES:
        close(dw[name], want_dw[name], grad_tol)


# ---------------------------------------------- the layer, at every load

@pytest.mark.parametrize("dtype,load,tokens", [
    *((dtype, load, T) for dtype in (jnp.float32, jnp.bfloat16)
      for load in ("none", "quarter", "all_but_one_row", "all")),
    # the last slab pushed back inside the one before: run, and not
    *((jnp.float32, load, T_RAGGED)
      for load in ("quarter", "all_but_one_row", "all")),
], ids=lambda v: {jnp.float32: "float32", jnp.bfloat16: "bf16", T: "whole",
                  T_RAGGED: "ragged"}.get(v, v))
def test_live_prefix_equals_the_whole_length(dtype, load, tokens):
    """Output and the gradient of x, router, gate, up, down; no slab, a
    last slab part live, every slab but for one row of the last, every
    slab; rows of whole slabs and rows the slab does not divide. Nothing is
    dropped at any load, and `slabs_run` is the slabs under the held
    assignments."""
    p, x, w, want_live = case(load, dtype, tokens=tokens)
    m = tokens * K
    got = with_grads(lambda x, w: layer(p, x, w), x, w)
    state = got[0][1][1]
    live = int(state["assignments_total"])
    if want_live is not None:
        assert live == want_live
    else:
        assert 0.15 * m < live < 0.35 * m and live % SLAB
    assert float(state["dropped_tokens"]) == 0.0
    assert int(state["slabs_run"]) == -(-live // SLAB)
    assert state["slabs_run"].dtype == jnp.int32
    same_as(got, with_grads(lambda x, w: (whole_length(p, x, w),), x, w),
            dtype)
    if load == "none":
        (_, (y, _)), (dx, dw) = got
        assert not np.any(np.asarray(y, np.float32))
        assert not any(np.any(np.asarray(dw[name])) for name in MATRICES)


# ------------------------------- what no expert and no slab wrote: poison

def nothing_written(like, after, mesh):
    return jnp.full(like.shape, jnp.nan, like.dtype)


@pytest.fixture
def poison(monkeypatch):
    """`poison(module, name, value)` replaces a function the layer's jitted
    forward and backward call: what they traced before is dropped, and
    what they trace under it is dropped after the test."""
    def replace(module, name, value):
        monkeypatch.setattr(module, name, value)
        jax.clear_caches()

    yield replace
    monkeypatch.undo()
    jax.clear_caches()


def unwritten_past_the_groups(real):
    """`real` (a grouped matmul) as the chip's kernel leaves its rows past
    the groups' sum, forward and dX alike: NaN here."""
    @jax.custom_vjp
    def unwritten(lhs, rhs, sizes):
        live = jnp.arange(lhs.shape[0]) < jnp.sum(sizes)
        return jnp.where(live[:, None], real(lhs, rhs, sizes), jnp.nan)

    def fwd(lhs, rhs, sizes):
        return unwritten(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        live = (jnp.arange(lhs.shape[0]) < jnp.sum(sizes))[:, None]
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes),
                         jnp.where(live, lhs, 0), rhs)
        dl, dr = vjp(jnp.where(live, g, 0))
        return jnp.where(live, dl, jnp.nan), dr, None

    unwritten.defvjp(fwd, bwd)
    return lambda a, b, sizes, mesh=None: unwritten(a, b, sizes)


@pytest.mark.parametrize("load", ["quarter", "all_but_one_row"])
def test_the_slabs_tail_and_the_rows_no_slab_wrote_give_nothing(
        poison, load):
    """PR 60's poison test at a shape that runs slabs: the grouped
    matmul's rows past `live` are NaN, forward and dX, so the last slab's
    tail is computed from NaN; the buffers the slabs write into start as
    NaN, so the rows no slab wrote are NaN. The output and every gradient
    are finite and what they are without."""
    import flexflow_tpu.kernels.grouped_matmul as gm

    p, x, w, _ = case(load)
    clean = with_grads(lambda x, w: layer(p, x, w), x, w)
    poison(gm, "grouped_matmul", unwritten_past_the_groups(gm.grouped_matmul))
    poison(moe_ops, "_unwritten", nothing_written)
    dirty = with_grads(lambda x, w: layer(p, x, w), x, w)
    live = int(clean[0][1][1]["assignments_total"])
    assert 0 < live < M                 # some rows ARE past the sum
    (_, (y0, _)), (dx0, dw0) = clean
    (_, (y1, _)), (dx1, dw1) = dirty
    assert np.all(np.isfinite(np.asarray(y1)))
    assert np.array_equal(np.asarray(y1), np.asarray(y0))
    close(dx1, dx0, 2e-6)
    for name in MATRICES:
        close(dw1[name], dw0[name], 2e-6)


@pytest.mark.parametrize("tokens,live", [
    *((T, live) for live in (0, 1, SLAB, SLAB + 1, M - 1, M)),
    # four slabs and a half: the fifth not run, and run from inside the
    # fourth
    (T_RAGGED, 4 * SLAB), (T_RAGGED, 4 * SLAB + 1)])
def test_a_pass_writes_the_live_slabs_and_no_other_row(poison, tokens, live):
    """Each pass alone, its buffer NaN where the chip leaves it unwritten:
    rows [0, live) are the whole length's, the rest of the last slab that
    ran is something finite, and no row past it was written."""
    poison(moe_ops, "_unwritten", nothing_written)
    rng = np.random.default_rng(live)
    m = tokens * K
    gate, up = (jnp.asarray(rng.normal(0, 1, (m, F)), jnp.float32)
                for _ in range(2))
    x = jnp.asarray(rng.normal(0, 1, (tokens, D)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, N, (tokens, K)), jnp.int32)
    order, _, _ = moe_ops.moe_sort(ids, N)
    n = jnp.int32(live)
    ran = min(m, -(-live // SLAB) * SLAB)

    def holds(got, want):
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_allclose(got[:live], want[:live], rtol=2e-6,
                                   atol=1e-6)
        assert np.all(np.isfinite(got[:ran]))
        assert np.all(np.isnan(got[ran:]))

    holds(moe_ops._silu_gate_live(gate, up, n, None),
          moe_ops._silu_gate(gate, up))
    holds(moe_ops._sorted_rows(x, order, n, K, None), x[order // K])
    holds(moe_ops._over_live_slabs(
        n, lambda at, a, b: moe_ops._slab(a, at) + moe_ops._slab(b, at),
        jax.ShapeDtypeStruct(gate.shape, gate.dtype), gate, up), gate + up)


# ---------------------------------------- when it engages, and the counter

@pytest.mark.parametrize("held,tokens,slabs", [
    ((8, 8), moe_ops.MIN_SLABS * SLAB // K, True),      # lfm2-train-8k's kind
    ((8, 8), moe_ops.MIN_SLABS * SLAB // K - 1, False),
    ((8, 8), (moe_ops.MIN_SLABS * SLAB + SLAB // 2) // K, True),  # no whole
    ((8, 8), 768, False),               # solar2-serve-reason's largest step
    (None, 32768, False),               # olmoe-train-4k: a whole share
])
def test_the_state_has_slabs_run_where_the_layer_runs_slabs(held, tokens,
                                                            slabs):
    p = moe_ops.MoEMLPParams(32, K, F, scoring="sigmoid", experts_held=held)
    assert moe_ops._by_slabs(p, tokens * K) == slabs
    names = [w.name for w in OP.weights(p, [(tokens, D)])]
    assert ("slabs_run" in names) == slabs
    assert SLAB % 512 == 0      # whole row tiles of the grouped matmul


@pytest.mark.parametrize("built,run,slabs_run", [
    (T, T_RAGGED, True),        # both run slabs: the leaf counts them
    (T, 64, 0),                 # built long, run short: the leaf reads 0
    (64, T, None),              # built short, run long: no leaf, the slabs run
])
def test_a_forward_at_other_rows_than_the_builds_keeps_the_states_tree(
        built, run, slabs_run):
    """The state a layer carries is what it was built with, whatever rows
    a forward sees: `slabs_run` is there where the build declared it, and
    the output is the whole length's either way."""
    p, x, w, _ = case("quarter", tokens=run)
    y, state = jax.jit(lambda x, w: layer(p, x, w, built=(built, D)))(x, w)
    declared = {spec.name for spec in OP.weights(p, [(built, D)])
                if not spec.trainable}
    assert set(state) == declared
    if slabs_run is None:
        assert "slabs_run" not in state
    else:
        live = int(state["assignments_total"])
        assert int(state["slabs_run"]) == (-(-live // SLAB) if slabs_run
                                           else 0)
    close(y, whole_length(p, x, w), 2e-6)


def old_forward(p, inputs, weights, state, ctx):
    """`_moe_mlp_forward` as it stood before PR 62, letter for letter but
    for the module's name in front of what it takes from there."""
    from flexflow_tpu.kernels.grouped_matmul import grouped_matmul

    (x,) = inputs
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    n, k = p.num_experts, p.num_experts_per_tok
    first, held = p.held
    with jax.named_scope("moe.route"):
        if p.scoring == "sigmoid":
            gates, ids, probs = moe_ops.moe_route_sigmoid(
                x, weights["router"], weights.get("router_bias"), p)
        else:
            gates, ids, probs = moe_ops.moe_route(x, weights["router"], k)
            if p.norm_topk_prob:
                gates = gates / moe_ops._gate_sum(gates, p)
            if p.routed_scaling_factor != 1.0:
                gates = gates * p.routed_scaling_factor
    with jax.named_scope("moe.dispatch"):
        if p.experts_held is None:
            order, position, group_sizes = moe_ops.moe_sort(ids, n)
        else:
            # assignments to experts held elsewhere sort behind the last
            # group, where no expert computes them, and count for nothing
            here = (ids >= first) & (ids < first + held)
            gates = jnp.where(here, gates, 0.0)
            order, position, group_sizes = moe_ops.moe_sort(
                jnp.where(here, ids - first, held), held + 1)
            group_sizes = group_sizes[:held]
        rows = moe_ops._gather_sorted(
            x, order, position,
            None if p.experts_held is None else jnp.sum(group_sizes))
    with jax.named_scope("moe.experts"):
        gate = grouped_matmul(rows, weights["gate"].astype(x.dtype),
                              group_sizes, ctx.mesh)
        up = grouped_matmul(rows, weights["up"].astype(x.dtype),
                            group_sizes, ctx.mesh)
        hidden = (jax.nn.silu(gate.astype(jnp.float32))
                  * up.astype(jnp.float32)).astype(x.dtype)
        out = grouped_matmul(hidden, weights["down"].astype(x.dtype),
                             group_sizes, ctx.mesh)
    with jax.named_scope("moe.combine"):
        picked = moe_ops._gather_back(out, order, position)
        if p.experts_held is not None:
            # rows past the groups' sum are whatever the kernel left there
            picked = jnp.where(here[..., None], picked, 0.0)
        y = jnp.sum(gates[..., None] * picked.astype(jnp.float32), axis=1)
    if p.shared_intermediate_size:
        with jax.named_scope("moe.shared"):
            def dot(a, w):
                return jnp.dot(a, w.astype(a.dtype),
                               preferred_element_type=jnp.float32)

            h = (jax.nn.silu(dot(x, weights["shared_gate"]))
                 * dot(x, weights["shared_up"])).astype(x.dtype)
            shared = dot(h, weights["shared_down"])
            if p.shared_scale != 1.0:
                shared = shared * p.shared_scale
            y = y + shared
    state = dict(state or {})
    computed = jnp.sum(group_sizes)
    wanted = ids.size if p.experts_held is None else jnp.sum(here)
    state["dropped_tokens"] = (wanted - computed).astype(jnp.float32)
    state["load_max_over_mean"] = (
        jnp.max(group_sizes)
        * (held / jnp.maximum(computed, 1).astype(jnp.float32)))
    declared = weights.get("expert_ids")
    if declared is None or ids.shape == declared.shape:
        state["expert_ids"] = ids
    elif len(shape) == 3 and shape[1] == 1 and shape[0] > declared.shape[0]:
        # a serving step with a prefill chunk riding as single-query rows
        # past the slots': the record keeps the slots' rows, which come
        # first (a state leaf keeps its shape; any other layout leaves
        # the record as it was), and the chunk's rows beside it where the
        # graph keeps that record
        state["expert_ids"] = ids[:declared.shape[0]]
        past, n = weights.get("chunk_expert_ids"), declared.shape[0]
        if past is not None and shape[0] - n <= past.shape[0]:
            state["chunk_expert_ids"] = past.at[:shape[0] - n].set(ids[n:])
    if p.experts_held is not None:
        state["assignments_total"] = (weights.get("assignments_total", 0)
                                      + computed.astype(jnp.int32))
        state["dropped_total"] = (weights.get("dropped_total", 0)
                                  + (wanted - computed).astype(jnp.int32))
    if p.aux_loss_coef:
        state["aux_loss"] = p.aux_loss_coef * moe_ops.load_balancing_loss(
            probs, group_sizes)
    return [y.astype(x.dtype).reshape(shape)], state


def outputs(forward):
    def run(p, inputs, weights, state, ctx):
        (y,), state = forward(p, inputs, weights, state, ctx)
        return y, (state["dropped_tokens"], state["expert_ids"],
                   state.get("aux_loss", 0.0))
    return run


def lowered(forward, p, shape, dtype, train):
    weights = {w.name: jax.ShapeDtypeStruct(w.shape, jnp.float32)
               for w in OP.weights(p, [shape]) if w.trainable}

    def run(x, w):
        y, more = outputs(forward)(p, [x], w, {}, OpContext(training=train))
        return jnp.sum(y.astype(jnp.float32)) + more[2], (y, *more)

    fn = jax.value_and_grad(run, argnums=(0, 1), has_aux=True) if train \
        else run
    return jax.jit(fn).lower(jax.ShapeDtypeStruct(shape, dtype),
                             weights).as_text()


def _held_at(slots, **routing):
    """A serving cell's expert layer at small widths, its published
    routing (benchmarks/configs/, through models/transformer.py's
    `*_lm_config`), and the rows of its step: the slots and a prefill chunk
    of 256 (benchmarks/workloads/)."""
    n, k = routing.pop("n"), routing.pop("k")
    return (moe_ops.MoEMLPParams(n, k, F, norm_topk_prob=True,
                                 chunk_rows=256, **routing),
            (slots + 256, 1, D), False)


OTHER_CELLS = [
    # OLMoE's layer, every expert held, forward and backward, at a sort of
    # sixteen slabs: nothing to skip
    ("olmoe-train-4k", moe_ops.MoEMLPParams(64, 8, F, aux_loss_coef=0.01),
     (1, 4096, D), True),
    # the held shares at a serving step's rows, (slots + chunk rows) x k =
    # 1,088 to 3,072 sorted rows: under four slabs
    ("solar2-serve-reason", *_held_at(
        128, n=320, k=8, routed_scaling_factor=1,
        shared_intermediate_size=F, experts_held=(0, 40))),
    ("dsv32-serve-sessions", *_held_at(
        16, n=256, k=8, scoring="sigmoid", n_group=8, topk_group=4,
        routed_scaling_factor=2.5, shared_intermediate_size=F,
        experts_held=(0, 16))),
    ("mimo2f-serve-longdoc", *_held_at(
        32, n=256, k=8, scoring="sigmoid", n_group=1, topk_group=1,
        routed_scaling_factor=1.0, experts_held=(0, 16))),
    ("ms4-serve-longctx", *_held_at(
        16, n=128, k=4, scoring="softmax", routed_scaling_factor=1.0,
        shared_intermediate_size=F, experts_held=(0, 16))),
    ("cmdap-serve-agentmix", *_held_at(
        32, n=128, k=8, scoring="sigmoid", correction_bias=False,
        shared_intermediate_size=4 * F, shared_scale=0.25,
        experts_held=(0, 16))),
    # (every expert, said as a share)
    ("keye2-serve-mediaqa", *_held_at(16, n=128, k=8,
                                      experts_held=(0, 128))),
]


@pytest.mark.parametrize("cell,p,shape,train", OTHER_CELLS,
                         ids=[cell for cell, *_ in OTHER_CELLS])
def test_the_other_cells_layers_are_the_programs_they_were(cell, p, shape,
                                                           train):
    """The layer of a cell the mechanism bypasses lowers to the text of
    the layer as it stood, and holds no loop (as
    `test_causal_conv_default_is_the_program_it_was` holds the causal
    convolution's callers)."""
    text = lowered(OP.forward, p, shape, jnp.bfloat16, train)
    assert text == lowered(old_forward, p, shape, jnp.bfloat16, train)
    assert "while" not in text


def test_a_long_held_sort_lowers_to_loops():
    """The probe of the test above finds the loops where they are: one a
    pass, whatever the slabs' count, which the device decides. The forward
    runs two (the gather into sorted order, the SiLU gate), the backward
    those two again (it keeps `gate` and `up` and makes `rows` and
    `hidden` over) and three of its own (the gate-weighted rows of dy, the
    SiLU gate's backward, the sum of the two cotangents of `rows`)."""
    p = params((2, 2))
    assert lowered(OP.forward, p, (T, D), jnp.bfloat16, False).count(
        "stablehlo.while") == 2
    text = lowered(OP.forward, p, (T, D), jnp.bfloat16, True)
    assert text.count("stablehlo.while") == 7
    assert "while" not in lowered(old_forward, p, (T, D), jnp.bfloat16, True)


def test_two_stacked_layers_compose_under_one_jit():
    """value_and_grad through two layers in one program: a loop's carry
    and the next layer's, the first layer's backward after the
    second's."""
    p, x, w1, _ = case("quarter", seed=7)
    _, _, w2, _ = case("quarter", seed=8)

    def two(fn):
        def run(x, ws):
            h = x + fn(p, x, ws[0])
            return (h + fn(p, h, ws[1]),)
        return run

    got = with_grads(two(lambda p, x, w: layer(p, x, w)[0]), x, (w1, w2))
    want = with_grads(two(whole_length), x, (w1, w2))
    close(got[0][1][0], want[0][1][0], 2e-6)
    close(got[1][0], want[1][0], 2e-6)
    for mine, theirs in zip(got[1][1], want[1][1]):
        for name in MATRICES:
            close(mine[name], theirs[name], 2e-6)
