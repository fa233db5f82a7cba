"""One held share's expert layer alone on the chip: the sorted order run
whole against the live prefix in slabs of several sizes.

    chiprun -- python scripts/moe_live_bench.py [--cell lfm2|solar2|ms4]
        [--slabs 1024,2048,4096] [--shares 0.25,0.27] [--calls 20]

`--cell lfm2` (the default) is `lfm2-train-8k`'s layer, forward and
backward: 16,384 tokens x 4 of 32 sigmoid-routed experts, 8 held, hidden
2,048, experts of 1,792, bf16 over float32 masters, as the cell's step
runs one layer. `solar2` and `ms4` are a serving step's, the forward alone
and 32 layers of it chained in one program (a call is some 0.1 ms): the
largest and the smallest sort of the serving cells, 384 rows x 8 of 320
experts with 40 held (3,072 sorted rows) and 272 x 4 of 128 with 16 held
(1,088), where `ops/moe.py` keeps the whole length (`MIN_SLABS`); the
slab path is forced there (`MIN_SLABS` 1) to say what it would cost.
`--shares` lifts the held experts' bias until that share of the
assignments falls on them (the first is the draw's, no lift). For each
share: milliseconds a call with every pass over all the sorted rows
(`MIN_SLABS` out of reach: the layer as it was), then with the live
prefix in slabs of each size (`ops/moe.py`'s SLAB set before the trace),
the slabs run, and the largest difference of the output and of dx from
the whole length's over their largest entry (bf16: the two programs round
apart).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# tokens, hidden, experts' width, experts, a token's, first held, held,
# whether the backward runs, the slabs and the shares to try (the router
# is lfm2's sigmoid with a bias everywhere: what is timed comes after it)
CELLS = {
    "lfm2": (16384, 2048, 1792, 32, 4, 8, 8, True,
             "1024,2048,4096", "0.25,0.27"),
    "solar2": (384, 4096, 1280, 320, 8, 0, 40, False,
               "256,512,1024", "0.125,0.25"),
    "ms4": (272, 4096, 2048, 128, 4, 0, 16, False,
            "128,256,512", "0.125,0.25"),
}
CHAIN = 32


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="lfm2", choices=sorted(CELLS))
    ap.add_argument("--slabs", default=None)
    ap.add_argument("--shares", default=None)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", type=int, default=0,
                    help="print the N longest instructions of one call")
    opts = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.fftype import OperatorType as OT
    from flexflow_tpu.ops import moe as moe_ops
    from flexflow_tpu.ops.base import OpContext, get_op_def

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"moe_live_bench: needs a TPU, JAX found {device.platform}")
    t, d, f, n, k, first, held, train, slabs, shares = CELLS[opts.cell]
    slabs, shares = opts.slabs or slabs, opts.shares or shares
    p = moe_ops.MoEMLPParams(n, k, f, scoring="sigmoid", norm_topk_prob=True,
                             norm_topk_eps=1e-6, experts_held=(first, held))
    op = get_op_def(OT.OP_MOE_MLP)
    keys = jax.random.split(jax.random.key(opts.seed), 6)
    w = {"router": 0.02 * jax.random.normal(keys[0], (d, n)),
         "gate": 0.02 * jax.random.normal(keys[1], (held, d, f)),
         "up": 0.02 * jax.random.normal(keys[2], (held, d, f)),
         "down": 0.02 * jax.random.normal(keys[3], (held, f, d))}
    x = jax.random.normal(keys[4], (t, d), jnp.bfloat16)
    weight = jax.random.normal(keys[5], (t, d), jnp.float32)

    def layer(x, w, bias):
        wb = {name: a.astype(jnp.bfloat16) for name, a in w.items()}
        # (`slabs_run` as the executor brings a built layer's leaf)
        (y,), state = op.forward(
            p, [x], {**wb, "router_bias": bias,
                     "slabs_run": jnp.zeros((), jnp.int32)}, {},
            OpContext(training=train))
        return y, (y, state["assignments_total"], state["dropped_tokens"],
                   state["slabs_run"])

    def loss(x, w, bias):
        y, more = layer(x, w, bias)
        return jnp.sum(y.astype(jnp.float32) * weight), more

    def chained(x, w, bias):
        """CHAIN forwards, each layer's input the one before's output
        beside x; dx is a stand-in (the first layer's output)."""
        def one(h, _):
            y, more = layer(h, w, bias)
            return x + y, more
        _, (ys, *more) = jax.lax.scan(one, x, None, length=CHAIN)
        return (None, (ys[0], *(m[0] for m in more))), (ys[0], None)

    def timed(bias):
        jax.clear_caches()      # the layer's jitted halves read SLAB as traced
        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
                       if train else chained)
        out = jax.block_until_ready(step(x, w, bias))
        t0 = time.perf_counter()
        for _ in range(opts.calls):
            out = step(x, w, bias)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / opts.calls * 1e3
        ms = ms if train else ms / CHAIN
        if opts.trace:
            instructions(lambda: jax.block_until_ready(step(x, w, bias)))
        return ms, out

    def instructions(call):
        """The call's device events by instruction, the longest first."""
        import shutil

        from benchmarks import trace

        where = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".bench_trace", "moe_live_bench")
        shutil.rmtree(where, ignore_errors=True)
        jax.profiler.start_trace(where)
        call()
        jax.profiler.stop_trace()
        by = {}
        for text, a, b in trace.read_file(
                trace.newest_xplane(where)).chips[0].ops:
            name = trace.op_name(text)
            what = text.split(" = ", 1)[-1][:90]
            ms, n, _ = by.get(name, (0.0, 0, ""))
            by[name] = (ms + (b - a) / 1e6, n + 1, what)
        for name, (ms, n, what) in sorted(
                by.items(), key=lambda kv: -kv[1][0])[:opts.trace]:
            print(f"[live]     {ms:7.3f} ms x{n:<3d} {name:42s} {what}")

    def lift_for(share):
        """The bias on the held experts that puts `share` of the choices
        on them: a bisection over eager router calls."""
        lo, hi = 0.0, 1.0
        for _ in range(20):
            mid = (lo + hi) / 2
            bias = jnp.zeros((n,)).at[first:first + held].set(mid)
            _, ids, _ = moe_ops.moe_route_sigmoid(
                x, w["router"].astype(x.dtype), bias, p)
            got = float(jnp.mean((ids >= first) & (ids < first + held)))
            lo, hi = (mid, hi) if got < share else (lo, mid)
        return hi

    def far(a, b):
        a, b = (np.asarray(v, np.float32) for v in (a, b))
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    slab, least = moe_ops.SLAB, moe_ops.MIN_SLABS
    for i, share in enumerate(map(float, shares.split(","))):
        lift = lift_for(share) if i else 0.0
        bias = jnp.zeros((n,)).at[first:first + held].set(lift)
        moe_ops.MIN_SLABS = 10**9
        ms, ((_, (y0, live, dropped, _)), (dx0, _)) = timed(bias)
        print(f"[live] share {share}: a lift of {lift:.4f}; {int(live)} of "
              f"{t * k} assignments held, {float(dropped):.0f} dropped")
        print(f"[live]   whole length: {ms:8.3f} ms a call")
        moe_ops.MIN_SLABS = least if train else 1
        for rows in map(int, slabs.split(",")):
            moe_ops.SLAB = rows
            ms, ((_, (y, _, _, ran)), (dx, _)) = timed(bias)
            print(f"[live]   slab {rows:5d}: {ms:8.3f} ms a call; {int(ran)} "
                  f"slabs run ({int(ran) * rows} rows); y {far(y, y0):.1e}, "
                  f"dx {far(dx, dx0):.1e} from the whole length's; finite "
                  f"{bool(jnp.all(jnp.isfinite(dx.astype(jnp.float32))))}")
        moe_ops.SLAB, moe_ops.MIN_SLABS = slab, least
    print(f'{{"ok": true, "device": "{device.device_kind}"}}')
    return 0


if __name__ == "__main__":
    sys.exit(main())
