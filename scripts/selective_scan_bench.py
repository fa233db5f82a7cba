"""The selective-scan kernel alone, on the chip, at `jamba2-serve-shortchat`'s
shapes: 5,120 channels, a state of 16, float32.

    chiprun -- python scripts/selective_scan_bench.py [--slots 256]
        [--chunks 512,64,8] [--blocks 1280,2560,5120]

Two shapes of call (kernels/selective_scan.py): the slots' rows, one token
each (a decode step's, once a layer), and one slot's chunk, one row of
many tokens. Prints, for each channel block, the kernel's time a call, its
share of the bytes floor (h read once and written once, the operands and
outputs once, over the chip's HBM bandwidth) and its largest difference
from the jnp scan on the same operands; beside them the jnp forms XLA
compiles (one token a row as fused elementwise passes, the chunk as a
`lax.scan`), timed the same way. A number from here is a kernel's, not a
step's. Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N, E = 16, 5120


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=256)
    ap.add_argument("--chunks", default="512,64,8")
    ap.add_argument("--blocks", default="1280,2560,5120")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--layers", type=int, default=26)
    opts = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.kernels import selective_scan as ss

    if jax.devices()[0].platform != "tpu":
        sys.exit("selective_scan_bench: needs a TPU")
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "peaks.json")) as f:
        hbm = json.load(f)[jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    rng = np.random.default_rng(0)

    def operands(rows, tokens):
        f = jnp.float32
        return dict(
            dt=jnp.asarray(rng.uniform(0.001, 0.1, (rows, tokens, E)), f),
            c=jnp.asarray(rng.normal(size=(rows, tokens, E)), f),
            B=jnp.asarray(rng.normal(size=(rows, tokens, N)), f),
            C=jnp.asarray(rng.normal(size=(rows, tokens, N)), f),
            A=-jnp.exp(jnp.asarray(rng.uniform(0, 2.77, (N, E)), f)),
            D=jnp.asarray(rng.normal(size=(E,)), f),
            keep=jnp.ones((rows,), bool))

    def timed(fn, state, o):
        """Seconds a call, of `layers` calls one after another inside one
        program (a step runs the kernel once a layer; a call dispatched
        alone is the host's 0.3 ms, not the kernel's)."""
        def many(state, o):
            y, state = fn(state, o)
            return jax.lax.fori_loop(
                1, opts.layers, lambda _, ys: fn(ys[1], o), (y, state))

        many = jax.jit(many, donate_argnums=(0,))
        y, state = many(state, o)                    # compiles
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        for _ in range(opts.calls):
            y, state = many(state, o)
        jax.block_until_ready((y, state))
        return (time.perf_counter() - t0) / opts.calls / opts.layers

    def error(a, b):
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    shapes = [(opts.slots, 1)] + [(1, int(c)) for c in opts.chunks.split(",")]
    for rows, tokens in shapes:
        o = operands(rows, tokens)
        live = jnp.ones((rows, tokens), bool)
        state = jnp.asarray(rng.normal(size=(rows, N, E)), jnp.float32)
        moved = (2 * rows * N * E * 4                    # h in and out
                 + rows * tokens * (3 * E + 2 * N) * 4)  # dt, c, y, B, C
        floor = moved / hbm
        def scan(s, o):
            return ss.selective_scan_reference(
                s, o["dt"], o["c"], o["B"], o["C"], o["A"], o["D"], live,
                o["keep"])

        want_y, want_s = jax.jit(scan)(state, o)
        took = timed(scan, state + 0.0, o)
        print(f"[scan] rows {rows} x tokens {tokens}: the jnp scan "
              f"{took * 1e3:.3f} ms a call ({100 * floor / took:.1f} % of "
              f"the bytes floor of {floor * 1e3:.3f} ms)")
        for eb in map(int, opts.blocks.split(",")):
            # the sweep sets the module's constant (the kernel has no knob
            # for it) and traces anew
            ss._CHANNEL_BLOCK = eb
            ss._call.clear_cache()

            def kernel(s, o):
                return ss.selective_scan_update(
                    s, o["dt"], o["c"], o["B"], o["C"], o["A"], o["D"],
                    live, o["keep"])

            try:
                got_y, got_s = jax.jit(kernel)(state, o)
                took = timed(kernel, state + 0.0, o)
            except Exception as e:  # noqa: BLE001 - what the chip refuses
                print(f"[scan]   channel block {eb}: refused: "
                      f"{str(e).splitlines()[0][:200]}")
                continue
            print(f"[scan]   channel block {eb}: {took * 1e3:.3f} ms a call"
                  f" ({took / tokens * 1e6:.2f} us a token a row block), "
                  f"{100 * floor / took:.1f} % of the bytes floor; y "
                  f"{error(got_y, want_y):.2e}, h {error(got_s, want_s):.2e}"
                  f" from the jnp scan")
    return 0


if __name__ == "__main__":
    sys.exit(main())
