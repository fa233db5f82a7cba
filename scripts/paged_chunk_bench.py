"""The paged chunk kernel alone, on the chip, at the shapes of
`cmdap-serve-agentmix`: a chunk of 256 rows of 128 query heads of 128 on 8
KV heads over one slot's history in a pool of 256-row blocks of 1,024 bf16
lanes, read whole (the global layer) or through a window of 4,096 keys (a
window layer), beside the tile loop in XLA on the same operands.

    chiprun -- python scripts/paged_chunk_bench.py [--starts 11008,32000]

Prints, for each kind and start, a call's time through
`paged_flash_chunk_attention` and through `paged_chunk_attention_tiled`,
the kernel's share of the MXU's peak (the 4 x heads x head_dim a row a key
the mathematics needs, over the keys each row attends) and its largest
difference from the einsum reference on the same operands. A number from
here is a kernel's, not a step's. Needs a TPU.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--starts", default="11008,32000",
                    help="the global layer's contexts before the chunk")
    ap.add_argument("--window-start", type=int, default=8192)
    ap.add_argument("--calls", type=int, default=20)
    opts = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")

    if jax.devices()[0].platform != "tpu":
        sys.exit("paged_chunk_bench: needs a TPU")
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "peaks.json")) as f:
        peaks = json.load(f)[jax.devices()[0].device_kind]
    heads, kv, d, bs, b, window, max_seq = 128, 8, 128, 256, 256, 4096, 33536
    width = max_seq // bs
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, 1, heads * d)), jnp.bfloat16)
    pk, pv = (jax.jit(lambda key: jax.random.normal(
        key, (width + 1, bs, kv * d), jnp.bfloat16))(jax.random.key(i))
        for i in (1, 2))
    table = jnp.asarray(rng.permutation(np.arange(1, width + 1)), jnp.int32)
    scale = d ** -0.5

    def timed(fn, *args):
        fn(*args).block_until_ready()
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            outs = [fn(*args) for _ in range(opts.calls)]
            outs[-1].block_until_ready()
            took.append((time.perf_counter() - t0) / opts.calls)
        return float(np.median(took))

    cases = [("global", 0, int(s)) for s in opts.starts.split(",")]
    cases.append(("window", window, opts.window_start))
    for kind, w, start in cases:
        n = jnp.arange(start + 1, start + 1 + b, dtype=jnp.int32)
        kw = dict(num_heads=heads, num_kv_heads=kv, scale=scale, window=w)
        ways = {
            "loop": jax.jit(lambda q, pk, pv, table, n: (
                fa.paged_chunk_attention_tiled(q, pk, pv, table, n - 1,
                                               **kw))),
            "kernel": jax.jit(lambda q, pk, pv, table, n: (
                fa.paged_flash_chunk_attention(q, pk, pv, table, n, **kw)))}
        args = (q, pk, pv, table, n)
        some = np.r_[0:2, b - 2:b]  # the reference gathers a cache a row
        want = fa.paged_decode_attention_reference(
            q[some], pk, pv, jnp.broadcast_to(table, (len(some), width)),
            (n - 1)[some, None], **kw).astype(jnp.float32)
        keys = sum(min(int(x), w) if w else int(x) for x in np.asarray(n))
        flops = keys * 4 * heads * d
        for name, fn in ways.items():
            got = fn(*args)[some].astype(jnp.float32)
            err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
            s = timed(fn, *args)
            print(f"[bench] {kind}, {b} rows at {start}, {name}: "
                  f"{s * 1e3:.3f} ms a call; "
                  f"{100 * flops / s / peaks['bf16_flops_per_s']:.1f} % of "
                  f"the MXU's peak; off the reference by {err:.5f} of its "
                  f"largest ({len(some)} rows)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
