"""The grouped paged decode kernel alone, on the chip, at the shapes of
`cmdap-serve-agentmix`: 32 rows of 128 query heads of 128 on 8 KV heads
that read the cell's 32 histories (2-31 k rows, 354 k in all) whole, as the
global layer does, or their last 4,096 rows, as a window layer does, from
pools of 128- or 256-row blocks of 1,024 bf16 lanes.

    chiprun -- python scripts/paged_grouped_bench.py [--blocks 128,256]

Prints, for each block size and kind, the kernel's time a call, its share
of the bytes floor (the rows a call attends x 4,096 B over the chip's HBM
bandwidth) and of the MXU's peak (the block-diagonal query spends it 8
times over: counted as the 2 x 2 x heads x head_dim a row the mathematics
needs), and its largest difference from the einsum reference on the same
operands. A number from here is a kernel's, not a step's. Needs a TPU.
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
    ap.add_argument("--blocks", default="128,256")
    ap.add_argument("--calls", type=int, default=20)
    opts = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import traffic
    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")

    if jax.devices()[0].platform != "tpu":
        sys.exit("paged_grouped_bench: needs a TPU")
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "peaks.json")) as f:
        peaks = json.load(f)[jax.devices()[0].device_kind]
    heads, kv, d, window, max_seq = 128, 8, 128, 4096, 33536
    lengths = [n + 300 for n in traffic.quantiles(
        {"dist": "log_uniform", "min": 2048, "max": 32768}, 32)]
    rows = len(lengths)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(rows, 1, heads * d)), jnp.bfloat16)
    n = jnp.asarray(lengths, jnp.int32)

    def timed(fn):
        fn = jax.jit(fn)
        fn().block_until_ready()
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            outs = [fn() for _ in range(opts.calls)]
            outs[-1].block_until_ready()
            took.append((time.perf_counter() - t0) / opts.calls)
        return float(np.median(took))

    for bs in map(int, opts.blocks.split(",")):
        width = max_seq // bs
        blocks = sum(-(-x // bs) for x in lengths) + 1
        table = np.zeros((rows, width), np.int32)
        free = rng.permutation(np.arange(1, blocks))
        at = 0
        for r, x in enumerate(lengths):
            need = -(-x // bs)
            table[r, :need] = free[at:at + need]
            at += need
        table = jnp.asarray(table)
        pk, pv = (jax.jit(lambda key: jax.random.normal(
            key, (blocks, bs, kv * d), jnp.bfloat16))(jax.random.key(i))
            for i in (1, 2))
        for kind, w in (("global", 0), ("window", window)):
            kw = dict(num_heads=heads, num_kv_heads=kv, window=w)
            got = fa.paged_flash_decode_attention(q, pk, pv, table, n, **kw)
            want = fa.paged_decode_attention_reference(
                q[:4], pk, pv, table[:4], (n - 1)[:4, None], num_heads=heads,
                num_kv_heads=kv, window=w)
            err = float(jnp.max(jnp.abs(got[:4].astype(jnp.float32)
                                        - want.astype(jnp.float32)))
                        / jnp.max(jnp.abs(want.astype(jnp.float32))))
            s = timed(lambda: fa.paged_flash_decode_attention(
                q, pk, pv, table, n, **kw))
            read = sum(min(x, w) if w else x for x in lengths)
            flops = read * 4 * heads * d
            print(f"[bench] blocks of {bs}, {kind}: {s * 1e3:.3f} ms a call "
                  f"over {read} rows; "
                  f"{100 * read * 4096 / peaks['hbm_bytes_per_s'] / s:.1f} % "
                  f"of the bytes floor, "
                  f"{100 * flops / s / peaks['bf16_flops_per_s']:.1f} % of "
                  f"the MXU's peak; off the reference by {err:.5f} of its "
                  f"largest (4 rows)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
