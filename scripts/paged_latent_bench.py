"""The paged latent decode kernel alone, on the chip, at the shapes of
`ms4-serve-longctx`: 16 rows that read histories of 17-63 k latent rows
(the cell's sixteen strata) from a pool of 2,560 blocks of 256 rows of 384
bf16 lanes, 32 absorbed queries a row.

    chiprun -- python scripts/paged_latent_bench.py [--rounds 1024,2048,4096]

Prints, for each round size, the kernel's time a call, its share of the
bytes floor (the rows' published 640 B and the stored 768 B over the chip's
HBM bandwidth) and of the MXU's peak, and its largest difference from
`paged_latent_decode_reference` on the same operands. A number from here
is a kernel's, not a step's. Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rounds", default="2048")
    ap.add_argument("--calls", type=int, default=20)
    opts = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import traffic
    from flexflow_tpu.kernels import paged_latent_attention as pla

    if jax.devices()[0].platform != "tpu":
        sys.exit("paged_latent_bench: needs a TPU")
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "peaks.json")) as f:
        peaks = json.load(f)[jax.devices()[0].device_kind]
    heads, lanes, latent, row, bs, width, blocks = 32, 384, 256, 320, 256, \
        260, 2560
    lengths = traffic.quantiles(
        {"dist": "log_uniform", "min": 16384, "max": 65536}, 16)
    rng = np.random.default_rng(0)
    rows = len(lengths)
    q = np.zeros((rows, heads, lanes), np.float32)
    q[..., :row] = rng.normal(size=(rows, heads, row))
    table = np.zeros((rows, width), np.int32)
    free = rng.permutation(np.arange(1, blocks))
    at = 0
    for r, n in enumerate(lengths):
        need = -(-n // bs)
        table[r, :need] = free[at:at + need]
        at += need
    pool = jax.jit(lambda key: jnp.pad(
        jax.random.normal(key, (blocks, bs, row), jnp.bfloat16),
        ((0, 0), (0, 0), (0, lanes - row))))(jax.random.key(1))
    q, table = jnp.asarray(q, jnp.bfloat16), jnp.asarray(table)
    positions = jnp.asarray(lengths, jnp.int32) - 1
    scale = 128 ** -0.5 * (0.1 * np.log(128) + 1) ** 2 / 16  # keep p spread

    def timed(fn):
        """Seconds a call: `--calls` calls dispatched back to back and one
        wait, so that the host's dispatch hides behind the device."""
        fn = jax.jit(fn)
        fn().block_until_ready()
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            outs = [fn() for _ in range(opts.calls)]
            outs[-1].block_until_ready()
            took.append((time.perf_counter() - t0) / opts.calls)
        return float(np.median(took)), float(np.min(took))

    want = pla.paged_latent_decode_reference(
        q, pool, table, positions, latent_dim=latent, scale=scale)
    ref_s, _ = timed(lambda: pla.paged_latent_decode_reference(
        q, pool, table, positions, latent_dim=latent, scale=scale))
    read = sum(lengths)
    print(f"[bench] 16 rows over {read} latent rows; the reference (XLA's "
          f"gather of every page and two einsums) {ref_s * 1e3:.3f} ms a "
          f"call")
    for rounds in map(int, opts.rounds.split(",")):
        pla._ROUND_ROWS = rounds
        pla._paged_latent_call.clear_cache()
        got = pla.paged_latent_decode(q, pool, table, positions,
                                      latent_dim=latent, scale=scale)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32)))
                    / jnp.max(jnp.abs(want.astype(jnp.float32))))
        med, least = timed(lambda: pla.paged_latent_decode(
            q, pool, table, positions, latent_dim=latent, scale=scale))
        flops = read * 2 * heads * (row + latent)
        print(f"[bench] rounds of {rounds} keys: {med * 1e3:.3f} ms a call "
              f"(least {least * 1e3:.3f}); {100 * read * 640 / peaks['hbm_bytes_per_s'] / med:.1f} "
              f"% of the published rows' bytes floor, "
              f"{100 * read * 768 / peaks['hbm_bytes_per_s'] / med:.1f} % of "
              f"the stored rows'; {flops / med / 1e12:.1f} TFLOP/s, "
              f"{100 * flops / med / peaks['bf16_flops_per_s']:.1f} % of the "
              f"MXU's peak; off the reference by {err:.5f} of its largest")
    return 0


if __name__ == "__main__":
    sys.exit(main())
