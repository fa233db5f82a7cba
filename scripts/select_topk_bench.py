"""A decoding row's top-2,048 alone, on the chip, at the shapes of the two
cells that decode under a learned selection: 16 rows of 33,536 index scores
in six layers (`keye2-serve-mediaqa`) and of 33,280 in five
(`dsv32-serve-sessions`), scored as the cells score them (sums of a few
ReLUs: exact zeros, some in runs and one row's across the k-th place; NEG
behind a row's length of 8-33 k).

    chiprun -- python scripts/select_topk_bench.py [--k 2048] [--reps 8]

Prints, for each shape, the device ms a layer of `lax.top_k` (a full sort,
what `select_topk` called until PR 47), of `select_topk`, and of its two
halves (`_top_mask`: the k-th score by bisection and the ties; `_compact`:
the mask into positions), and whether every row's set is `lax.top_k`'s. A
call runs every layer `--reps` times in one program, so that its dispatch
hides behind the device. A number from here is a function's, not a step's;
a later kernel (a bisection inside `paged_index_scores`, where a row's
scores lie whole in VMEM) is measured against these. Needs a TPU.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = {"keye2-serve-mediaqa": (6, 33536),
          "dsv32-serve-sessions": (5, 33280)}
ROWS = 16


def scores(layers: int, S: int, seed: int):
    """(layers, ROWS, S) float32 index scores and the rows' lengths."""
    import numpy as np

    from flexflow_tpu.kernels.sparse_selection import NEG

    rng = np.random.default_rng(seed)
    lengths = np.exp(np.linspace(np.log(8555), np.log(S - 100),
                                 ROWS)).astype(int)
    heads = rng.normal(size=(layers, ROWS, 4, S)).astype(np.float32)
    weights = np.abs(rng.normal(size=(layers, ROWS, 4, 1))).astype(np.float32)
    index = (np.maximum(heads, 0) * weights).sum(axis=2)  # 1 in 16 is 0.0
    index[:, :, S // 30:S // 10] = 0.0  # a run of exact zeros
    index[:, 3] = np.where(rng.random((layers, S)) < 0.97, 0.0, index[:, 3])
    seen = np.arange(S)[None, None, :] < lengths[None, :, None]
    return np.where(seen, index, NEG).astype(np.float32), lengths


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--k", type=int, default=2048)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=1)
    opts = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.kernels import sparse_selection as sel

    if jax.devices()[0].platform != "tpu":
        sys.exit("select_topk_bench: needs a TPU")
    k = opts.k

    def every_layer(fn):
        """fn over every layer, `--reps` times in one program: two rolled
        loops, the input made to depend on the outer one's counter so that
        no repetition is hoisted out."""
        def run(stack, zero):
            def rep(count, _):
                def layer(_, x):
                    return None, fn(jnp.where(count >= 0, x,
                                              jnp.zeros_like(x)))
                return count + 1, jax.lax.scan(layer, None, stack)[1]
            return jax.lax.scan(rep, zero, None, length=opts.reps)[1]
        return jax.jit(run)

    def timed(fn, stack):
        """(device ms a layer, the first repetition's outputs)."""
        run, zero = every_layer(fn), jnp.zeros((), jnp.int32)
        out = jax.block_until_ready(run(stack, zero))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            outs = [run(stack, zero) for _ in range(opts.calls)]
            jax.block_until_ready(outs[-1])
            took.append((time.perf_counter() - t0) / opts.calls)
        ms = float(np.median(took)) * 1e3 / opts.reps / stack.shape[0]
        return ms, jax.tree.map(lambda a: np.asarray(a[0]), out)

    def top_k(index):
        vals, picked = jax.lax.top_k(index, k)
        return picked.astype(jnp.int32), vals > sel.NEG / 2

    def sets(picked, valid):
        return [frozenset(p[v].tolist())
                for p, v in zip(picked.reshape(-1, k), valid.reshape(-1, k))]

    for cell, (layers, S) in SHAPES.items():
        index, lengths = scores(layers, S, opts.seed)
        index = jnp.asarray(index)
        sort_ms, want = timed(top_k, index)
        new_ms, got = timed(lambda x: sel.select_topk(x, k), index)
        mask_ms, masks = timed(lambda x: sel._top_mask(x, k), index)
        compact_ms, _ = timed(lambda m: sel._compact(m, k), jnp.asarray(masks))
        same = sets(*want) == sets(*got)
        prefix = bool((got[1][..., :-1] >= got[1][..., 1:]).all())
        print(f"[bench] {cell}: {ROWS} rows x {S} scores (lengths "
              f"{lengths.min()}-{lengths.max()}), k {k}, ms a layer: "
              f"lax.top_k {sort_ms:.4f}; select_topk {new_ms:.4f} "
              f"(_top_mask {mask_ms:.4f} + _compact {compact_ms:.4f} alone); "
              f"{layers} layers {sort_ms * layers:.3f} -> "
              f"{new_ms * layers:.3f} ms; every row's set is lax.top_k's: "
              f"{same}; valid is a prefix: {prefix}", flush=True)
        if not (same and prefix):
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
