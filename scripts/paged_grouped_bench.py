"""The paged decode kernel alone, on the chip, at a cell's shapes.

`--cell cmdap` (`cmdap-serve-agentmix`): 32 rows of 128 query heads of 128
on 8 KV heads that read the cell's 32 histories (2-31 k rows, 354 k in all)
whole, as the global layer does, or their last 4,096 rows, as a window layer
does, from pools of 128- or 256-row blocks of 1,024 bf16 lanes.

`--cell mimo2f` (`mimo2f-serve-longdoc`): 32 rows of 64 query heads of 192
(values of 128) that read the cell's 32 histories (8-32 k rows, 573 k in
all) whole on 4 KV heads, as a global layer does, or their last 128 rows on
8 KV heads under a sink, as a window layer does, from pools of 128-row
blocks.

`--cell c13b` (`c13b-serve-chat`): 16 rows of 16 heads of 128, ungrouped,
that read the cell's kind of context (a prompt of 32-512 tokens, log-uniform,
and 0-128 of a reply: 1-5 rounds of 8 pages of 16 rows a row) from a table
40 pages wide, 24 calls in one program as a step's 24 layers make them.

    chiprun -- python scripts/paged_grouped_bench.py [--cell mimo2f]
        [--blocks 128,256] [--pages 1,2,4,8] [--chain 24]

Prints, for each block size and kind, the pages and the K + V bytes of a DMA
round, the kernel's time a call, a round and each 128 rows, its share of the
bytes floor (the rows a call attends x a row's K + V bytes over the chip's
HBM bandwidth) and of the MXU's peak (the block-diagonal query spends it
once a KV head over: counted as the 2 x heads x (key + value head) a row the
mathematics needs), and its largest difference from the einsum reference on
the same operands. Without `--pages` a round is what the kernel's own rule
gives (`_paged_round_pages`); with it, each listed number of pages a round
is run in turn, past the rule, where two rounds fit the kernel's VMEM. A
number from here is a kernel's, not a step's. Needs a TPU.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# heads, key and value head sizes, the histories' strata and the rows a
# request adds to one (a number, or the bounds of a draw a row), the table's
# rows, the block sizes run by default, each kind's (KV heads, window, sink),
# and, where they are not 32 and 1, the call's rows and the calls chained in
# one program
CELLS = {
    "c13b": dict(
        heads=16, d=128, d_v=128, history=(32, 512), more=(0, 128),
        max_seq=640, blocks="16", kinds={"plain": (16, 0, False)},
        rows=16, chain=24),
    "cmdap": dict(
        heads=128, d=128, d_v=128, history=(2048, 32768), more=300,
        max_seq=33536, blocks="128,256",
        kinds={"global": (8, 0, False), "window": (8, 4096, False)}),
    "mimo2f": dict(
        heads=64, d=192, d_v=128, history=(8192, 32768), more=200,
        max_seq=33536, blocks="128",
        kinds={"global": (4, 0, False), "window": (8, 128, True)}),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="cmdap", choices=sorted(CELLS))
    ap.add_argument("--blocks", default=None)
    ap.add_argument("--pages", default=None,
                    help="pages a round to run in turn (the rule's alone "
                         "by default)")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--chain", type=int, default=None,
                    help="calls in one program, each one's queries taken "
                         "from the last one's output (the cell's by default)")
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
    cell = CELLS[opts.cell]
    heads, d, d_v = cell["heads"], cell["d"], cell["d_v"]
    lo, hi = cell["history"]
    rows = cell.get("rows", 32)
    chain = opts.chain or cell.get("chain", 1)
    rng = np.random.default_rng(0)
    lengths = traffic.quantiles(
        {"dist": "log_uniform", "min": lo, "max": hi}, rows)
    more = cell["more"]
    if isinstance(more, tuple):
        # slots at every stage of a reply, in no order of length
        lengths = rng.permutation(lengths)
        more = rng.integers(more[0], more[1] + 1, rows)
    lengths = [int(x) for x in np.asarray(lengths) + more]
    q = jnp.asarray(rng.normal(size=(rows, 1, heads * d)), jnp.bfloat16)
    n = jnp.asarray(lengths, jnp.int32)
    scale = 1.0 / math.sqrt(d)

    def timed(fn, *args):
        # (the pools come as arguments: closed over, a jit holds their
        # hundreds of MB as constants and compiles for a minute)
        def chained(table, n, q, *rest):
            # `chain` calls in one program, as a step's layers are: a
            # call's queries wait for the call before it
            for _ in range(chain):
                out = fn(table, n, q, *rest)
                q = q + (out[:, :, :1] * 0).astype(q.dtype)
            return out

        run = jax.jit(chained)
        run(*args).block_until_ready()
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            outs = [run(*args) for _ in range(opts.calls)]
            outs[-1].block_until_ready()
            took.append((time.perf_counter() - t0) / opts.calls / chain)
        return float(np.median(took))

    for bs in map(int, (opts.blocks or cell["blocks"]).split(",")):
        width = cell["max_seq"] // bs
        blocks = sum(-(-x // bs) for x in lengths) + 1
        table = np.zeros((rows, width), np.int32)
        free = rng.permutation(np.arange(1, blocks))
        at = 0
        for r, x in enumerate(lengths):
            need = -(-x // bs)
            table[r, :need] = free[at:at + need]
            at += need
        table = jnp.asarray(table)
        for kind, (kv, w, has_sink) in cell["kinds"].items():
            pk, pv = (jax.jit(lambda key, e=e: jax.random.normal(
                key, (blocks, bs, e), jnp.bfloat16))(jax.random.key(i))
                for i, e in ((1, kv * d), (2, kv * d_v)))
            sink = (jnp.asarray(rng.normal(size=(heads,)) * 3, jnp.float32)
                    if has_sink else None)
            k_row, v_row = kv * d * 2, kv * d_v * 2
            row_bytes = k_row + v_row
            operands = (table, n, q, pk, pv, sink)
            # (a row at a time: the reference lays a row's whole history
            # out once a query head)
            want = jnp.concatenate([fa.paged_decode_attention_reference(
                q[r:r + 1], pk, pv, table[r:r + 1], (n - 1)[r:r + 1, None],
                num_heads=heads, num_kv_heads=kv, window=w, sink=sink,
                scale=scale).astype(jnp.float32) for r in range(4)])
            rule = fa._paged_round_pages(bs, k_row, v_row, width, w)
            for pages in (map(int, opts.pages.split(",")) if opts.pages
                          else (rule,)):
                tag = (f"[bench] {opts.cell}, blocks of {bs}, {kind}, "
                       f"{pages} pages a round"
                       + (" (the rule's)" if pages == rule else ""))
                if 2 * pages * bs * row_bytes > fa._PAGED_ROUND_VMEM:
                    print(f"{tag}: two rounds pass the kernel's VMEM")
                    continue

                def call(*operands, pages=pages):
                    return fa._paged_decode_call(
                        *operands, num_heads=heads, scale=scale, pages=pages,
                        interpret=False, window=w)

                got = call(*operands)[:4].astype(jnp.float32)
                err = float(jnp.max(jnp.abs(got - want))
                            / jnp.max(jnp.abs(want)))
                s = timed(call, *operands)
                per = pages * bs
                # the rounds the kernel walks: from the round that holds a
                # window's first key to the one that holds the last key
                rounds = sum(-(-x // per) - (max(x - w, 0) // per if w else 0)
                             for x in lengths)
                read = sum(min(x, w) if w else x for x in lengths)
                flops = read * 2 * heads * (d + d_v)
                floor = read * row_bytes / peaks["hbm_bytes_per_s"]
                print(f"{tag} of {per * row_bytes} B: {s * 1e3:.3f} ms a "
                      f"call over {read} rows in {rounds} rounds, "
                      f"{s * 1e6 / rounds:.3f} us a round, "
                      f"{s * 1e6 * 128 / read:.3f} us each 128 rows; "
                      f"{100 * floor / s:.1f} % of the bytes floor, "
                      f"{100 * flops / s / peaks['bf16_flops_per_s']:.1f} % "
                      f"of the MXU's peak; off the reference by {err:.5f} "
                      f"of its largest (4 rows)")
            del pk, pv, operands  # before the next kind's pools are made
    return 0


if __name__ == "__main__":
    sys.exit(main())
