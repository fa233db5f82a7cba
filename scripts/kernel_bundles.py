"""The TPU compiler's own schedule of the packed flash attention kernels,
read without a chip.

    JAX_PLATFORMS=cpu python scripts/kernel_bundles.py --batch 8 --seq 1024 \
        --width 1024 --heads 16 [--bwd] [--block 512]
    JAX_PLATFORMS=cpu python scripts/kernel_bundles.py --paged-chunk 8 \
        --batch 256 --seq 33536 --width 16384 --heads 128 --block 256 \
        [--window 4096]

Compiles `flash_attention_packed` (or, with `--paged-chunk KV_HEADS`, the
paged chunk kernel for a chunk of `--batch` rows over a cache of `--seq`
rows in blocks of `--block`: `cmdap-serve-agentmix`'s shapes above) for a
described v5e (as tests/test_chip_compile.py does) with libtpu's LLO dump
on, and prints, for
every Pallas kernel in the program, the VLIW bundles of its final schedule
by loop depth (depth 1: a grid step; deeper: the loops inside the body) with
what fills them: MXU pushes, result pops, VPU and EUP operations, vector
loads and stores, cross-lane (XLU) operations. A bundle issues in one cycle
and a v5e bundle holds 4 MXU, 4 VALU, 1 EUP, 3 load, 1 store and 3 XLU
slots, so a loop whose stores equal its bundles is store-bound (spills) and
one whose bundles equal the LHS rows its matmuls stream is MXU-bound.
Counts are static: multiply a depth by its trip count yourself. A time
comes only from a chip run (PERF.md section 6, PR 40, has both side by
side). The dump aborts the compiling process after the files are written,
so the compile runs in a child.
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import os, sys
os.environ["LIBTPU_INIT_ARGS"] = "--xla_jf_dump_to={dump} --xla_jf_dump_llo_text=true"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {repo!r})
import importlib, jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
jax.default_backend = lambda: "tpu"
fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
x = jax.ShapeDtypeStruct(({batch}, {seq}, {width}), jnp.bfloat16,
                         sharding=SingleDeviceSharding(topo.devices[0]))
def attend(q, k, v):
    return fa.flash_attention_packed(q, k, v, num_heads={heads}, causal=True,
                                     block_q={block}, block_k={block})
if {bwd}:
    fn = jax.value_and_grad(lambda *a: attend(*a).astype(jnp.float32).sum(),
                            argnums=(0, 1, 2))
else:
    fn = lambda *a: fa._flash_packed_vjp_fwd(*a, {heads}, True,
                                             ({width} // {heads}) ** -0.5,
                                             {block}, {block})[0]
if {paged_chunk}:
    s = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=x.sharding)
    d, pages = {width} // {heads}, {seq} // {block}
    pool = s(pages + 1, {block}, {paged_chunk} * d)
    jax.jit(lambda *a: fa.paged_flash_chunk_attention(
        *a, num_heads={heads}, num_kv_heads={paged_chunk},
        **(dict(window={window}) if {window} else {{}}))).lower(
        s({batch}, 1, {width}), pool, pool, s(pages, dtype=jnp.int32),
        s({batch}, dtype=jnp.int32)).compile()
else:
    jax.jit(fn).lower(x, x, x).compile()
"""

BUNDLE = re.compile(r"^\s*(?:0x[0-9a-f]+|\d+)\s+(?:[A-Z]{2})?:?\s*(>*)\s*\{(.*)\}")
SLOTS = (("vst", "store"), ("vld", "load"), ("vpop", "pop"), ("vmat", "mxu"),
         ("vpow2", "eup"), ("vrcp", "eup"), ("vlog2", "eup"))


def slot(op: str) -> str | None:
    for prefix, name in SLOTS:
        if op.startswith(prefix):
            return name
    if "xlane" in op or "xlu" in op:
        return "xlu"
    return "valu" if op.startswith("v") else None


def segments(path: str):
    """[[depth, bundles, Counter(slot -> ops)], ...] in program order."""
    out, depth = [], None
    for line in open(path):
        m = BUNDLE.match(line)
        if not m:
            continue
        if len(m.group(1)) != depth:
            depth = len(m.group(1))
            out.append([depth, 0, collections.Counter()])
        out[-1][1] += 1
        for ins in m.group(2).split(";;"):
            op = (re.search(r"=\s*([a-z][\w.]*)", ins)
                  or re.match(r"\s*([a-z][\w.]*)", ins))
            if op and slot(op.group(1)):
                out[-1][2][slot(op.group(1))] += 1
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--block", type=int, default=512)
    ap.add_argument("--bwd", action="store_true")
    ap.add_argument("--paged-chunk", type=int, default=0, metavar="KV_HEADS")
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--min-bundles", type=int, default=20)
    opts = ap.parse_args()
    with tempfile.TemporaryDirectory() as dump:
        child = subprocess.run(
            [sys.executable, "-c",
             CHILD.format(dump=dump, repo=REPO, **vars(opts))],
            capture_output=True, text=True)
        files = sorted(p for p in glob.glob(dump + "/*-final_bundles.txt")
                       if "schedule-analysis" not in p
                       and "flash_attention" in p)
        if not files:
            print(child.stderr[-3000:], file=sys.stderr)
            return 1
        for path in files:
            name = re.search(r"(flash_attention\w*?)_*\.", path).group(1)
            segs = segments(path)
            print(f"{name}: {sum(s[1] for s in segs)} bundles")
            for depth, n, ops in segs:
                if n >= opts.min_bundles:
                    print(f"  depth {depth}: {n:5d} bundles  " + "  ".join(
                        f"{k} {v}" for k, v in sorted(ops.items())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
