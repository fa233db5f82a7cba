"""Whether a change left the accepted serving cells' attention layers as they
were, instruction for instruction, without a chip:

    JAX_PLATFORMS=cpu python scripts/layer_text_compare.py <parent tree> <change tree>

Each tree (a checkout: `git archive <commit> | tar -x -C <dir>`) compiles,
in a process of its own, the paged attention layer of `c13b-serve-chat`,
`solar2-serve-reason`, `mimo2f-serve-longdoc` (global and window) and
`cmdap-serve-agentmix` (global and window) at the cell's real widths for a
described v5e, the slots' rows alone and with a prefill chunk riding as
rows, as tests/test_chip_compile.py does. The two compiled texts are held
line against line with what names a source line left out, and each Mosaic
kernel's module is parsed and printed without its locations and held
likewise. Nothing runs: this says two programs are the same program, never
how fast either is (the recipe of PR 53's check, PERF.md section 6).
"""

from __future__ import annotations

import base64
import json
import os
import re
import subprocess
import sys
import tempfile

TABLES = re.compile(r"^(\d+ |FileNames|FunctionNames|FileLocations|"
                    r"StackFrames)")
STRIP = re.compile(r", metadata=\{[^}]*\}")
KERNEL = re.compile(r"%(\S+) = .*backend_config=(\{.*\})\s*$")


def compile_layers(tree: str, out: str) -> None:
    """In the child: the layers of `tree` compiled, a file a case."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, tree)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"  # the kernels leave interpret mode
    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    import flexflow_tpu
    from flexflow_tpu.fftype import DataType, OperatorType as OT
    from flexflow_tpu.ops import inc_attention as inc
    from flexflow_tpu.ops.attention import AttentionFrontEnd
    from flexflow_tpu.ops.base import OpContext, get_op_def

    assert os.path.realpath(flexflow_tpu.__file__).startswith(
        os.path.realpath(tree)), flexflow_tpu.__file__
    cases = {  # front end, max_seq, block, blocks, slots, table width, chunk
        "c13b": (AttentionFrontEnd(2048, 16), 640, 16, 641, 16, 40, 128),
        "solar2": (AttentionFrontEnd(
            4096, 64, use_bias=False, num_kv_heads=8, head_size=128,
            output_gate=True), 4352, 256, 2200, 128, 17, 256)}
    for kind in ("global", "window"):
        w = kind == "window"
        cases[f"mimo2f_{kind}"] = (AttentionFrontEnd(
            4096, 64, use_bias=False, rope_theta=1e4 if w else 5e6,
            num_kv_heads=8 if w else 4, head_size=192, v_head_size=128,
            rope_dim=64, window=128 if w else 0, sink=w, value_scale=0.707),
            33536, 128, 512 if w else 5400, 32, 262, 256)
        cases[f"cmdap_{kind}"] = (AttentionFrontEnd(
            4096, 128, use_bias=False, rope_theta=5e4 if w else 0.0,
            num_kv_heads=8, head_size=128, window=4096 if w else 0,
            rope_interleaved=w), 33536, 256, 768 if w else 1800, 32, 131,
            256)
    op = get_op_def(OT.OP_PAGED_INC_MULTIHEAD_ATTENTION)
    for name, (front, max_seq, bs, blocks, slots, width, chunk) in (
            cases.items()):
        p = inc.PagedIncMultiHeadAttentionParams(
            front, max_seq, bs, blocks, impl="flash",
            cache_dtype=DataType.DT_BFLOAT16, chunk_from=slots)

        def layer(weights, x, positions, table, p=p):
            (y,), state = op.forward(p, [x, positions, table], weights, None,
                                     OpContext(training=False, mesh=None))
            return y, state

        for rows in (slots, slots + chunk):
            d = front.embed_dim
            specs = op.weights(p, [(rows, 1, d), (rows, 1), (rows, width)])
            weights = {w.name: s(w.shape, jnp.float32 if w.name == "sink"
                                 else jnp.bfloat16) for w in specs}
            text = jax.jit(layer, donate_argnums=(0,)).lower(
                weights, s((rows, 1, d)), s((rows, 1), jnp.int32),
                s((rows, width), jnp.int32)).compile().as_text()
            with open(os.path.join(out, f"{name}_{rows}.txt"), "w") as f:
                f.write(text)


def kernel_modules(path: str) -> dict:
    """{kernel instruction: (its Mosaic module printed without locations,
    the rest of its config)} of a compiled text."""
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir

    found = {}
    for line in open(path):
        m = KERNEL.search(line)
        if not m or "tpu_custom_call" not in line:
            continue
        config = json.loads(m.group(2))["custom_call_config"]
        with jmlir.make_ir_context() as ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(config.pop("body")))
            found[m.group(1)] = (
                module.operation.get_asm(enable_debug_info=False),
                json.dumps(config, sort_keys=True))
    return found


def instructions(path: str) -> list:
    return [re.sub(r"backend_config=\{.*$", "", STRIP.sub("", line))
            for line in open(path) if not TABLES.match(line)]


def main() -> int:
    if len(sys.argv) == 4 and sys.argv[1] == "--compile":
        compile_layers(sys.argv[2], sys.argv[3])
        return 0
    parent, change = sys.argv[1:3]
    differ = 0
    with tempfile.TemporaryDirectory() as tmp:
        dirs = []
        for tree in (parent, change):
            dirs.append(os.path.join(tmp, str(len(dirs))))
            os.makedirs(dirs[-1])
            subprocess.run([sys.executable, __file__, "--compile",
                            os.path.abspath(tree), dirs[-1]], check=True)
        for name in sorted(os.listdir(dirs[0])):
            a, b = (os.path.join(d, name) for d in dirs)
            same = (instructions(a) == instructions(b)
                    and kernel_modules(a) == kernel_modules(b))
            differ += not same
            print(f"{name[:-4]}: {len(instructions(a))} lines, kernels "
                  f"{sorted(kernel_modules(a))}: "
                  f"{'the same program' if same else 'DIFFERS'}")
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
