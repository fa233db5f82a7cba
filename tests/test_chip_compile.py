"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

Interpret mode on the CPU cannot show what the TPU's compiler refuses: a
block shape Mosaic cannot tile, a kernel GSPMD cannot partition. The TPU
compiler is installed with JAX and compiles for a chip that is described,
not attached (`topologies.get_topology_desc`), so the main path's kernels
are compiled here at real widths: about two seconds each, no chip time.
Nothing runs — these say a program lowers and which kernels it holds,
never how fast it is.

The code under test asks `jax.default_backend()` to choose between Mosaic
and interpret mode and would take its CPU branch here; the `tpu` fixture
steers it (in the test, not through an option of the program). The
persistent compilation cache is off around the module: such a compile can
be written to it but not read back without a chip.
"""

import collections
import importlib
import os
import re
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from flexflow_tpu.kernels import layer_norm
from flexflow_tpu.kernels.dispatch import KernelFallbackWarning, pallas_kernels

fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu(topology, monkeypatch):
    """The described devices, with the code under test told it is on a
    TPU so that it lowers through Mosaic."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return topology.devices


def _kernels(fn, *shapes):
    return pallas_kernels(jax.jit(fn).lower(*shapes).compile().as_text())


def _selection_passes(text, S):
    """How the top-k under `dsa.topk` was compiled: (sorts over S columns,
    `while` loops, counting passes in the loops' bodies). A `lax.top_k` at
    k = 2,048 is a `sort` of (rows, S) floats with their positions; the
    bisection is one `while` a selection whose body holds ONE count over
    the scores, where an unrolled loop would be no `while` and 33 counts."""
    under = [line for line in text.splitlines() if "dsa.topk" in line]
    sorts = [line for line in under
             if re.search(r" sort\(", line) and f",{S}]" in line]
    loops = [line for line in under if re.search(r" while\(", line)]
    counts = [line for line in under if "dsa.topk/while/body" in line
              and re.search(r"= s32\[\d+\]\S* reduce\(", line)]
    return len(sorts), len(loops), len(counts)


def _on(device):
    one = SingleDeviceSharding(device)
    return lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


def _with_grads(fn):
    def run(*args):
        return jax.value_and_grad(
            lambda *a: fn(*a).astype(jnp.float32).sum(),
            argnums=tuple(range(len(args))))(*args)
    return run


@pytest.mark.parametrize("batch,seq,heads,fwd,bwd", [
    (8, 512, 16, "fwd", ["bwd_packed_grouped"]),
    (1, 4096, 16, "fwd", ["bwd_packed_grouped"]),
    # the training cells' shapes: gpt2-medium's 16 heads of 64 over 1,024
    # tokens; heads of 128 over c13b's 2,048 and OLMoE's 4,096
    (8, 1024, 16, "fwd", ["bwd_packed_grouped"]),
    (1, 2048, 8, "fwd", ["bwd_packed"]),
    (4, 4096, 8, "fwd", ["bwd_packed"]),
    # the last sequence whose K and V stay resident in the forward (4 x
    # 2 MiB a 128-lane group) and whose dq does in the backward
    (1, 8192, 16, "fwd", ["bwd_packed_grouped"]),
    (1, 8192, 8, "fwd", ["bwd_packed"]),
    # past both VMEM gates: kv blocks streamed through the forward's grid,
    # the dq and the dkv kernel (8 + 2 x 4 MiB of dq a head pair)
    (1, 16384, 16, "fwd_streamed",
     ["bwd_dq_packed_grouped", "bwd_dkv_packed_grouped"]),
    (1, 16384, 8, "fwd_streamed", ["bwd_dq_packed", "bwd_dkv_packed"]),
])
def test_packed_flash_fwd_bwd(tpu, batch, seq, heads, fwd, bwd):
    """Attention 1,024 wide on the packed (b, s, h·d) layout, 16 heads of
    64 (lm-base, gpt2-medium) or 8 of 128: the forward that keeps a head
    group's K and V resident and the ONE backward kernel, or the streamed
    forward and the split pair where the sequence does not fit."""
    s = _on(tpu[0])
    x = s((batch, seq, 1024))

    def attend(q, k, v):
        return fa.flash_attention_packed(q, k, v, num_heads=heads,
                                         causal=True)

    kernels = _kernels(_with_grads(attend), x, x, x)
    family = "_packed_grouped" if heads == 16 else "_packed"
    assert kernels == {f"flash_attention_{name}": 1
                       for name in [fwd + family] + bwd}
    # the resident forward's grid has no kv axis and its K / V operand is
    # the whole sequence of one 128-lane group: a return to a kv block a
    # grid step under the old name would pass the check above
    call = re.search(r"grid_mapping=GridMapping\(grid=(\([\d, ]+\)).*?"
                     r"name=flash_attention_fwd\w*",
                     str(jax.make_jaxpr(attend)(x, x, x)), re.S).group(0)
    blocks = [tuple(map(int, re.findall(r"block_size=(\d+)", block)))
              for block in re.findall(r"BlockMapping\(block_shape=\((.*?)\)\)",
                                      call)]
    steps = seq // 512
    if fwd == "fwd":
        assert f"grid=({batch}, 8, {steps})" in call
        assert blocks[:3] == [(1, 512, 128)] + 2 * [(1, seq, 128)]
    else:
        assert f"grid=({batch}, 8, {steps}, {steps})" in call
        assert blocks[:3] == 3 * [(1, 512, 128)]


def test_fused_layer_norm_fwd_bwd(tpu):
    s = _on(tpu[0])
    kernels = _kernels(_with_grads(
        lambda x, sc, b: layer_norm.fused_layer_norm_or_none(
            x, sc, b, (1,), 1e-5)),
        s((4096, 1024)), s((1024,), jnp.float32), s((1024,), jnp.float32))
    assert kernels == {"layer_norm_fwd": 1, "layer_norm_bwd": 1}


def test_grouped_matmul_fwd_bwd_at_olmoe_widths(tpu):
    """The expert layer's up and down projections at OLMoE's widths, 64
    experts over 131,072 sorted rows: the Pallas grouped matmul forward,
    for dX and transposed for dW; and float32 operands take the reference
    and say so."""
    from flexflow_tpu.kernels import grouped_matmul as gm

    s = _on(tpu[0])
    sizes = s((64,), jnp.int32)
    for k, n in ((2048, 1024), (1024, 2048)):
        kernels = _kernels(
            lambda x, w, g: jax.value_and_grad(
                lambda x, w: gm.grouped_matmul(x, w, g).astype(
                    jnp.float32).sum(), argnums=(0, 1))(x, w),
            s((131072, k)), s((64, k, n)), sizes)
        assert kernels == {"gmm": 2, "tgmm": 1}
    with pytest.warns(KernelFallbackWarning, match="not bfloat16"):
        kernels = _kernels(gm.grouped_matmul, s((1024, 256), jnp.float32),
                           s((8, 256, 128), jnp.float32), s((8,), jnp.int32))
    assert not kernels


@pytest.mark.parametrize("rows", [16, 272])
def test_sparse_latent_attention_at_deepseek_v32_widths(tpu, rows):
    """A layer's indexer scores, exact top-2,048 and selected-row
    attention over the paged latent pool of `dsv32-serve-sessions` (1,536
    blocks of 256, page tables 130 wide): 16 decoding rows, and the same
    with a 256-token chunk under one page-table row (its selection a mask
    by bisection, its attention dense over the shared context). The 16
    rows' scores are the paged indexer kernel's, once a layer whatever
    rides beside them; the rest is XLA's gather, matmuls and one rolled
    loop a selection (no sort of the 33,280 scores: `_selection_passes`),
    and what the step needs beside its arguments stays under 2 GB."""
    from flexflow_tpu.kernels import sparse_latent_attention as sla

    s = _on(tpu[0])
    n = 16

    def layer(qi, wt, q, pool_i, pool_c, table, pos):
        index = sla.index_scores_rows(qi[:n], wt[:n], pool_i, table[:n],
                                      pos[:n])
        with jax.named_scope("dsa.topk"):  # as ops/latent_attention.py does
            sel, valid = sla.select_topk(index, 2048)
        out = sla.attend_selected(q[:n], pool_c, table[:n], sel, valid,
                                  latent_dim=512, scale=0.1)
        if rows > n:
            index = sla.index_scores_chunk(qi[n:], wt[n:], pool_i, table[n],
                                           pos[n:])
            with jax.named_scope("dsa.topk"):
                mask = sla.selection_mask(index, 2048)
            out = jnp.concatenate([out, sla.attend_chunk(
                q[n:], pool_c, table[n], mask, pos[n:], latent_dim=512,
                scale=0.1)], 0)
        return out

    compiled = jax.jit(layer).lower(
        s((rows, 64, 128)), s((rows, 64), jnp.float32), s((rows, 128, 576)),
        s((1536, 256, 128)), s((1536, 256, 576)), s((rows, 130), jnp.int32),
        s((rows,), jnp.int32)).compile()
    assert pallas_kernels(compiled.as_text()) == {"paged_index_scores": 1}
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    selections = 1 + (rows > n)  # the slots' rows, the chunk's mask
    assert _selection_passes(compiled.as_text(), 33280) == (
        0, selections, selections)


@pytest.mark.parametrize("rows", [16, 144, 272])
def test_grouped_matmul_at_a_serving_steps_rows(tpu, rows):
    """16 held experts of DeepSeek-V3.2's widths at a decode step's 8
    assignments a row: the rows are padded to the kernel's row tile, so
    the Pallas grouped matmul serves every step shape."""
    from flexflow_tpu.kernels import grouped_matmul as gm

    s = _on(tpu[0])
    for k, n_out in ((7168, 2048), (2048, 7168)):
        kernels = _kernels(gm.grouped_matmul, s((rows * 8, k)),
                           s((16, k, n_out)), s((16,), jnp.int32))
        assert kernels == {"gmm": 1}


@pytest.mark.parametrize("rows", [128, 128 + 32, 128 + 256])
def test_grouped_matmul_at_solar_open2_widths(tpu, rows):
    """40 held experts 1,280 wide at 8 assignments a row, 128 slots alone
    and with a chunk riding as rows: tiles of 640 divide 1,280 and the
    Pallas grouped matmul serves every step shape."""
    from flexflow_tpu.kernels import grouped_matmul as gm

    s = _on(tpu[0])
    for k, n_out in ((4096, 1280), (1280, 4096)):
        kernels = _kernels(gm.grouped_matmul, s((rows * 8, k)),
                           s((40, k, n_out)), s((40,), jnp.int32))
        assert kernels == {"gmm": 1}


@pytest.mark.parametrize("rows", [128, 128 + 256])
def test_grouped_paged_decode_at_solar_open2_widths(tpu, rows):
    """`solar2-serve-reason`'s softmax layer: 64 query heads over 8 KV
    heads of 128, a pool of 1,600 blocks of 256 rows 1,024 wide, page
    tables 17 wide; 128 slots, and the same with a chunk of 256 riding as
    rows. The grouped body of the paged kernel serves both."""
    s = _on(tpu[0])
    pool = s((1600, 256, 8 * 128))
    kernels = _kernels(
        lambda q, pk, pv, tbl, n: fa.paged_flash_decode_attention(
            q, pk, pv, tbl, n, num_heads=64, num_kv_heads=8),
        s((rows, 1, 64 * 128)), pool, pool, s((rows, 17), jnp.int32),
        s((rows,), jnp.int32))
    assert kernels == {"flash_attention_paged_decode_grouped": 1}


def _paged_attention_layer(front, max_seq, block_size, blocks, slots):
    """(forward over (x, positions, page_table) -> y, weight shapes) of
    the paged attention op as a bf16 decode graph built for `slots` holds
    it: rows past the slots are one chunk (`chunk_from`)."""
    from flexflow_tpu.fftype import DataType, OperatorType as OT
    from flexflow_tpu.ops import inc_attention as inc
    from flexflow_tpu.ops.base import OpContext, get_op_def

    p = inc.PagedIncMultiHeadAttentionParams(
        front, max_seq, block_size, blocks, impl="flash",
        cache_dtype=DataType.DT_BFLOAT16, chunk_from=slots)
    op = get_op_def(OT.OP_PAGED_INC_MULTIHEAD_ATTENTION)

    def layer(weights, x, positions, page_table):
        (y,), state = op.forward(p, [x, positions, page_table], weights,
                                 None, OpContext(training=False, mesh=None))
        return y, state

    return p, op, layer


@pytest.mark.parametrize("rows", [16, 16 + 128, 16 + 256])
def test_grouped_matmul_at_keye_vl2_widths(tpu, rows):
    """All 128 experts, 768 wide, at 8 assignments a row, 16 slots alone
    and with a question's chunk riding as rows: tiles of 768 divide 768
    (six 128s) and the Pallas grouped matmul serves every step shape."""
    from flexflow_tpu.kernels import grouped_matmul as gm

    s = _on(tpu[0])
    for k, n_out in ((2048, 768), (768, 2048)):
        kernels = _kernels(gm.grouped_matmul, s((rows * 8, k)),
                           s((128, k, n_out)), s((128,), jnp.int32))
        assert kernels == {"gmm": 1}


@pytest.mark.parametrize("rows", [16, 16 + 256])
def test_selected_grouped_attention_at_keye_vl2_widths(tpu, rows):
    """A layer of `keye2-serve-mediaqa` as the decode graph runs it: 32
    query heads over 4 KV heads of 128 with per-head QK-norm and RoPE, the
    indexer (16 heads of 64) over a pool of 1,440 blocks of 256 rows,
    [k ; v] 1,024 wide beside an indexer key of 64, page tables 131 wide;
    16 decoding rows that take 2,048 and gather them, and the same with a
    chunk of 256 under one page-table row (its selection a mask by
    bisection, its attention dense over the shared context). The compiled
    layer holds the paged indexer kernel once, for the slots' rows: the
    count that says the mechanism engaged; the rest is XLA's gather,
    matmuls and one rolled loop a selection (no sort of the 33,536 scores
    under `dsa.topk`: `_selection_passes`), and what the layer needs beside
    its arguments stays under 2 GB."""
    from flexflow_tpu.ops.attention import AttentionFrontEnd, Indexer

    s = _on(tpu[0])
    front = AttentionFrontEnd(
        2048, 32, use_bias=False, rope_theta=1e7, qk_norm="head",
        qk_norm_eps=1e-6, num_kv_heads=4, head_size=128,
        index=Indexer(n_heads=16, head_dim=64, topk=2048, rope_dim=64))
    p, op, layer = _paged_attention_layer(front, 33536, 256, 1440, slots=16)
    assert p.selected == 2048
    # the indexer's key of 64 lies in a lane-aligned row of 128
    assert p.cache_row_widths == {"pool_kv": 1024, "pool_i": 128}
    specs = op.weights(p, [(rows, 1, 2048), (rows, 1), (rows, 131)])
    weights = {w.name: s(w.shape, jnp.int32 if w.name == "sel_rows"
                         else jnp.bfloat16) for w in specs}
    # the pools are donated, as the engine's step donates its state
    compiled = jax.jit(layer, donate_argnums=(0,)).lower(
        weights, s((rows, 1, 2048)), s((rows, 1), jnp.int32),
        s((rows, 131), jnp.int32)).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"paged_index_scores": 1}
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    selections = 1 + (rows > 16)  # the slots' rows, the chunk's mask
    assert _selection_passes(text, 33536) == (0, selections, selections)
    # no step copies a pool (a row of 64 lanes made every step copy the
    # indexer pool into the row layout and back)
    import re

    assert not re.findall(r"= bf16\[1440,256,\d+\]\S* copy\(", text)


@pytest.mark.parametrize("heads,width,blocks", [
    (16, 131, 1440),   # keye2-serve-mediaqa: 16 heads of 64 in 128 lanes
    (64, 130, 1408),   # dsv32-serve-sessions: 64 heads of 128
])
def test_paged_index_scores_at_both_sparse_cells_widths(tpu, heads, width,
                                                        blocks):
    """The paged indexer kernel lowers for the v5e at both cells' widths:
    16 rows, blocks of 256 keys in 128-lane bf16 rows, a round's scores
    stored into the row's (1, S) float32 block at a page's lane offset."""
    from flexflow_tpu.kernels import sparse_selection as sel

    s = _on(tpu[0])
    kernels = _kernels(
        sel.index_scores_rows, s((16, heads, 128)),
        s((16, heads), jnp.float32), s((blocks, 256, 128)),
        s((16, width), jnp.int32), s((16,), jnp.int32))
    assert kernels == {"paged_index_scores": 1}


@pytest.mark.parametrize("why,width,block,lanes,call_gate", [
    (r"block_size 16 % 128", 8, 16, 128, None),
    (r"64 lanes", 8, 256, 64, None),
    (r"bytes of VMEM", 1024, 256, 128, None),
    (r"4-device mesh", 8, 256, 128,
     "4-device mesh: kernel not run per shard"),
])
def test_paged_index_scores_refused_takes_the_reference_and_says_so(
        tpu, why, width, block, lanes, call_gate):
    """A geometry `paged_index_gate` refuses (a block that is no whole
    lane tiles, a 64-lane pool row, a table row whose scores do not sit
    in VMEM) and a call the op's own gate refuses (a multi-device mesh)
    take XLA's gather and einsum; on a TPU that is said, not hidden."""
    from flexflow_tpu.kernels import sparse_selection as sel

    s = _on(tpu[0])
    fn = lambda *a: sel.index_scores_rows(*a, call_gate=call_gate)  # noqa: E731
    with pytest.warns(KernelFallbackWarning, match=why):
        kernels = _kernels(
            fn, s((4, 16, lanes)), s((4, 16), jnp.float32),
            s((64, block, lanes)), s((4, width), jnp.int32),
            s((4,), jnp.int32))
    assert not kernels


@pytest.mark.parametrize("dtype,lanes", [
    (jnp.bfloat16, 1024),  # keye2's [k ; v] row as the pool holds it
    (jnp.float32, 1024),
    (jnp.uint32, 512),     # the same row as 32-bit words, two bf16 lanes each
])
def test_one_token_row_of_a_tiled_pool_is_not_a_dma(tpu, dtype, lanes):
    """Why the selected rows' read is XLA's gather and no DMA a row inside
    a kernel (PERF.md section 6, PR 44): a (blocks, block size, lanes) pool
    lies in HBM in (8, 128) tiles, and Mosaic refuses to slice one token's
    row out of it, as 16-bit lanes and as their 32-bit view alike. A JAX
    whose Mosaic lowers this fails here: ROADMAP Queue 1 item 1 (b), the
    DMA walk over the selected rows, is then open again."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def body(block_ref, offset_ref, pool_hbm, out_ref, sem):
        copy = pltpu.make_async_copy(
            pool_hbm.at[block_ref[0], pl.ds(offset_ref[0], 1)],
            out_ref.at[pl.ds(0, 1)], sem.at[0])
        copy.start()
        copy.wait()

    def row(block, offset, pool):
        return pl.pallas_call(
            body,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)],
                out_specs=pl.BlockSpec((8, lanes), lambda i, b, o: (0, 0)),
                scratch_shapes=[pltpu.SemaphoreType.DMA((1,))]),
            out_shape=jax.ShapeDtypeStruct((8, lanes), dtype),
            name="row_dma")(block, offset, pool)

    s = _on(tpu[0])
    with pytest.raises(Exception, match=r"aligned to tiling \(8\), but is 1"):
        jax.jit(row).lower(s((1,), jnp.int32), s((1,), jnp.int32),
                           s((64, 256, lanes), dtype)).compile()


@pytest.mark.parametrize("rows", [32, 32 + 256])
@pytest.mark.parametrize("kind", ["global", "window"])
def test_paged_attention_at_mimo_v2_flash_widths(tpu, kind, rows):
    """A layer of `mimo2f-serve-longdoc` as the decode graph runs it: 64
    query heads of 192 over 4 (global) or 8 (window) KV heads, values of
    128, RoPE on 64 lanes, pools of 5,400 (512) blocks of 128 rows, keys
    768 (1,536) and values 512 (1,024) wide, page tables 262 wide; 32
    decoding rows through the single-query kernel (grouped; key heads of
    192 through `spread`; the window walk and the sink named apart), and
    the same with a chunk of 256 riding as rows, which the tile loop in
    XLA takes. No step copies a pool."""
    import re

    from flexflow_tpu.ops.attention import AttentionFrontEnd

    s = _on(tpu[0])
    window = kind == "window"
    front = AttentionFrontEnd(
        4096, 64, use_bias=False, rope_theta=1e4 if window else 5e6,
        num_kv_heads=8 if window else 4, head_size=192, v_head_size=128,
        rope_dim=64, window=128 if window else 0, sink=window,
        value_scale=0.707)
    blocks = 512 if window else 5400
    p, op, layer = _paged_attention_layer(front, 33536, 128, blocks,
                                          slots=32)
    kv = front.kv_heads
    assert p.cache_row_widths == {"pool_k": kv * 192, "pool_v": kv * 128}
    specs = op.weights(p, [(rows, 1, 4096), (rows, 1), (rows, 262)])
    weights = {w.name: s(w.shape, jnp.float32 if w.name == "sink"
                         else jnp.bfloat16) for w in specs}
    compiled = jax.jit(layer, donate_argnums=(0,)).lower(
        weights, s((rows, 1, 4096)), s((rows, 1), jnp.int32),
        s((rows, 262), jnp.int32)).compile()
    text = compiled.as_text()
    name = ("flash_attention_paged_decode_window_grouped" if window
            else "flash_attention_paged_decode_grouped")
    assert pallas_kernels(text) == {name: 1}
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    assert not re.findall(rf"= bf16\[{blocks},128,\d+\]\S* copy\(", text)


@pytest.mark.parametrize("rows", [32, 32 + 256])
@pytest.mark.parametrize("kind", ["global", "window"])
def test_paged_attention_at_command_a_plus_widths(tpu, kind, rows):
    """A layer of `cmdap-serve-agentmix` as the decode graph runs it: 128
    query heads of 128 on 8 KV heads (a query projection of 16,384, a group
    of 16), interleaved RoPE and a window of 4,096 keys in the window
    layers, no position in the global one; pools of 1,800 (768) blocks of
    256 rows of 1,024, page tables 131 wide; 32 decoding rows through the
    single-query kernel (grouped; the window walk named apart), and the
    same with a chunk of 256 riding as rows through ONE call of the grouped
    chunk kernel, the window layers' under its window walk (named apart as
    well). No step copies a pool."""
    import re

    from flexflow_tpu.ops.attention import AttentionFrontEnd

    s = _on(tpu[0])
    window = kind == "window"
    front = AttentionFrontEnd(
        4096, 128, use_bias=False, rope_theta=5e4 if window else 0.0,
        num_kv_heads=8, head_size=128, window=4096 if window else 0,
        rope_interleaved=window)
    blocks = 768 if window else 1800
    p, op, layer = _paged_attention_layer(front, 33536, 256, blocks,
                                          slots=32)
    assert p.cache_row_widths == {"pool_k": 1024, "pool_v": 1024}
    specs = op.weights(p, [(rows, 1, 4096), (rows, 1), (rows, 131)])
    weights = {w.name: s(w.shape, jnp.bfloat16) for w in specs}
    assert weights["wq"].shape == (4096, 16384)
    compiled = jax.jit(layer, donate_argnums=(0,)).lower(
        weights, s((rows, 1, 4096)), s((rows, 1), jnp.int32),
        s((rows, 131), jnp.int32)).compile()
    text = compiled.as_text()
    want = {("flash_attention_paged_decode_window_grouped" if window
             else "flash_attention_paged_decode_grouped"): 1}
    if rows > 32:
        want["flash_attention_paged_chunk_window_grouped" if window
             else "flash_attention_paged_chunk_grouped"] = 1
    assert pallas_kernels(text) == want
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    assert not re.findall(rf"= bf16\[{blocks},256,\d+\]\S* copy\(", text)


@pytest.mark.parametrize("rows", [16, 16 + 256])
def test_paged_attention_at_evabyte_widths(tpu, rows):
    """A layer of `evabyte-serve-bytedocs` as the decode graph runs it: 32
    query heads of 128 on as many KV heads, RoPE over the whole head, an
    aligned window of 2,048 exact keys beside a summary for every 16; the
    exact rows in the window group's pool (232 blocks of 256 rows of 4,096),
    the summaries in the global group's (1,200 blocks of 16 rows), page
    tables 118 wide; 16 decoding rows, and the same with a chunk of 256
    riding as rows: the slots' rows through two calls of the single-query
    kernel (the window's pages, the summary pages) that hand their
    log-sum-exp to one merge, the chunk's through three of the chunk kernel
    (its first row's window, the next, the summaries) merged likewise. No
    step copies a pool."""
    import re

    from flexflow_tpu.fftype import DataType, OperatorType as OT
    from flexflow_tpu.ops import inc_attention as inc
    from flexflow_tpu.ops.attention import AttentionFrontEnd
    from flexflow_tpu.ops.base import OpContext, get_op_def

    s = _on(tpu[0])
    front = AttentionFrontEnd(4096, 32, use_bias=False, rope_theta=1e5,
                              window=2048, summary_chunk=16)
    p = inc.PagedIncMultiHeadAttentionParams(
        front, 30208, 256, 1200, impl="flash",
        cache_dtype=DataType.DT_BFLOAT16, chunk_from=16, window_blocks=232)
    op = get_op_def(OT.OP_PAGED_INC_MULTIHEAD_ATTENTION)
    state = op.state(p)
    assert {l.name: (l.group, l.every) for l in state.leaves} == {
        "pool_k": (1, 1), "pool_v": (1, 1), "pool_ksum": (0, 16),
        "pool_vsum": (0, 16)}
    assert (state.blocks, state.window_blocks, state.window_aligned) == (
        1200, 232, True)

    def layer(weights, x, positions, table, table_w):
        (y,), new = op.forward(p, [x, positions, table, table_w], weights,
                               None, OpContext(training=False, mesh=None))
        return y, new

    specs = op.weights(p, [(rows, 1, 4096), (rows, 1), (rows, 118),
                           (rows, 118)])
    weights = {w.name: s(w.shape, jnp.bfloat16) for w in specs}
    assert weights["pool_k"].shape == (232, 256, 4096)
    assert weights["pool_ksum"].shape == (1200, 16, 4096)
    assert weights["phi"].shape == (32, 128)
    compiled = jax.jit(layer, donate_argnums=(0,)).lower(
        weights, s((rows, 1, 4096)), s((rows, 1), jnp.int32),
        s((rows, 118), jnp.int32), s((rows, 118), jnp.int32)).compile()
    text = compiled.as_text()
    want = {"flash_attention_paged_decode_lse": 2}
    if rows > 16:
        want["flash_attention_paged_chunk_lse"] = 3
    assert pallas_kernels(text) == want
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    assert not re.findall(r"= bf16\[(232,256|1200,16),\d+\]\S* copy\(", text)


@pytest.mark.parametrize("chunk", [128, 16])
def test_paged_chunk_kernel_at_c13b_widths(tpu, chunk):
    """`c13b-serve-chat`'s chunk step as the decode graph runs a layer of
    it: 16 slots through the single-query kernel, the chunk's 128 (or 16)
    rows through ONE call of the multi-query chunk kernel under the table
    row of the first of them; 16 heads of 128, blocks of 16, tables 40
    wide. A pure-decode call keeps the single-query kernel alone."""
    from flexflow_tpu.ops.attention import AttentionFrontEnd

    s = _on(tpu[0])
    p, op, layer = _paged_attention_layer(
        AttentionFrontEnd(2048, 16), 640, 16, 641, slots=16)

    def shapes(rows):
        specs = op.weights(p, [(rows, 1, 2048), (rows, 1), (rows, 40)])
        return ({w.name: s(w.shape) for w in specs}, s((rows, 1, 2048)),
                s((rows, 1), jnp.int32), s((rows, 40), jnp.int32))

    assert _kernels(layer, *shapes(16 + chunk)) == {
        "flash_attention_paged_decode": 1, "flash_attention_paged_chunk": 1}
    assert _kernels(layer, *shapes(16)) == {
        "flash_attention_paged_decode": 1}


@pytest.mark.parametrize("chunk", [256, 8])
def test_grouped_paged_chunk_kernel_at_solar_open2_widths(tpu, chunk):
    """`solar2-serve-reason`'s softmax layer in a chunk step: 128 slots
    through the grouped single-query kernel, a chunk of 256 (or the
    smallest bucket, 8) through the grouped chunk kernel: 64 query heads
    of 128 over 8 KV heads, blocks of 256, tables 17 wide; a query tile
    is 128 rows and a head tile 4 KV heads with their 32 query heads."""
    from flexflow_tpu.ops.attention import AttentionFrontEnd

    s = _on(tpu[0])
    front = AttentionFrontEnd(4096, 64, use_bias=False, num_kv_heads=8,
                              head_size=128, output_gate=True)
    p, op, layer = _paged_attention_layer(front, 4352, 256, 1600, slots=128)
    assert fa._paged_chunk_tile(256, 8192, 1024, 64, 256, 2, False) == 4
    rows = 128 + chunk
    specs = op.weights(p, [(rows, 1, 4096), (rows, 1), (rows, 17)])
    kernels = _kernels(
        layer, {w.name: s(w.shape) for w in specs}, s((rows, 1, 4096)),
        s((rows, 1), jnp.int32), s((rows, 17), jnp.int32))
    assert kernels == {"flash_attention_paged_decode_grouped": 1,
                       "flash_attention_paged_chunk_grouped": 1}


@pytest.mark.parametrize("rows", [128, 128 + 256])
def test_delta_rule_decode_layer_at_solar_open2_widths(tpu, rows):
    """One delta-rule layer of `solar2-serve-reason` as the decode graph
    runs it: 128 slots of 64 heads x 128 x 128 float32 state, one token a
    slot, and the same with a chunk of 256 as rows of one slot. The state
    update is the Pallas kernel (twice with a chunk: the slots', then the
    chunk's from its slot's state), the state aliased in place: the step
    needs under 1 GB beside its 537 MB of state."""
    from flexflow_tpu.fftype import DataType, OperatorType as OT
    from flexflow_tpu.ops import delta_attention as da
    from flexflow_tpu.ops.base import OpContext, get_op_def

    s = _on(tpu[0])
    p = da.GatedDeltaDecodeParams(
        da.DeltaFrontEnd(4096, 64, 128), slots=128, max_seq_len=4352,
        cache_dtype=DataType.DT_BFLOAT16)
    op = get_op_def(OT.OP_GATED_DELTA_ATTENTION_DECODE)
    specs = op.weights(p, [(rows, 1, 4096)])
    weights = {w.name: s(w.shape) for w in specs if w.trainable}
    state = {"state_s": s(p.state_leaves["state_s"], jnp.float32),
             "state_conv": s(p.state_leaves["state_conv"])}

    def layer(state, weights, x, positions, state_slot):
        (y,), state = op.forward(
            p, [x, positions, state_slot], {**weights, **state}, None,
            OpContext(training=False, mesh=None))
        return y, state

    compiled = jax.jit(layer, donate_argnums=(0,)).lower(
        state, weights, s((rows, 1, 4096)), s((rows, 1), jnp.int32),
        s((rows, 1), jnp.int32)).compile()
    assert pallas_kernels(compiled.as_text()) == {
        "delta_rule_update": 1 if rows == 128 else 2}
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


@pytest.mark.parametrize("chunk", [0, 1024, 512, 8])
def test_multi_query_paged_kernels_at_jamba2_widths(tpu, chunk):
    """`jamba2-serve-shortchat`'s two softmax layers: 20 query heads of
    128 over ONE KV head (a group of 20, no multiple of 8 sublanes; a pool
    row of 128 lanes), blocks of 256, tables 6 wide; 256 slots through the
    grouped single-query kernel and a chunk of 512 (or the smallest
    bucket) through the grouped chunk kernel."""
    from flexflow_tpu.ops.attention import AttentionFrontEnd

    s = _on(tpu[0])
    front = AttentionFrontEnd(2560, 20, use_bias=False, num_kv_heads=1,
                              head_size=128)
    p, op, layer = _paged_attention_layer(front, 1536, 256, 1600, slots=256)
    rows = 256 + chunk
    specs = op.weights(p, [(rows, 1, 2560), (rows, 1), (rows, 6)])
    kernels = _kernels(
        layer, {w.name: s(w.shape) for w in specs}, s((rows, 1, 2560)),
        s((rows, 1), jnp.int32), s((rows, 6), jnp.int32))
    assert kernels == {
        "flash_attention_paged_decode_grouped": 1,
        **({"flash_attention_paged_chunk_grouped": 1} if chunk else {})}


@pytest.mark.parametrize("rows", [256, 256 + 1024, 256 + 512, 256 + 8])
def test_selective_ssm_decode_layer_at_jamba2_widths(tpu, rows):
    """One state-space layer of `jamba2-serve-shortchat` as the decode
    graph runs it: 256 slots of 16 x 5,120 float32 state, one token a
    slot, and the same with a chunk of 512 (or 8) as rows of one slot. The
    state update is the Pallas kernel (twice with a chunk: the slots',
    then the chunk's from its slot's state), the state aliased in place:
    the step needs under 0.5 GB beside its 84 MB of state."""
    from flexflow_tpu.fftype import DataType, OperatorType as OT
    from flexflow_tpu.ops import ssm
    from flexflow_tpu.ops.base import OpContext, get_op_def

    s = _on(tpu[0])
    p = ssm.SelectiveSSMDecodeParams(
        ssm.MambaFrontEnd(2560, 5120, 16, 160), slots=256, max_seq_len=1536,
        cache_dtype=DataType.DT_BFLOAT16)
    op = get_op_def(OT.OP_SELECTIVE_SSM_DECODE)
    specs = op.weights(p, [(rows, 1, 2560)])
    weights = {w.name: s(w.shape) for w in specs if w.trainable}
    state = {w.name: s(w.shape, jnp.float32 if w.name == "state_h"
                       else jnp.bfloat16)
             for w in specs if not w.trainable}
    assert state["state_h"].shape == (256, 16, 5120)

    def layer(state, weights, x, positions, state_slot):
        (y,), state = op.forward(
            p, [x, positions, state_slot], {**weights, **state}, None,
            OpContext(training=False, mesh=None))
        return y, state

    compiled = jax.jit(layer, donate_argnums=(0,)).lower(
        state, weights, s((rows, 1, 2560)), s((rows, 1), jnp.int32),
        s((rows, 1), jnp.int32)).compile()
    assert pallas_kernels(compiled.as_text()) == {
        "selective_scan_update": 1 if rows == 256 else 2}
    assert compiled.memory_analysis().temp_size_in_bytes < 5e8


def test_grouped_attention_trains_through_the_packed_kernels_at_lfm2_widths(
        tpu):
    """A `full_attention` layer of `lfm2-train-8k`, forward and backward:
    32 query heads on 8 KV heads of 64, an RMSNorm a head, RoPE, one
    sequence of 8,192 tokens in bf16 under impl "flash". The KV heads are
    repeated in front of `gpt2m-train-1k`'s grouped-narrow-head kernels,
    nothing falls back to the einsum, and the compiled text holds no array
    of (heads, 8,192, 8,192) scores (8.6 GB in float32): the step needs
    under 1 GB beside its operands."""
    import warnings

    from flexflow_tpu.fftype import OperatorType as OT
    from flexflow_tpu.ops import attention as attn_ops
    from flexflow_tpu.ops.base import OpContext, get_op_def

    s = _on(tpu[0])
    front = attn_ops.AttentionFrontEnd(
        embed_dim=2048, num_heads=32, use_bias=False, rope_theta=1e6,
        qk_norm="head", num_kv_heads=8)
    p = attn_ops.MultiHeadAttentionParams(front, causal=True, impl="flash")
    op = get_op_def(OT.OP_MULTIHEAD_ATTENTION)
    weights = {w.name: s(w.shape, jnp.float32)
               for w in op.weights(p, [(1, 8192, 2048)] * 3)}
    assert weights["wk"].shape == (2048, 512)

    def loss(weights, x, positions):
        weights = jax.tree.map(lambda a: a.astype(jnp.bfloat16), weights)
        (y,), _ = op.forward(p, [x, x, x, positions], weights, {},
                             OpContext(training=True, mesh=None))
        return jnp.sum(y.astype(jnp.float32))

    with warnings.catch_warnings():
        warnings.simplefilter("error", KernelFallbackWarning)
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            weights, s((1, 8192, 2048)), s((1, 8192), jnp.int32)).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"flash_attention_fwd_packed_grouped": 1,
                                    "flash_attention_bwd_packed_grouped": 1}
    assert not re.search(r"\[(\d+,)*8192,8192\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def _instructions(text):
    """[(computation, name, shape, opcode, operands, op_name)] of a
    compiled text's instructions outside its fused computations."""
    found, computation = [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            computation = head.group(1)
        m = re.match(r"\s+(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(([^)]*)\)", line)
        if m and computation and "fused_computation" not in computation:
            op = re.search(r'op_name="([^"]*)"', line)
            found.append((computation, *m.groups(), op.group(1) if op else ""))
    return found


def _held_expert_layers(tpu, layers):
    """`layers` expert layers of `lfm2-train-8k` one after another round a
    residual sum, forward and backward, compiled for the described chip:
    16,384 tokens x 4 of 32 experts, 8 held, hidden 2,048, experts of
    1,792, bf16."""
    import warnings

    from flexflow_tpu.fftype import OperatorType as OT
    from flexflow_tpu.ops import moe as moe_ops
    from flexflow_tpu.ops.base import OpContext, get_op_def

    s = _on(tpu[0])
    t, d, f = 16384, 2048, 1792
    p = moe_ops.MoEMLPParams(32, 4, f, scoring="sigmoid", norm_topk_prob=True,
                             norm_topk_eps=1e-6, experts_held=(8, 8))
    op = get_op_def(OT.OP_MOE_MLP)
    specs = op.weights(p, [(2, t // 2, d)])
    assert "slabs_run" in [w.name for w in specs]
    weights = [{w.name: s(w.shape, jnp.float32) for w in specs if w.trainable}
               for _ in range(layers)]

    def loss(weights, x):
        ran = []
        for w in weights:
            w = jax.tree.map(lambda a: a.astype(jnp.bfloat16), w)
            (y,), state = op.forward(
                p, [x], {**w, "slabs_run": jnp.zeros((), jnp.int32)}, {},
                OpContext(training=True, mesh=None))
            x = x + y
            ran.append(state["slabs_run"])
        return jnp.sum(x.astype(jnp.float32)), ran

    with warnings.catch_warnings():
        warnings.simplefilter("error", KernelFallbackWarning)
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)).lower(
            weights, s((2, t // 2, d))).compile()


def test_short_conv_layer_keeps_its_middle_in_vmem_at_lfm2_widths(tpu):
    """A `conv` layer of `lfm2-train-8k`, forward and backward with every
    gradient: two sequences of 8,192 tokens, 2,048 channels, 3 taps, bf16
    under float32 masters. The middle is the two Pallas kernels and nothing
    falls back; both lie in `sconv.conv` for the cell's readers (the
    backward's scope is opened inside the rule), the matmuls round them in
    `sconv.proj` and `sconv.out`; and NO instruction of the layer writes a
    float32 array of (rows, tokens, channels): XLA's own program of the jnp
    form wrote eight a layer (the window, the taps' sum, both again, three
    shares of dc and their sum: 134 MB each, PERF.md section 6, PR 63), so
    the compiler or refactor that brings them back is seen here."""
    import warnings

    from benchmarks import lfm2_events
    from flexflow_tpu.fftype import OperatorType as OT
    from flexflow_tpu.ops.base import OpContext, get_op_def
    from flexflow_tpu.ops.short_conv import ShortConvFrontEnd, ShortConvParams

    s = _on(tpu[0])
    rows, tokens, channels = 2, 8192, 2048
    p = ShortConvParams(ShortConvFrontEnd(embed_dim=channels, conv_kernel=3))
    op = get_op_def(OT.OP_SHORT_CONV)
    weights = {w.name: s(w.shape, jnp.float32)
               for w in op.weights(p, [(rows, tokens, channels)])}

    def loss(weights, x):
        weights = jax.tree.map(lambda a: a.astype(jnp.bfloat16), weights)
        (y,), _ = op.forward(p, [x], weights, {},
                             OpContext(training=True, mesh=None))
        return jnp.sum(y.astype(jnp.float32))

    with warnings.catch_warnings():
        warnings.simplefilter("error", KernelFallbackWarning)
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            weights, s((rows, tokens, channels))).compile().as_text()
    assert pallas_kernels(text) == {"short_conv_fwd": 1, "short_conv_bwd": 1}
    scope = dict(map(tuple, lfm2_events.scoped_instructions(text)))
    kernels = {name: of for name, of in scope.items()
               if name.startswith("short_conv_")}
    assert sorted(re.sub(r"\.\d+$", "", k) for k in kernels) == [
        "short_conv_bwd", "short_conv_fwd"]
    assert set(kernels.values()) == {"sconv.conv"}
    assert {"sconv.proj", "sconv.conv", "sconv.out"} == set(scope.values())
    found = _instructions(text)
    assert len(found) > 10
    whole = rows * tokens * channels
    for computation, name, shape, opcode, operands, op_name in found:
        for dims in re.findall(r"f32\[([\d,]+)\]", shape):
            assert np.prod([int(d) for d in dims.split(",")]) < whole, (
                name, shape, op_name)


def test_held_expert_layer_runs_its_live_prefix_at_lfm2_widths(tpu):
    """An expert layer of `lfm2-train-8k`, forward and backward. The
    sorted-order passes are seven loops whose trip count the device
    decides (the forward's two, which the backward runs again, and the
    backward's own three; they pass XLA's TPU pipeline beside the nine
    Pallas grouped matmuls: the three forwards the backward linearises at
    are dropped), each starts from a buffer that is allocated and left
    (the SiLU's backward from two), no instruction of the layer fills a
    whole (65536, .) array from a constant alone (a memset of 268 MB a
    loop), and a loop's body writes its slab from inside the pass's fusion
    and computes nothing of the whole length (XLA sinks a loop's
    elementwise producer into its body where no barrier holds it)."""
    text = _held_expert_layers(tpu, 1).as_text()
    assert pallas_kernels(text) == {"gmm": 6, "tgmm": 3, "moe_unwritten": 8}
    found = _instructions(text)
    loops = [i for i in found if i[3] == "while" and "moe." in i[5]
             and "searchsorted" not in i[5]]
    assert len(loops) == 7
    whole = re.compile(r"\[65536,(2048|1792)\]")
    layer = [i for i in found if whole.search(i[2]) and "moe." in i[5]]
    started = [i for i in layer if i[3] == "custom-call"
               and "moe_unwritten" in i[5]]
    assert len(started) == 8
    assert all(i[4].strip() for i in started)   # each waits for an operand
    for computation, name, shape, opcode, operands, op_name in layer:
        assert opcode != "broadcast", (name, op_name)
        if opcode == "fusion":
            assert not all(o.strip().startswith("%constant")
                           for o in operands.split(",")), (name, op_name)
        if "/while/body/" in op_name:
            assert op_name.endswith("dynamic_update_slice"), (name, op_name)
            # the slab's update is the end of the fusion that makes the
            # slab: a start XLA cannot see to be whole tiles (a clamp on
            # it) leaves the update an instruction of its own
            assert opcode != "dynamic-update-slice", (name, op_name)


def test_held_expert_layers_hold_no_more_than_they_keep_at_lfm2_widths(tpu):
    """Three such layers, the third's backward before the first's: a layer
    keeps its input, `gate`, `up` and the picked rows from forward to
    backward (0.8 GB) and nothing else, and its backward's buffers are gone
    when the next layer's begins. 4.32 GB of temporaries as it stands;
    6.62 with the region's barriers gone (XLA keeps the forward's loops'
    results for the backward, and leaves the weights' `tgmm`s and their
    operands for later). `lfm2-train-8k`'s whole step has 8.97 GiB for its
    temporaries beside 6.78 of arguments, and its five layers took 2 GiB
    more than that before the barriers (PERF.md section 6, PR 62): too
    long a compile for a test, so this holds the part that grew."""
    compiled = _held_expert_layers(tpu, 3)
    assert compiled.memory_analysis().temp_size_in_bytes < 5.0e9


def test_contiguous_decode_head_dim_128(tpu):
    """The contiguous decode kernel at the engine's real cache shape
    (slots, max_seq + 1, E): max_seq + 1 is odd, so the last kv block is
    ragged. The lengths ride scalar prefetch — as a (1, lanes) stripe
    block Mosaic refused them, and the kernel had only ever run in
    interpret mode."""
    s = _on(tpu[0])
    slots, heads, e = 8, 32, 32 * 128
    kernels = _kernels(
        lambda q, k, v, n: fa.flash_decode_attention(
            q, k, v, n, num_heads=heads),
        s((slots, 1, e)), s((slots, 1025, e)), s((slots, 1025, e)),
        s((slots,), jnp.int32))
    assert kernels == {"flash_attention_decode": 1}


@pytest.mark.parametrize("slots,heads,width,block_size,blocks", [
    (8, 32, 64, 16, 8 * 64 + 1),
    (8, 32, 8, 128, 8 * 8 + 1),
    # c13b-serve-chat's own call: 16 slots x 640 rows in blocks of 16, the
    # pool the engine sizes for one v5e
    (16, 16, 40, 16, 641),
    # and its step that carries a chunk of 128 tokens, laid out as rows
    # (serving/engine.py): 16 slots + 128 single-query rows, a (144, 40)
    # table in SMEM
    (16 + 128, 16, 40, 16, 641),
])
def test_paged_decode_head_dim_128(tpu, slots, heads, width, block_size,
                                   blocks):
    """`slots` is the call's rows: a slot's one query each, or more rows
    than slots where a prefill chunk's tokens ride as rows of their own."""
    s = _on(tpu[0])
    e = heads * 128
    pool = s((blocks, block_size, e))
    kernels = _kernels(
        lambda q, pk, pv, tbl, n: fa.paged_flash_decode_attention(
            q, pk, pv, tbl, n, num_heads=heads),
        s((slots, 1, e)), pool, pool, s((slots, width), jnp.int32),
        s((slots,), jnp.int32))
    assert kernels == {"flash_attention_paged_decode": 1}


def _pallas_calls(jaxpr):
    """The `pallas_call` equations of a jaxpr, those of its calls too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _pallas_calls(inner)


# a cell's single-query call: rows, query heads, KV heads, key and value
# head, block, table width, pool blocks, window, sink, lse, the kernel
_DECODE = "flash_attention_paged_decode"
WALKS = {
    "c13b": (16, 16, 16, 128, 128, 16, 40, 641, 0, False, False, _DECODE),
    "solar2": (128, 64, 8, 128, 128, 256, 17, 1600, 0, False, False,
               _DECODE + "_grouped"),
    "mimo2f-global": (32, 64, 4, 192, 128, 128, 262, 5400, 0, False, False,
                      _DECODE + "_grouped"),
    "mimo2f-window": (32, 64, 8, 192, 128, 128, 262, 512, 128, True, False,
                      _DECODE + "_window_grouped"),
    "cmdap-global": (32, 128, 8, 128, 128, 256, 131, 1800, 0, False, False,
                     _DECODE + "_grouped"),
    "cmdap-window": (32, 128, 8, 128, 128, 256, 131, 768, 4096, False, False,
                     _DECODE + "_window_grouped"),
    # EvaByte's two walks a row: its window's 8 pages, its summaries
    "evabyte-exact": (16, 32, 32, 128, 128, 256, 8, 232, 0, False, True,
                      _DECODE + "_lse"),
    "evabyte-summaries": (16, 32, 32, 128, 128, 16, 118, 1200, 0, False,
                          True, _DECODE + "_lse"),
    "jamba2": (256, 20, 1, 128, 128, 256, 6, 1600, 0, False, False,
               _DECODE + "_grouped"),
}


@pytest.mark.parametrize("cell", list(WALKS))
def test_the_paged_decode_walk_lowers_in_order_at_every_cells_shapes(
        tpu, cell):
    """The walk's DMAs follow the call (a row's last round starts the
    next row's first; a round copies its row's live pages in a loop): the
    grid is declared to run in order, the buffer a row starts in is
    carried in SMEM, and Mosaic takes the body at the shapes of each of
    the seven serving cells that run it, under the name their per-layer
    readers look for."""
    (rows, heads, kv, dk, dv, bs, width, blocks, window, sink, lse,
     name) = WALKS[cell]
    s = _on(tpu[0])

    def fn(q, pk, pv, tbl, n, bias=None):
        return fa.paged_flash_decode_attention(
            q, pk, pv, tbl, n, num_heads=heads, num_kv_heads=kv,
            window=window, sink=bias, return_lse=lse)

    shapes = [s((rows, 1, heads * dk)), s((blocks, bs, kv * dk)),
              s((blocks, bs, kv * dv)), s((rows, width), jnp.int32),
              s((rows,), jnp.int32)]
    if sink:
        shapes.append(s((heads,), jnp.float32))
    call, = _pallas_calls(jax.make_jaxpr(fn)(*shapes).jaxpr)
    assert call.params["compiler_params"]["mosaic_tpu"] \
        .dimension_semantics == ("arbitrary",)
    assert _kernels(fn, *shapes) == {name: 1}


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_decode_head_dim_64_takes_the_reference_and_says_so(tpu, layout):
    """Every zoo tier but lm-xxl-fsdp has head_dim 64, where heads cannot
    be selected by lane offset: the decode kernels give way to the
    reference einsum. On a TPU that is said, not hidden."""
    s = _on(tpu[0])
    slots, heads, e = 4, 16, 16 * 64
    q, n = s((slots, 1, e)), s((slots,), jnp.int32)
    if layout == "contiguous":
        fn = lambda q, k, v, n: fa.flash_decode_attention(  # noqa: E731
            q, k, v, n, num_heads=heads)
        args = (q, s((slots, 513, e)), s((slots, 513, e)), n)
    else:
        fn = lambda q, pk, pv, tbl, n: fa.paged_flash_decode_attention(  # noqa: E731
            q, pk, pv, tbl, n, num_heads=heads)
        pool = s((slots * 32 + 1, 16, e))
        args = (q, pool, pool, s((slots, 32), jnp.int32), n)
    with pytest.warns(KernelFallbackWarning, match=r"head_dim 64 % 128"):
        kernels = _kernels(fn, *args)
    assert not kernels


def test_paged_decode_too_wide_for_vmem_takes_the_reference_and_says_so(tpu):
    """The paged kernel keeps two rounds of whole K and V pool rows in
    VMEM, and a round is as many pages as fit there (_paged_round_pages):
    64 heads of 128 in blocks of 16 take the kernel in bf16 and, a page a
    round, in f32. One page is the least a round can be: in blocks of 128
    two such pages in f32 are 16 MiB, all a Mosaic kernel gets, and that
    shape goes to the reference by a gate that names the bytes."""
    s = _on(tpu[0])
    slots, heads, e = 4, 64, 64 * 128
    fn = lambda q, pk, pv, tbl, n: fa.paged_flash_decode_attention(  # noqa: E731
        q, pk, pv, tbl, n, num_heads=heads)

    def args(dtype, bs=16):
        pool = s((slots * 8 + 1, bs, e), dtype)
        return (s((slots, 1, e), dtype), pool, pool,
                s((slots, 8), jnp.int32), s((slots,), jnp.int32))

    for dtype in (jnp.bfloat16, jnp.float32):
        assert _kernels(fn, *args(dtype)) == {
            "flash_attention_paged_decode": 1}
    with pytest.warns(KernelFallbackWarning, match=r"bytes of VMEM"):
        kernels = _kernels(fn, *args(jnp.float32, 128))
    assert not kernels


def _step_text_for_four_chips(ff, topology, monkeypatch, batch, seq):
    """The compiled text of a compiled model's train step, its arguments
    described on the four described chips."""
    ex = ff.executor
    mesh = Mesh(np.array(topology.devices).reshape(ff.mesh.devices.shape),
                ff.mesh.axis_names)

    def described(x):
        spec = (x.sharding.spec if isinstance(x.sharding, NamedSharding)
                else PartitionSpec())
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(mesh, spec))

    toks = np.zeros((batch, seq), np.int32)
    data = ff._make_batch({"tokens": toks, "positions": toks},
                          np.zeros((batch, seq, 1), np.int32))
    rng = jax.device_put(jax.random.key(0),
                         NamedSharding(ff.mesh, PartitionSpec()))
    args = jax.tree.map(described, (
        ff._params, ff._state, ff._opt_slots, ff._step, ff._counters, rng,
        data))
    monkeypatch.setattr(ex, "mesh", mesh)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return jax.jit(ex._train_step_body).lower(*args).compile().as_text()


@pytest.mark.parametrize("mesh_flag,megatron", [("4,1,1,1", False),
                                                ("2,2,1,1", True)])
def test_train_step_lowers_for_four_chips(topology, monkeypatch, mesh_flag,
                                          megatron):
    """A whole train step with flash attention and the fused LayerNorm
    for a four-chip mesh. GSPMD cannot partition a Mosaic kernel, so the
    ops run them per shard (kernels/dispatch.per_shard); left to GSPMD
    this step does not lower at all — which the virtual CPU mesh, where
    the kernels are interpreted, cannot show."""
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.fftype import DataType
    from flexflow_tpu.models import TransformerLMConfig, build_transformer_lm
    from flexflow_tpu.parallel import megatron_transformer

    sys.argv = ["test", "--mesh", mesh_flag]
    config = FFConfig()
    config.batch_size = 8
    config.computation_dtype = DataType.DT_BFLOAT16
    ff = FFModel(config)
    cfg = TransformerLMConfig(
        vocab_size=512, hidden_size=256, num_heads=4, num_layers=1,
        sequence_length=128, attention_impl="flash")
    build_transformer_lm(ff, cfg, batch_size=8)
    if megatron:
        ff.set_strategy(megatron_transformer(ff))
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)

    text = _step_text_for_four_chips(ff, topology, monkeypatch, 8, 128)
    kernels = pallas_kernels(text)
    # the forward and the one backward kernel of the layer
    assert sum(v for k, v in kernels.items()
               if k.startswith("flash_attention")) == 2, kernels
    assert kernels["layer_norm_fwd"] == 3 and kernels["layer_norm_bwd"] == 3
    assert "all-reduce" in text


def _param_gathers(text):
    """What a compiled step's text holds under the stage-3 gathers' scope
    `param_gather/<owner>.<weight>`: per weight the all-gathers it EXECUTES
    (a synchronous `all-gather` outside any fusion, or one asynchronous
    chain: the TPU compiler writes an asynchronous all-gather as a start
    fusion (`AsyncCollectiveStart`), continuation fusions that carry its
    buffers and semaphores beside other work, and a done fusion, and every
    one of them holds the `all-gather` instruction), the result dtypes on
    those collectives, the ring's leftovers (collective-permutes and
    dynamic-update-slices under the scope), and the all-gathers inside a
    fusion that belongs to no chain: a gather duplicated into a reader."""
    comp, body = None, collections.defaultdict(list)
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if m:
            comp = m.group(1)
        elif comp:
            body[comp].append(line)
    fused = set(re.findall(r"calls=%?([\w.\-]+)", text))
    out = {"executed": collections.Counter(), "dtypes": collections.Counter(),
           "permutes": 0, "update_slices": 0, "stray": 0, "bytes": 0}
    for name, lines in body.items():
        chain = any("AsyncCollectiveStart" in l for l in lines)
        done = any("AsyncCollectiveDone" in l for l in lines)
        semaphores = sum(1 for l in lines
                         if re.search(r"= u32\[\]\S* parameter\(", l))
        for l in lines:
            m = re.search(r'op_name="[^"]*param_gather/([\w.\-]+)/', l)
            if not m:
                continue
            if re.search(r" collective-permute(-start)?\(", l):
                out["permutes"] += 1
            elif " dynamic-update-slice(" in l:
                out["update_slices"] += 1
            elif re.search(r" all-gather(-start)?\(", l):
                if name not in fused or chain:
                    out["executed"][m.group(1)] += 1
                    t = re.search(r"= \(?(\w+)\[([\d,]*)\]", l)
                    out["dtypes"][t.group(1)] += 1
                    n = 1
                    for d in t.group(2).split(","):
                        n *= int(d) if d else 1
                    out["bytes"] += n * (2 if t.group(1) == "bf16" else 4)
                elif not done and semaphores < 2:
                    out["stray"] += 1
    return out


def test_stage3_step_gathers_each_weight_once_in_bf16(topology, monkeypatch):
    """A stage-3 Adam step in bf16 for four chips: every weight that rests
    sharded comes to its compute placement by ONE all-gather a step, the
    wire carries the compute dtype (XLA hoists the cast in front of the
    collective), and nothing of a ring is left: no hop, no
    dynamic-update-slice assembling what arrived. The one-way ring this
    replaced compiled to 159 hops and 212 update-slices for this model's 53
    weights; a gather re-run in the backward or copied into its readers
    would show as a second execution or as a stray."""
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.models import TransformerLMConfig, build_transformer_lm

    monkeypatch.setattr(sys, "argv", [
        "test", "--mesh", "4,1,1,1", "--weight-update-sharding", "stage3",
        "--dtype", "bf16", "-b", "4"])
    ff = FFModel(FFConfig())
    cfg = TransformerLMConfig(
        vocab_size=2048, hidden_size=1024, num_heads=8, num_layers=3,
        sequence_length=64, attention_impl="xla")
    build_transformer_lm(ff, cfg, batch_size=4)
    ff.compile(optimizer=AdamOptimizer(),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    weights = {f"{node}.{w}" for node, w in ff.executor.gather_specs}
    assert len(weights) == 53

    found = _param_gathers(
        _step_text_for_four_chips(ff, topology, monkeypatch, 4, 64))
    assert set(found["executed"]) == weights
    assert set(found["executed"].values()) == {1}, found["executed"]
    assert set(found["dtypes"]) == {"bf16"}, found["dtypes"]
    assert found["permutes"] == found["update_slices"] == 0, found
    assert found["stray"] == 0
    # the gathered weights once, two bytes a parameter
    assert found["bytes"] == 2 * sum(
        int(np.prod(shape)) for key, (_spec, shape)
        in ff.executor.update_specs.items() if key in ff.executor.gather_specs)


def _ms4_front():
    from flexflow_tpu.ops.latent_attention import LatentFrontEnd

    return LatentFrontEnd(
        embed_dim=4096, num_heads=32, q_lora_rank=1024, kv_lora_rank=256,
        qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling=(128, 8192, 32, 1, 1), query_scale=(0.1, 8192))


@pytest.mark.parametrize("rows", [16, 16 + 128, 16 + 256])
def test_latent_layer_without_a_selection_at_mistral_small4_widths(tpu, rows):
    """A layer of `ms4-serve-longctx` as the decode graph runs it: 32
    heads over a latent row of 256 + 64 stored 384 wide, a pool of 2,560
    blocks of 256 rows, page tables 260 wide; 16 decoding rows that read
    their whole history in the paged latent kernel, and the same with a
    question's chunk under one page-table row (dense under the causal
    mask its positions give). The compiled layer holds the kernel once,
    for the slots' rows; no step copies the pool, and what the layer needs
    beside its arguments stays under 1 GB (no (rows, context) mask, no
    gathered history)."""
    from flexflow_tpu.fftype import DataType, OperatorType as OT
    from flexflow_tpu.ops.base import OpContext, get_op_def
    from flexflow_tpu.ops.latent_attention import PagedLatentAttentionParams

    s = _on(tpu[0])
    p = PagedLatentAttentionParams(
        _ms4_front(), 66560, 256, 2560, chunk_from=16,
        cache_dtype=DataType.DT_BFLOAT16)
    op = get_op_def(OT.OP_PAGED_LATENT_ATTENTION)
    state = op.state(p)
    assert state.selected == 0 and [l.name for l in state.leaves] == [
        "pool_c", "attended"]
    specs = op.weights(p, [(rows, 1, 4096), (rows, 1), (rows, 260)])
    weights = {w.name: s(w.shape, jnp.float32 if w.name == "attended"
                         else jnp.bfloat16) for w in specs}
    assert weights["pool_c"].shape == (2560, 256, 384)
    assert weights["attended"].shape == (16, 4096)

    def layer(weights, x, positions, page_table):
        (y,), new = op.forward(p, [x, positions, page_table], weights, None,
                               OpContext(training=False, mesh=None))
        return y, new

    compiled = jax.jit(layer, donate_argnums=(0,)).lower(
        weights, s((rows, 1, 4096)), s((rows, 1), jnp.int32),
        s((rows, 260), jnp.int32)).compile()
    text = compiled.as_text()
    assert pallas_kernels(text) == {"paged_latent_decode": 1}
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9
    assert not re.findall(r"= bf16\[2560,256,\d+\]\S* copy\(", text)


@pytest.mark.parametrize("why,width,block,lanes,call_gate", [
    (r"block_size 4 % 16", 8, 4, 384, None),
    (r"320 lanes", 8, 256, 320, None),
    (r"4-device mesh", 8, 256, 384,
     "4-device mesh: kernel not run per shard"),
])
def test_paged_latent_decode_refused_takes_the_reference_and_says_so(
        tpu, why, width, block, lanes, call_gate):
    """A geometry `paged_latent_gate` refuses and a call the op's own gate
    refuses (a multi-device mesh) take XLA's gather and einsums; on a TPU
    that is said, not hidden."""
    from flexflow_tpu.kernels import paged_latent_attention as pla

    s = _on(tpu[0])
    fn = lambda *a: pla.attend_rows(  # noqa: E731
        *a, latent_dim=256, scale=0.1, call_gate=call_gate)
    with pytest.warns(KernelFallbackWarning, match=why):
        kernels = _kernels(fn, s((4, 32, lanes)), s((64, block, lanes)),
                           s((4, width), jnp.int32), s((4,), jnp.int32))
    assert not kernels


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """chip_smoke.py has no CPU mode: with JAX held to the CPU it exits
    non-zero and prints no result line."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
