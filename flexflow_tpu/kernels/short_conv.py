"""The middle of the gated short convolution (ops/short_conv.py), between
its two projections, as one Pallas kernel each way:

    u = B * x                                  (rounded as the activations)
    c[t] = sum_j w[j] * u[t - (taps - 1) + j]  (float32, an empty window at
                                                each row's start)
    y = C * c                                  (one rounding)

with `bcx` (3, rows, tokens, channels) holding B, C, x and `w` the taps
(taps, channels). XLA's own program of these lines writes the padded window
and `c` to HBM in float32, shares them with the backward (or makes them
again, the in-projection with them, where the step is short of memory) and
hands the backward's three shifted float32 arrays to HBM once more: eight
float32 arrays of (rows, tokens, channels) a layer (PERF.md section 6,
PR 63). Here `forward` reads B, C, x once and writes y; `backward` reads
them and dy once, makes u and c again in VMEM and writes d_bcx

    dC = dy * c      dc = dy * C
    du[t] = sum_j w[j] * dc[t + (taps - 1) - j]     (the anti-causal mirror)
    dB = du * x      dx = du * B
    dw[j] = sum over rows and tokens of u[t] * dc[t + (taps - 1) - j]

beside a partial sum of dw a row (one small XLA sum ends it, as
kernels/layer_norm.py's `ds` / `db` are ended) and y once more, which the
out-projection's dW wants: the op joins the two (ops/short_conv.py
`_through_kernels`, one `custom_vjp` round the middle AND the
out-projection), so nothing is kept from forward to backward but `bcx`, the
taps and `w_out`. A y kept from the forward is a custom call's result, which
XLA cannot make again when a step is short of memory, where the jnp form
kept no y at all.

The grid is (rows, channel blocks, token blocks). A token block needs the
last `taps - 1` tokens of u before it (and, backward, the first `taps - 1`
of dc after it): a second BlockSpec over the same operand brings the
neighbouring sublane tile, zeroed at a row's first (last) block, so rows
never see each other and no grid step waits for another. Inside a block a
rolled loop walks sub-blocks of `_SUB` tokens with the neighbour's eight
rows as its carry; the backward walks twice, forward for c and dC, then
backwards for dc and what follows from it. A shift along the tokens is a
sublane roll of the sub-block with the carry joined on.

`refusal` is the shape gate; off a TPU the kernels run in Pallas interpret
mode (the tests' path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
# tokens a loop iteration takes: whole sublane tiles of bf16 and float32
_SUB = 32
# a program's block: (512, 512) of bf16 is 0.5 MiB (float32 takes half the
# tokens); the backward holds four such blocks in and four out,
# double-buffered 8 MiB of the 16 MiB a Mosaic kernel gets, and a grid step
# moves 4 MiB (5 us of HBM against 0.35 us of a step's overhead)
_TOKEN_BLOCK = 512
_CHANNEL_BLOCK = 512


def refusal(tokens: int, channels: int, taps: int, dtype) -> str | None:
    """Why the kernels do not take (.., tokens, channels) of `dtype` under
    `taps` taps, or None where they do."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return f"activations of {jnp.dtype(dtype).name}, not bfloat16 or float32"
    if channels % _LANES:
        return f"{channels} channels % {_LANES} != 0"
    if tokens % _SUBLANES:
        return f"{tokens} tokens % {_SUBLANES} != 0"
    if not 1 <= taps <= _SUBLANES + 1:
        return (f"{taps} taps reach further back than the {_SUBLANES} tokens "
                f"a block takes of its neighbour")
    return None


def _blocks(tokens: int, channels: int, dtype):
    """(token block, channel block, rows of a neighbour's tile)."""
    lanes = channels // _LANES
    cb = _LANES * max(d for d in range(1, _CHANNEL_BLOCK // _LANES + 1)
                      if lanes % d == 0)
    size = jnp.dtype(dtype).itemsize
    tb = min(max(_TOKEN_BLOCK * 2 // size, _SUB), -(-tokens // _SUB) * _SUB)
    return tb, cb, 32 // size


def _specs(tb: int, cb: int, halo: int, tokens: int, leading: tuple):
    """BlockSpecs over (*leading, rows, tokens, channels), `leading` whole:
    a program's block, the tile before it and the tile after it (each held
    inside the array at a row's ends, where the kernels zero them)."""
    per, last = tb // halo, -(-tokens // halo) - 1
    none = (0,) * len(leading)

    def spec(rows, at):
        return pl.BlockSpec(
            (*leading, None, rows, cb),
            lambda r, c, t: (*none, r, at(t), c))

    return (spec(tb, lambda t: t),
            spec(halo, lambda t: jnp.maximum(t * per - 1, 0)),
            spec(halo, lambda t: jnp.minimum((t + 1) * per, last)))


def _gated(b, x, dtype):
    """u = B * x in float32, rounded as the activations are."""
    f = jnp.float32
    return (b.astype(f) * x.astype(f)).astype(dtype).astype(f)


def _taps_sum(w, joined, at):
    """sum_j w[j] * shifted_j in float32, taps in order, and the shifted
    arrays: `at(k)` cuts the array `k = taps - 1 - j` tokens away out of
    `joined` rolled."""
    total, shifted = None, []
    for j, wj in enumerate(w):
        s = at(joined, len(w) - 1 - j)
        shifted.append(s)
        total = wj * s if total is None else total + wj * s
    return total, shifted


def _earlier(joined, k):
    """Rows [8 - k, rows - k) of `joined` (eight carried rows, then the
    sub-block): the sub-block k tokens earlier."""
    return (pltpu.roll(joined, k, 0) if k else joined)[_SUBLANES:]


def _later(joined, k):
    """Rows [k, rows - 8 + k) of `joined` (the sub-block, then eight
    carried rows): the sub-block k tokens later."""
    rows = joined.shape[0]
    return (pltpu.roll(joined, rows - k, 0) if k else joined)[:rows - _SUBLANES]


def _taps_of(w_ref, taps):
    return [w_ref[pl.ds(j, 1), :] for j in range(taps)]


def _in_order(bcx_ref, before_ref, w, dtype, write):
    """The block's sub-blocks, tokens in order: u, and c from u and the
    eight tokens of u before it (the neighbour's tile at the block's start,
    nothing at a row's), handed to `write(rows, c)`."""
    def sub_block(i, tail):
        rows = pl.ds(pl.multiple_of(i * _SUB, _SUB), _SUB)
        u = _gated(bcx_ref[0, rows, :], bcx_ref[2, rows, :], dtype)
        c, _ = _taps_sum(w, jnp.concatenate([tail, u], axis=0), _earlier)
        write(rows, c)
        return u[-_SUBLANES:]

    jax.lax.fori_loop(
        0, bcx_ref.shape[1] // _SUB, sub_block,
        jnp.where(pl.program_id(2) > 0,
                  _gated(before_ref[0], before_ref[2], dtype)[-_SUBLANES:],
                  0.0))


def _fwd_kernel(bcx_ref, before_ref, w_ref, y_ref, *, taps, dtype):
    def write(rows, c):
        y_ref[rows, :] = (bcx_ref[1, rows, :].astype(jnp.float32) * c
                          ).astype(y_ref.dtype)

    _in_order(bcx_ref, before_ref, _taps_of(w_ref, taps), dtype, write)


def _bwd_kernel(bcx_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
                d_ref, dw_ref, y_ref, *, taps, dtype, tokens):
    f = jnp.float32
    tb = dy_ref.shape[0]
    n = tb // _SUB
    t = pl.program_id(2)
    w = _taps_of(w_ref, taps)

    # tokens in order: dC = dy * c, and y = C * c again (dW_out's operand:
    # nobody kept it)
    def write(rows, c):
        d_ref[1, rows, :] = (dy_ref[rows, :].astype(f) * c).astype(d_ref.dtype)
        y_ref[rows, :] = (bcx_ref[1, rows, :].astype(f) * c).astype(y_ref.dtype)

    _in_order(bcx_ref, before_ref, w, dtype, write)

    # tokens backwards: dc = dy * C and the tokens after it give du (dB,
    # dx) and, against u, the taps' gradients
    def window_grad(i, carry):
        head, sums = carry
        start = pl.multiple_of((n - 1 - i) * _SUB, _SUB)
        rows = pl.ds(start, _SUB)
        b, x = bcx_ref[0, rows, :].astype(f), bcx_ref[2, rows, :].astype(f)
        u = _gated(b, x, dtype)
        dc = dy_ref[rows, :].astype(f) * bcx_ref[1, rows, :].astype(f)
        if tokens % tb:
            # the last block hangs over the row's end: what lies there is
            # no token's, and nothing of it may reach du or the taps
            live = (t * tb + start + jax.lax.broadcasted_iota(
                jnp.int32, dc.shape, 0)) < tokens
            dc, u = jnp.where(live, dc, 0.0), jnp.where(live, u, 0.0)
        du, later = _taps_sum(w, jnp.concatenate([dc, head], axis=0), _later)
        d_ref[0, rows, :] = (du * x).astype(d_ref.dtype)
        d_ref[2, rows, :] = (du * b).astype(d_ref.dtype)
        sums = tuple(
            s + sum((u * dcs)[r:r + _SUBLANES]
                    for r in range(0, _SUB, _SUBLANES))
            for s, dcs in zip(sums, later))
        return dc[:_SUBLANES], sums

    head = jnp.where(
        t < pl.num_programs(2) - 1,
        (dy_after_ref[...].astype(f) * after_ref[1].astype(f))[:_SUBLANES],
        0.0)
    zero = jnp.zeros((_SUBLANES, dy_ref.shape[1]), f)
    _, sums = jax.lax.fori_loop(0, n, window_grad, (head, (zero,) * taps))

    @pl.when(t == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for j in range(taps):
        dw_ref[j] += sums[j]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def forward(bcx, taps):
    """y (rows, tokens, channels) in bcx's dtype of `bcx` (3, rows, tokens,
    channels) and `taps` (taps, channels): the module's docstring. The
    shapes are `refusal`'s to pass first."""
    _, rows, tokens, channels = bcx.shape
    w, taps, dtype = taps.astype(jnp.float32), taps.shape[0], bcx.dtype
    tb, cb, halo = _blocks(tokens, channels, dtype)
    block, before, _ = _specs(tb, cb, halo, tokens, (3,))
    out, _, _ = _specs(tb, cb, halo, tokens, ())
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, dtype=dtype),
        grid=(rows, channels // cb, pl.cdiv(tokens, tb)),
        in_specs=[block, before,
                  pl.BlockSpec((taps, cb), lambda r, c, t: (0, c))],
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct((rows, tokens, channels), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=_interpret(),
        name="short_conv_fwd",
    )(bcx, bcx, w)


def backward(bcx, taps, dy):
    """(d_bcx as bcx lies, the taps' gradient (1, taps, channels) float32,
    y again) of `forward`'s operands and y's cotangent `dy`; the leading 1
    is the sum over THESE rows: a caller whose rows are split over devices
    stacks and sums them."""
    _, rows, tokens, channels = bcx.shape
    w, taps, dtype = taps.astype(jnp.float32), taps.shape[0], bcx.dtype
    tb, cb, halo = _blocks(tokens, channels, dtype)
    block, before, after = _specs(tb, cb, halo, tokens, (3,))
    dy_block, _, dy_after = _specs(tb, cb, halo, tokens, ())
    d_bcx, dw, y = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, dtype=dtype, tokens=tokens),
        grid=(rows, channels // cb, pl.cdiv(tokens, tb)),
        in_specs=[block, before, after, dy_block, dy_after,
                  pl.BlockSpec((taps, cb), lambda r, c, t: (0, c))],
        out_specs=[block,
                   # a row's sum over its token blocks, eight sublanes of
                   # partial sums a tap: revisited along the token axis
                   pl.BlockSpec((None, taps, _SUBLANES, cb),
                                lambda r, c, t: (r, 0, 0, c)),
                   dy_block],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, dtype),
                   jax.ShapeDtypeStruct((rows, taps, _SUBLANES, channels),
                                        jnp.float32),
                   jax.ShapeDtypeStruct(dy.shape, dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="short_conv_bwd",
    )(bcx, bcx, bcx, dy, dy, w)
    return d_bcx, dw.sum(axis=(0, 2))[None], y
