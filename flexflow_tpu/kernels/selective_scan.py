"""The selective state-space recurrence of a Mamba-1 layer (ops/ssm.py):
a row's state h (n x channels, float32; the state's n on sublanes, the
channels on lanes) takes one token as

    h = exp(dt_t (x) A) . h + (dt_t . c_t) (x) B_t   A < 0, dt_t > 0 a channel
    y_t = h^T C_t + D . c_t                            B_t, C_t of n

`selective_scan_reference` is the recurrence in jnp, a `lax.scan` over the
tokens of each row: the CPU path, the training-shaped op's (autodiff goes
through it) and the numerics oracle. `selective_scan_update` runs it as one
Pallas kernel where `selective_scan_gate` lets it: the grid is (row blocks,
channel blocks, token blocks), a program holds the states of its rows in
VMEM, and the state's block index does not depend on the token, so a state
is read from HBM once and written once however many tokens its row has.
One read and one write of the state is the floor of a decode step (one
token a row). The state is aliased in place.

Two shapes of call matter (ops/recurrent.decode_rows): the slots' rows, one
token each, which go eight rows a program (the row vectors dt and c fill
whole sublane tiles); and one slot's chunk, one row of many tokens, which
goes eight tokens a program over a state that stays in VMEM. The elementwise
work is the vector unit's and the exponential the transcendental unit's: a
row of a decode step is bound by its state's bytes (XLA's fusion of the
jnp form is as fast there: the kernel buys the alias and the chunk), a
chunk's token by the vector unit, 0.26 us a token at 5,120 channels, a
third of the `lax.scan`'s (PERF.md section 6, PR 55).

Rows and tokens: `state` is (rows, n, channels); dt and c are (rows,
tokens, channels), B and C (rows, tokens, n), A (n, channels), D
(channels,), `live` (rows, tokens) bool, `keep` (rows,) bool. A token that
is not live leaves the state as it is and gives a zero output. A row whose
`keep` is false starts from the zero state (its first token is a request's
first): the reset costs no pass of its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import warn_reference

# rows or tokens a program takes: a sublane tile of the row vectors
_SUBLANES = 8
# the widest block of channels a program holds: 8 rows x 16 x 2,560 float32
# are 1.25 MiB, in and out and double-buffered 5 MiB of the 16 MiB a
# Mosaic kernel gets. On a v5e 256 rows of one token take 0.274 ms at 1,280,
# 2,560 and 5,120 alike (82 % of the bytes floor), a chunk of 512 tokens
# 0.133 ms at 2,560 against 0.151 and 0.157 (PERF.md section 6, PR 55)
_CHANNEL_BLOCK = 2560


def _prepare(dt, c, B, C, live):
    """The recurrence's operands in float32 with dead tokens made the
    identity (dt 0: no decay, nothing added) and silent (C 0)."""
    f = jnp.float32
    m = live[:, :, None]
    return (jnp.where(m, dt.astype(f), 0.0), jnp.where(m, c.astype(f), 0.0),
            jnp.where(m, B.astype(f), 0.0), jnp.where(m, C.astype(f), 0.0))


def selective_scan_reference(state, dt, c, B, C, A, D, live, keep):
    """(y (rows, tokens, channels) float32, new state): module docstring."""
    dt, c, B, C = _prepare(dt, c, B, C, live)
    A, D = A.astype(jnp.float32), D.astype(jnp.float32)
    state = jnp.where(keep[:, None, None], state.astype(jnp.float32), 0.0)

    def token(h, xs):
        dt_t, c_t, b_t, c_out = xs     # (rows, channels) x 2, (rows, n) x 2
        h = (jnp.exp(dt_t[:, None, :] * A) * h
             + (dt_t * c_t)[:, None, :] * b_t[:, :, None])
        y = jnp.sum(h * c_out[:, :, None], axis=1) + D * c_t
        return h, y

    state, y = jax.lax.scan(
        token, state, tuple(jnp.moveaxis(x, 1, 0) for x in (dt, c, B, C)))
    return jnp.moveaxis(y, 0, 1), state


def _blocks(rows: int, tokens: int) -> tuple:
    """(rows, tokens) a program takes: eight rows of one token, or one row
    of eight tokens; a shape that is neither goes a row and a token."""
    if rows % _SUBLANES == 0:
        return _SUBLANES, 1
    if tokens % _SUBLANES == 0 and rows == 1:
        return 1, _SUBLANES
    return 1, 1


def _channel_block(channels: int) -> int:
    if channels <= _CHANNEL_BLOCK:
        return channels
    return next((e for e in range(_CHANNEL_BLOCK, 127, -128)
                 if channels % e == 0), channels)


def selective_scan_gate(rows: int, n: int, channels: int,
                        interpret: bool) -> str | None:
    """Why the kernel cannot take this shape, or None: a state is a
    (sublane, lane) tile of n x channels, and rows that are no multiple of
    eight go one a program, which only one row (a chunk) may."""
    if n % 8 != 0:
        return f"state size {n} % 8 != 0"
    if channels % 128 != 0 and not interpret:
        return f"channels {channels} % 128 != 0"
    if rows % _SUBLANES and rows != 1 and not interpret:
        return f"rows {rows} % {_SUBLANES} != 0"
    return None


def _kernel(keep_ref, dt_ref, c_ref, cols_ref, a_ref, d_ref, h_ref,
            y_ref, h_out_ref, *, rb: int, tb: int):
    @pl.when(pl.program_id(2) == 0)
    def _load():
        # a row that starts a request starts from nothing
        h_out_ref[...] = jnp.where(keep_ref[...] > 0, h_ref[...], 0.0)

    A, D = a_ref[...], d_ref[...]          # (n, e), (1, e)
    for t in range(tb):
        dt, c = dt_ref[t], c_ref[t]        # (rb, e): a row a sublane
        y = D * c
        outs = []
        for r in range(rb):
            # the state's n lies on sublanes: B_t and C_t come as columns
            cols = cols_ref[t, r]          # (n, 2)
            dt_r, c_r = dt[r:r + 1], c[r:r + 1]
            h = (jnp.exp(dt_r * A) * h_out_ref[r]
                 + (dt_r * c_r) * cols[:, 0:1])
            h_out_ref[r] = h
            outs.append(jnp.sum(h * cols[:, 1:2], axis=0, keepdims=True))
        y_ref[t] = y + (outs[0] if rb == 1 else jnp.concatenate(outs, axis=0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(state, dt, c, B, C, A, D, keep, *, interpret: bool):
    rows, tokens, E = dt.shape
    n = B.shape[-1]
    rb, tb = _blocks(rows, tokens)
    eb = _channel_block(E)
    # tokens lead: a program's rows are a sublane tile of (rows, channels)
    dt, c = (jnp.swapaxes(x, 0, 1) for x in (dt, c))
    cols = jnp.swapaxes(jnp.stack([B, C], axis=-1), 0, 1)  # (T, R, n, 2)
    keep = jnp.broadcast_to(
        keep.astype(jnp.float32)[:, None, None], (rows, n, 1))
    row_spec = pl.BlockSpec((tb, rb, eb), lambda i, e, t: (t, i, e))
    state_spec = pl.BlockSpec((rb, n, eb), lambda i, e, t: (i, 0, e))
    y, state = pl.pallas_call(
        functools.partial(_kernel, rb=rb, tb=tb),
        grid=(rows // rb, E // eb, tokens // tb),
        in_specs=[pl.BlockSpec((rb, n, 1), lambda i, e, t: (i, 0, 0)),
                  row_spec, row_spec,
                  pl.BlockSpec((tb, rb, n, 2), lambda i, e, t: (t, i, 0, 0)),
                  pl.BlockSpec((n, eb), lambda i, e, t: (0, e)),
                  pl.BlockSpec((1, eb), lambda i, e, t: (0, e)),
                  state_spec],
        out_specs=[row_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct(dt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan_update",
    )(keep, dt, c, cols, A, D[None], state)
    return jnp.swapaxes(y, 0, 1), state


def selective_scan_update(state, dt, c, B, C, A, D, live, keep):
    """`selective_scan_reference`'s results from the Pallas kernel, or from
    the reference where the gate declines (with a warning on a TPU)."""
    interpret = jax.default_backend() != "tpu"
    rows, _, channels = dt.shape
    gate = selective_scan_gate(rows, B.shape[-1], channels, interpret)
    if gate is not None:
        warn_reference("selective_scan_update", tuple(dt.shape), gate)
        return selective_scan_reference(state, dt, c, B, C, A, D, live, keep)
    dt, c, B, C = _prepare(dt, c, B, C, live)
    return _call(state.astype(jnp.float32), dt, c, B, C,
                 A.astype(jnp.float32), D.astype(jnp.float32), keep,
                 interpret=interpret)
