"""The gated delta rule's state update (ops/delta_attention.py): a head's
state S (d_k x d_v, float32) takes one token as

    S' = Diag(alpha_t) S          alpha_t in (0, 1)^d_k, a decay a key channel
    S  = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S^T q_t

`delta_rule_reference` is the recurrence in jnp, a `lax.scan` over the
tokens of each row: the CPU path and the numerics oracle. `delta_rule_update`
runs it as one Pallas kernel where `delta_rule_gate` lets it: the grid is
(rows, head blocks, tokens), a program holds a block of heads' states in
VMEM, and the state's block index does not depend on the token, so a row's
state is read from HBM once and written once however many tokens the row
has. One read and one write of the state is the floor of a decode step
(one token a row); XLA's unfused form passes over it three or four times.
The state is aliased in place.

Rows and tokens: `state` is (rows, heads, d, d); q, k, v, alpha are
(rows, tokens, heads, d), beta (rows, tokens, heads), `live` (rows,
tokens) bool, `keep` (rows,) bool. A token that is not live leaves the
state as it is and gives a zero output. A row whose `keep` is false starts
from the zero state (its first token is a request's first): the reset
costs no pass of its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import warn_reference

# heads a program holds: 16 states of 128 x 128 float32 are 1 MiB, in and
# out and double-buffered 4 MiB of the 16 MiB a Mosaic kernel gets
_HEAD_BLOCKS = (16, 8)


def _prepare(q, k, v, alpha, beta, live, keep):
    """The recurrence's operands in float32 with dead tokens made the
    identity (alpha 1, beta 0, q = k = v = 0) and the reset folded into
    the first token's decay (alpha 0 forgets everything)."""
    f = jnp.float32
    m = live[:, :, None, None]
    a = jnp.where(m, alpha.astype(f), 1.0)
    first = jnp.arange(a.shape[1])[None, :, None, None] == 0
    a = jnp.where(first & ~keep[:, None, None, None], 0.0, a)
    b = jnp.where(live[:, :, None], beta.astype(f), 0.0)
    q, k, v = (jnp.where(m, x.astype(f), 0.0) for x in (q, k, v))
    return q, k, v, a, b


def delta_rule_reference(state, q, k, v, alpha, beta, live, keep):
    """(o (rows, tokens, heads, d) float32, new state): module docstring."""
    q, k, v, a, b = _prepare(q, k, v, alpha, beta, live, keep)

    def token(s, xs):
        qt, kt, vt, at, bt = xs            # (rows, heads, d), bt (rows, heads)
        s = s * at[..., None]
        u = vt - jnp.einsum("nhkv,nhk->nhv", s, kt,
                            precision=jax.lax.Precision.HIGHEST)
        s = s + (bt[..., None] * kt)[..., None] * u[..., None, :]
        o = jnp.einsum("nhkv,nhk->nhv", s, qt,
                       precision=jax.lax.Precision.HIGHEST)
        return s, o

    state, o = jax.lax.scan(
        token, state.astype(jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, a, b)))
    return jnp.moveaxis(o, 0, 1), state


def delta_rule_gate(heads: int, d: int, interpret: bool) -> str | None:
    """Why the kernel cannot take this shape, or None: a state is a
    (sublane, lane) tile of d x d, heads go a block to a program."""
    if d % 128 != 0 and not interpret:
        return f"head_dim {d} % 128 != 0"
    if d % 8 != 0:
        return f"head_dim {d} % 8 != 0"
    if not any(heads % hb == 0 for hb in _HEAD_BLOCKS):
        return f"heads {heads} % 8 != 0"
    return None


def _kernel(cols_ref, v_ref, s_ref, o_ref, s_out_ref, *, hb: int):
    @pl.when(pl.program_id(2) == 0)
    def _load():
        s_out_ref[...] = s_ref[...]

    # a key channel lies on a sublane: alpha, k, beta k and q come as
    # columns (d, 1), a head a lane; v and o are rows (1, d)
    cols = cols_ref[0, 0, 0]              # (d, 4 hb)
    vv = v_ref[0, 0]                      # (hb, d)
    for h in range(hb):
        a, k, kb, q = (cols[:, j * hb + h:j * hb + h + 1] for j in range(4))
        s = s_out_ref[0, h] * a
        u = vv[h:h + 1] - jnp.sum(s * k, axis=0, keepdims=True)
        s = s + kb * u
        s_out_ref[0, h] = s
        o_ref[0, 0, h:h + 1] = jnp.sum(s * q, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(state, q, k, v, a, b, *, interpret: bool):
    rows, tokens, heads, d = q.shape
    hb = next(n for n in _HEAD_BLOCKS if heads % n == 0)

    def columns(x):  # (rows, tokens, heads / hb, d, hb)
        return jnp.swapaxes(x.reshape(rows, tokens, heads // hb, hb, d),
                            -1, -2)

    cols = jnp.concatenate(
        [columns(x) for x in (a, k, b[..., None] * k, q)], axis=-1)
    state_spec = pl.BlockSpec((1, hb, d, d), lambda n, g, t: (n, g, 0, 0))
    row_spec = pl.BlockSpec((1, 1, hb, d), lambda n, g, t: (n, t, g, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid=(rows, heads // hb, tokens),
        in_specs=[pl.BlockSpec((1, 1, 1, d, 4 * hb),
                               lambda n, g, t: (n, t, g, 0, 0)),
                  row_spec, state_spec],
        out_specs=[row_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="delta_rule_update",
    )(cols, v, state)
    return o, state


def delta_rule_update(state, q, k, v, alpha, beta, live, keep):
    """`delta_rule_reference`'s results from the Pallas kernel, or from
    the reference where the gate declines (with a warning on a TPU)."""
    interpret = jax.default_backend() != "tpu"
    gate = delta_rule_gate(q.shape[2], q.shape[3], interpret)
    if gate is not None:
        warn_reference("delta_rule_update", tuple(q.shape), gate)
        return delta_rule_reference(state, q, k, v, alpha, beta, live, keep)
    q, k, v, a, b = _prepare(q, k, v, alpha, beta, live, keep)
    return _call(state.astype(jnp.float32), q, k, v, a, b,
                 interpret=interpret)
