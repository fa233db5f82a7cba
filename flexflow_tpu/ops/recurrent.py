"""What the recurrent layers share: gated delta-rule attention
(ops/delta_attention.py) and the selective state-space layer (ops/ssm.py)
both keep, a slot, a state of fixed size and the last `taps - 1` inputs of
a short causal depthwise convolution, and both take the serving engine's
two layouts of a step alike.

- The convolution over a tail kept a slot: `conv_window` puts a row's
  earlier inputs before its tokens (none where the row starts a request),
  `causal_conv` is the SiLU of the taps over it (the taps' sum as it is
  under `activation=None`: ops/short_conv.py), `next_tail` the row's last
  `taps - 1` inputs up to its last live token.
- `decode_rows`, the decode op's forward over its state leaves: which
  tokens are live, which rows start from nothing, the slots' rows and then
  one chunk's rows run in order from the slot `state_slot` names.
- `slot_state`, the DecodeState of such leaves and what it cannot follow.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import BY_SLOT, HANDOFF, PREFIX, REWIND, DecodeState, StateLeaf


def infer_shapes(p, in_shapes):
    """A recurrent op's output: its input's rows, `p.embed_dim` wide."""
    return [tuple(in_shapes[0][:-1]) + (p.embed_dim,)]


def conv_window(tail, u, keep):
    """(rows, taps - 1 + tokens, width): each row's `tail` (rows, taps -
    1, width) of earlier inputs before its tokens' `u` (rows, tokens,
    width), in u's dtype; a row whose `keep` is false starts from an empty
    window."""
    tail = jnp.where(keep[:, None, None], tail.astype(u.dtype), 0)
    return jnp.concatenate([tail, u], axis=1)


def causal_conv(taps, window, tokens: int, bias=None, activation="silu"):
    """SiLU of the causal depthwise convolution of `taps` (taps, width)
    over `window` (conv_window), plus `bias` (width,) where there is one;
    (rows, tokens, width) float32. `activation` None: the sum as it is."""
    taps = taps.astype(jnp.float32)
    wf = window.astype(jnp.float32)
    y = sum(taps[i] * wf[:, i:i + tokens] for i in range(taps.shape[0]))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if activation is None:
        return y
    if activation != "silu":
        raise ValueError(f"causal_conv: activation is 'silu' or None, got "
                         f"{activation!r}")
    return y * jax.nn.sigmoid(y)


def next_tail(window, live, taps: int):
    """A row's last `taps - 1` inputs up to its last live token (live
    tokens lead their row): (rows, taps - 1, width) of `window`."""
    n_live = jnp.sum(live, axis=1).astype(jnp.int32)
    at = n_live[:, None] + jnp.arange(taps - 1)
    return jnp.take_along_axis(window, at[:, :, None], axis=1)


def slot_state(leaves, slots: int, what: str, step_counts=None
               ) -> DecodeState:
    """The DecodeState of per-slot `leaves` ((name, width, dtype), ..): not
    paged, not shareable block by block, reset when a slot's row starts a
    request (position 0). `what` names the layer's kind in what is said
    when a prefix cache, a rewind or a handoff is asked of such a graph."""
    return DecodeState(
        tuple(StateLeaf(name, BY_SLOT, width, dtype)
              for name, width, dtype in leaves),
        slots=slots, cannot=dict.fromkeys(
            (HANDOFF, REWIND, PREFIX),
            f"recurrent layers ({what}: {{layer}}, ...): their per-slot "
            f"state is neither rewound nor handed off, nor kept at a cached "
            f"prefix's end"),
        step_counts=step_counts)


def decode_rows(what: str, slots: int, max_seq_len: int, inputs, leaves,
                run):
    """A recurrent decode op's forward over the engine's two layouts of a
    step (serving/engine.py):
      the rectangle (slots, q): row i is slot i's next q tokens in order;
      rows (slots + q, 1): rows [0, slots) are one token of their own slot,
      rows [slots, slots + q) are q consecutive tokens of ONE slot, the one
      the `state_slot` input names for them, run in order from that slot's
      state and written back to it.
    `inputs` = (x, positions, state_slot); a token is live where 0 <=
    position < max_seq_len, and live tokens lead their row; a row whose
    first live token is at position 0 is a request's first and starts from
    nothing (keep false). `leaves`: the state leaves (slots, ..), in the
    order `run(x (rows, tokens, hidden), live, keep, *leaves) -> (y,
    *new leaves)` takes and returns them. Returns (y, new leaves), each
    leaf in the dtype it came in."""
    x, positions, state_slot = inputs
    rows, q_len, _ = x.shape
    positions = positions.astype(jnp.int32)
    live = (positions >= 0) & (positions < max_seq_len)
    # a row that starts a request starts from nothing
    keep = ~(live[:, 0] & (positions[:, 0] == 0))
    n = slots
    if rows < n or (rows > n and q_len != 1):
        raise ValueError(
            f"{what}: a call has the {n} slots' rows, and past them "
            f"single-query rows of one chunk; got ({rows}, {q_len})")

    def rest(new, old):
        return tuple(a.astype(b.dtype) for a, b in zip(new, old))

    y, *new = run(x[:n], live[:n], keep[:n], *leaves)
    leaves = rest(new, leaves)
    if rows > n:
        # one chunk: its tokens in order from its slot's state
        c = state_slot[n, 0].astype(jnp.int32)
        y_c, *new = run(
            x[n:, 0][None], live[n:, 0][None], keep[n][None],
            *(jax.lax.dynamic_index_in_dim(a, c, keepdims=True)
              for a in leaves))
        leaves = tuple(
            jax.lax.dynamic_update_index_in_dim(a, b[0].astype(a.dtype), c,
                                                axis=0)
            for a, b in zip(leaves, new))
        y = jnp.concatenate([y, y_c[0][:, None]], axis=0)
    return y, leaves
