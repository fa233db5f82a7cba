"""How a Pallas kernel call reaches the device, and what is said when it
does not.

Two facts of the TPU lowering live here so every kernel call site shares
them, and the means to read back which kernels a compiled program holds
(`pallas_kernels`):

- A shape gate may route a call to the XLA reference. On the CPU test
  mesh that is routine; on a TPU it means the hot path the user asked for
  (`impl="flash"` / `"auto"`) is not the one running, so it is said:
  `warn_reference` raises a `KernelFallbackWarning` naming op, shapes and
  gate. It fires at trace time, i.e. once per compile per distinct
  message (Python's warning registry folds the repeats of a layer stack).
- GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
  automatically partitioned"): under a multi-device mesh the call must run
  per shard inside `shard_map`. `per_shard` does that; operands are
  resharded to `in_specs` by the partitioner, so a spec that differs from
  the plan costs a collective, never correctness.
"""

from __future__ import annotations

import collections
import math
import re
import warnings

import jax


class KernelFallbackWarning(UserWarning):
    """A TPU run resolved a Pallas kernel request to the XLA reference."""


def warn_reference(op: str, shapes, gate: str) -> None:
    """Say that `op` on `shapes` takes the XLA reference because `gate`
    fired. Silent off-TPU, where the reference is the expected path."""
    if jax.default_backend() != "tpu":
        return
    warnings.warn(
        f"{op} {shapes}: {gate} — running the XLA reference, not the "
        f"Pallas kernel", KernelFallbackWarning, stacklevel=3)


def spec_entries(spec, ndim: int) -> tuple:
    """`spec` padded with None to `ndim` entries."""
    entries = tuple(spec) if spec is not None else ()
    return entries + (None,) * (ndim - len(entries))


def shards_of(mesh, entry) -> int:
    """Number of shards a PartitionSpec entry (None | axis | tuple of
    axes) cuts a dim into on `mesh`."""
    if mesh is None or entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    return math.prod(int(mesh.shape[a]) for a in axes)


def per_shard(fn, mesh, in_specs, out_specs):
    """`fn` run once per shard of `mesh` (identity on one device)."""
    if mesh is None or mesh.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pallas_kernels(hlo_text: str) -> collections.Counter:
    """{kernel name: count} of the Mosaic custom calls in a compiled
    executable's text (`compiled.as_text()`): what the program runs, as
    opposed to what was asked for. The names are the `name=` of each
    `pallas_call`, read from the custom call's own op_name metadata
    (".../layer_norm_fwd/pallas_call", or wrapped by autodiff as in
    ".../transpose(jvp(layer_norm_bwd))/pallas_call"); a path that
    resolved to an XLA reference has none."""
    scopes = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?'
        r'op_name="[^"]*?([^/"]+)/pallas_call', hlo_text)
    return collections.Counter(
        re.findall(r"\w+", scope)[-1] for scope in scopes)
