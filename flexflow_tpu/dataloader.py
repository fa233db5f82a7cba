"""Data loading.

Reference: SingleDataLoader (python/flexflow_dataloader.h:34-100 +
flexflow_dataloader.cc) — a two-stage path: the full numpy array is staged
into zero-copy host memory once, then a per-batch GPU index task copies each
shard's slice into framebuffer. TPU-native equivalent: the full array stays in
host RAM (numpy); each `next_batch` slices on host and `device_put`s with the
input's NamedSharding, so each chip receives exactly its shard over PCIe —
same data-movement shape, no task runtime. Batches are issued round-robin
with an epoch-stable order, matching reference semantics (sequential batches,
reset() to restart).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import NamedSharding

from . import telemetry


class SingleDataLoader:
    def __init__(self, ffmodel, batch_tensor, full_array: np.ndarray):
        self.ffmodel = ffmodel
        self.batch_tensor = batch_tensor
        self.full_array = np.ascontiguousarray(full_array)
        self.num_samples = int(full_array.shape[0])
        self.batch_size = batch_tensor.dims[0]
        self.next_index = 0
        # the input's device sharding, resolved once on first use: the
        # spec cannot change after compile, so the per-batch linear scan
        # of graph.sources() was pure overhead in the hot path
        self._sharding = None

    @property
    def num_batches(self) -> int:
        return self.num_samples // self.batch_size

    def reset(self):
        self.next_index = 0

    # ---- resumable cursor (resilience/): a checkpointed run restores the
    # loader mid-epoch and the next batch is exactly the one the killed run
    # would have issued
    def state_dict(self) -> dict:
        return {"next_index": int(self.next_index)}

    def load_state_dict(self, state: dict):
        idx = int(state["next_index"])
        if idx < 0 or idx > self.num_samples:
            raise ValueError(
                f"dataloader cursor {idx} out of range for "
                f"{self.num_samples} samples")
        self.next_index = idx

    def next_batch(self, ffmodel=None) -> np.ndarray:
        if self.next_index + self.batch_size > self.num_samples:
            self.next_index = 0
        sl = slice(self.next_index, self.next_index + self.batch_size)
        self.next_index += self.batch_size
        return self.full_array[sl]

    def _resolve_sharding(self):
        """The input node's NamedSharding, cached at first use (False
        when the tensor is not a graph input — plain device_put then)."""
        if self._sharding is None:
            ff = self.ffmodel
            spec = ff._input_partition_spec(self.batch_tensor.name)
            self._sharding = (NamedSharding(ff.mesh, spec)
                              if spec is not None else False)
        return self._sharding

    def next_batch_sharded(self):
        """Batch pre-placed on the mesh with the input's sharding. The
        data_wait span covers slice + device_put — the host-side stall a
        training step pays before dispatch (telemetry/)."""
        with telemetry.span("data_wait"):
            batch = self.next_batch()
            sharding = self._resolve_sharding()
            if sharding is not False:
                return jax.device_put(batch, sharding)
            return jax.device_put(batch)
