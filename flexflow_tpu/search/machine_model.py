"""Analytic TPU machine model: compute roofline + ICI/DCN collectives.

Reference: the MachineModel hierarchy (include/flexflow/simulator.h:212-615 —
SimpleMachineModel's intra/inter bandwidths, EnhancedMachineModel's per-path
congestion, NetworkedMachineModel's topology routing). On TPU the network is
a wraparound torus of uniform ICI links per chip, so the analytic model is
simpler and *more* accurate than the reference's NIC/NVLink approximations:
bandwidth-optimal collectives on a ring/torus have closed-form costs.

Collective costs over an axis of size n with per-chip payload B bytes on a
ring (all links active, bidirectional):
  all_gather / reduce_scatter:  (n-1)/n · B_full / bw      (B_full = n·B out)
  all_reduce:                   2·(n-1)/n · B / bw
  all_to_all:                   (n-1)/n · B / bw           (B = per-chip send)
  ppermute (ring hop):          B / bw
Latency: per-hop α added once per step ((n-1) steps).

Chip specs default to the device JAX reports; numbers are public datasheet
values (bf16 peak, HBM BW, ICI per-link).
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops: float      # bf16 FLOP/s
    hbm_bandwidth: float   # B/s
    hbm_bytes: float       # device memory capacity
    ici_bandwidth: float   # B/s per link direction
    ici_links: int         # torus links per chip
    ici_latency: float = 1e-6
    dcn_bandwidth: float = 25e9 / 8  # per-host, conservative
    dcn_latency: float = 10e-6


# Peaks of one chip. The one table every consumer reads — the search's
# roofline, the goodput anchor (model.py) and bench.py's MFU. v5e: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of interconnect over 4 links); the other rows
# are the same pages' figures for their chips. The `cpu` row is for the
# search tests on the virtual CPU mesh, not a device anyone measures.
CHIPS = {
    "v5e": ChipSpec("v5e", 197e12, 8.19e11, 16e9, 4.5e10, 4),
    "v5p": ChipSpec("v5p", 459e12, 2.765e12, 95e9, 9e10, 6),
    "v4": ChipSpec("v4", 275e12, 1.2e12, 32e9, 4.5e10, 6),
    "v6e": ChipSpec("v6e", 918e12, 1.64e12, 32e9, 9e10, 4),
    "cpu": ChipSpec("cpu", 2e11, 5e10, 32e9, 1e10, 2),
}

# `device_kind` as JAX reports it -> row of CHIPS.
DEVICE_KINDS = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v5": "v5p",
    "TPU v5p": "v5p",
    "TPU v6 lite": "v6e",
    "TPU v6e": "v6e",
    "cpu": "cpu",
}


def chip_for(device) -> ChipSpec:
    """The table's row for a JAX device. A device that is not in the
    table is an error, not a default: every number priced or normalised
    against the wrong chip's peaks would be wrong without a sign of it."""
    kind = device.device_kind
    if kind not in DEVICE_KINDS:
        raise ValueError(
            f"unknown device_kind {kind!r} (platform {device.platform!r}): "
            f"add its peaks to search/machine_model.CHIPS; have "
            f"{sorted(DEVICE_KINDS)}")
    return CHIPS[DEVICE_KINDS[kind]]


def detect_chip() -> ChipSpec:
    import jax

    return chip_for(jax.devices()[0])


@dataclass
class TPUMachineModel:
    """Collective cost oracle over the mesh's named axes.

    `axis_links[axis]` = number of physical torus links serving that mesh
    axis (a mesh axis folded over 2 torus dims gets 2× bandwidth); axes that
    span hosts use DCN instead (axis_over_dcn)."""

    chip: ChipSpec
    axis_sizes: dict  # axis name -> size
    axis_links: dict | None = None
    axis_over_dcn: frozenset = frozenset()
    # per-axis effective-bandwidth derating for shared/contended paths —
    # the EnhancedMachineModel congestion knob (simulator.h:279) recast:
    # 1.0 = dedicated links, >1 divides the axis's bandwidth
    axis_congestion: dict | None = None

    def _bw(self, axis: str) -> float:
        cong = (self.axis_congestion or {}).get(axis, 1.0)
        if axis in self.axis_over_dcn:
            return self.chip.dcn_bandwidth / cong
        links = (self.axis_links or {}).get(axis, 1)
        return self.chip.ici_bandwidth * links / cong

    def _lat(self, axis: str) -> float:
        return (self.chip.dcn_latency if axis in self.axis_over_dcn
                else self.chip.ici_latency)

    def axis_size(self, axis: str) -> int:
        return self.axis_sizes.get(axis, 1)

    def all_gather(self, out_bytes: float, axis: str) -> float:
        n = self.axis_size(axis)
        if n <= 1:
            return 0.0
        return (n - 1) / n * out_bytes / self._bw(axis) + (n - 1) * self._lat(axis)

    def reduce_scatter(self, in_bytes: float, axis: str) -> float:
        return self.all_gather(in_bytes, axis)

    def all_reduce(self, bytes_per_chip: float, axis: str) -> float:
        n = self.axis_size(axis)
        if n <= 1:
            return 0.0
        return (2.0 * (n - 1) / n * bytes_per_chip / self._bw(axis)
                + 2 * (n - 1) * self._lat(axis))

    def all_to_all(self, send_bytes_per_chip: float, axis: str) -> float:
        n = self.axis_size(axis)
        if n <= 1:
            return 0.0
        return ((n - 1) / n * send_bytes_per_chip / self._bw(axis)
                + (n - 1) * self._lat(axis))

    def ppermute(self, bytes_per_chip: float, axis: str) -> float:
        return bytes_per_chip / self._bw(axis) + self._lat(axis)

    def rotate(self, bytes_per_chip: float, axis: str) -> float:
        """One ring-rotation step (every chip shifts to its +1 neighbor,
        INCLUDING the wrap pair) — ring attention's K/V hop. On the uniform
        model this equals ppermute; TorusMachineModel prices the wrap edge
        of a non-wraparound axis as a serialized multi-hop traversal."""
        return self.ppermute(bytes_per_chip, axis)

    def compute_time(self, flops: float, bytes_touched: float) -> float:
        """Roofline: max of MXU time and HBM time (the simulator's measured
        per-op µs analog; see CostModel.calibrate for the measured path)."""
        return max(flops / self.chip.peak_flops,
                   bytes_touched / self.chip.hbm_bandwidth)


@dataclass(frozen=True)
class AxisTopology:
    """Physical shape of one mesh axis on the interconnect.

    The NetworkedMachineModel topology analog (simulator.h:212-615,
    network.cc:1-586 — arbitrary adjacency + ECMP shortest-path routing)
    specialized to what TPU fabrics actually are: each mesh axis maps onto
    one or more torus dimensions (`links` physical links per chip serve the
    axis), each either wrapped (full-pod torus dimension) or open (a
    sub-slice is a mesh, not a torus — no wraparound links). Routing on a
    1-D ring/line is shortest-path by construction, so the ECMP machinery
    reduces to closed forms (see TorusMachineModel)."""

    links: int = 1
    wraparound: bool = True
    over_dcn: bool = False


@dataclass
class TorusMachineModel(TPUMachineModel):
    """Topology-aware collective pricing on a (partial) torus.

    Where TPUMachineModel treats every axis as a uniform abstract pipe,
    this model derives collective costs from the axis's physical topology
    (the NetworkedMachineModel/EnhancedMachineModel analog,
    simulator.h:212-615 + network.cc routing, recast to torus closed forms
    instead of per-packet ECMP simulation):

    - ring collectives (all_gather / reduce_scatter / all_reduce) on a
      WRAPPED axis use both ring directions (half the payload each way):
      2× the effective bandwidth of an open (non-wraparound) axis, where
      the missing wrap link leaves only the one-directional-ring schedule;
    - all_to_all pays minimal-route hop·bytes transit spread over the
      axis's link-directions: mean hop distance n/4 on a wrapped ring vs
      (n²−1)/3n on an open line — long axes without wraparound get
      markedly more expensive, exactly the signal a flat model misses;
    - rotate (ring attention's K/V shift) is one neighbor hop everywhere
      on a wrapped axis, but on an open axis the wrap pair must traverse
      the whole line against traffic: (n−1) serialized hops;
    - DCN axes model per-host NIC fan-in: all `chips_per_host` chips of a
      host issue their cross-slice transfers through ONE shared NIC, so
      per-chip DCN bandwidth divides by the fan-in (the shared-bottleneck
      congestion the reference prices via per-path contention counts,
      machine_model.cc:1-1287).
    """

    topology: dict | None = None       # axis -> AxisTopology
    chips_per_host: int = 1            # DCN NIC fan-in

    def _topo(self, axis: str) -> AxisTopology:
        t = (self.topology or {}).get(axis)
        if t is not None:
            return t
        return AxisTopology(links=(self.axis_links or {}).get(axis, 1),
                            wraparound=True,
                            over_dcn=axis in self.axis_over_dcn)

    def _cong(self, axis: str) -> float:
        return (self.axis_congestion or {}).get(axis, 1.0)

    def _link_bw(self, axis: str) -> float:
        """Per-direction bandwidth × parallel links serving the axis."""
        t = self._topo(axis)
        if t.over_dcn:
            # shared per-host NIC: every chip on the host pushes its own
            # cross-slice stream through it simultaneously
            return self.chip.dcn_bandwidth / (
                max(1, self.chips_per_host) * self._cong(axis))
        return self.chip.ici_bandwidth * t.links / self._cong(axis)

    def _ring_bw(self, axis: str) -> float:
        """Effective ring-schedule bandwidth: a wrapped axis runs the
        bidirectional ring (payload halved each way)."""
        t = self._topo(axis)
        bw = self._link_bw(axis)
        if t.over_dcn:
            return bw  # DCN is switched, not a torus: direction-agnostic
        return bw * (2 if t.wraparound else 1)

    def _lat(self, axis: str) -> float:
        return (self.chip.dcn_latency if self._topo(axis).over_dcn
                else self.chip.ici_latency)

    def all_gather(self, out_bytes: float, axis: str) -> float:
        n = self.axis_size(axis)
        if n <= 1:
            return 0.0
        return ((n - 1) / n * out_bytes / self._ring_bw(axis)
                + (n - 1) * self._lat(axis))

    def all_reduce(self, bytes_per_chip: float, axis: str) -> float:
        n = self.axis_size(axis)
        if n <= 1:
            return 0.0
        return (2.0 * (n - 1) / n * bytes_per_chip / self._ring_bw(axis)
                + 2 * (n - 1) * self._lat(axis))

    def all_to_all(self, send_bytes_per_chip: float, axis: str) -> float:
        """Minimal-route transit: chip i sends B/n to each j over d(i,j)
        hops; total hop·bytes spreads over the axis's link-directions.
        Wrapped ring (even n): Σ_j d(i,j) = n²/4, 2n link-dirs
          → time = B·n / (8·link_bw).
        Open line: Σ_{i,j} d = n(n²−1)/3, 2(n−1) link-dirs
          → time = B·(n+1) / (6·link_bw).
        DCN (switched): every byte leaves the host once — the uniform
        (n−1)/n·B over the fan-in-derated NIC bandwidth."""
        n = self.axis_size(axis)
        if n <= 1:
            return 0.0
        t = self._topo(axis)
        bw = self._link_bw(axis)
        lat = self._lat(axis)
        if t.over_dcn:
            return (n - 1) / n * send_bytes_per_chip / bw + (n - 1) * lat
        if t.wraparound:
            # total transit B·n²/4 over 2n link-dirs (odd n: (n²−1)/4,
            # folded into the even form — off by <2% at n≥5)
            time = send_bytes_per_chip * n / (8 * bw)
        else:
            time = send_bytes_per_chip * (n + 1) / (6 * bw)
        return time + (n - 1) * lat

    def ppermute(self, bytes_per_chip: float, axis: str) -> float:
        """Neighbor hop (no wrap edge) — pipeline stage hand-off."""
        return bytes_per_chip / self._link_bw(axis) + self._lat(axis)

    def rotate(self, bytes_per_chip: float, axis: str) -> float:
        """Full ring rotation (wrap pair included). On an open axis the
        wrap transfer traverses all n−1 links of the line serially while
        they also carry the neighbor shifts — the whole step is gated by
        that traversal."""
        n = self.axis_size(axis)
        t = self._topo(axis)
        hop = bytes_per_chip / self._link_bw(axis) + self._lat(axis)
        if t.over_dcn or t.wraparound or n <= 2:
            return hop
        return (n - 1) * hop


def machine_model_from_file(path: str, mesh) -> TPUMachineModel:
    """--machine-model-file analog (reference EnhancedMachineModel config,
    simulator.h:279 + --machine-model-file in model.cc): a JSON description
    of the machine overriding the detected chip and topology heuristics.

    Format:
      {"chip": "v5p"                      # name from CHIPS, or an object:
               | {"name": ..., "peak_flops": ..., "hbm_bandwidth": ...,
                  "hbm_bytes": ..., "ici_bandwidth": ..., "ici_links": ...,
                  ["ici_latency", "dcn_bandwidth", "dcn_latency"]},
       "axis_links": {"data": 2, ...},    # torus links per mesh axis (opt)
       "dcn_axes": ["dcn"],               # axes that ride DCN (opt)
       "congestion": {"dcn": 2.0},        # per-axis bandwidth derating
                                          # (EnhancedMachineModel's
                                          # congestion, simulator.h:279)
       "topology": {"data": {"wraparound": false, "links": 2}},
                                          # per-axis physical shape: open
                                          # sub-slice axes vs wrapped torus
                                          # dims (NetworkedMachineModel)
       "chips_per_host": 4}               # DCN NIC fan-in (default: inferred
                                          # from the mesh size / host count)
    """
    import json

    with open(path) as f:
        data = json.load(f)
    chip_cfg = data.get("chip", None)
    if chip_cfg is None:
        chip = detect_chip()
    elif isinstance(chip_cfg, str):
        if chip_cfg not in CHIPS:
            raise ValueError(
                f"machine model file {path}: unknown chip {chip_cfg!r}; "
                f"have {sorted(CHIPS)}")
        chip = CHIPS[chip_cfg]
    else:
        name = chip_cfg.get("name", "custom")
        base = CHIPS.get(name)
        core = ("peak_flops", "hbm_bandwidth", "hbm_bytes",
                "ici_bandwidth", "ici_links")
        if base is None and not all(f in chip_cfg for f in core):
            # unknown base chip: every core field must be spelled out,
            # otherwise a typoed name would silently price against v5p
            missing = [f for f in core if f not in chip_cfg]
            raise ValueError(
                f"machine model file {path}: chip name {name!r} is not a "
                f"known base ({sorted(CHIPS)}) and the spec is missing "
                f"{missing}")
        base = base or CHIPS["v5p"]
        fields = {f: chip_cfg.get(f, getattr(base, f))
                  for f in ("name", "peak_flops", "hbm_bandwidth",
                            "hbm_bytes", "ici_bandwidth", "ici_links",
                            "ici_latency", "dcn_bandwidth", "dcn_latency")}
        fields["name"] = name
        chip = ChipSpec(**fields)
    from ..machine import AXIS_DCN

    axis_sizes = dict(mesh.shape) if hasattr(mesh, "shape") else dict(mesh)
    links = {a: 1 for a in axis_sizes}
    links.update({a: int(v) for a, v in data.get("axis_links", {}).items()
                  if a in links})
    # the canonical dcn axis always rides DCN, with or without a file entry
    # (same auto-marking as machine_model_for_mesh)
    over_dcn = {a for a in data.get("dcn_axes", ()) if a in axis_sizes}
    over_dcn |= {a for a in axis_sizes if a == AXIS_DCN}
    unknown = [a for a in data.get("congestion", {}) if a not in axis_sizes]
    if unknown:
        # a typoed axis name must not silently price as uncongested (same
        # strictness as the unknown-chip check above)
        raise ValueError(
            f"machine model file {path}: congestion axes {unknown} not in "
            f"the mesh (have {sorted(axis_sizes)})")
    congestion = {a: float(v) for a, v in data.get("congestion", {}).items()}
    bad = {a: v for a, v in congestion.items() if v < 1.0}
    if bad:
        # reject rather than silently clamp: a fractional value usually
        # means the user meant link efficiency (the inverse convention)
        raise ValueError(
            f"machine model file {path}: congestion factors must be >= 1 "
            f"(bandwidth derating), got {bad}")
    # "topology": {"axis": {"wraparound": bool, "links": int}} — the
    # NetworkedMachineModel config surface; "chips_per_host" sets the DCN
    # NIC fan-in. Unknown axis names rejected like congestion typos.
    topo_cfg = data.get("topology", {})
    unknown = [a for a in topo_cfg if a not in axis_sizes]
    if unknown:
        raise ValueError(
            f"machine model file {path}: topology axes {unknown} not in "
            f"the mesh (have {sorted(axis_sizes)})")
    topology = {}
    for a in axis_sizes:
        spec = topo_cfg.get(a, {})
        topology[a] = AxisTopology(
            links=int(spec.get("links", links.get(a, 1))),
            wraparound=bool(spec.get("wraparound", a not in over_dcn)),
            over_dcn=a in over_dcn)
    if "chips_per_host" in data:
        chips_per_host = max(1, int(data["chips_per_host"]))
    else:
        # infer like machine_model_for_mesh: chips ÷ hosts (hosts = product
        # of the DCN axes) — a file supplied just to tweak congestion must
        # not silently drop the NIC fan-in derating
        total = hosts = 1
        for a, v in axis_sizes.items():
            total *= v
            if a in over_dcn:
                hosts *= v
        chips_per_host = max(1, total // hosts) if hosts > 1 else 1
    return TorusMachineModel(
        chip, axis_sizes, links, frozenset(over_dcn), congestion or None,
        topology=topology, chips_per_host=chips_per_host)


def machine_model_for_mesh(mesh, chip: ChipSpec | None = None,
                           num_hosts: int = 1) -> TorusMachineModel:
    from ..machine import AXIS_DCN

    chip = chip or detect_chip()
    axis_sizes = dict(mesh.shape) if hasattr(mesh, "shape") else dict(mesh)
    # collectives on the dedicated DCN axis (multi-host meshes lead with
    # it, machine.MULTIHOST_AXES) cross the data-center network
    over_dcn = {a for a in axis_sizes if a == AXIS_DCN}
    if num_hosts > 1 and not over_dcn and axis_sizes:
        # legacy spelling: a multi-host run without an explicit dcn axis —
        # the outermost axis spans hosts
        over_dcn.add(next(iter(axis_sizes)))
    # heuristic: the largest ICI axis gets folded over 2 torus dims when
    # the chip has >4 links (v5p 3D torus)
    links = {a: 1 for a in axis_sizes}
    ici_axes = [a for a in axis_sizes if a not in over_dcn]
    if chip.ici_links >= 6 and ici_axes:
        big = max(ici_axes, key=lambda a: axis_sizes[a])
        links[big] = 2
    # default topology: ICI axes are wrapped torus dimensions (full-pod
    # slices wrap; declare open sub-slice axes via --machine-model-file),
    # the DCN NIC is shared by every chip of a host
    topology = {a: AxisTopology(links=links[a], wraparound=a not in over_dcn,
                                over_dcn=a in over_dcn)
                for a in axis_sizes}
    total = 1
    for v in axis_sizes.values():
        total *= v
    chips_per_host = max(1, total // max(1, num_hosts)) if num_hosts > 1 else 1
    return TorusMachineModel(chip, axis_sizes, links, frozenset(over_dcn),
                             topology=topology,
                             chips_per_host=chips_per_host)
