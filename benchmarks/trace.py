"""From a profiler trace (xplane, read with jax.profiler.ProfileData) to
the numbers the per-layer readers use.

What a TPU trace of this installation holds (looked at by hand, PR 24):
one plane per chip named `/device:TPU:<n>` whose line `XLA Ops` has one
event per executed HLO instruction, named by the instruction's whole text
(`%flash_attention_fwd_packed.2 = (bf16[...]) custom-call(...)`), and
whose line `Async XLA Ops` has the asynchronous copies and collectives from
start to done; a plane `/host:CPU` whose thread lines hold the
`jax.profiler.TraceAnnotation` spans of the harness (named `bench/...`).
Starts and durations are nanoseconds on one clock for all planes.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def op_name(event_name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    name = event_name.split(" = ", 1)[0].strip()
    return name[1:] if name.startswith("%") else name


def op_family(event_name: str) -> str:
    """`fusion.12` -> `fusion`: the instruction's name without the number
    XLA appends, so that the 24 layers' copies of one kernel add up."""
    return re.sub(r"(\.\d+)+$", "", op_name(event_name))


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def total(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy, lo, hi):
    """The idle [start, end) intervals of [lo, hi) given merged busy ones."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


@dataclasses.dataclass
class Chip:
    index: int
    ops: list          # (name, start_ns, end_ns), the XLA Ops line
    async_ops: list    # the same of the Async XLA Ops line


@dataclasses.dataclass
class Trace:
    chips: list        # Chip, by device index
    spans: list        # (name, start_ns, end_ns) harness spans, host plane
    window: tuple      # (start_ns, end_ns): the bench/window span, else
    #                    the extent of chip 0's operations

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy(self, chip: int = 0):
        c = self.chips[chip]
        return clip(union((a, b) for _, a, b in c.ops), *self.window)

    def busy_s(self, chip: int = 0) -> float:
        return total(self.busy(chip)) / 1e9

    def mean_busy_s(self, chips: int) -> float:
        """Busy seconds averaged over the first `chips` chips."""
        return sum(self.busy_s(i) for i in range(chips)) / chips

    def idle_pct(self, chip: int = 0) -> float:
        return 100.0 * (1.0 - self.busy_s(chip) / self.window_s)

    def seconds_of(self, match, chip: int = 0, lines=("ops",)) -> float:
        """Device seconds inside the window of chip's events whose
        instruction name `match` accepts (union, so overlap counts once)."""
        c = self.chips[chip]
        events = []
        if "ops" in lines:
            events += c.ops
        if "async" in lines:
            events += c.async_ops
        hit = [(a, b) for name, a, b in events if match(op_name(name))]
        return total(clip(union(hit), *self.window)) / 1e9

    def device_ops(self, chip: int = 0, top: int = 10):
        """[[family, seconds], ...]: device time by instruction family."""
        by = {}
        lo, hi = self.window
        for name, a, b in self.chips[chip].ops:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                fam = op_family(name)
                by[fam] = by.get(fam, 0.0) + (b - a) / 1e9
        ranked = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v] for k, v in ranked]

    def span_at(self, t) -> str:
        """The innermost harness span that covers time t, by name without
        the prefix; `outside` where none does."""
        best = None
        for name, a, b in self.spans:
            if a <= t < b and name != WINDOW_SPAN:
                if best is None or (b - a) < (best[2] - best[1]):
                    best = (name, a, b)
        return best[0][len(SPAN_PREFIX):] if best else "outside"

    def idle_gaps(self, chip: int = 0, top: int = 10):
        """[[label, seconds], ...]: idle device time inside the window by
        what the harness was doing meanwhile. A gap is cut where a harness
        span starts or ends, and each piece goes to the innermost span
        over it. Host and device clocks agree to about a millisecond."""
        cuts = sorted({t for _, a, b in self.spans for t in (a, b)})
        labels = [self.span_at((lo + hi) / 2)
                  for lo, hi in zip(cuts, cuts[1:])]
        by = {}
        for a, b in gaps(self.busy(chip), *self.window):
            first, last = bisect.bisect_right(cuts, a), bisect.bisect_left(
                cuts, b)
            edges = [a] + cuts[first:last] + [b]
            for i, (lo, hi) in enumerate(zip(edges, edges[1:])):
                at = first - 1 + i
                label = labels[at] if 0 <= at < len(labels) else "outside"
                by[label] = by.get(label, 0.0) + (hi - lo) / 1e9
        ranked = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v] for k, v in ranked]


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def read(profile) -> Trace:
    """A Trace from a jax.profiler.ProfileData."""
    chips, spans = [], []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, async_ops = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line)
                elif line.name == ASYNC_LINE:
                    async_ops = _events(line)
            chips.append(Chip(int(m.group(1)), ops, async_ops))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith(SPAN_PREFIX)]
    chips.sort(key=lambda c: c.index)
    if not chips or not chips[0].ops:
        raise ValueError("the trace holds no device operation")
    windows = [(a, b) for name, a, b in spans if name == WINDOW_SPAN]
    if windows:
        window = windows[0]
    else:
        window = (min(a for _, a, _ in chips[0].ops),
                  max(b for _, _, b in chips[0].ops))
    return Trace(chips, spans, window)


def read_file(path: str) -> Trace:
    import jax

    return read(jax.profiler.ProfileData.from_file(path))


def newest_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no xplane file under {directory}")
    return max(found, key=os.path.getmtime)
