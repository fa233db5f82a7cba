"""The chunk steps' share of the joined steps' device time: what
`prefill_share_pct` times from outside, a call of `engine.step()` on the
harness's clock, seen from the device, a step's own execution."""

from benchmarks import device_steps


def read(run):
    found = device_steps.sound(run)
    if not found:
        return None
    ms = {kind: sum(s.ms for s in found.steps if s.kind == kind)
          for kind in ("decode", "chunk")}
    return 100.0 * ms["chunk"] / (ms["chunk"] + ms["decode"])
