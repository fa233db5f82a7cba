"""Median host milliseconds of an engine iteration that dispatched a step
of kind `decode`, less its `ff/serve.fetch`: the `ff/serve.iteration`
span without the part in which the host only waits for the device, so
what the host itself needs an iteration before it can wait. Above
`device_step_ms.decode.serve`, the host paces the steps that only
decode."""

from benchmarks import device_steps, harness


def read(run):
    found = device_steps.sound(run)
    its = [it for it in (found.iterations if found else [])
           if it.kind == "decode"]
    if not its:
        return None
    own = harness.median([(it.ns - it.fetch_ns) / 1e6 for it in its])
    print(f"[steps] host ms an iteration that dispatched a decode step, "
          f"medians of {len(its)}: the iteration "
          f"{harness.median([it.ns / 1e6 for it in its]):.3f}, its fetch "
          f"{harness.median([it.fetch_ns / 1e6 for it in its]):.3f}, the "
          f"rest {own:.3f}")
    return own
