"""Mean host milliseconds an engine iteration inside `ff/serve.stage`,
over the traced window's iterations that staged a step; beside it the
reader prints the spans inside it, which carry its name and a `part`:
`build` (the host's arrays and the page table), `put` (the host-to-device
puts) and `feed` (the token select and the rng split, two small device
programs)."""

from benchmarks import device_steps


def read(run):
    found = device_steps.sound(run)
    its = [it for it in (found.iterations if found else [])
           if it.stage_ns is not None]
    if not its:
        return None
    mean = sum(it.stage_ns for it in its) / len(its) / 1e6
    parts = {name: sum(it.parts[name] for it in its) / len(its) / 1e6
             for name in device_steps.STAGE_PARTS}
    print(f"[steps] host ms an iteration in serve.stage {mean:.3f}: "
          + ", ".join(f"{name} {ms:.3f}" for name, ms in parts.items())
          + f", its own {mean - sum(parts.values()):.3f} "
          f"({len(its)} iterations)")
    return mean
