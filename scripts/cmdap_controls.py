"""The controls of `cmdap-serve-agentmix` on the chip: one run of the cell
whose every comparison (the pre-window check, the two served streams, the
global layer's rows of their turns) is made against the sound reference
AND against each spoil of it, on the same recorded logits and pool rows,
so that eight controls cost one set-up and one window:

    chiprun -- python scripts/cmdap_controls.py --seed <n>

prints a `[controls]` line a comparison a spoil: the logit error, the
cache error and the routings beyond the margin, to hold against
benchmarks/command_a_plus_reference.py's LOGIT_TOL, CACHE_TOL and
ROUTE_MARGIN (each spoil has to fail one of them in one comparison at
least). `--control lost_window_block` runs the job's own control instead
(the replay over zeroed window blocks: another run, since the replay is
what differs). The result line is the cell's own, of the sound reference.
Needs a TPU.
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
CELL = "cmdap-serve-agentmix"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--control", default=None)
    opts = ap.parse_args()

    spec = importlib.util.spec_from_file_location(
        "benchmarks_run", os.path.join(REPO, "benchmarks", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from benchmarks import command_a_plus_reference as ref, harness

    if opts.control:
        load = harness.load_module

        def loaded(*parts):
            module = load(*parts)
            if parts == ("jobs", "serve_agentmix.py"):
                job = module.run
                module.run = lambda ctx: job(ctx, control=opts.control)
            return module

        harness.load_module = loaded
    else:
        compare = ref.compare

        def against_every_spoil(get, tokens, config, rows, program, **kw):
            kw.pop("spoil", None)
            sound = compare(get, tokens, config, rows, program, **kw)
            for spoil in (None, *ref.SPOILS[1:]):
                r = sound if spoil is None else compare(
                    get, tokens, config, rows, program, spoil=spoil, **kw)
                cache = r.get("cache_error")
                failed = (r["error"] > ref.LOGIT_TOL or r["route_bad"]
                          or (cache is not None and cache > ref.CACHE_TOL))
                print(f"[controls] {len(tokens)} tokens, {spoil}: logits "
                      f"{r['error']:.5f} (limit {ref.LOGIT_TOL}), cache "
                      f"{'-' if cache is None else format(cache, '.5f')} "
                      f"(limit {ref.CACHE_TOL}), {r['route_bad']} routings "
                      f"beyond the margin, the largest gap routed apart "
                      f"{r['route_gap_max']:.5f} (margin "
                      f"{ref.ROUTE_MARGIN}): "
                      f"{'not correct' if failed else 'passes'}",
                      flush=True)
            return sound

        ref.compare = against_every_spoil
    return run.main(["--workload", CELL, "--seed", str(opts.seed),
                     "--seconds", str(opts.seconds), "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
