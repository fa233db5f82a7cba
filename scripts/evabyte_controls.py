"""The controls of `evabyte-serve-bytedocs` on the chip: one run of the cell
whose every comparison (the pre-window check, the two served streams, the
last layer's pool rows of their prompts) is made against the sound
reference AND against each spoil of it, on the same recorded logits and
pool rows, so that eleven controls cost one set-up and one window:

    chiprun -- python scripts/evabyte_controls.py --seed <n>

prints a `[controls]` line a comparison a spoil: the logit error, the
cache errors (k, v, ksum, vsum) and, for the sound reference and the one
whose stream is bfloat16, the share of what the stream's precision moves
the reference by that the logits' error carries (that comparison's; the job
holds the run's), to hold against benchmarks/evabyte_reference.py's
LOGIT_TOL, CACHE_TOL and STREAM_SHARE (each spoil has to fail one of them
in one comparison at least). `--control lost_window_block` /
`lost_summary_block` / `bf16_stream` runs the job's own control instead
(the replay over zeroed blocks, the program built with a bfloat16 stream:
another run, since the program's side is what differs). The result line is
the cell's own, of the sound reference. Needs a TPU.
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
CELL = "evabyte-serve-bytedocs"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--spoils", default=None,
                    help="comma-separated spoils to try, all by default")
    opts = ap.parse_args()

    spec = importlib.util.spec_from_file_location(
        "benchmarks_run", os.path.join(REPO, "benchmarks", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from benchmarks import evabyte_reference as ref, harness

    if opts.control:
        load = harness.load_module

        def loaded(*parts):
            module = load(*parts)
            if parts == ("jobs", "serve_bytedocs.py"):
                job = module.run
                module.run = lambda ctx: job(ctx, control=opts.control)
            return module

        harness.load_module = loaded
    else:
        compare = ref.compare

        def against_every_spoil(get, tokens, config, rows, program=None,
                                **kw):
            kw.pop("spoil", None)
            sound = compare(get, tokens, config, rows, program, **kw)
            for spoil in (None, *(opts.spoils.split(",") if opts.spoils
                                  else ref.SPOILS[1:])):
                r = sound if spoil is None else compare(
                    get, tokens, config, rows, program, spoil=spoil, **kw)
                cache = r.get("cache_errors")
                failed = (r["error"] > ref.LOGIT_TOL
                          or (cache is not None
                              and max(cache) > ref.CACHE_TOL))
                share = (None if r["stream"] is None else
                         round(ref.stream_reading([r["stream"]])[0], 4))
                failed = failed or (share is not None
                                    and share > ref.STREAM_SHARE)
                print(f"[controls] {len(tokens)} tokens, {spoil}: logits "
                      f"{r['error']:.5f} (limit {ref.LOGIT_TOL}), stream "
                      f"share {share} (limit {ref.STREAM_SHARE}), cache "
                      f"{'-' if cache is None else [round(e, 5) for e in cache]}"
                      f" (limit {ref.CACHE_TOL}): "
                      f"{'not correct' if failed else 'passes'}",
                      flush=True)
            return sound

        ref.compare = against_every_spoil
    return run.main(["--workload", CELL, "--seed", str(opts.seed),
                     "--seconds", str(opts.seconds), "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
