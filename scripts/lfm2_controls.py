"""The controls and the sizing runs of `lfm2-train-8k` on the chip: one run
of the cell through benchmarks/run.py's own main, with

    chiprun -- python scripts/lfm2_controls.py --seed <n> [--spoils kv_shift,bf16_taps,e4m3]

making the pre-window comparison against the sound reference AND against
each spoil of it (benchmarks/lfm2_moe_reference's `spoil`: a key head
shifted by one, the taps' sum in bfloat16, every matrix in an 8-bit
float), one `[controls]` line a spoil with each reading beside its limit:
each spoil has to fail one of the job's limits at least. `--control
<spoil>` instead hands the spoil to the job's own `run(ctx, control=)`,
whose result line then has to say `"correct": false`. `--global-batch` /
`--steps-per-call` run the cell at another batch than its traffic file's
(the sizing runs of PERF.md section 6, PR 60). The result line is the
cell's own. Needs a TPU.
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
CELL = "lfm2-train-8k"
JOB = ("jobs", "train_lfm2_moe.py")
TRAFFIC = ("traffic", "train-8k.json")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--spoils", default="")
    ap.add_argument("--control", default=None)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--steps-per-call", type=int, default=None)
    opts = ap.parse_args()

    spec = importlib.util.spec_from_file_location(
        "benchmarks_run", os.path.join(REPO, "benchmarks", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from benchmarks import harness

    load_json, load_module = harness.load_json, harness.load_module

    def sized(*parts):
        found = load_json(*parts)
        if parts == TRAFFIC:
            if opts.global_batch:
                found["global_batch"] = opts.global_batch
            if opts.steps_per_call:
                found["steps_per_call"] = opts.steps_per_call
        return found

    def loaded(*parts):
        job = load_module(*parts)
        if parts != JOB:
            return job
        check, job_run = job.check, job.run

        def against_every_spoil(ff, config, x, y, batch, spoil=None):
            sound = check(ff, config, x, y, batch, spoil=spoil)
            for s in filter(None, opts.spoils.split(",")):
                c = check(ff, config, x, y, batch, spoil=s)
                ok = job.passes(c)
                print(f"[controls] {s}: logits {c['logit_error']:.5f} "
                      f"({c['logit_error_near']:.5f} at the near end, "
                      f"{c['logit_error_far']:.5f} at the far; "
                      f"limit {job.LOGIT_TOL}), attention alone at the "
                      f"far rows {c['attn_far_error']:.5f} (limit "
                      f"{job.ATTN_FAR_TOL}), loss {c['loss_error']:.6f} "
                      f"apart (limit {job.LOSS_TOL}), choice taken "
                      f"{c['choice_taken']} of {c['compared']} (limit "
                      f"{job.MAX_TAKEN_SHARE:.0%}), routed unlike "
                      f"{c['routed_unlike']}: "
                      f"{'passes' if all(ok.values()) else 'not correct'} "
                      f"{ok}", flush=True)
            return sound

        job.check = against_every_spoil
        if opts.control:
            job.run = lambda ctx: job_run(ctx, control=opts.control)
        return job

    harness.load_json, harness.load_module = sized, loaded
    return run.main(["--workload", CELL, "--seed", str(opts.seed),
                     "--seconds", str(opts.seconds), "--trace",
                     str(opts.trace)])


if __name__ == "__main__":
    sys.exit(main())
