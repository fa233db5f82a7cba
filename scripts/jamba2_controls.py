"""The controls of `jamba2-serve-shortchat` on the chip: one run of the
cell whose every comparison (the pre-window check, the three served
streams) is made against the sound reference AND against each spoil of it
(benchmarks/jamba2_reference.SPOILS: e4m3 matrices, a bf16 h, the three
inner norms, the convolution's bias, D or dt's bias left out), on the same
recorded logits, states and tails, and whose state-update check is also
made against a reference that keeps h in bfloat16, so that seven controls
cost one set-up and one window:

    chiprun -- python scripts/jamba2_controls.py --seed <n>

prints a `[controls]` line a comparison a spoil: the logit error, the
slot's last h's and its convolution tail's, beside the job's LOGIT_TOL,
STATE_END_TOL and TAIL_TOL (each spoil has to fail one of them in one
comparison at least; `bf16_state` has to fail STATE_TOL in the state
update's check, where no compute noise hides it). `--control bf16_h` /
`no_reset` / `swapped` spoils the PROGRAM instead and runs the job as it
is (another run each, since the program's side is what differs): the state
update rounds h to bfloat16; a row at position 0 keeps what its slot held;
rows 0 and 1 of every state-space layer's h change places before every
step of the loop. The result line is the cell's own. Needs a TPU.
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
CELL = "jamba2-serve-shortchat"
JOB = ("jobs", "serve_shortchat.py")


def spoil_program(control: str) -> None:
    import jax

    if control == "bf16_h":
        from flexflow_tpu.kernels import selective_scan

        real = selective_scan.selective_scan_update

        def rounded(*args):
            y, state = real(*args)
            # (a cast there and back is the compiler's to fold)
            return y, jax.lax.reduce_precision(state, exponent_bits=8,
                                               mantissa_bits=7)

        selective_scan.selective_scan_update = rounded
    elif control == "no_reset":
        import jax.numpy as jnp

        from flexflow_tpu.ops import ssm

        real_rows = ssm.decode_rows

        def kept(what, slots, max_seq_len, inputs, leaves, run):
            return real_rows(
                what, slots, max_seq_len, inputs, leaves,
                lambda x, live, keep, *state: run(
                    x, live, jnp.ones_like(keep), *state))

        ssm.decode_rows = kept
    elif control == "swapped":
        from flexflow_tpu.serving.engine import ServingEngine

        real_step = ServingEngine.step

        def step(engine):
            engine._complete_in_flight()
            state = engine.decode_model._state
            for name, leaves in state.items():
                if "state_h" in leaves:
                    h = leaves["state_h"]
                    state[name] = {**leaves,
                                   "state_h": h.at[:2].set(h[1::-1])}
            return real_step(engine)

        ServingEngine.step = step
    else:
        sys.exit(f"jamba2_controls: no control {control!r}")


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
    from benchmarks import harness, jamba2_reference as ref

    if opts.control:
        spoil_program(opts.control)
    else:
        spoils = ref.SPOILS[1:]
        load = harness.load_module

        def loaded(*parts):
            job = load(*parts)
            if parts != JOB:
                return job
            compare, state_check = job.compare, job.state_check

            def against_every_spoil(engine, ctx, replayed, states, pad_to,
                                    pad_rows, spoil=None):
                sound = compare(engine, ctx, replayed, states, pad_to,
                                pad_rows)
                for s in (None, *spoils):
                    r = sound if s is None else compare(
                        engine, ctx, replayed, states, pad_to, pad_rows,
                        spoil=s)
                    print(f"[controls] {len(replayed[0])} tokens, {s}: "
                          f"logits {r['error']:.5f} (limit {job.LOGIT_TOL}),"
                          f" last h {r['state_error']:.5f} (limit "
                          f"{job.STATE_END_TOL}), tail {r['tail_error']:.5f}"
                          f" (limit {job.TAIL_TOL}): "
                          f"{'passes' if job.sound(r) else 'not correct'}",
                          flush=True)
                return sound

            def both_states(ctx, engine, state_dtype=None):
                import jax.numpy as jnp

                error = state_check(ctx, engine)
                control = state_check(ctx, engine, jnp.bfloat16)
                said = ("passes" if control <= job.STATE_TOL
                        else "is not correct")
                print(f"[controls] the state update alone: {error:.2e} "
                      f"against a float32 h, {control:.2e} against a "
                      f"bfloat16 h (limit {job.STATE_TOL}): the control "
                      f"{said}",
                      flush=True)
                return error

            job.compare, job.state_check = against_every_spoil, both_states
            return job

        harness.load_module = loaded
    return run.main(["--workload", CELL, "--seed", str(opts.seed),
                     "--seconds", str(opts.seconds), "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
