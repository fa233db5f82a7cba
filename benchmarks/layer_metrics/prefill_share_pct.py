"""Share of the window spent in engine.step() calls that carried a
prefill chunk (the engine's prefill_calls count rose during the call)."""


def read(run):
    step_s = run.result["counters"].get("prefill_step_s")
    if step_s is None:
        return None
    return 100.0 * sum(step_s) / run.ctx.window_s
