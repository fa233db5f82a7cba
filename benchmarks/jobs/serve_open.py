"""The open-loop serving job: FFModel.compile -> serve() -> requests that
arrive on their own clock, submitted when due whether or not earlier ones
ended (an API behind which independent users arrive), where jobs/serve.py
is a closed loop of clients that wait. Set-up and checks are that job's,
loaded from its file.

The arrival instants are part of the traffic mix: exponential gaps at the
mix's `rate` (requests a second), drawn once from its `schedule_seed`, so
every run sees the same instants; the run's seed pairs and orders the
sizes and draws the tokens and the weights, as in the closed loop. The
rate is fixed in the mix (a share of the knee a sweep found; PERF.md).

Set-up: as jobs/serve.py, then one whole cycle of the mix's sizes served
to the end (every prefill shape the mix can hit), which leaves the engine
empty. Window: one thread; submit what is due, `engine.step()`, and sleep
to the next arrival when nothing is left to do. A traced run serves the
schedule untraced for the cell's `trace_lead_seconds` first, so the short
traced window sees a loaded engine and not a cold start. Time to first
token and queue wait count from the instant a request was due, not from
when this loop got round to submitting it; how late it submitted is a
counter.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import harness, reference
from benchmarks import traffic as traffic_gen

serve = harness.load_module("jobs", "serve.py")


def arrival_offsets(rate: float, schedule_seed: int, seconds: float):
    """Seconds from the loop's start at which requests are due: a Poisson
    process at `rate` a second (scripts/serve_bench.py::open_loop_offsets
    with burst 1), long enough to cover `seconds`."""
    rs = np.random.RandomState(schedule_seed)
    n = int(rate * seconds * 1.5) + 64
    offsets = np.cumsum(rs.exponential(1.0 / rate, size=n))
    if offsets[-1] < seconds:
        raise ValueError("the schedule ends before the window does")
    return offsets


class OpenLoop:
    """The loop and what it stamps: `due` (request id -> the instant it
    was due), `asked`, every engine step with the queue's depth after it,
    finished requests, refusals."""

    def __init__(self, engine, ctx, stream, offsets):
        self.engine, self.ctx, self.stream = engine, ctx, stream
        self.offsets, self.next = offsets, 0
        self.due, self.asked = {}, {}
        self.steps, self.finished, self.rejected = [], [], []
        self.t0 = None

    def submit_due(self, now: float) -> None:
        while self.t0 + self.offsets[self.next] <= now:
            due = self.t0 + self.offsets[self.next]
            self.next += 1
            prompt, new = next(self.stream)
            try:
                with self.ctx.span("submit"):
                    req = self.engine.submit(prompt, max_new_tokens=new)
            except ValueError:  # the engine refuses what it can never serve
                self.rejected.append(due)
                continue
            self.due[req.request_id], self.asked[req.request_id] = due, new

    def run_until(self, end: float) -> None:
        """Serve the schedule until the clock reads `end`."""
        engine = self.engine
        if self.t0 is None:
            self.t0 = time.perf_counter()
        while True:
            now = time.perf_counter()
            if now >= end:
                return
            self.submit_due(now)
            if engine.scheduler.drained:
                wake = min(end, self.t0 + self.offsets[self.next])
                with self.ctx.span("wait_for_arrival"):
                    time.sleep(max(0.0, wake - time.perf_counter()))
                continue
            before = engine._prefill_calls
            t0 = time.perf_counter()
            with self.ctx.span("engine_step"):
                done = engine.step()
            self.steps.append((t0, time.perf_counter(),
                               engine._prefill_calls > before,
                               engine.scheduler.queue_depth))
            self.finished.extend(done)


def run(ctx) -> dict:
    t, cell = ctx.traffic, ctx.cell
    vocab = ctx.config["vocab_size"]
    cfg = harness.lm_config(ctx.config, ctx.config["n_positions"],
                            cell["attention_impl"])
    with ctx.span("ffcompile"):
        ff = harness.build_lm(
            cfg, [*cell["flags"], "--seed", str(ctx.seed % (2**31 - 1))],
            cell["train_batch"], cell["optimizer"])
    with ctx.span("ffcompile"):
        engine = ff.serve(**cell["serve"])
    mgr = engine.block_manager
    print(f"[serve_open] engine: {engine.spec.slots} slots x "
          f"{engine.max_seq_len}, prefill chunk {engine.spec.prefill_chunk}"
          f", pool {mgr.num_blocks} blocks of {mgr.block_size}; arrivals at "
          f"{t['rate']} requests/s from schedule seed {t['schedule_seed']}")

    rng = np.random.default_rng(ctx.seed)
    with ctx.span("reference_check"):
        err = serve.logit_error(engine, ctx, [
            rng.integers(0, vocab, n).tolist()
            for n in t["check_prompt_tokens"]])
    print(f"[serve_open] decode-graph logits against the reference, "
          f"prefill + {serve.CHECK_DECODED} decoded: {err:.5f} of max "
          f"|logit| (tolerance {reference.LOGIT_TOL})")

    stream = traffic_gen.requests(t, vocab, ctx.seed)
    with ctx.span("warmup"):
        shortest = min(traffic_gen.request_sizes(t)[0])
        engine.generate([rng.integers(0, vocab, shortest).tolist()],
                        max_new_tokens=2)
        serve.warm_copies(engine)
    with ctx.span("first_round"):
        # one whole cycle of the mix's sizes, all at once, to the end
        for _ in range(t["cycle"]):
            prompt, new = next(stream)
            engine.submit(prompt, max_new_tokens=new)
        while not engine.scheduler.drained:
            engine.step()

    lead = cell.get("trace_lead_seconds", 0.0) if ctx.trace_dir else 0.0
    loop = OpenLoop(engine, ctx, stream, arrival_offsets(
        t["rate"], t["schedule_seed"], lead + ctx.seconds))
    if lead:
        with ctx.span("lead_in"):
            loop.run_until(time.perf_counter() + lead)
    tokens_before = engine.stats()["decode_tokens"]
    w0 = ctx.open_window()
    loop.run_until(w0 + ctx.seconds)
    w1 = ctx.close_window()
    tokens = engine.stats()["decode_tokens"] - tokens_before

    ended = [r for r in loop.finished if w0 <= r.finish_t <= w1]
    wrong = [r for r in ended if not serve.came_back_right(
        r, loop.asked[r.request_id], vocab)]
    right = [r for r in ended if r not in wrong]
    with ctx.span("stream_check"):
        checked = serve.spread_by_prompt(right, serve.CHECK_STREAMS)
        off = serve.off_the_reference(engine, ctx, checked)
    refused = [r for r in loop.rejected if w0 <= r <= w1]
    _, tpot = serve.request_latencies(right)
    # from the instant a request was due
    ttft = [r.first_token_t - loop.due[r.request_id] for r in right]
    waits = [r.admit_t - loop.due[r.request_id] for r in right]
    late = [r.submit_t - loop.due[r.request_id] for r in right]
    in_window = [s for s in loop.steps if s[0] >= w0 and s[1] <= w1]
    prefill_step_s = [b - a for a, b, pre, _ in in_window if pre]
    thirds = [[d for a, _, _, d in in_window
               if w0 + i * ctx.seconds / 3 <= a < w0 + (i + 1) * ctx.seconds / 3]
              for i in range(3)]
    depth = [float(np.mean(d)) if d else 0.0 for d in thirds]
    print(f"[serve_open] {len(ended)} requests ended in {ctx.window_s:.2f} "
          f"s ({len(wrong)} wrong, {len(refused)} refused), {tokens} tokens"
          f", {len(in_window)} engine steps, {len(prefill_step_s)} of them "
          f"with a prefill chunk; mean queue depth by third of the window "
          f"{[round(d, 2) for d in depth]}")
    print(f"[serve_open] streams of the loop against the reference, "
          f"prompts of {[len(r.prompt) for r in checked]} tokens: "
          f"{len(off)} of {len(checked)} off it")
    latencies = serve.latency_statistics(ttft, tpot)
    p90 = lambda v: harness.percentile(v, 90) * 1e3 if v else None  # noqa: E731
    return {
        "attempted": len(ended) + len(refused),
        "failed": len(wrong) + len(refused) + len(off),
        "correct": bool(err <= reference.LOGIT_TOL and not wrong and not off
                        and not refused and ended),
        "end_to_end": {"serve_tok_s": tokens / ctx.window_s, **latencies},
        "counters": {
            "tokens": tokens, "requests": len(ended),
            "step_s": [b - a for a, b, _, _ in in_window],
            "prefill_step_s": prefill_step_s,
            "logit_error": err,
            "prefill_share_pct": 100.0 * sum(prefill_step_s) / ctx.window_s,
            "ttft_from_due_ms.p90": p90(ttft),
            "queue_wait_ms.p90": p90(waits),
            "queue_wait_ms.mean": float(np.mean(waits)) * 1e3 if waits else None,
            "submit_late_ms.p90": p90(late),
            "queue_depth_by_third": depth,
            **{k: round(v, 3) for k, v in latencies.items()
               if v is not None},
        },
    }
