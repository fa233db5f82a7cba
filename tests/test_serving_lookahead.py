"""One step in flight (serving/engine.py, docs/serving.md): a call of
`step()` dispatches step n+1 before it fetches step n's tokens.

What has to hold, on the CPU mesh:

  - greedy streams are token for token those of the same engine made to
    complete every step at once (a step function that hands its tokens
    back as a NumPy array), over a mixed run;
  - an end by EOS is learnt one step late: the row already dispatched is
    discarded and counted, the stream ends at the EOS, and the slot's
    blocks serve the next owner and the prefix cache;
  - an end by length is known at dispatch: no request is sampled a token
    past `max_new_tokens` or the cache's last row;
  - `steps_ahead` beside `iterations` says how often the mechanism
    engaged: nearly always in a steady run, never where every step is
    completed at once;
  - what reads or replaces decode or scheduler state from outside the
    loop leaves nothing in flight;
  - a host function in the step's place finds the scheduler current.
"""

import numpy as np
import pytest

from small_lms import (
    ROWS, build_lm, build_rows_lm, complete_every_step_at_once, engine,
)

BASE = [3, 7, 11, 2, 5, 9, 1, 4]
# over the buckets 1, 2 and 4 of a chunk of 4; three share a prefix of two
# blocks and part inside the third (copy-on-write of a published block)
MIXED = [BASE + [6], [5, 2], BASE + [8, 2, 12], list(range(20, 33)),
         BASE[:6], [60], BASE + [6, 6, 6], [1, 9, 30, 30, 12, 4, 8]]


LAYOUTS = {"rows": ROWS, "contiguous": {"kv_layout": "contiguous"},
           "paged": {"kv_layout": "paged", "kv_block_size": 4}}


def _engine(layout="paged", argv=(), **kw):
    ff = (build_rows_lm() if layout == "rows"
          else build_lm(batch=1, argv=argv))
    return ff, {"slots": 3, "max_new_tokens": 6, "prefill_chunk": 4,
                **LAYOUTS[layout], **kw}


def _step_until_in_flight(eng, calls=3):
    for _ in range(calls):
        eng.step()
    assert eng._in_flight is not None
    return eng


@pytest.mark.parametrize("layout", ["paged", "contiguous", "rows"])
def test_streams_are_those_of_the_loop_that_completes_every_step(
        layout, monkeypatch):
    ff, opts = _engine(layout)
    eng = engine(ff, **opts)
    reqs = [eng.submit(p) for p in MIXED]       # more requests than slots
    eng.run_until_drained()
    got = eng.stats()
    if layout != "contiguous":
        eng.block_manager.check_invariants()
    at_once = complete_every_step_at_once(engine(ff, **opts), monkeypatch)
    want = at_once.generate(MIXED)
    assert [r.generated for r in reqs] == want
    assert {r.finish_reason for r in reqs} == {"max_tokens"}
    ref = at_once.stats()
    assert got["decode_tokens"] == ref["decode_tokens"] == 6 * len(MIXED)
    assert got["prefill_tokens"] == ref["prefill_tokens"]
    assert got["steps_ahead"] > 0 and ref["steps_ahead"] == 0
    if layout != "contiguous":
        assert got["prefix_hit_tokens"] > 0 and got["cow_copies"] > 0


def test_a_row_that_outran_an_eos_is_discarded(monkeypatch):
    ff, opts = _engine(slots=1, max_new_tokens=8)
    prompt, other = BASE + [6], [5, 2, 8]
    stream, after = engine(ff, **opts).generate([prompt, other])
    # a token the stream first shows in its middle: by then the slot
    # decodes, and the request is not at its last token by length
    k = next(i for i in range(1, 6) if stream[i] not in stream[:i])
    eng = engine(ff, **opts)
    req = eng.submit(prompt, eos_id=stream[k])
    nxt = eng.submit(other)                     # the slot's next owner
    eng.run_until_drained()
    assert req.finish_reason == "eos"
    assert req.generated == stream[:k + 1]      # ends at the EOS
    st = eng.stats()
    assert st["rows_discarded"] == 1
    assert st["decode_tokens"] == k + 1 + len(after)   # fetched, kept
    assert nxt.generated == after
    eng.block_manager.check_invariants()
    # the prompt's blocks are the prefix cache's: the same request again
    # finds them, and its stream is the same
    again = eng.submit(prompt, eos_id=stream[k])
    eng.run_until_drained()
    assert again.matched_prefix_len >= 8
    assert again.generated == stream[:k + 1]
    assert eng.stats()["rows_discarded"] == 2
    # completed at once, no row outruns anything
    sync = complete_every_step_at_once(engine(ff, **opts), monkeypatch)
    assert sync.generate([prompt], eos_id=stream[k]) == [stream[:k + 1]]
    assert sync.stats()["rows_discarded"] == 0


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_an_end_by_length_is_known_at_dispatch(layout, monkeypatch):
    ff, opts = _engine(layout, slots=2)
    eng = engine(ff, **opts)
    budgets = [1, 2, 3, 6, 1, 4]
    reqs = [eng.submit(p, max_new_tokens=n)
            for p, n in zip(MIXED, budgets)]
    # the cache's last row: the prompt leaves room for two tokens
    full = eng.submit(list(range(1, eng.max_seq_len)), max_new_tokens=9)
    step, decoded = eng._step_fn, {}

    def spy(*args):     # the decode rows each step runs, by request
        for s in eng.scheduler.slots:
            if s.decoding:
                rid = s.request.request_id
                decoded[rid] = decoded.get(rid, 0) + 1
        return step(*args)

    with monkeypatch.context() as patched:
        patched.setattr(eng, "_step_fn", spy)
        eng.run_until_drained()
    assert [len(r.generated) for r in reqs] == budgets
    assert {r.finish_reason for r in reqs} == {"max_tokens"}
    assert (full.finish_reason, len(full.generated)) == ("length", 2)
    # its last chunk samples a request's first token, a decode row each
    # of the others: no request rode in a step after its last token's
    for r in [*reqs, full]:
        assert decoded.get(r.request_id, 0) == len(r.generated) - 1
    st = eng.stats()
    assert st["rows_discarded"] == 0 and st["steps_ahead"] > 0
    at_once = complete_every_step_at_once(engine(ff, **opts), monkeypatch)
    assert at_once.generate([full.prompt], max_new_tokens=9) == [
        full.generated]


@pytest.mark.parametrize("mode", ["in_flight", "at_once", "sanitize"])
def test_steps_ahead_says_how_often_a_step_was_left_in_flight(
        mode, monkeypatch):
    ff, opts = _engine(
        argv=["--sanitize-numerics"] if mode == "sanitize" else (),
        slots=2, max_new_tokens=24)
    eng = engine(ff, **opts)
    if mode == "at_once":
        complete_every_step_at_once(eng, monkeypatch)
    eng.generate(MIXED[:4])
    st = eng.stats()
    assert st["iterations"] >= 48
    if mode == "in_flight":
        # every step but the first after an empty engine
        assert st["steps_ahead"] == st["iterations"] - 1
        assert st["steps_ahead"] / st["iterations"] > 0.9
    else:
        assert st["steps_ahead"] == 0
    eng.reset_stats()
    st = eng.stats()
    assert (st["iterations"], st["steps_ahead"], st["rows_discarded"]) == (
        0, 0, 0)


def _leaves_nothing_in_flight(eng, reqs, what):
    _step_until_in_flight(eng)
    finished = {r.request_id for r in reqs if r.finished}
    tokens = sum(len(r.generated) for r in reqs)
    what(eng)
    assert eng._in_flight is None
    # the step was fetched and booked, not dropped
    assert sum(len(r.generated) for r in reqs) > tokens
    # a request that finished goes to the caller of the next step()
    handed = eng.step()
    assert all(r in handed for r in reqs
               if r.finished and r.request_id not in finished)


@pytest.mark.parametrize("what", [
    "stats", "reset_stats", "metrics_summary", "extract_kv",
    "apply_copies", "profile_step"])
def test_reading_decode_state_completes_the_step_in_flight(
        what, monkeypatch):
    from flexflow_tpu.serving.paged import SCRATCH_BLOCK, CopyPlan

    ff, opts = _engine(slots=2, max_new_tokens=3)
    eng = engine(ff, **opts)
    reqs = [eng.submit(p) for p in ([5, 2], [60], BASE)]
    _leaves_nothing_in_flight(eng, reqs, {
        "stats": lambda e: e.stats(),
        "reset_stats": lambda e: e.reset_stats(),
        "metrics_summary": lambda e: e.metrics_summary(),
        "extract_kv": lambda e: e.extract_kv(0, 2),
        "apply_copies": lambda e: e._apply_copies(
            [CopyPlan(src=SCRATCH_BLOCK, dst=SCRATCH_BLOCK)]),
        "profile_step": lambda e: e.profile_step(),
    }[what])
    eng.run_until_drained()
    assert eng._in_flight is None
    want = complete_every_step_at_once(
        engine(ff, **opts), monkeypatch).generate([r.prompt for r in reqs])
    assert [r.generated for r in reqs] == want


def test_run_until_drained_drains_the_last_step():
    ff, opts = _engine(slots=2, max_new_tokens=4)
    eng = engine(ff, **opts)
    reqs = [eng.submit(p) for p in MIXED[:3]]
    done = eng.run_until_drained(max_iterations=4)  # stopped mid-run
    assert eng._in_flight is None
    assert not eng.scheduler.drained
    done += eng.run_until_drained()
    assert eng._in_flight is None and eng.scheduler.drained
    assert sorted(r.request_id for r in done) == [
        r.request_id for r in reqs]
    assert eng.step() == []


def test_replan_mesh_completes_the_step_in_flight():
    ff = build_lm(mesh=(1, 1, 1, 1), batch=2,
                  argv=["--elastic-min-devices", "1"])
    opts = dict(slots=2, max_new_tokens=8, prefill_chunk=4)
    want = engine(ff, **opts).generate(MIXED[:2])
    eng = ff.serve(**opts)      # its own: it moves to another mesh
    reqs = [eng.submit(p) for p in MIXED[:2]]
    _step_until_in_flight(eng, calls=5)
    eng.replan_mesh((2, 1, 1, 1))
    assert eng._in_flight is None
    # tokens sampled before the move are on the host: the new mesh's
    # first step is fed from there
    eng.run_until_drained()
    assert [r.generated for r in reqs] == want


def test_admit_prefilled_completes_the_step_in_flight():
    from flexflow_tpu.serving.scheduler import Request

    ff, opts = _engine(slots=2, max_new_tokens=5, prefix_cache=False,
                       prefix_sharing=False)
    eng = engine(ff, **opts)
    first = eng.submit(BASE)
    _step_until_in_flight(eng, calls=4)
    ks, vs = eng.extract_kv(0, len(BASE))       # the prompt's rows
    eng.step()
    assert eng._in_flight is not None
    handed = Request(prompt=list(BASE), max_new_tokens=5,
                     generated=[first.generated[0]])
    assert eng.admit_prefilled(handed, first.generated[0], ks, vs) == 2
    assert eng._in_flight is None
    eng.run_until_drained()
    assert handed.generated == first.generated


def test_a_speculative_round_starts_and_ends_with_nothing_in_flight():
    from test_speculative import _build_lm as build, _force_speculation

    ff = build()
    base = engine(ff, slots=2, max_new_tokens=8,
                  prefill_chunk=4).generate(MIXED[:3])
    # serve(): a speculative engine holds a drafter's engine of its own
    eng = ff.serve(speculate=True, draft_model=build(), slots=2,
                   max_new_tokens=8, prefill_chunk=4)
    _force_speculation(eng)
    reqs = [eng.submit(p) for p in MIXED[:3]]
    verify, seen = eng._run_verify, []

    def spy(tokens, positions):
        seen.append(eng._in_flight)
        return verify(tokens, positions)

    eng._run_verify = spy
    done = []
    while not eng.scheduler.drained:
        rounds = eng._spec_rounds
        done += eng.step()
        if eng._spec_rounds > rounds:
            assert eng._in_flight is None
    assert seen and set(seen) == {None}
    assert eng.stats()["steps_ahead"] > 0       # the prefill steps
    assert [r.generated for r in reqs] == base
    # every completion reached the caller once
    assert sorted(r.request_id for r in done) == [
        r.request_id for r in reqs]


@pytest.mark.parametrize("swapped", ["from_the_start", "in_mid_flight"])
def test_a_host_step_function_finds_the_scheduler_current(
        swapped, monkeypatch):
    """benchmarks/jobs/serve_sessions.py replays served streams through
    the engine with `_step_fn` swapped for a host function that reads
    `scheduler.slots` and `len(request.generated)` when it is called."""
    ff, opts = _engine(slots=2, max_new_tokens=6)
    eng = engine(ff, **opts)
    reqs = [eng.submit(p) for p in MIXED[:4]]
    if swapped == "in_mid_flight":
        _step_until_in_flight(eng, calls=5)
    step, calls = eng._step_fn, []

    def on_the_host(params, state, xs, *rest):
        assert eng._in_flight is None
        for s in eng.scheduler.active_slots:
            req = s.request
            assert (s.ahead, s.closing) == (0, False)
            if s.decoding:
                # every token sampled so far is in `generated`, and the
                # last of them is this step's input
                assert len(req.generated) == s.length - len(req.prompt) + 1
                assert int(xs["tokens"][s.index, 0]) == req.generated[-1]
            else:
                assert not req.generated
        calls.append(len(eng.scheduler.active_slots))
        state, sampled = step(params, state, xs, *rest)
        return state, np.asarray(sampled)

    with monkeypatch.context() as patched:
        patched.setattr(eng, "_step_fn", on_the_host)
        eng.run_until_drained()
    assert calls
    want = complete_every_step_at_once(
        engine(ff, **opts), monkeypatch).generate([r.prompt for r in reqs])
    assert [r.generated for r in reqs] == want
