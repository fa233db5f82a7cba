"""The `steps_ahead_pct.serve` reader on a profile made by hand: the share
of the window's `ff/serve.fetch` spans whose `ahead` is 1, and nothing to
read where the fetch spans carry no `ahead` (a parent commit)."""

import pytest

from benchmarks import harness

from test_program_spans import (
    SERVE_OPS, SERVE_SPANS, profile_text, run_over,
)


def with_ahead(values):
    values = iter(values)
    return [(n, a, b, dict(args, ahead=next(values))
             if n == "ff/serve.fetch" else args)
            for n, a, b, args in SERVE_SPANS]


@pytest.mark.parametrize("ahead, share", [((1, 1), 100.0), ((1, 0), 50.0),
                                          ((0, 0), 0.0)])
def test_the_share_of_fetches_that_found_the_next_step_dispatched(
        ahead, share, tmp_path):
    reader = harness.load_reader("steps_ahead_pct.serve")
    run = run_over(profile_text(SERVE_OPS, [], with_ahead(ahead)), tmp_path)
    assert reader.read(run) == pytest.approx(share)


def test_fetch_spans_without_the_argument_leave_nothing_to_read(tmp_path):
    reader = harness.load_reader("steps_ahead_pct.serve")
    assert reader.read(run_over(
        profile_text(SERVE_OPS, [], SERVE_SPANS), tmp_path / "old")) is None
    assert reader.read(run_over(
        profile_text(SERVE_OPS, [], SERVE_SPANS[:1]),
        tmp_path / "bare")) is None
