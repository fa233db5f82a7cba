"""The `paged_chunk_ms.serve` reader on a profile made by hand: chip 0's
time in events named flash_attention_paged_chunk* over the window's
`ff/serve.prefill` spans with more than one token; events of the
single-query kernel's name do not count, and a program in which no chunk
kernel ran (a parent commit) leaves nothing to read."""

import pytest

from benchmarks import harness

from test_program_spans import (
    SERVE_OPS, SERVE_SPANS, profile_text, run_over,
)

CHUNK_OPS = [
    ("%flash_attention_paged_chunk.1 = bf16[4] custom-call(q)", 640, 660),
    ("%flash_attention_paged_chunk_grouped.7 = bf16[4] custom-call(q)",
     700, 730),
    # outside the window: not counted
    ("%flash_attention_paged_chunk.2 = bf16[4] custom-call(q)", 1100, 1200),
]


def with_prefills(first, second):
    """SERVE_SPANS, whose first iteration only decodes and whose second
    carries a chunk of 4 tokens: the first made a chunk step of `first`
    tokens (None leaves it), the second's chunk made `second` tokens."""
    out = []
    for name, a, b, args in SERVE_SPANS:
        if name == "ff/serve.step" and first is not None:
            name, args = "ff/serve.prefill", dict(args, tokens=first)
        elif name == "ff/serve.prefill":
            args = dict(args, tokens=second)
        out.append((name, a, b, args))
    return out


@pytest.mark.parametrize("tokens, ms", [
    ((None, 100), 50e-6),     # one chunk step: 20 + 30 ns
    ((64, 100), 25e-6),       # two
    ((1, 100), 50e-6),        # a chunk of one token is the decode program
])
def test_chunk_kernel_time_over_the_steps_that_carried_a_chunk(
        tokens, ms, tmp_path):
    reader = harness.load_reader("paged_chunk_ms.serve")
    ops = sorted(SERVE_OPS[:5] + CHUNK_OPS, key=lambda op: op[1])
    run = run_over(profile_text(ops, [], with_prefills(*tokens)), tmp_path)
    assert reader.read(run) == pytest.approx(ms)


def test_a_program_without_the_kernel_leaves_nothing_to_read(tmp_path):
    reader = harness.load_reader("paged_chunk_ms.serve")
    # the parent: chunk steps, and the single-query kernel's events alone
    assert reader.read(run_over(
        profile_text(SERVE_OPS, [], with_prefills(64, 100)),
        tmp_path / "parent")) is None
    # no step with a chunk of more than one token in the window
    ops = sorted(SERVE_OPS[:5] + CHUNK_OPS, key=lambda op: op[1])
    assert reader.read(run_over(
        profile_text(ops, [], with_prefills(None, 1)),
        tmp_path / "decode")) is None
    # no program spans at all
    assert reader.read(run_over(
        profile_text(ops, [], SERVE_SPANS[:1]), tmp_path / "bare")) is None
