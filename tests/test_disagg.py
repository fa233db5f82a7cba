"""Disaggregated prefill/decode serving + radix prefix cache tests
(serving/disagg.py, serving/radix.py, docs/serving.md).

The acceptance surface of the split-pool serving path on the 8-device
CPU mesh:

  - the radix cache's LRU eviction can never free a block a live slot's
    page table still maps (eviction only ever takes cached-ONLY blocks);
  - a longest-prefix-match admission is token-identical to the cold
    path — mapped prefix KV reads back exactly what recompute writes;
  - a slot's decode extension never poisons the published prefix
    (registration keys on the prompt extent; the tail block COWs);
  - disaggregated serving is bit-identical to the unified engine, and
    every KV handoff references a verified fftrans transfer program
    whose predicted seconds reproduce from the program alone;
  - a prefix published before a FULL drain is still matched by a
    re-admission after it (the cross-time cache's reason to exist);
  - the prefill:decode ratio trigger produces payoff-gated decision
    records the doctor's elastic gate reproduces arithmetically.
"""

import sys

import numpy as np
import pytest


def _lm_config():
    from flexflow_tpu.models import TransformerLMConfig

    return TransformerLMConfig(
        vocab_size=64, hidden_size=32, num_heads=4, num_layers=2,
        sequence_length=32, attention_impl="xla")


def _build_lm(mesh=(8, 1, 1, 1), batch=8, argv=()):
    sys.argv = ["test"] + list(argv)
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import build_transformer_lm

    cfg = FFConfig()
    if cfg.mesh_axis_sizes is None:
        cfg.mesh_axis_sizes = mesh
    cfg.batch_size = batch
    ff = FFModel(cfg)
    build_transformer_lm(ff, _lm_config(), batch_size=batch)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


# --------------------------------------------------------- radix (host-side)


@pytest.mark.parametrize("as_type", [list, tuple, np.asarray])
def test_radix_match_compares_a_block_with_a_nodes_run_as_one(as_type,
                                                              monkeypatch):
    """A node's run is a tuple and a prompt comes as a list (or an array):
    `match` hands `_common_len` two tuples, whose whole-run comparison is
    one `==`; a list slice never equals a tuple, and every cached block
    would fall to the element-by-element loop (PERF.md section 7, "From PR
    46": 2 ms a match of a 60 k-token prompt)."""
    from flexflow_tpu.serving import radix

    seen = []
    common_len = radix._common_len

    def watched(a, b):
        seen.append((type(a), type(b)))
        return common_len(a, b)

    monkeypatch.setattr(radix, "_common_len", watched)
    cache = radix.RadixPrefixCache(4)
    history = list(range(10))
    assert cache.insert(history, [7, 8, 9]) == [7, 8, 9]
    assert cache.match(as_type(history + [99]), peek=True) == (10, [7, 8, 9])
    assert cache.match(as_type(history[:6] + [99, 98]), peek=True) == (
        6, [7, 8])
    assert seen and set(seen) == {(tuple, tuple)}


def test_radix_lru_eviction_never_frees_live_block():
    """Pool pressure evicts cached-ONLY blocks, never a block a live
    slot maps: fill the pool past its budget with distinct published
    prefixes while one resident stays live, and at every step the live
    slot's table entries must stay allocated to it."""
    from flexflow_tpu.serving.paged import BlockManager

    bs = 4
    mgr = BlockManager(num_blocks=12, block_size=bs, table_width=8,
                       cross_time=True)
    live_prompt = list(range(100, 100 + 2 * bs))
    assert mgr.reserve("live", len(live_prompt), bs)
    mgr.bind_reservation("live", 0)
    mgr.admit(0, live_prompt)
    for pos in range(len(live_prompt)):
        mgr.ensure_writable(0, [pos])
    mgr.register_prompt(0, live_prompt)
    live_blocks = set(mgr.table(0)[:2])

    # churn: distinct prompts published then released, until the pool
    # has recycled its whole evictable budget several times over
    for i in range(8):
        p = [200 + 10 * i + j for j in range(2 * bs)]
        assert mgr.reserve(f"r{i}", len(p), bs), \
            f"churn request {i} could not reserve (eviction failed)"
        mgr.bind_reservation(f"r{i}", 1)
        mgr.admit(1, p)
        for pos in range(len(p)):
            mgr.ensure_writable(1, [pos])
        mgr.register_prompt(1, p)
        mgr.release(1)
        # the live slot's mapping survives every eviction round
        assert set(mgr.table(0)[:2]) == live_blocks
        for blk in live_blocks:
            assert mgr.refcount(blk) >= 1, \
                f"live block {blk} lost its slot reference"
            assert blk not in mgr._free, \
                f"live block {blk} returned to the free list"
        mgr.check_invariants()
    assert mgr.stats.radix_evictions > 0, \
        "churn never exercised eviction — test is vacuous"
    mgr.release(0)
    mgr.check_invariants()


def test_radix_eviction_only_takes_cached_only_blocks():
    """The evictable set is exactly `cached_only_blocks`: blocks whose
    only holder is the cache pin. A published prefix whose resident is
    still live contributes zero evictable blocks."""
    from flexflow_tpu.serving.paged import BlockManager

    bs = 4
    mgr = BlockManager(num_blocks=8, block_size=bs, table_width=8,
                       cross_time=True)
    p = list(range(2 * bs))
    assert mgr.reserve("a", len(p), bs)
    mgr.bind_reservation("a", 0)
    mgr.admit(0, p)
    for pos in range(len(p)):
        mgr.ensure_writable(0, [pos])
    mgr.register_prompt(0, p)
    assert mgr.cached_blocks == 2
    assert mgr.cached_only_blocks == 0  # live slot still maps both
    before = mgr.stats.radix_evicted_blocks
    assert mgr._evict_blocks(2) == 0, \
        "eviction freed blocks while their resident was live"
    assert mgr.stats.radix_evicted_blocks == before
    mgr.release(0)
    assert mgr.cached_only_blocks == 2  # now evictable
    assert mgr._evict_blocks(2) == 2
    mgr.check_invariants()


# ---------------------------------------------------------- engine identity


@pytest.fixture(scope="module")
def lm():
    return _build_lm()


SHARED = [1, 2, 3, 4, 5, 6, 7, 8, 9]


def test_longest_prefix_match_token_identity(lm):
    """A radix-matched admission (prompt extends a published prefix)
    decodes the SAME tokens as a cold engine that recomputes every
    prompt position — mapped KV must read back bit-exactly."""
    kw = dict(slots=2, max_new_tokens=6, prefill_chunk=4)
    cold = lm.serve(**kw)
    warm = lm.serve(**kw)
    extended = SHARED + [40, 41, 42]
    want = cold.generate([extended])

    first = warm.submit(SHARED)
    warm.run_until_drained()
    assert first.matched_prefix_len == 0  # nothing published yet
    req = warm.submit(extended)
    warm.run_until_drained()
    assert req.matched_prefix_len and req.matched_prefix_len > 0, \
        "the shared prefix was not matched — cache cold"
    assert [req.generated] == want, \
        "prefix-matched decode diverged from the cold path"


def test_decode_extension_never_poisons_cache(lm):
    """Regression: registration covers the PROMPT extent only, and a
    resident's decode tokens COW off the published tail block — a later
    request matching the same prompt must decode exactly like a cold
    engine, not see request A's generated rows."""
    kw = dict(slots=2, prefill_chunk=4)
    cold = lm.serve(**kw)
    warm = lm.serve(**kw)
    # A generates MANY tokens: they land in (and beyond) the partial
    # tail block of the prompt extent that register_prompt published
    a = warm.submit(SHARED, max_new_tokens=10)
    warm.run_until_drained()
    assert len(a.generated) == 10
    b = warm.submit(SHARED, max_new_tokens=10)
    warm.run_until_drained()
    assert b.matched_prefix_len and b.matched_prefix_len > 0
    want = cold.generate([SHARED], max_new_tokens=10)
    assert [b.generated] == want, \
        "cached prefix was poisoned by the first resident's decode"
    assert b.generated == a.generated  # same prompt, greedy


def test_disagg_token_identity_and_verified_handoffs(lm):
    """Disaggregated serving (two Unity plans on disjoint sub-meshes,
    per-request KV handoff) is bit-identical to the unified engine, and
    every handoff's transfer program re-verifies from its own JSON."""
    from flexflow_tpu.analysis.transition import verify_transition_total

    kw = dict(slots=4, max_new_tokens=6, prefill_chunk=4)
    prompts = [SHARED, SHARED + [40, 41], [20, 21, 22], SHARED]
    want = lm.serve(**kw).generate(prompts)
    dis = lm.serve(disaggregate=True, **kw)
    assert dis.prefill_chips == 4 and dis.decode_chips == 4
    assert dict(dis.prefill.decode_model.mesh.shape)["data"] == 4
    assert dict(dis.decode.decode_model.mesh.shape)["data"] == 4
    got = dis.generate(prompts)
    assert got == want, "disaggregated decode diverged from unified"

    sec = dis.disagg_section()
    assert sec["summary"]["count"] == len(prompts)
    assert not dis._pending and not dis._kv_stash
    for h in sec["handoffs"]:
        if h["injected_blocks"] == 0:
            assert h["predicted_s"] == 0.0
            continue
        prog = sec["programs"][str(h["injected_blocks"])]
        assert prog["analysis"]["errors"] == 0
        total = verify_transition_total(prog)
        assert abs(total - prog["predicted_s"]) < 1e-9
        assert abs(h["predicted_s"] - prog["predicted_s"]) < 1e-9
        kinds = {c["kind"] for t in prog["transfers"]
                 for c in t["collectives"]}
        assert kinds == {"host_hop"}, \
            "handoff rows must be modeled as host hops"
    # the decode side saw the shared prefix arrive more than once: the
    # later handoffs land radix-cached (fewer rows moved than blocks)
    assert any(h["injected_blocks"] < h["prompt_blocks"]
               or h["injected_blocks"] == 0
               for h in sec["handoffs"][1:])


def test_disagg_cross_time_prefix_hit_after_drain(lm):
    """The decode-side radix cache survives a FULL drain: a prompt
    handed off, decoded, completed, and released is matched when the
    same prompt is re-admitted later — zero injection on the re-run."""
    kw = dict(slots=2, max_new_tokens=5, prefill_chunk=4)
    dis = lm.serve(disaggregate=True, **kw)
    first = dis.generate([SHARED])
    assert dis.drained
    assert dis.decode.scheduler.drained  # nothing resident anywhere
    second = dis.generate([SHARED])
    assert second == first
    assert dis.decode.block_manager.stats.cross_time_hits > 0, \
        "the re-admitted prompt missed the cross-time cache"
    # the re-run's handoff moved nothing: its full extent was cached
    assert dis.handoffs[-1]["injected_blocks"] == 0
    assert dis.handoffs[-1]["predicted_s"] == 0.0


def test_disagg_requests_finishing_at_prefill(lm):
    """EOS on the first token and one-token budgets complete on the
    prefill pool without a handoff; the decode side still records the
    completion (the pair's single accounting point)."""
    kw = dict(slots=2, prefill_chunk=4)
    dis = lm.serve(disaggregate=True, **kw)
    uni = lm.serve(**kw)
    want = uni.generate([[5, 6, 7]], max_new_tokens=1)
    req = dis.submit([5, 6, 7], max_new_tokens=1)
    dis.run_until_drained()
    assert [req.generated] == want
    assert req.finish_reason == "max_tokens"
    assert not dis.handoffs, "a one-token request must not hand off"
    assert req in dis.decode.scheduler.completed
    # EOS at prefill: make the first sampled token the eos_id
    eos = want[0][0]
    req2 = dis.submit([5, 6, 7], max_new_tokens=8, eos_id=eos)
    dis.run_until_drained()
    assert req2.finish_reason == "eos"
    assert req2.generated == [eos]
    assert len(dis.handoffs) == 0


def test_disagg_ratio_trigger_payoff_record(lm):
    """maybe_rebalance prices the proposed chip-ratio shift through the
    payoff inequality and records BOTH sides from their factors — the
    exact arithmetic run_doctor's elastic gate recomputes. A declined
    decision moves no chips."""
    kw = dict(slots=4, max_new_tokens=5, prefill_chunk=4)
    dis = lm.serve(disaggregate=True, **kw)
    dis.generate([[i, i + 1, i + 2] for i in range(1, 9)])
    assert dis.maybe_rebalance() is None or True  # thresholds not met OK
    # force a proposal, then make migration unpayable: horizon 0 means
    # rhs == 0, so the inequality can never hold
    dis.rebalance_min_samples = 1
    dis.rebalance_factor = 0.0001
    before = (dis.prefill_chips, dis.decode_chips)
    d = dis.maybe_rebalance(horizon_steps=0)
    assert d is not None and d["decision"] == "declined"
    assert (dis.prefill_chips, dis.decode_chips) == before
    assert d["lhs_s"] == pytest.approx(
        d["predicted_migration_s"] * d["fidelity_ratio"])
    assert d["rhs_s"] == pytest.approx(
        d["benefit_s_per_step"] * d["horizon_steps"])
    assert not d["would_migrate"]
    assert d in lm._elastic_decisions  # rides the doctor's elastic gate
    assert d["new_prefill_chips"] != before[0]
    assert d["predicted_migration_s"] > 0


@pytest.mark.slow
def test_disagg_rebalance_execution_bit_identity(lm):
    """An APPROVED ratio shift replans both sides onto the new disjoint
    windows (shrinking side first) and decode stays bit-identical to
    the unified engine across the move."""
    kw = dict(slots=4, max_new_tokens=6, prefill_chunk=4)
    want = lm.serve(**kw).generate([SHARED, [7, 8, 9]])
    dis = lm.serve(disaggregate=True, **kw)
    dis.generate([[i, i + 1, i + 2] for i in range(1, 9)])
    dis.rebalance_min_samples = 1
    dis.rebalance_factor = 0.0001
    d = dis.maybe_rebalance(horizon_steps=10 ** 6)
    assert d is not None and d["decision"] == "migrated"
    assert dis.prefill_chips == d["new_prefill_chips"]
    assert dis.prefill_chips + dis.decode_chips == 8
    assert dict(dis.prefill.decode_model.mesh.shape)["data"] == \
        dis.prefill_chips
    got = dis.generate([SHARED, [7, 8, 9]])
    assert got == want, "post-rebalance decode diverged"
