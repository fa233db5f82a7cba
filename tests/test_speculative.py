"""Speculative-decoding tests (serving/speculative.py, docs/serving.md).

The acceptance surface of the drafter/verify path on the 8-device CPU
mesh:

  - speculative token streams are BIT-IDENTICAL to the unified engine at
    both acceptance extremes — a drafter that always agrees (seed-clone
    of the target) and one that never does (monkeypatched proposals of a
    token the target never samples);
  - slot reuse under continuous batching never leaks drafter cursor
    state between residents;
  - verify rollback composes with the paged COW/radix machinery — the
    BlockManager invariants hold after every speculative round and a
    shared prefix is never poisoned by rejected rows;
  - the drafter compiles role-keyed: a second speculative engine against
    one --warmstart-dir is a 0-eval plan-cache hit for BOTH plans;
  - the acceptance EMA round-trips through the warm-start calibration
    DB keyed per (target, drafter) pair;
  - payoff decisions carry every factor and reproduce arithmetically
    under the doctor's rule, and the flag validation names the flag.
"""

import sys

import pytest

from small_lms import SearchSpy

PROMPTS = [[3, 7, 11, 2, 5], [5, 2], [1, 9, 30, 30, 12, 4, 8], [60, 1, 2]]


def _lm_config(**kw):
    from flexflow_tpu.models import TransformerLMConfig

    base = dict(vocab_size=64, hidden_size=32, num_heads=4, num_layers=2,
                sequence_length=32, attention_impl="xla")
    base.update(kw)
    return TransformerLMConfig(**base)


def _build_lm(mesh=(1, 1, 1, 1), batch=1, argv=(), **lm_kw):
    sys.argv = ["test"] + list(argv)
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import build_transformer_lm

    cfg = FFConfig()
    if cfg.mesh_axis_sizes is None:
        cfg.mesh_axis_sizes = mesh
    cfg.batch_size = batch
    ff = FFModel(cfg)
    build_transformer_lm(ff, _lm_config(**lm_kw), batch_size=batch)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


def _force_speculation(eng):
    """White-box: bypass the payoff gate so every eligible round
    speculates — the sustained-speculation harness the rollback/reuse
    tests need. The honest gate (correctly) declines on CPU, where a
    drafter call costs as much as a target call, and an all-reject EMA
    zeroes the expected payoff entirely."""
    def always(k_cap):
        d = {"k": min(eng.k_max, k_cap),
             "reason": "bootstrap",
             "chosen": "speculate" if k_cap >= 1 else "decode",
             "would_speculate": k_cap >= 1,
             "acceptance_ema": float(eng.acceptance_ema),
             "acceptance_samples": int(eng.acceptance_samples)}
        eng._decision_counts[d["chosen"]] += 1
        eng.decisions.append(d)
        return d

    eng._decide = always


def _reject_all(eng, tok):
    """Monkeypatch the drafter to always propose `tok` — with `tok`
    verified absent from every plain-decode stream, every proposal
    rejects and every verify emits exactly the correction token."""
    def propose(decoding, ks):
        return ({i: [tok] * k for i, k in ks.items()}, 1e-6)

    eng.drafter.propose = propose


# ------------------------------------------------------------ bit-identity


def test_spec_all_accept_bit_identity():
    """Drafter = seed-clone of the target: every proposal matches, the
    stream is bit-identical, and the engine's speculation accounting
    shows the all-accept extreme (acceptance rate 1.0, K+1 tokens per
    verified slot-round)."""
    ff = _build_lm()
    plain = ff.serve(slots=2, max_new_tokens=8, prefill_chunk=4)
    base = plain.generate(PROMPTS)

    dff = _build_lm()  # same config + seed -> identical weights
    eng = ff.serve(speculate=True, draft_model=dff, slots=2,
                   max_new_tokens=8, prefill_chunk=4)
    assert eng.generate(PROMPTS) == base
    sp = eng.stats()["speculation"]
    assert sp["rounds"] >= 1, "bootstrap round must have speculated"
    assert sp["draft_tokens"] > 0
    assert sp["accepted_tokens"] == sp["draft_tokens"]
    assert sp["acceptance_rate"] == 1.0
    assert eng.acceptance_ema == 1.0
    # metrics plane: the pre-created spec series saw the rounds
    assert eng._c_spec_rounds.value == sp["rounds"]
    assert eng._h_spec_accept_rate.count > 0


def test_spec_all_reject_bit_identity():
    """Adversarial drafter (proposes a token the target never samples):
    every round rejects everything and emits only the correction token —
    still bit-identical, and the acceptance EMA collapses toward 0."""
    ff = _build_lm()
    plain = ff.serve(slots=2, max_new_tokens=8, prefill_chunk=4)
    base = plain.generate(PROMPTS)
    bad = 63
    assert all(bad not in g for g in base), \
        "pick a proposal token plain decode never emits"

    dff = _build_lm()
    eng = ff.serve(speculate=True, draft_model=dff, slots=2,
                   max_new_tokens=8, prefill_chunk=4)
    _force_speculation(eng)
    _reject_all(eng, bad)
    assert eng.generate(PROMPTS) == base
    sp = eng.stats()["speculation"]
    assert sp["rounds"] > 1, "forced speculation must have run repeatedly"
    assert sp["accepted_tokens"] == 0
    # every rejected round emits exactly one correction token per slot
    assert sp["rounds"] <= sp["emitted_tokens"] <= 2 * sp["rounds"]
    assert eng.acceptance_ema < 0.5


def test_spec_slot_reuse_under_continuous_batching():
    """Six requests through two slots with sustained speculation: every
    admission reuses a slot whose drafter cursor belonged to the prior
    resident — the owner check must reset it, keeping streams identical
    to the unified engine's interleaved run."""
    ff = _build_lm()
    prompts = PROMPTS + [[2, 4, 6, 8], [33, 1]]
    plain = ff.serve(slots=2, max_new_tokens=6, prefill_chunk=4)
    base = plain.generate(prompts)

    dff = _build_lm()
    eng = ff.serve(speculate=True, draft_model=dff, slots=2,
                   max_new_tokens=6, prefill_chunk=4)
    _force_speculation(eng)
    assert eng.generate(prompts) == base
    assert eng.stats()["speculation"]["rounds"] > 1
    assert eng.scheduler.drained


def test_spec_paged_cow_radix_rollback_safety():
    """Rejection-heavy speculation over shared-prefix prompts on the
    paged layout: the verify rollback (host cursor rewind) must never
    corrupt a shared block — BlockManager invariants hold after every
    step, streams stay bit-identical, and a SECOND pass over the same
    prompts (radix cross-time hits serving cached prefix blocks) still
    matches."""
    shared = [7, 7, 7, 7, 3, 3, 3, 3]
    prompts = [shared + [t] for t in (1, 2, 3)]
    ff = _build_lm()
    kw = dict(slots=2, max_new_tokens=6, prefill_chunk=4,
              kv_block_size=4, kv_num_blocks=64)
    plain = ff.serve(**kw)
    base = plain.generate(prompts)
    bad = 63
    assert all(bad not in g for g in base)

    dff = _build_lm()
    eng = ff.serve(speculate=True, draft_model=dff, **kw)
    assert eng.block_manager is not None
    _force_speculation(eng)
    _reject_all(eng, bad)
    for ever in range(2):  # second pass: cross-time radix hits
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        while not eng.scheduler.drained:
            eng.step()
            eng.block_manager.check_invariants()
        assert [r.generated for r in reqs] == base, f"pass {ever}"
    assert eng.block_manager.stats.cross_time_hits > 0, \
        "second pass never hit the radix cache — test is vacuous"
    assert eng.stats()["speculation"]["rounds"] > 1


# ------------------------------------------------------------ placement


def test_spec_draft_chips_disjoint_submesh():
    """--serve-draft-chips carves the drafter onto the trailing chips:
    device sets are disjoint, the section records the split, and the
    stream stays bit-identical to the colocated plain engine."""
    ff = _build_lm(mesh=(8, 1, 1, 1), batch=8)
    plain = ff.serve(slots=4, max_new_tokens=6, prefill_chunk=4)
    base = plain.generate(PROMPTS)

    dff = _build_lm(mesh=(1, 1, 1, 1), batch=1)
    eng = ff.serve(speculate=True, draft_model=dff, draft_chips=4,
                   slots=4, max_new_tokens=6, prefill_chunk=4)
    tdev = {d.id for d in eng.decode_model.mesh.devices.flat}
    ddev = {d.id for d in
            eng.drafter.engine.decode_model.mesh.devices.flat}
    assert len(tdev) == 4 and len(ddev) == 4
    assert not tdev & ddev, "drafter and target sub-meshes overlap"
    assert eng.generate(PROMPTS) == base
    sec = eng.speculation_section()
    assert sec["draft_chips"] == 4 and not sec["colocated"]
    assert eng.drafter.engine.decode_model.config.serve_role == "draft"


# ------------------------------------------------------------ warm start


def test_spec_warmstart_role_keyed_plan_cache(tmp_path):
    """Second speculative engine against one --warmstart-dir: ZERO
    search evaluations — the target hits the plain serving plan address
    (colocated speculation adds no config delta) and the drafter hits
    its role="draft"-keyed address."""
    ws = str(tmp_path / "ws")
    search_argv = ["--warmstart-dir", ws, "--search-budget", "4",
                   "--enable-parameter-parallel",
                   "--enable-attribute-parallel"]
    ff = _build_lm(mesh=(2, 4, 1, 1), batch=8, argv=search_argv)
    # the drafter's decode config derives from the DRAFT model's own
    # config (user overrides apply to the target only), so its search
    # and warm-start flags ride the draft model's argv
    dff = _build_lm(mesh=(2, 4, 1, 1), batch=8, argv=search_argv)
    kw = dict(slots=8, max_new_tokens=4, prefill_chunk=4)
    eng1 = ff.serve(speculate=True, draft_model=dff, **kw)
    assert eng1.decode_model._plan_source == "search"
    assert eng1.drafter.engine.decode_model._plan_source == "search"
    out1 = eng1.generate(PROMPTS[:2])

    with SearchSpy() as spy:
        eng2 = ff.serve(speculate=True, draft_model=dff, **kw)
    assert spy.searches == 0, "speculative re-serve must not re-search"
    assert spy.evals == 0, "speculative re-serve must cost 0 evaluations"
    assert eng2.decode_model._plan_source == "cache"
    assert eng2.drafter.engine.decode_model._plan_source == "cache"
    assert eng2.generate(PROMPTS[:2]) == out1


def test_spec_acceptance_ema_roundtrips_calibration_db(tmp_path):
    """The per-(target, drafter) acceptance EMA persists in the
    warm-start calibration DB at drain and seeds a FRESH process's
    engine (new model objects, same arch + dir) — the r20
    migration-fidelity treatment."""
    from flexflow_tpu.serving.speculative import (
        DEFAULT_ACCEPTANCE, load_acceptance,
    )

    ws = str(tmp_path / "ws")
    ff = _build_lm(argv=["--warmstart-dir", ws])
    dff = _build_lm(argv=["--warmstart-dir", ws])
    eng = ff.serve(speculate=True, draft_model=dff, slots=2,
                   max_new_tokens=8, prefill_chunk=4)
    eng.generate(PROMPTS)  # drain -> forced persist
    assert eng.acceptance_samples > 0
    assert eng.acceptance_ema != DEFAULT_ACCEPTANCE

    ff2 = _build_lm(argv=["--warmstart-dir", ws])
    dff2 = _build_lm(argv=["--warmstart-dir", ws])
    eng2 = ff2.serve(speculate=True, draft_model=dff2, slots=2,
                     max_new_tokens=8, prefill_chunk=4)
    assert eng2.pair_key == eng.pair_key
    assert eng2.acceptance_ema == pytest.approx(eng.acceptance_ema)
    assert eng2.acceptance_samples == eng.acceptance_samples
    # and the loader itself reports the DB entry, not the default
    rate, samples = load_acceptance(ff2, eng.pair_key)
    assert rate == pytest.approx(eng.acceptance_ema) and samples > 0


# ------------------------------------------------------------ payoff gate


def test_spec_payoff_decision_arithmetic():
    """The decision record reproduces under the doctor's rule: lhs =
    K·draft + verify, rhs = (Σ a^i)·decode with the engine's own
    accumulation order, chosen agrees with the inequality, and the
    engine picks the net-maximizing K."""
    from flexflow_tpu.search.cost_model import price_verify_scale
    from flexflow_tpu.serving.speculative import expected_accepted

    assert expected_accepted(0.8, 3) == pytest.approx(
        0.8 + 0.8 ** 2 + 0.8 ** 3)
    assert price_verify_scale(1) == 1.0
    assert price_verify_scale(5) == pytest.approx(2.0)

    ff = _build_lm()
    dff = _build_lm()
    eng = ff.serve(speculate=True, draft_model=dff, slots=2,
                   max_new_tokens=4, prefill_chunk=4)
    eng._decode_cost_s = 1.0
    eng._draft_cost_s = 0.1
    eng._verify_cost_s = {k + 1: 0.2 + 0.05 * k for k in range(1, 5)}
    eng.acceptance_ema, eng.acceptance_samples = 0.8, 10
    d = eng._decide(4)
    assert d["reason"] == "payoff"
    # doctor-rule reproduction, same accumulation order
    exp, x = 0.0, 1.0
    for _ in range(d["k"]):
        x *= d["acceptance_ema"]
        exp += x
    lhs = d["k"] * d["draft_cost_s"] + d["verify_cost_s"]
    rhs = exp * d["decode_cost_s"]
    assert d["expected_accepted"] == pytest.approx(exp, abs=1e-12)
    assert d["lhs_s"] == pytest.approx(lhs, abs=1e-12)
    assert d["rhs_s"] == pytest.approx(rhs, abs=1e-12)
    assert d["would_speculate"] == (lhs < rhs)
    assert d["chosen"] == ("speculate" if lhs < rhs else "decode")
    # K maximizes net over every candidate
    nets = []
    for k in range(1, 5):
        e, x = 0.0, 1.0
        for _ in range(k):
            x *= 0.8
            e += x
        nets.append(e * 1.0 - (k * 0.1 + eng._verify_cost_s[k + 1]))
    assert d["k"] == nets.index(max(nets)) + 1
    # no headroom forces plain decode with the reason on record
    d0 = eng._decide(0)
    assert d0["reason"] == "no_headroom" and d0["chosen"] == "decode"
    # an unmeasured verify bucket prices off the cost-model prior and
    # says so
    eng._verify_cost_s = {}
    d2 = eng._decide(2)
    assert d2["verify_cost_source"] == "assumed"
    assert eng.decisions[-1] is d2


# ------------------------------------------------------------ validation


def test_spec_flag_and_argument_validation():
    """Misconfigurations fail fast with the flag named: chip budgets
    past the visible device count, speculate without a drafter,
    speculate+disaggregate, K < 1, a drafter whose positional table is
    too short, and a drafter with a foreign vocabulary."""
    import jax

    n = len(jax.devices())
    ff = _build_lm(argv=["--serve-draft-chips", str(n)])
    with pytest.raises(ValueError, match="--serve-draft-chips"):
        ff.serve(slots=2)
    ff = _build_lm(argv=["--serve-prefill-chips", str(n + 1)])
    with pytest.raises(ValueError, match="--serve-prefill-chips"):
        ff.serve(slots=2)

    ff = _build_lm()
    with pytest.raises(ValueError, match="draft_model"):
        ff.serve(speculate=True, slots=2)
    with pytest.raises(ValueError, match="mutually exclusive"):
        ff.serve(speculate=True, disaggregate=True, draft_model=ff,
                 slots=2)
    with pytest.raises(ValueError, match="--serve-spec-k"):
        ff.serve(speculate=True, draft_model=ff, spec_k=0, slots=2)
    # kwarg draft_chips out of range names the flag too
    with pytest.raises(ValueError, match="--serve-draft-chips"):
        ff.serve(speculate=True, draft_model=ff, draft_chips=n, slots=2)

    short = _build_lm(sequence_length=16)
    with pytest.raises(ValueError, match="positional table"):
        ff.serve(speculate=True, draft_model=short, slots=2)
    alien = _build_lm(vocab_size=32)
    with pytest.raises(ValueError, match="vocab"):
        ff.serve(speculate=True, draft_model=alien, slots=2)
