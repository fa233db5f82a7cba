"""Cost-model fidelity CI leg (CPU mesh; the real-chip battery is
scripts/cost_model_fidelity.py → FIDELITY_r05.json). The search only needs
RANKING fidelity to pick the right plan, so the real-chip artifact's
headline number is Spearman rank correlation between composed predictions
and measured step times. On a shared CI CPU, however, the two smallest
configs are dispatch-dominated and their wall-clock order flips under
machine noise (the long-standing flake), so the CI assertions are split:
the PREDICTION ordering is deterministic and asserted exactly, while the
only wall-clock fact asserted is a generous monotonic bound between the
battery's extremes (~30x FLOPs apart — an inversion there would mean the
measurement harness itself is broken, not that the machine was busy)."""

import os
import sys

# scripts/ lies beside tests/, and only tests/ is on pytest's path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_fidelity_rank_correlation_and_calibration():
    from flexflow_tpu import LossType, SGDOptimizer
    from scripts.cost_model_fidelity import (
        _lm,
        predict_step_time,
        run_fidelity,
    )

    small = _lm("lm_h64_s32_b4", 64, 4, 2, 32, 4, "xla", vocab=256)
    middle = _lm("lm_h128_s64_b4", 128, 4, 2, 64, 4, "xla", vocab=256)
    large = _lm("lm_h256_s64_b8", 256, 4, 4, 64, 8, "xla", vocab=256)
    # the clock reads the battery's extremes only, at the fewest steps:
    # the one wall-clock fact asserted below is between those two
    rep = run_fidelity([small, large], steps=1, calibrate_top_k=4)
    # the middle configuration is predicted as run_fidelity predicts, and
    # not measured
    ff, _, _ = middle["make"]()
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    rep["configs"].append({
        "name": middle["name"],
        "predicted_ms": predict_step_time(ff) * 1e3,
        "predicted_calibrated_ms": predict_step_time(
            ff, calibrate_top_k=4) * 1e3})
    rows = {r["name"]: r for r in rep["configs"]}
    # deterministic proxy for ranking fidelity: the composed analytic
    # predictions must order the size-separated family exactly — this is
    # what the search consumes, and it involves no wall clock at all
    assert (rows["lm_h64_s32_b4"]["predicted_ms"]
            < rows["lm_h128_s64_b4"]["predicted_ms"]
            < rows["lm_h256_s64_b8"]["predicted_ms"]), rep
    # generous monotonic bound on the measurement harness: the ~30x-FLOPs
    # config must not measure FASTER than the smallest. Adjacent configs
    # are deliberately NOT compared (dispatch-bound CPU times are noise-
    # ordered); the fine-grained ranking lives in the real-chip artifact.
    assert (rows["lm_h256_s64_b8"]["measured_ms"]
            >= rows["lm_h64_s32_b4"]["measured_ms"]), rep
    # calibration ran and changed the composed prediction (its absolute
    # accuracy is only meaningful on the real chip — the cpu ChipSpec is a
    # placeholder and XLA:CPU step overhead dwarfs per-op kernel time; the
    # error-shrink demonstration lives in the FIDELITY_r05.json artifact)
    for row in rep["configs"]:
        assert row["predicted_calibrated_ms"] > 0
        assert (row["predicted_calibrated_ms"] != row["predicted_ms"]), row


def test_spearman_helper():
    from scripts.cost_model_fidelity import _spearman

    assert _spearman([1, 2, 3], [10, 20, 30]) == 1.0
    assert _spearman([1, 2, 3], [30, 20, 10]) == -1.0
    assert _spearman([1, 1, 1], [1, 2, 3]) == 0.0
