"""Cost-model fidelity harness: composed prediction vs measured step time.

The reference earns trust in its simulator by MEASURING every op on the
real device inside the search (Simulator::measure_operator_cost,
src/runtime/model.cu:38-75, consumed by graph.cc:1586-1735). This repo
calibrates the dominant ops the same way — but a calibrated op model still
has to COMPOSE into an accurate whole-step prediction (makespan over the
task graph + collective pricing). This harness validates exactly that:

for a battery of single-chip configs (hidden/seq/batch/attention-impl/MoE/
MLP), it
  1. measures the real training-step time with the dispatch-immune jitted
     lax.scan loop (bench.py's measurement methodology),
  2. predicts the step time with the analytic cost model (fixed-mfu
     roofline) and again with on-device calibration
     (CostModel.calibrate_graph),
and emits a JSON artifact with per-config errors and the Spearman rank
correlation between predicted and measured — the search only needs
*ranking* fidelity to pick the right plan, so rank correlation is the
headline number, and calibration must demonstrably shrink the error.

Run on the real chip:  python scripts/cost_model_fidelity.py [out.json]
CI (CPU mesh) asserts rank correlation via tests/test_fidelity.py.
"""

from __future__ import annotations

import json
import os
import sys
import time

# runnable as `python scripts/cost_model_fidelity.py` (script dir, not the
# repo root, lands on sys.path)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _lm(name, hidden, heads, layers, seq, batch, impl, vocab=8192):
    def make():
        import numpy as np

        from flexflow_tpu import FFConfig, FFModel
        from flexflow_tpu.models import (
            TransformerLMConfig,
            build_transformer_lm,
        )

        sys.argv = [sys.argv[0]]
        config = FFConfig()
        config.batch_size = batch
        ff = FFModel(config)
        c = TransformerLMConfig(vocab_size=vocab, hidden_size=hidden,
                                num_heads=heads, num_layers=layers,
                                sequence_length=seq, attention_impl=impl)
        build_transformer_lm(ff, c, batch_size=batch)
        rs = np.random.RandomState(0)
        feeds = {
            "tokens": rs.randint(0, vocab, (batch, seq)).astype(np.int32),
            "positions": np.tile(np.arange(seq, dtype=np.int32),
                                 (batch, 1)),
        }
        labels = rs.randint(0, vocab, (batch, seq, 1)).astype(np.int32)
        return ff, feeds, labels

    return {"name": name, "make": make}


def _mlp(name, batch, in_dim, hidden):
    def make():
        import numpy as np

        from flexflow_tpu import FFConfig, FFModel
        from flexflow_tpu.models import build_mlp_unify

        sys.argv = [sys.argv[0]]
        config = FFConfig()
        config.batch_size = batch
        ff = FFModel(config)
        build_mlp_unify(ff, batch_size=batch, in_dim=in_dim,
                        hidden_dims=(hidden,) * 4)
        rs = np.random.RandomState(0)
        feeds = {
            "input1": rs.randn(batch, in_dim).astype(np.float32),
            "input2": rs.randn(batch, in_dim).astype(np.float32),
        }
        labels = rs.randint(0, hidden, (batch, 1)).astype(np.int32)
        return ff, feeds, labels

    return {"name": name, "make": make}


def _moe(name, batch, fused=True):
    def make():
        import numpy as np

        from flexflow_tpu import FFConfig, FFModel
        from flexflow_tpu.models import MoeConfig, build_moe

        sys.argv = [sys.argv[0]]
        config = FFConfig()
        config.batch_size = batch
        ff = FFModel(config)
        c = MoeConfig()
        build_moe(ff, c, batch_size=batch, fused=fused)
        rs = np.random.RandomState(0)
        feeds = {"input": rs.randn(batch, c.in_dim).astype(np.float32)}
        labels = rs.randint(0, c.num_classes, (batch, 1)).astype(np.int32)
        return ff, feeds, labels

    return {"name": name, "make": make}


def tpu_configs():
    """10 single-chip configs varying hidden / seq / batch / attention
    impl / model family (the VERDICT battery). Bounded by calibration
    compile time: each distinct op key costs two jitted-loop compiles; the
    calibration cache is shared across configs (same-shape ops measure
    once)."""
    return [
        _lm("lm_h512_s512_b8_xla", 512, 8, 6, 512, 8, "xla"),
        _lm("lm_h1024_s128_b8_xla", 1024, 16, 6, 128, 8, "xla"),
        _lm("lm_h1024_s512_b8_flash", 1024, 16, 6, 512, 8, "flash"),
        _lm("lm_h1024_s512_b4_flash", 1024, 16, 6, 512, 4, "flash"),
        _lm("lm_h1024_s512_b16_flash", 1024, 16, 6, 512, 16, "flash"),
        _lm("lm_flagship12_flash", 1024, 16, 12, 512, 8, "flash",
            vocab=32000),
        _lm("lm_h2048_s256_b8_flash", 2048, 16, 4, 256, 8, "flash"),
        _mlp("mlp_unify_b256_h8192", 256, 1024, 8192),
        _mlp("mlp_unify_b64_h4096", 64, 1024, 4096),
        _moe("moe_flat_b256_fused", 256, fused=True),
    ]


def cpu_configs():
    """Small, strongly size-separated battery for the CPU-mesh CI test."""
    return [
        _lm("lm_h64_s32_b4", 64, 4, 2, 32, 4, "xla", vocab=256),
        _lm("lm_h128_s64_b4", 128, 4, 2, 64, 4, "xla", vocab=256),
        _lm("lm_h256_s64_b8", 256, 4, 4, 64, 8, "xla", vocab=256),
        _mlp("mlp_b16_h256", 16, 128, 256),
        _mlp("mlp_b64_h1024", 64, 256, 1024),
    ]


def measure_step_time(ff, feeds, labels, steps=10,
                      floor_s: float = 0.0) -> float:
    """Measured seconds/step by two-point slope timing (see
    CostModel.calibrate's docstring): one jitted fori_loop of train steps
    with a DYNAMIC trip count, synchronized by fetching the step counter,
    timed at n and 3n — the slope is the per-step device time with the
    per-call constants (dispatch, the fetch) cancelled. Readings below
    `floor_s` (a roofline-derived physical bound) are retried as flukes."""
    import statistics

    import jax
    import jax.numpy as jnp

    from flexflow_tpu import LossType, SGDOptimizer

    if not getattr(ff, "_compiled", False):
        ff.compile(optimizer=SGDOptimizer(lr=0.01),
                   loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    step_fn = ff.executor.build_train_step()
    batch_data = ff._make_batch(feeds, labels)
    state = (ff._params, ff._state, ff._opt_slots, ff._step, ff._counters)
    rng = jax.random.key(0)

    @jax.jit
    def loop(st, r, batch, n):
        def body(_, carry):
            st, r = carry
            r, sub = jax.random.split(r)
            out = step_fn(*st, sub, batch)
            return (out[:5], r)

        return jax.lax.fori_loop(0, n, body, (st, r))

    def sync(st):
        return int(jax.device_get(st[3]))  # fetching forces completion

    st, rng = loop(state, rng, batch_data, jnp.int32(steps))
    sync(st)  # compile + warm

    def t_of(n):
        nonlocal st, rng
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            st, rng = loop(st, rng, batch_data, jnp.int32(n))
            sync(st)
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    for _ in range(4):
        t1 = t_of(steps)
        t2 = t_of(3 * steps)
        per_step = (t2 - t1) / (2 * steps)
        if per_step >= floor_s:
            return per_step
    raise RuntimeError(
        f"step-time slope repeatedly below the physical floor "
        f"{floor_s * 1e3:.3f} ms — backend measurement flukes")


def predict_step_time(ff, calibrate_top_k: int = 0,
                      calibration_cache: dict | None = None) -> float:
    """Predicted seconds/step: the composed makespan of the compiled PCG
    under the machine model (evaluate_graph — compute roofline + collective
    classification + task-graph critical path). calibrate_top_k > 0 first
    measures the K dominant distinct ops on the local device
    (measure_operator_cost analog) and predicts from those;
    `calibration_cache` shares measurements across configs (the cache is
    keyed by op params + unsharded input shapes, so it is config-safe)."""
    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.machine_model import machine_model_for_mesh
    from flexflow_tpu.search.substitution import evaluate_graph

    cm = CostModel(machine_model_for_mesh(ff.mesh))
    if calibration_cache is not None:
        cm._calibration = calibration_cache
    if calibrate_top_k:
        cm.calibrate_graph(ff.graph, top_k=calibrate_top_k)
    t, _ = evaluate_graph(ff.graph, ff.mesh, cm)
    return t


def _spearman(xs, ys) -> float:
    import numpy as np

    def ranks(v):
        v = np.asarray(v, dtype=float)
        order = np.argsort(v)
        r = np.empty(len(v))
        r[order] = np.arange(len(v), dtype=float)
        for val in np.unique(v):  # ties share the average rank
            mask = v == val
            r[mask] = r[mask].mean()
        return r

    rx, ry = ranks(np.asarray(xs)), ranks(np.asarray(ys))
    if rx.std() == 0 or ry.std() == 0:
        return 0.0
    return float(np.corrcoef(rx, ry)[0, 1])


def run_fidelity(configs, steps=10, calibrate_top_k=6,
                 partial_path: str | None = None) -> dict:
    import jax

    from flexflow_tpu import LossType, SGDOptimizer

    on_tpu = jax.devices()[0].platform == "tpu"
    cal_cache: dict = {}  # shared across configs (keyed by op + shapes)
    rows = []
    for spec in configs:
        ff, feeds, labels = spec["make"]()
        ff.compile(optimizer=SGDOptimizer(lr=0.01),
                   loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
        pred_raw = predict_step_time(ff)
        # the roofline composed prediction is a (loose) physical lower
        # bound: a tenth of it floors the fluke filter on the real chip
        floor = 0.1 * pred_raw if on_tpu else 0.0
        measured = measure_step_time(ff, feeds, labels, steps=steps,
                                     floor_s=floor)
        pred_cal = predict_step_time(ff, calibrate_top_k=calibrate_top_k,
                                     calibration_cache=cal_cache)
        rows.append({
            "name": spec["name"],
            "measured_ms": round(measured * 1e3, 4),
            "predicted_ms": round(pred_raw * 1e3, 4),
            "predicted_calibrated_ms": round(pred_cal * 1e3, 4),
            "rel_err": round(pred_raw / measured - 1.0, 4),
            "rel_err_calibrated": round(pred_cal / measured - 1.0, 4),
        })
        print(f"fidelity: {rows[-1]}", flush=True)
        if partial_path:  # survive a timeout with partial evidence
            with open(partial_path, "w") as f:
                json.dump({"partial": True, "configs": rows}, f, indent=1)
    measured = [r["measured_ms"] for r in rows]
    raw = [r["predicted_ms"] for r in rows]
    cal = [r["predicted_calibrated_ms"] for r in rows]

    def mare(pred):
        return round(sum(abs(p / m - 1.0) for p, m in zip(pred, measured))
                     / len(measured), 4)

    return {
        "device": str(jax.devices()[0]),
        "n_configs": len(rows),
        "configs": rows,
        "spearman": _spearman(raw, measured),
        "spearman_calibrated": _spearman(cal, measured),
        "mean_abs_rel_err": mare(raw),
        "mean_abs_rel_err_calibrated": mare(cal),
    }


def main():
    out_path = sys.argv[1] if len(sys.argv) > 1 else "FIDELITY_r05.json"
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    report = run_fidelity(tpu_configs() if on_tpu else cpu_configs(),
                          steps=10 if on_tpu else 3,
                          calibrate_top_k=4 if on_tpu else 4,
                          partial_path=out_path + ".partial")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: v for k, v in report.items() if k != "configs"},
                     indent=1))
    for r in report["configs"]:
        print(f"  {r['name']:28s} measured {r['measured_ms']:9.3f} ms  "
              f"raw {r['predicted_ms']:9.3f} ({r['rel_err']:+.0%})  "
              f"cal {r['predicted_calibrated_ms']:9.3f} "
              f"({r['rel_err_calibrated']:+.0%})")


if __name__ == "__main__":
    main()
