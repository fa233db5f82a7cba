"""One run of one benchmark cell:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one measured window, and as the last line of stdout
one JSON object: correct, attempted, failed, metrics, device (and breakdown
in a traced run). With --trace 0 the metrics are the cell's end-to-end
metrics; with --trace 1 the profiler is on for a short window of its own
(the cell file's `trace_seconds`) and the metrics are the cell's per-layer
metrics. Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result line.

Everything that belongs to one cell, configuration, traffic mix, job kind or
per-layer metric is a file found by its name in BENCHMARK.json (README.md).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
# the script's directory leaves the path (its trace.py would shadow the
# standard library's) and the checkout takes its place: the benchmark's
# modules are `benchmarks.<name>`, the program is `flexflow_tpu`
if os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = REPO
elif REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import harness  # noqa: E402

# where JAX_COMPILATION_CACHE_DIR is unset: one fixed place in the checkout
# (the path is part of the cache key, so a directory that moves never hits)
CACHE_DIR = os.path.join(REPO, ".jax_cache")
TRACE_DIR = os.path.join(REPO, ".bench_trace")


def find_device(chips: int) -> dict:
    """The TPU-or-exit guard: the device as JAX reports it."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"benchmark: needs a TPU, JAX found "
                 f"{devices[0].platform!r} ({devices[0].device_kind}); "
                 f"nothing is measured on it")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell needs {chips} chips, JAX found "
                 f"{len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes(chips: int) -> int:
    """The peak on the fullest of the chips the cell used."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def manifest_entry(manifest: dict, section: str, name: str) -> dict:
    for entry in manifest[section]:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"benchmark: BENCHMARK.json has no {section} entry "
                     f"named {name!r}")


def metrics_of(manifest: dict, section: str, cell: str) -> list[dict]:
    """The section's metrics that this cell reports."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


def main(argv=None, manifest_path=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)

    with open(manifest_path or os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    workload = manifest_entry(manifest, "workloads", opts.workload)
    cell = harness.load_json("workloads", workload["name"] + ".json")
    config = harness.load_json("configs", workload["config"] + ".json")
    traffic = harness.load_json("traffic", workload["traffic"] + ".json")
    chips = workload["chips"]

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    device = find_device(chips)
    peaks = harness.load_json("peaks.json").get(device["kind"])
    if peaks is None:
        sys.exit(f"benchmark: no peaks for device kind {device['kind']!r} "
                 f"in peaks.json")

    trace_dir = None
    seconds = opts.seconds
    if opts.trace:
        trace_dir = os.path.join(TRACE_DIR, workload["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        seconds = min(seconds, cell["trace_seconds"])
    ctx = harness.Context(
        cell=cell, config=config, traffic=traffic, seed=opts.seed,
        seconds=seconds, trace_dir=trace_dir, t_start=T_START,
        compile_log=harness.CompileLog())
    job = harness.load_module("jobs", cell["job"] + ".py")
    result = job.run(ctx)

    correct = bool(result["correct"])
    if ctx.compiles_in_window:
        print(f"[run] {ctx.compiles_in_window} compilation(s) inside the "
              f"window: the run is not correct")
        correct = False
    device["memory_peak_bytes"] = memory_peak_bytes(chips)
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {}, "device": device}
    if not opts.trace:
        values = dict(result["end_to_end"], setup_s=ctx.setup_s)
        for m in metrics_of(manifest, "end_to_end", workload["name"]):
            if values.get(m["name"]) is not None:
                line["metrics"][m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"]}
    else:
        from benchmarks import trace as trace_reader

        run = types.SimpleNamespace(
            ctx=ctx, result=result, cell=cell, config=config,
            traffic=traffic, chips=chips, peaks=peaks,
            trace=trace_reader.read_file(
                trace_reader.newest_xplane(trace_dir)))
        for m in metrics_of(manifest, "per_layer", workload["name"]):
            value = harness.load_reader(m["name"]).read(run)
            if value is not None:
                line["metrics"][m["name"]] = {
                    "value": value, "unit": m["unit"]}
        device["busy_s"] = run.trace.mean_busy_s(chips)
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {"device_ops": run.trace.device_ops(),
                             "idle_gaps": run.trace.idle_gaps()}
    print(f"[run] set-up {ctx.setup_s:.2f} s (XLA compiles or cache reads "
          f"{ctx.xla_compile_setup_s:.2f} s), window {ctx.window_s:.2f} s, "
          f"counters "
          f"{ {k: v for k, v in result['counters'].items() if not isinstance(v, list)} }")
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
