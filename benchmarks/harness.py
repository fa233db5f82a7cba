"""What the jobs and the readers share: the run's context (spans, compile
log, window), the model builder, the FLOP count and percentile arithmetic.

The builder and the compile log are copies of `chip_smoke.py`'s working
recipes (PERF.md section 7 lists the originals); the FLOP count is a copy of
`models.transformer.transformer_lm_flops_per_token`. The benchmark keeps
its own so that a later PR cannot move the yardstick.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


# where the benchmark's files are looked for, by name, in this order
ROOTS = [HERE]


def find_file(*parts) -> str:
    for root in ROOTS:
        path = os.path.join(root, *parts)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(
        f"the benchmark has no file {os.path.join(*parts)} under {ROOTS}")


def load_json(*parts):
    with open(find_file(*parts)) as f:
        return json.load(f)


def load_module(*parts):
    """A module of the benchmark from its file (names may hold dots)."""
    path = find_file(*parts)
    name = "bench_" + "_".join(parts).replace(".", "_").replace("/", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(metric: str):
    """A per-layer metric's reader: `layer_metrics/<metric>.py`, or, for
    metrics that differ only in the suffix after their last dot (one
    quantity split by the end-to-end metric it moves), the file of the
    name without it."""
    try:
        return load_module("layer_metrics", metric + ".py")
    except FileNotFoundError:
        if "." not in metric:
            raise
        return load_module("layer_metrics", metric.rsplit(".", 1)[0] + ".py")


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100), linear interpolation between the sorted
    values (numpy's default)."""
    if not len(values):
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))


def median(values) -> float:
    return percentile(values, 50.0)


class CompileLog:
    """What JAX reports about compilation, process-wide: programs compiled
    or fetched from the persistent cache, and the seconds the backend
    spent on them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.compilations = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, name, secs, **kw):
        if name == self.EVENT:
            self.compilations += 1
            self.seconds += secs


class Context:
    """One run: the cell's files, the clock and what the run collects."""

    def __init__(self, *, cell, config, traffic, seed, seconds, trace_dir,
                 t_start, compile_log):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed = seed
        self.seconds = seconds          # length of the measured window
        self.trace_dir = trace_dir      # None: profiler off
        self.t_start = t_start
        self.compile_log = compile_log
        self.spans = []                 # (name, start, end), perf_counter
        self.setup_s = None
        self.xla_compile_setup_s = None
        self.window = None              # (start, end), perf_counter
        self._compiles_at_open = None
        self._compiles_at_close = None
        self._window_span = None

    @contextlib.contextmanager
    def span(self, name: str):
        """A harness span: kept for the readers, and written into the
        profiler's trace (when one is on) so it sits on the device's
        clock."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/" + name):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def seconds_in(self, name: str):
        return [b - a for n, a, b in self.spans if n == name]

    def open_window(self) -> float:
        """Set-up ends here: nothing may compile until close_window."""
        import jax

        self.xla_compile_setup_s = self.compile_log.seconds
        self._compiles_at_open = self.compile_log.compilations
        if self.trace_dir is not None:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._window_span = jax.profiler.TraceAnnotation("bench/window")
        self._window_span.__enter__()
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        self.window = (now, None)
        return now

    def close_window(self) -> float:
        import jax

        now = time.perf_counter()
        self._window_span.__exit__(None, None, None)
        self.window = (self.window[0], now)
        self._compiles_at_close = self.compile_log.compilations
        if self.trace_dir is not None:
            jax.profiler.stop_trace()
        return now

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def compiles_in_window(self) -> int:
        return self._compiles_at_close - self._compiles_at_open


def lm_config(config: dict, sequence_length: int, attention_impl: str):
    """The program's TransformerLMConfig from a configuration file's
    published keys (GPT-2 naming: n_embd, n_layer, n_head, n_inner)."""
    from flexflow_tpu.models import TransformerLMConfig

    d = config["n_embd"]
    if config["n_inner"] % d:
        raise ValueError("n_inner is not a multiple of n_embd")
    if sequence_length > config["n_positions"]:
        raise ValueError("the cell's sequences are longer than n_positions")
    return TransformerLMConfig(
        vocab_size=config["vocab_size"], hidden_size=d,
        num_heads=config["n_head"], num_layers=config["n_layer"],
        mlp_ratio=config["n_inner"] // d, sequence_length=sequence_length,
        attention_impl=attention_impl)


def build_lm(cfg, flags, batch: int, optimizer: str):
    """A compiled lm FFModel from the flags a user would put on the command
    line (FFConfig parses sys.argv)."""
    from flexflow_tpu import (
        AdamOptimizer, FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )
    from flexflow_tpu.models import build_transformer_lm

    argv = sys.argv
    sys.argv = [argv[0], "-b", str(batch), *flags]
    try:
        config = FFConfig()
    finally:
        sys.argv = argv
    ff = FFModel(config)
    build_transformer_lm(ff, cfg, batch_size=batch)
    ff.compile(
        optimizer={"adam": AdamOptimizer, "sgd": SGDOptimizer}[optimizer](),
        loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY])
    return ff


def param_getter(ff):
    """`get(node, weight)` over a model's live parameters, for the
    reference."""
    return lambda node, weight: ff._params[node][weight]


def flops_per_token(config: dict, sequence_length: int) -> float:
    """Forward and backward FLOPs a token needs, no recomputation: six per
    matmul parameter (the per-layer projections and MLP, and the head; the
    embeddings are gathers) and causal attention's scores and values."""
    d, layers = config["n_embd"], config["n_layer"]
    per_layer = 4 * d * d + 2 * config["n_inner"] * d
    matmul_params = layers * per_layer + config["vocab_size"] * d
    return (6.0 * matmul_params
            + layers * 12.0 * d * sequence_length / 2)


def attention_least_seconds(config: dict, sequence_length: int,
                            batch_per_chip: int, peaks: dict):
    """(seconds, what bounds it) the chip needs at the least for one
    step's causal attention calls, forward and backward, all layers: six
    matmuls of 2 s^2 head_dim a head (QK^T and PV forward; dV, dP, dQ, dK
    backward; the backward's recomputation of the scores is not counted),
    halved by the causal mask, against q, k, v, o read or written once
    forward and q, k, v, o, do, dq, dk, dv once backward, in bf16."""
    d, layers, s = config["n_embd"], config["n_layer"], sequence_length
    flops = layers * 6.0 * batch_per_chip * s * s * d
    moved = layers * 12.0 * batch_per_chip * s * d * 2
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes
                                     else "bytes")
