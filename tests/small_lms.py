"""The small language models the serving tests run, and their engines.

A model is built once a process for each tuple of arguments (`build_lm`),
and so is an engine for each (model, `serve()` options): `engine(ff, **kw)`
hands back the one `ff.serve(**kw)` made, as new. What that saves is the
lowering: an engine lowers and compiles each bucket's step the first time
the bucket runs, a `(rows, 1)` step of an `impl="flash"` engine through the
Pallas interpreter at about five seconds a kernel call (PERF.md section 6,
PR 58), so a second `serve()` with the same options pays all of it again
for the same programs.

What an engine carries from one `generate` to the next, and what `engine`
does about each:

  - slots, the queue and the step in flight: a drained engine has none.
    An engine that is not drained (the test before failed in mid-run) is
    thrown away and built again;
  - the counters, the metrics registry and the completed requests:
    `reset_stats()` zeroes them. `_iterations` and `_step_ids` go on, as
    they do in one engine's life (a step's id names one step): read them
    before and after;
  - pages: the block manager's tables, free list, reservations and the
    radix prefix cache, which keeps a finished prompt's blocks. Replaced
    by a copy of the manager as `serve()` made it, so a prompt an earlier
    test sent matches nothing;
  - the pools' rows and the layers' per-slot state on the device: left as
    they lie. No live row reads them (rows past a slot's cursor are
    masked, a slot's recurrent state is reset by its first chunk), which
    is what the engine promises a slot's next owner in any run;
  - the sampling key: dropped, so the first sampled step splits it anew;
  - `max_new_tokens` and `eos_id` are a request's defaults and no part of
    an engine's programs: `engine` sets them on the engine it hands back,
    and engines that differ only there are one engine;
  - a method a test replaces (`_step_fn`, `_schedule`, `_stage_step`) is
    the test's to put back: `complete_every_step_at_once` and
    `staged_shapes` take `monkeypatch`.

A test that needs an engine of its own calls `ff.serve()` and says why in a
line: it patches a module constant that the build or a bucket's first
trace reads, counts the engine's executables or compile events from the
first, enables telemetry on its model, moves the engine to another mesh,
or changes the engine's tensors from outside.
"""

import copy
import functools
import sys

import numpy as np


def lm_config(sequence_length=32):
    from flexflow_tpu.models import TransformerLMConfig

    return TransformerLMConfig(
        vocab_size=64, hidden_size=32, num_heads=4, num_layers=2,
        sequence_length=sequence_length, attention_impl="xla")


def new_lm(mesh=(1, 1, 1, 1), batch=8, argv=(), sequence_length=32):
    """A model of the caller's own (one whose telemetry the test turns on,
    say); `build_lm` is the shared one."""
    sys.argv = ["test"] + list(argv)
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import build_transformer_lm

    cfg = FFConfig()
    if cfg.mesh_axis_sizes is None:
        cfg.mesh_axis_sizes = mesh
    cfg.batch_size = batch
    ff = FFModel(cfg)
    build_transformer_lm(ff, lm_config(sequence_length), batch_size=batch)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff


_shared_lm = functools.cache(new_lm)


def build_lm(mesh=(1, 1, 1, 1), batch=8, argv=(), sequence_length=32):
    return _shared_lm(tuple(mesh), batch, tuple(argv), sequence_length)


# A chunk step's two batch layouts (docs/serving.md). The engine lays a
# chunk out as single-query ROWS where the paged decode kernel serves a
# (rows, 1) call: impl="flash" asks for the kernel on the CPU too (the
# interpreter runs it), and it takes a cache of at least 128 rows in
# blocks of a multiple of 8. Everywhere else the RECTANGLE (slots, q)
# stays, as under the default impl on the CPU. Blocks of 16: a table row
# of 8 pages, and the interpreter's lowering of a kernel call is paid by
# the page (a DMA each for keys and values).
ROWS_SEQ = 128
ROWS = dict(impl="flash", kv_layout="paged", kv_block_size=16)

PROMPTS = [[3, 7, 11, 2, 5], [5, 2], [1, 9, 30, 30, 12, 4, 8], [60, 1, 2]]


def build_rows_lm():
    return build_lm(batch=1, sequence_length=ROWS_SEQ)


_REQUEST_DEFAULTS = ("max_new_tokens", "eos_id")
_engines: dict = {}


def engine(ff, **kw):
    """`ff.serve(**kw)`, built once a process and handed back as new (the
    module docstring says what that covers). `kw` holds hashable values."""
    from flexflow_tpu.serving import ServingSpec

    programs = {k: v for k, v in kw.items() if k not in _REQUEST_DEFAULTS}
    key = (ff, tuple(sorted(programs.items())))
    if key in _engines and not _engines[key][0].scheduler.drained:
        del _engines[key]
    if key not in _engines:
        eng = ff.serve(**programs)
        _engines[key] = (eng, copy.deepcopy(eng.block_manager))
    eng, blocks = _engines[key]
    eng.reset_stats()
    eng.block_manager = copy.deepcopy(blocks)
    eng._rng = None
    for name in _REQUEST_DEFAULTS:
        setattr(eng.spec, name, kw.get(name, getattr(ServingSpec, name)))
    return eng


def staged_shapes(eng, monkeypatch):
    """Record the (tokens shape, page-table shape) of every step `eng`
    stages from here on, as the step's program is handed them."""
    shapes, stage = [], eng._stage_step

    def spy(step):
        staged = stage(step)
        table = staged[0].get("page_table")
        shapes.append((staged[0][eng._token_input].shape,
                       None if table is None else table.shape))
        return staged

    monkeypatch.setattr(eng, "_stage_step", spy)
    return shapes


def complete_every_step_at_once(eng, monkeypatch):
    """Make `eng` the synchronous loop: a step function that hands its
    tokens back on the host (a NumPy array) has its step completed
    before the call that dispatched it returns (docs/serving.md)."""
    step = eng._step_fn

    def on_the_host(*args):
        state, sampled = step(*args)
        return state, np.asarray(sampled)

    monkeypatch.setattr(eng, "_step_fn", on_the_host)
    return eng


class SearchSpy:
    """Counts UnitySearch.evaluate + joint_graph_optimize calls (the
    test_warmstart.py hook, reused for the serving acceptance check)."""

    def __enter__(self):
        import flexflow_tpu.search.joint as joint
        import flexflow_tpu.search.unity as unity

        self.evals = 0
        self.searches = 0
        self._unity, self._joint = unity, joint
        self._orig_eval = unity.UnitySearch.evaluate
        self._orig_opt = joint.joint_graph_optimize
        spy = self

        def eval_spy(us, *a, **kw):
            spy.evals += 1
            return spy._orig_eval(us, *a, **kw)

        def opt_spy(*a, **kw):
            spy.searches += 1
            return spy._orig_opt(*a, **kw)

        unity.UnitySearch.evaluate = eval_spy
        joint.joint_graph_optimize = opt_spy
        return self

    def __exit__(self, *exc):
        self._unity.UnitySearch.evaluate = self._orig_eval
        self._joint.joint_graph_optimize = self._orig_opt
        return False
