"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once on a TPU, through the entry points a user calls,
at the full width and depth of `lm-base` (TRANSFORMER_LM_ZOO: hidden 1024,
16 heads, 12 layers, seq 512, vocab 32000; random weights from --seed):

  python chip_smoke.py            one chip: train, then serve the trained model
  python chip_smoke.py --chips 4  four chips: the parallel-training phase only

- train: build_transformer_lm -> FFModel.compile -> FFModel.fit, batch 8,
  bf16 compute with fp32 masters, flash attention; the eager loop and
  --pipeline-steps chunks. Loss finite and falling on a repeated batch; the
  step executable holds the packed flash fwd/bwd and fused LayerNorm kernels
  by name and aliases its donated state; nothing compiles after warm-up.
- serve: ff.serve() with the default paged KV layout, then contiguous.
  Against a full-sequence forward of the training graph on the same
  weights, each layout's logits at every prompt's last position agree
  within LOGIT_TOL and the first generated token is their argmax; greedy
  streams are identical between the layouts (in bf16: up to a tie under
  the training graph's logits); the decode executable's attention
  implementation is read from its own text, and a reference there must
  have been warned.
- --chips 4: the same steps under --mesh 1,1,1,1 (the comparison), 4,1,1,1
  (dp), 2,2,1,1 (dp x tp) and one searched plan, in this one process.
  Losses agree with the one-chip run within LOSS_TOL; every parameter's
  shards sit on four distinct chips with the shapes the plan gives them;
  the step holds collectives; the search's calibration measured on the chip.

There is no CPU mode: with no TPU the script fails at once (the tests cover
the same code on the CPU). Any failed check raises, so the run ends non-zero
and prints no result line. The last line of stdout is the result, one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}. The timings
printed on the way are for the reader of a bring-up log; none of them is a
benchmark result.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import gc
import json
import os
import re
import sys
import time
import warnings

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
# where JAX_COMPILATION_CACHE_DIR is unset: one fixed place in the checkout
# (the path is part of the cache key, so a directory that moves never hits)
CACHE_DIR = os.path.join(REPO, ".jax_cache")

BATCH = 8
# max |served logit - training-graph logit| at a prompt's last position,
# as a share of the largest |logit| there: both sides compute in bf16
# (eps 2^-8) through 12 layers, attention by different routes (flash
# kernel vs cache einsum)
LOGIT_TOL = 0.05
# |loss on n chips - loss on one| per step: the same bf16 math with the
# batch's rows reduced in another order and (tp) the heads' partial sums
# added across chips
LOSS_TOL = 0.02
PROMPT_LENGTHS = (5, 16, 24, 33, 48, 64)
MAX_NEW_TOKENS = 32


class SmokeFailure(Exception):
    pass


def check(ok, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


class CompileLog:
    """Counts what JAX reports about compilation, process-wide: programs
    compiled or fetched from the persistent cache, the requests among
    them that consulted the cache, and the hits."""

    def __init__(self):
        import jax

        self.requests = self.hits = self.compilations = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compilations += 1

    @contextlib.contextmanager
    def none_during(self, what: str):
        """Fail if anything compiles inside the block: a steady window
        runs programs that exist."""
        before = self.compilations
        yield
        check(self.compilations == before,
              f"{self.compilations - before} compilation(s) in {what}")

    def line(self) -> str:
        return (f"programs compiled or fetched: {self.compilations}; "
                f"compile cache: requests {self.requests}, hits "
                f"{self.hits}, misses {self.requests - self.hits}")


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


# ------------------------------------------------------------------ model

def build_lm(cfg, flags, strategy_fn=None):
    """A compiled lm FFModel from the flags a user would put on the
    command line (FFConfig parses sys.argv)."""
    from flexflow_tpu import (
        FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )
    from flexflow_tpu.models import build_transformer_lm

    prog = sys.argv[0]
    sys.argv = [prog, "-b", str(BATCH), "--dtype", "bf16", *flags]
    try:
        config = FFConfig()
    finally:
        sys.argv = [prog]
    ff = FFModel(config)
    build_transformer_lm(ff, cfg, batch_size=BATCH)
    if strategy_fn is not None:
        ff.set_strategy(strategy_fn(ff))
    ff.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY])
    return ff


def repeated_batch(cfg, steps: int, seed: int):
    """`steps` copies of one seeded batch (next-token labels), so every
    optimizer step of an unshuffled fit sees the same rows."""
    rs = np.random.RandomState(seed)
    seq = cfg.sequence_length
    toks = rs.randint(0, cfg.vocab_size, (BATCH, seq + 1)).astype(np.int32)
    x = {"tokens": np.tile(toks[:, :-1], (steps, 1)),
         "positions": np.tile(np.arange(seq, dtype=np.int32),
                              (steps * BATCH, 1))}
    y = np.tile(toks[:, 1:, None], (steps, 1, 1))
    return x, y


def fit_steps(ff, data, **fit_kw):
    """(mean loss, seconds) of one FFModel.fit over `data`, timed to the
    end of the device's work."""
    import jax

    x, y = data
    ff.reset_metrics()
    t0 = time.perf_counter()
    ff.fit(x, y, epochs=1, batch_size=BATCH, shuffle=False, verbose=False,
           **fit_kw)
    jax.block_until_ready(ff._params)
    dt = time.perf_counter() - t0
    loss = ff.get_perf_metrics().get_mean_loss()
    check(np.isfinite(loss), f"loss is not finite: {loss}")
    return float(loss), dt


def train_step_executable(ff, data):
    """The eager train step as fit dispatches it, compiled ahead of time
    from the model's live state (after a fit this is a cache hit)."""
    import jax

    x, y = data
    batch = ff._make_batch({k: v[:BATCH] for k, v in x.items()}, y[:BATCH])
    step = ff.executor._train_step or ff.executor.build_train_step()
    return step.lower(ff._params, ff._state, ff._opt_slots, ff._step,
                      ff._counters, jax.random.key(0), batch).compile()


COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(")


# ------------------------------------------------------------------ train

def train_phase(cfg, seed: int, log: CompileLog):
    import jax

    from flexflow_tpu.kernels.dispatch import pallas_kernels

    print(f"[train] lm-base {cfg.num_layers}L h{cfg.hidden_size} "
          f"seq{cfg.sequence_length} vocab{cfg.vocab_size}, batch {BATCH}, "
          f"bf16 compute / fp32 masters, attention_impl={cfg.attention_impl}")
    t0 = time.perf_counter()
    ff = build_lm(cfg, ["--mesh", "1,1,1,1", "--seed", str(seed)])
    print(f"[train] FFModel.compile: {time.perf_counter() - t0:.2f} s")

    warm, t_warm = fit_steps(ff, repeated_batch(cfg, 2, seed))
    print(f"[train] eager warm-up, 2 steps incl. compile: {t_warm:.2f} s, "
          f"mean loss {warm:.4f}")
    n = 8
    with log.none_during("the steady eager window"):
        eager, t_eager = fit_steps(ff, repeated_batch(cfg, n, seed))
    print(f"[train] eager fit, {n} steps: {t_eager / n * 1e3:.2f} ms/step, "
          f"mean loss {eager:.4f}")

    chunk = 4
    pwarm, t_pwarm = fit_steps(ff, repeated_batch(cfg, chunk, seed),
                               pipeline_steps=chunk)
    print(f"[train] --pipeline-steps {chunk} warm-up, 1 chunk incl. "
          f"compile: {t_pwarm:.2f} s, mean loss {pwarm:.4f}")
    with log.none_during("the steady pipelined window"):
        piped, t_piped = fit_steps(ff, repeated_batch(cfg, n, seed),
                                   pipeline_steps=chunk)
    print(f"[train] pipelined fit, {n} steps in chunks of {chunk}: "
          f"{t_piped / n * 1e3:.2f} ms/step, mean loss {piped:.4f}")
    check(warm > eager > pwarm > piped,
          f"loss is not falling on a repeated batch: "
          f"{warm:.4f}, {eager:.4f}, {pwarm:.4f}, {piped:.4f}")

    compiled = train_step_executable(ff, repeated_batch(cfg, 1, seed))
    kernels = pallas_kernels(compiled.as_text())
    print(f"[train] step executable kernels: {dict(kernels)}")
    for family in ("flash_attention_fwd_packed", "flash_attention_bwd",
                   "layer_norm_fwd", "layer_norm_bwd"):
        count = sum(v for k, v in kernels.items() if k.startswith(family))
        check(count >= cfg.num_layers,
              f"train step holds {count} {family}* kernels, expected at "
              f"least one per layer ({cfg.num_layers})")
    mem = compiled.memory_analysis()
    print(f"[train] step executable: arguments "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, aliased "
          f"{mem.alias_size_in_bytes / 2**30:.2f} GiB")
    check(mem.alias_size_in_bytes > 0,
          "train step aliases none of its donated state")
    print(f"[train] peak device memory: {peak_bytes(jax.devices()[0])}")
    return ff


# ------------------------------------------------------------------ serve

def make_prompts(cfg, seed: int):
    rs = np.random.RandomState(seed + 1)
    return [rs.randint(0, cfg.vocab_size, n).tolist()
            for n in PROMPT_LENGTHS]


def training_graph_logits(ff, cfg, prompts):
    """float32 logits at each prompt's last position from a plain
    full-sequence forward of the training graph (causal, so the padding
    after a prompt does not reach it)."""
    seq = cfg.sequence_length
    out = []
    for lo in range(0, len(prompts), BATCH):
        group = prompts[lo:lo + BATCH]
        toks = np.zeros((BATCH, seq), np.int32)
        for i, p in enumerate(group):
            toks[i, :len(p)] = p
        pos = np.tile(np.arange(seq, dtype=np.int32), (BATCH, 1))
        ff.start_batch({"tokens": toks, "positions": pos},
                       np.zeros((BATCH, seq, 1), np.int32))
        logits = ff.forward()
        for i, p in enumerate(group):
            out.append(np.asarray(logits[i, len(p) - 1], np.float32))
    return out


def decode_graph_logits(engine, prompts):
    """float32 logits at each prompt's last position from the engine's
    decode graph: each prompt fed as one chunk, one prompt per slot,
    padding pointed at the scratch row as the engine does; under the
    paged layout slot i reads and writes through its own run of blocks
    (block 0 is the scratch block)."""
    dec = engine.decode_model
    slots, scratch = engine.spec.slots, engine.max_seq_len
    out = []
    for lo in range(0, len(prompts), slots):
        group = prompts[lo:lo + slots]
        width = max(len(p) for p in group)
        toks = np.zeros((slots, width), np.int32)
        pos = np.full((slots, width), scratch, np.int32)
        for i, p in enumerate(group):
            toks[i, :len(p)] = p
            pos[i, :len(p)] = np.arange(len(p))
        xs = {"tokens": toks, "positions": pos}
        mgr = engine.block_manager
        if mgr is not None:
            per_slot = mgr.table_width
            check(mgr.num_blocks > slots * per_slot,
                  "pool too small to give every slot its own blocks")
            xs["page_table"] = (1 + np.arange(slots * per_slot, dtype=np.int32)
                                ).reshape(slots, per_slot)
        dec.start_batch(xs, np.zeros((slots, width, 1), np.int32))
        logits = dec.forward()
        for i, p in enumerate(group):
            out.append(np.asarray(logits[i, len(p) - 1], np.float32))
    return out


def decode_executable(engine):
    """The engine's single-token decode step, compiled ahead of time with
    the inputs the engine stages for it (a cache hit after generate)."""
    import jax
    import jax.numpy as jnp

    dec = engine.decode_model
    slots = engine.spec.slots
    xs = engine._stage_inputs(np.zeros((slots, 1), np.int32),
                              np.zeros((slots, 1), np.int32))
    return engine._step_fn.lower(
        dec._params, dec._state, xs, jnp.zeros((slots,), jnp.int32),
        jax.random.key(0), jnp.zeros((slots,), jnp.float32)).compile()


def serve_layout(ff, cfg, layout, prompts, seed, log):
    from flexflow_tpu.kernels.dispatch import (
        KernelFallbackWarning, pallas_kernels,
    )

    tag = f"[serve:{layout}]"
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always", KernelFallbackWarning)
        t0 = time.perf_counter()
        engine = ff.serve(kv_layout=layout, max_new_tokens=MAX_NEW_TOKENS)
        t_build = time.perf_counter() - t0
        t0 = time.perf_counter()
        streams = engine.generate(prompts)
        t_first = time.perf_counter() - t0
    fallbacks = sorted({str(w.message) for w in caught
                        if issubclass(w.category, KernelFallbackWarning)})
    print(f"{tag} ff.serve: {t_build:.2f} s; first generate of "
          f"{len(prompts)} prompts (lengths {PROMPT_LENGTHS}) x "
          f"{MAX_NEW_TOKENS} new tokens incl. compile: {t_first:.2f} s")
    for s in streams:
        check(len(s) == MAX_NEW_TOKENS
              and all(0 <= t < cfg.vocab_size for t in s),
              f"{tag} bad token stream {s}")

    # a second drain over other prompts of the same lengths: every
    # executable exists, and no prefix is cached
    engine.reset_stats()
    with log.none_during(f"{tag} the steady drain"):
        engine.generate(make_prompts(cfg, seed + 100))
    stats = engine.stats()
    print(f"{tag} steady drain: {stats['wall_s']:.2f} s wall, "
          f"{stats['decode_tokens']} decode tokens in "
          f"{stats['decode_iterations']} iterations "
          f"({stats['wall_s'] / stats['decode_iterations'] * 1e3:.2f} "
          f"ms/iteration), device calls {stats['device_s']:.2f} s")

    compiled = decode_executable(engine)
    kernels = pallas_kernels(compiled.as_text())
    decode_kernels = {k: v for k, v in kernels.items() if "decode" in k}
    print(f"{tag} decode executable attention: "
          f"{dict(decode_kernels) or 'XLA reference einsum'}"
          f"; all kernels: {dict(kernels)}")
    for message in fallbacks:
        print(f"{tag} warned: {message}")
    if not decode_kernels:
        check(any("decode_attention" in m for m in fallbacks),
              f"{tag} the decode executable holds no decode kernel and "
              f"no KernelFallbackWarning said so")
    mem = compiled.memory_analysis()
    check(mem.alias_size_in_bytes > 0,
          f"{tag} decode step aliases none of its donated KV state")
    print(f"{tag} decode executable: aliased "
          f"{mem.alias_size_in_bytes / 2**20:.1f} MiB of donated state")
    return engine, streams


def tie_window(ref) -> float:
    """How far below the largest logit a greedy pick may sit and still be
    a tie: two logits, each off by up to LOGIT_TOL of the largest |logit|."""
    return 2 * LOGIT_TOL * float(np.max(np.abs(ref)))


def near_argmax(ref, token: int) -> bool:
    return bool(ref[token] >= ref.max() - tie_window(ref))


def serve_phase(ff, cfg, seed: int, log: CompileLog):
    import jax

    prompts = make_prompts(cfg, seed)
    ref = training_graph_logits(ff, cfg, prompts)
    streams = {}
    for layout in ("paged", "contiguous"):
        engine, streams[layout] = serve_layout(ff, cfg, layout, prompts,
                                               seed, log)
        tag = f"[serve:{layout}]"
        exact = 0
        for i, (r, stream) in enumerate(zip(ref, streams[layout])):
            check(near_argmax(r, stream[0]),
                  f"{tag} prompt {i}: first token {stream[0]} (logit "
                  f"{r[stream[0]]:.4f}) is not the training graph's "
                  f"argmax {int(np.argmax(r))} ({r.max():.4f}) nor within "
                  f"{tie_window(r):.4f} of it")
            exact += stream[0] == int(np.argmax(r))
        worst = 0.0
        for i, (r, d) in enumerate(zip(ref, decode_graph_logits(engine,
                                                               prompts))):
            check(np.all(np.isfinite(d)),
                  f"{tag} prompt {i}: non-finite logits")
            rel = float(np.max(np.abs(d - r)) / np.max(np.abs(r)))
            worst = max(worst, rel)
            check(rel <= LOGIT_TOL,
                  f"{tag} prompt {i}: decode-graph logits differ from the "
                  f"training graph's by {rel:.4f} of max |logit| "
                  f"(tolerance {LOGIT_TOL})")
        print(f"{tag} first tokens: {exact}/{len(prompts)} are the "
              f"training graph's argmax, the rest tie with it; decode-graph "
              f"logits at the prompts' last positions within {worst:.4f} of "
              f"max |logit| of the training graph's (tolerance {LOGIT_TOL})")

    # the two layouts compute the same attention over caches of different
    # shapes; in bf16 that can round a tie the other way. Streams must be
    # identical up to such a tie, judged by the training graph's logits
    # for the common prefix.
    paged, contiguous = streams["paged"], streams["contiguous"]
    forks = [(i, next(j for j in range(MAX_NEW_TOKENS) if a[j] != b[j]))
             for i, (a, b) in enumerate(zip(paged, contiguous)) if a != b]
    if forks:
        at_fork = training_graph_logits(
            ff, cfg, [prompts[i] + paged[i][:j] for i, j in forks])
        for (i, j), r in zip(forks, at_fork):
            a, b = paged[i][j], contiguous[i][j]
            check(near_argmax(r, a) and near_argmax(r, b),
                  f"prompt {i}: layouts fork at new token {j} (paged {a}, "
                  f"logit {r[a]:.4f}; contiguous {b}, logit {r[b]:.4f}; "
                  f"max {r.max():.4f}) outside the tie window "
                  f"{tie_window(r):.4f}")
    print(f"[serve] greedy streams: {len(prompts) - len(forks)}/"
          f"{len(prompts)} identical across layouts over {MAX_NEW_TOKENS} "
          f"tokens; forks (prompt, token) {forks} are ties under the "
          f"training graph's logits")
    print(f"[serve] peak device memory: {peak_bytes(jax.devices()[0])}")


# ------------------------------------------------------------- four chips

def check_placement(ff, tag: str) -> int:
    """Every parameter leaf has one shard on each chip of the mesh, of
    the shape the plan gives it (executor.rest_specs: the update layout
    where the weight update is sharded, else the searched weight
    placement). Returns the number of leaves the plan shards."""
    from jax.sharding import NamedSharding

    chips = set(ff.mesh.devices.flat)
    check(len(chips) == 4 and len({d.id for d in chips}) == 4,
          f"{tag} mesh does not span four distinct chips: {chips}")
    sharded = 0
    for node_name, ws in ff._params.items():
        for wname, leaf in ws.items():
            spec = ff.executor.rest_specs[(node_name, wname)][0]
            want = NamedSharding(ff.mesh, spec).shard_shape(leaf.shape)
            shards = leaf.addressable_shards
            check({s.device for s in shards} == chips,
                  f"{tag} {node_name}.{wname}: shards on "
                  f"{sorted(s.device.id for s in shards)}, not on the "
                  f"mesh's four chips")
            for s in shards:
                check(tuple(s.data.shape) == tuple(want),
                      f"{tag} {node_name}.{wname}: shard {s.data.shape} "
                      f"on chip {s.device.id}, plan {spec} gives {want}")
            sharded += tuple(want) != tuple(leaf.shape)
    return sharded


def four_chip_phase(cfg, seed: int, log: CompileLog):
    import jax

    from flexflow_tpu.kernels.dispatch import pallas_kernels
    from flexflow_tpu.parallel import megatron_transformer

    steps = 4
    search = ["--budget", "4", "--enable-parameter-parallel",
              "--calibrate", "2"]
    plans = [
        ("one chip", ["--mesh", "1,1,1,1"], None),
        ("dp 4", ["--mesh", "4,1,1,1"], None),
        ("dp 2 x tp 2", ["--mesh", "2,2,1,1"], megatron_transformer),
        ("searched 2x2", ["--mesh", "2,2,1,1", *search], None),
    ]
    one_step = repeated_batch(cfg, 1, seed)
    baseline = None
    for name, flags, strategy_fn in plans:
        tag = f"[4chip:{name}]"
        t0 = time.perf_counter()
        ff = build_lm(cfg, [*flags, "--seed", str(seed)], strategy_fn)
        t_compile = time.perf_counter() - t0
        losses, times = [], []
        placed = jax.tree.map(lambda leaf: leaf.sharding, ff._params)
        for i in range(steps):  # one optimizer step per fit: its own loss
            # a step must hand its state back as it took it: in another
            # layout the next call compiles again, and in an equivalent
            # sharding that merely compares unequal (a trailing None) it
            # is dispatched from scratch
            with (log.none_during(f"{tag} step {i + 1}") if i
                  else contextlib.nullcontext()):
                loss, dt = fit_steps(ff, one_step)
            check(jax.tree.map(lambda leaf: leaf.sharding, ff._params)
                  == placed,
                  f"{tag} step {i + 1} changed the parameters' shardings")
            losses.append(loss)
            times.append(dt)
        upd = ff._update_sharding
        print(f"{tag} mesh {dict(ff.mesh.shape)}, plan source "
              f"{ff._plan_source}, weight update "
              f"{'stage ' + str(upd.get('stage')) if upd.get('enabled') else 'replicated'}"
              f" ({upd.get('reason', '')}); FFModel.compile "
              f"{t_compile:.2f} s, first step incl. compile "
              f"{times[0]:.2f} s, later steps "
              f"{[round(t * 1e3, 1) for t in times[1:]]} ms (one fit call "
              f"each)")
        print(f"{tag} losses {[round(v, 4) for v in losses]}")
        check(losses[-1] < losses[0], f"{tag} loss is not falling")
        compiled = train_step_executable(ff, one_step)
        text = compiled.as_text()
        kernels = pallas_kernels(text)
        collectives = collections.Counter(COLLECTIVE.findall(text))
        print(f"{tag} step kernels {sum(kernels.values())}, collectives "
              f"{dict(collectives)}, per-chip arguments "
              f"{compiled.memory_analysis().argument_size_in_bytes / 2**30:.2f}"
              f" GiB")
        check(sum(v for k, v in kernels.items()
                  if k.startswith("flash_attention")) >= 3 * cfg.num_layers,
              f"{tag} step does not hold the flash kernels: {dict(kernels)}")
        if baseline is None:
            baseline = losses
            check(not collectives,
                  f"{tag} collectives on one chip: {dict(collectives)}")
        else:
            worst = max(abs(a - b) for a, b in zip(losses, baseline))
            check(worst <= LOSS_TOL,
                  f"{tag} losses differ from the one-chip run by "
                  f"{worst:.4f} (tolerance {LOSS_TOL})")
            check(collectives, f"{tag} step holds no collective")
            sharded = check_placement(ff, tag)
            print(f"{tag} max |loss - one chip| {worst:.5f} (tolerance "
                  f"{LOSS_TOL}); every parameter on four distinct chips, "
                  f"{sharded} leaves sharded as planned")
        if "--calibrate" in flags:
            cm = ff._search_result[0].cm
            print(f"{tag} search calibration on the chip: "
                  f"{cm.calib_stats}; measured (fwd s, bwd s): "
                  f"{[tuple(round(t, 6) for t in v) for v in cm._calibration.values()]}")
            check(cm.calib_stats["measured"] == 2,
                  f"{tag} calibration measured "
                  f"{cm.calib_stats['measured']} of 2 ops on the chip")
        del ff, compiled
        gc.collect()
    print(f"[4chip] peak device memory (chip 0): "
          f"{peak_bytes(jax.devices()[0])}")


# ------------------------------------------------------------------- main

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args()
    sys.argv = sys.argv[:1]  # FFConfig parses argv; ours stops here

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
                 f"({dev.device_kind}); nothing runs on it")
    if len(jax.devices()) < opts.chips:
        sys.exit(f"chip_smoke: --chips {opts.chips} but JAX found "
                 f"{len(jax.devices())}")
    import importlib.metadata as md

    import jaxlib

    from flexflow_tpu import native
    from flexflow_tpu.models.transformer import TRANSFORMER_LM_ZOO
    from flexflow_tpu.search.machine_model import chip_for

    t_start = time.perf_counter()
    log = CompileLog()
    print(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
          f"{md.version('libtpu')}, python {sys.version.split()[0]}")
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, count "
          f"{len(jax.devices())}; peaks table row: {chip_for(dev)}")
    print(f"compile cache directory: "
          f"{jax.config.jax_compilation_cache_dir}")
    print(f"PCG core: "
          f"{'native (built from native/src/pcg_core.cc)' if native.available() else 'Python fallback'}")

    cfg = dataclasses.replace(TRANSFORMER_LM_ZOO["lm-base"],
                              attention_impl="flash")
    if opts.chips == 4:
        four_chip_phase(cfg, opts.seed, log)
    else:
        ff = train_phase(cfg, opts.seed, log)
        serve_phase(ff, cfg, opts.seed, log)
    print(log.line())
    print(f"total {time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
