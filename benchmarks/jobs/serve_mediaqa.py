"""The session-serving job of `keye2-serve-mediaqa`: the loop, the checks
and the replay of jobs/serve_sessions.py (sessions of one long history
each, held in the prefix cache; a request is history + a fresh question;
logits of the pre-window check and of two served streams against the
reference's full forward), over Keye-VL-2.0's language model and its
reference (benchmarks/keye_vl2_reference.py).

serve_sessions.py names DeepSeek-V3.2's configuration builder and
reference and is an accepted file, so this job loads a copy of that module
of its own (`harness.load_module` executes the file anew) and gives the
copy this configuration's parts: `build_model`, `reference` (the same
`compare` / `lowerings` / limits interface), `Choices` (every layer has an
expert layer: the configuration has no `first_k_dense_replace`),
`decode_instructions` (the scopes of benchmarks/keye2_events.py beside
those dsv32_events.py joins), and the two additions below, which sit on
`logits_step` (the first the loop does with its engine) and `replay`.
Everything else is that file's, line for line: the traffic, the window,
what `correct` needs of the logits.

**The experts a prompt's tokens chose.** Every expert here is held, so a
token routed otherwise by the bf16 program than by the float32 reference
(a near-tie of the 8th and 9th probability, 3 % of tokens a layer) has
another hidden state in both from there on, its keys in the next layers
score apart, and a hundred of a decoded row's 2,048 selected positions
then differ between the two for no fault of the program's (PR 38's first
chip run: PERF.md section 6). The expert layer records what a chunk's rows
chose (`chunk_expert_ids`), `ChunkChoices` keeps those records outside the
window (set-up's prefill of the histories and the check prompt, the
replay's questions), and `compare` hands them to the reference beside the
decoded rows' choices: it takes them at near-ties only, as it takes a
decoded row's.

**The first layer's cache rows** (`cache_check`, after the replay, while
the pools are still there): the [k ; v] rows and indexer keys the pool
holds of the two compared sessions' prompts against the reference's. A
first-layer row is a function of its own token and position, so this
comparison is to rounding, where a decoded row's logits are not moved at
all by a block of indexer keys lost (1.5 % of its candidates). `correct`
needs both prompts held whole and within `reference.CACHE_TOL`.

`run(ctx, control=...)` is for the builder's controls, which have to come
out not correct (PERF.md section 6, PR 38): a `spoil` of the reference
(keye_vl2_reference.SPOILS), or "lost_index_block", which zeroes one block
of 256 indexer keys in every replayed stream's cached history before the
replay.
"""

from __future__ import annotations

import sys
import types

import numpy as np

from benchmarks import harness
from benchmarks import keye_vl2_reference as reference


def build_model(ctx):
    """The compiled model, from the flags a user would put on the command
    line: the trunk builder, an inference compile."""
    from flexflow_tpu import (
        FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )
    from flexflow_tpu.fftype import CompMode
    from flexflow_tpu.models import build_transformer_lm, keye_vl2_lm_config

    cell = ctx.cell
    cfg = keye_vl2_lm_config(
        ctx.config, sequence_length=cell["train_sequence_length"],
        attention_impl=cell["attention_impl"],
        initializer_range=ctx.config["initializer_range"],
        embedding_range=ctx.config["embedding_initializer_range"])
    argv = sys.argv
    sys.argv = [argv[0], "-b", str(cell["train_batch"]), *cell["flags"],
                "--seed", str(ctx.seed % (2**31 - 1))]
    try:
        config = FFConfig()
    finally:
        sys.argv = argv
    ff = FFModel(config)
    build_transformer_lm(ff, cfg, batch_size=cell["train_batch"])
    ff.compile(
        optimizer=SGDOptimizer(),
        loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
        comp_mode=CompMode.COMP_MODE_INFERENCE)
    return ff


class ChunkChoices:
    """The engine's step function with a record beside it: after a step
    that carries a chunk, the chunk's tokens, positions and the experts
    every layer chose for them (copies made on the device, nothing
    fetched, so the loop runs ahead as before); off inside the window.
    `experts_of(tokens)` gives {layer: {position: ids}} for the leading
    positions of a sequence whose chunks were recorded."""

    def __init__(self, engine, layers):
        import jax
        import jax.numpy as jnp

        self.fn, self.on = engine._step_fn, True
        self.__name__ = getattr(self.fn, "__name__", "decode_step")
        self.lower = self.fn.lower
        self.slots, self.dead = engine.spec.slots, engine.max_seq_len
        self.layers = layers
        self.kept, self.by_start = [], None
        self.copy = jax.jit(lambda leaves: [jnp.copy(a) for a in leaves])

    def __call__(self, params, state, xs, *rest):
        out = self.fn(params, state, xs, *rest)
        if self.on and xs["tokens"].shape[0] > self.slots:
            self.kept.append((xs["tokens"], xs["positions"], self.copy(
                [out[0][name]["chunk_expert_ids"] for name in self.layers])))
            self.by_start = None
        return out

    def experts_of(self, tokens) -> dict:
        import jax

        if self.by_start is None:
            self.by_start = {}
            for toks, positions, ids in jax.device_get(self.kept):
                toks, positions = toks[self.slots:, 0], positions[
                    self.slots:, 0]
                n = int(np.sum((positions >= 0) & (positions < self.dead)))
                if n:
                    self.by_start.setdefault(int(positions[0]), []).append(
                        (toks[:n].tolist(), [a[:n] for a in ids]))
        found = {layer: {} for layer in range(len(self.layers))}
        tokens, at = list(tokens), 0
        while at < len(tokens):
            match = next((c for c in self.by_start.get(at, [])
                          if tokens[at:at + len(c[0])] == c[0]), None)
            if match is None:
                break
            for layer, ids in enumerate(match[1]):
                found[layer].update(
                    (at + i, row) for i, row in enumerate(ids))
            at += len(match[0])
        return found


def cache_check(engine, get, config, prompt, pad_to, spoil=None) -> dict:
    """The first layer's rows the pool holds of a served prompt (its
    history from the radix cache, its question: whatever the cache still
    maps) against the reference's (`reference.cache_rows`): every row
    there is a function of its own token and position alone, so the two
    agree to bf16's rounding, and a lost or stale block, a norm over the
    wrong lanes or a missing rotation shows at full size. -> the rows
    held, and max |difference| over max |reference| of [k ; v] and of the
    indexer's key."""
    mgr, state = engine.block_manager, engine.decode_model._state["l0_attn"]
    held, blocks = mgr.cache.match(prompt, peek=True)
    blocks = np.asarray(blocks, np.int32)
    want_kv, want_i = reference.cache_rows(
        get, list(prompt) + [0] * (pad_to - len(prompt)), config, spoil=spoil)

    def error(leaf, want):
        rows = np.asarray(state[leaf][blocks], np.float32)
        rows = rows.reshape(-1, rows.shape[-1])[:held, :want.shape[1]]
        return float(np.max(np.abs(rows - want[:held]))
                     / np.max(np.abs(want[:held])))

    return {"rows": held, "kv_error": error("pool_kv", want_kv),
            "index_error": error("pool_i", want_i)}


def lose_index_block(engine, prompts, which: int = 2) -> list:
    """Zero, in every layer's indexer pool, block `which` of each prompt's
    cached prefix; -> the blocks lost."""
    mgr, dec = engine.block_manager, engine.decode_model
    lost = sorted({mgr.cache.match(p, peek=True)[1][which] for p in prompts})
    for leaves in dec._state.values():
        if "pool_i" in leaves:
            leaves["pool_i"] = leaves["pool_i"].at[np.asarray(lost)].set(0)
    return lost


def run(ctx, control=None) -> dict:
    sessions = harness.load_module("jobs", "serve_sessions.py")
    scoped = {}

    class Choices(sessions.Choices):
        def __init__(self, ctx):
            self.layers = range(ctx.config["num_hidden_layers"])
            self.attn = [f"l{i}_attn" for i in self.layers]
            self.moe = {i: f"l{i}_moe" for i in self.layers}

    def decode_instructions(engine) -> list:
        """The pure-decode step's [[instruction, scope]] pairs under
        dsv32_events' scopes (returned: `decode_instructions`) and under
        keye2_events' (`keye2_instructions`), from one compiled text."""
        import jax
        import jax.numpy as jnp

        from benchmarks import dsv32_events, keye2_events

        dec, slots = engine.decode_model, engine.spec.slots
        xs = engine._stage_inputs(
            np.zeros((slots, 1), np.int32),
            np.full((slots, 1), engine.max_seq_len, np.int32))
        text = engine._step_fn.lower(
            dec._params, dec._state, xs, jnp.zeros((slots,), jnp.int32),
            jax.random.key(0), jnp.zeros((slots,), jnp.float32)
        ).compile().as_text()
        scoped["keye2_instructions"] = keye2_events.scoped_instructions(text)
        return dsv32_events.scoped_instructions(text)

    chunks = []
    logits_step = sessions.logits_step

    def logits_step_and_record(engine):
        # the first the job does with its engine: the record goes in here
        chunks.append(ChunkChoices(engine, list(Choices(ctx).moe.values())))
        engine._step_fn = chunks[0]
        return logits_step(engine)

    def compare(get, tokens, config, rows, program, **kw):
        """reference.compare, the prompt rows' experts beside the decoded
        rows' in `program` (module docstring)."""
        found = chunks[0].experts_of(tokens)
        print(f"[mediaqa] the reference is given the experts the program "
              f"chose at {len(found[0])} prompt positions of {len(tokens)} "
              f"tokens, beside the {len(rows)} decoded rows'")
        for layer, chosen in found.items():
            mine = program.setdefault(layer, {}).setdefault("experts", {})
            for position, ids in chosen.items():
                mine.setdefault(position, ids)
        return reference.compare(get, tokens, config, rows, program,
                                 spoil=spoil, **kw)

    open_window, close_window = ctx.open_window, ctx.close_window

    def opened():
        chunks[0].on = False
        return open_window()

    def closed():
        chunks[0].on = True
        return close_window()

    ctx.open_window, ctx.close_window = opened, closed
    if control not in (*reference.SPOILS, "lost_index_block"):
        raise ValueError(f"unknown control {control!r}")
    spoil = control if control in reference.SPOILS else None
    t = ctx.traffic
    lengths = sessions.traffic_gen.quantiles(t["history_tokens"],
                                             t["clients"])
    compared = [lengths[c] for c in t["check_stream_histories"]]
    questions, replies = sessions.traffic_gen.request_sizes(t)
    longest = max(compared) + max(questions) + max(replies)
    pad_to = longest + -longest % 256     # as the loop pads its sequences
    caches = []
    replay = sessions.replay

    def replay_then_check_the_cache(engine, ctx, step, served):
        """The loop's replay, then the first layer's cache rows of the
        compared sessions' prompts against the reference's, while the
        pools are still there (the loop deletes them after)."""
        if control == "lost_index_block":
            lost = lose_index_block(engine, [r.prompt for r in served])
            print(f"[mediaqa] control: indexer keys of blocks {lost} "
                  f"zeroed in every layer before the replay")
        record = replay(engine, ctx, step, served)
        get = harness.param_getter(engine.decode_model)
        for r in served:
            if any(min(questions) <= len(r.prompt) - n <= max(questions)
                   for n in compared):
                caches.append(cache_check(engine, get, ctx.config, r.prompt,
                                          pad_to, spoil))
                print(f"[mediaqa] the cache's first-layer rows of a served "
                      f"prompt of {len(r.prompt)}: {caches[-1]['rows']} "
                      f"held, [k ; v] {caches[-1]['kv_error']:.5f} and the "
                      f"indexer's key {caches[-1]['index_error']:.5f} of "
                      f"the largest off the reference's (tolerance "
                      f"{reference.CACHE_TOL})")
        return record

    sessions.build_model = build_model
    sessions.Choices = Choices
    sessions.decode_instructions = decode_instructions
    sessions.logits_step = logits_step_and_record
    sessions.replay = replay_then_check_the_cache
    sessions.reference = types.SimpleNamespace(
        **{**vars(reference), "compare": compare})
    result = sessions.run(ctx)
    held = (len(caches) == len(compared) and all(
        c["rows"] >= n and max(c["kv_error"], c["index_error"])
        <= reference.CACHE_TOL for c, n in zip(sorted(
            caches, key=lambda c: c["rows"]), sorted(compared))))
    if not held:
        print(f"[mediaqa] the cache's rows of the compared sessions are "
              f"off the reference's, or not all held: {caches}")
        result["correct"] = False
        result["failed"] += len(compared)
    result["counters"].update(
        scoped, cache_kv_error=max((c["kv_error"] for c in caches),
                                   default=None),
        cache_index_error=max((c["index_error"] for c in caches),
                              default=None))
    return result
