"""The session-serving job of `ms4-serve-longctx`: the loop, the checks and
the replay of jobs/serve_sessions.py (sessions of one long history each,
held in the prefix cache; a request is history + a fresh question; logits
of the pre-window check and of two served streams against the reference's
full forward), over Mistral-Small-4's language model and its reference
(benchmarks/mistral_small4_reference.py).

serve_sessions.py names DeepSeek-V3.2's configuration builder and
reference and is an accepted file, so this job loads a copy of that module
of its own (`harness.load_module` executes the file anew), as
jobs/serve_mediaqa.py and jobs/serve_longdoc.py do, and gives the copy
this configuration's parts: `build_model`, `reference` (the same `compare`
/ `lowerings` / limits interface; no layer selects, so the selection's
readings are empty), `Choices` (the experts a decoded row chose and what
every layer's attention gave it; no `sel_rows`) and `decode_instructions`
(the scopes of benchmarks/ms4_events.py beside those dsv32_events.py
joins). The copy's own `logit_check` serves as it is: the cache is one
group under one page table. Everything else is that file's, line for
line: the traffic, the window, what `correct` needs of the logits, of the
histories and of the experts.

**Every layer's attention, from the replayed step's own program.** In this
seeded model the attention's share of the residual stream at 17-41 k rows
of context is under a hundredth (a row's attention is an average over
thousands of random values, then W_o at 0.02), so the logits hardly see
the attention at all: a lost block of 256 cached rows moves them by
nothing and the query scale left out by a fifth of their rounding (PERF.md
section 6, PR 46). The decode op keeps what it gave the slots' rows in the
last call (the state leaf `attended`, as an expert layer keeps
`expert_ids`), so the step the check and the replay run, the engine's own
graph under the engine's own page tables and positions, hands back every
layer's attention output at every compared row beside the logits, and
`compare` holds each layer's to the reference's there, norm over norm
(`reference.LAYER_ATTEND_TOL`): `correct` needs every layer of every
compared sequence inside it.

**A length a compared sequence.** The copy pads every compared sequence to
the longest (here some 41.5 k tokens), so that its reference compiles
once; at these contexts a float32 forward costs with the square of the
length (20 s at 41.5 k), and three of them would be a minute of a run
that has six. `lowerings` and `compare` below plan one padded length a
compared sequence instead (2,560 for the check prompt, some 17.9 k and
41.5 k for the two streams): three sets of the reference's five programs,
compiled ahead in the copy's threads beside the histories' prefill, and
each forward over its own length.

**The first layer's attention and cache rows** (`first_layer_probe`, after
the replay, while the pools are still there): a second witness, beside
the step's own, where the attention can be read alone: the first layer,
whose input is the embedding's row of each token. The program's own
decode op is called once more, outside any step, on the first layer's
pool as the loop left it: the last 16 cached positions of
each compared session's prompt as 16 single-query rows under the page-table
row the radix cache maps for that prompt, so on the chip the paged latent
kernel over 17 k and 41 k rows; its output against the reference's
attention output there (`reference.ATTEND_TOL`), and the pool's rows of the
whole prompt against the reference's [c_kv ; k_R] (`reference.CACHE_TOL`: a
row is a function of its own token and position, so this is rounding).

**The experts a prompt's tokens chose.** A history row the bf16 program
routes otherwise than the float32 reference (a near-tie of the 4th and
5th probability, 3 % of rows a layer) has another hidden state in both
from there on, so its latent rows in the deeper layers differ, and a
decoded row's attention, an average over them, read 0.03-0.06 off by its
largest entry in layers 3-5 for no fault of the program's, where a lost
block read 0.04 (PERF.md section 6, PR 46, second round). The expert layer records what a
chunk's rows chose (`chunk_expert_ids`); jobs/serve_mediaqa.py's
`ChunkChoices` keeps those records outside the window (set-up's prefill of
the histories and the check prompt, the replay's questions), and `compare`
hands them to the reference beside the decoded rows' choices: it takes
them at near-ties only, as it takes a decoded row's.

`kv_bytes_a_token` is the engine's `kv_pool_bytes` over
`kv_cached_tokens` when the window closes, as jobs/serve_longdoc.py reads
it.

`run(ctx, control=...)` is for the builder's controls, which have to come
out not correct (PERF.md section 6, PR 46): a `spoil` of the reference
(mistral_small4_reference.SPOILS), "bf16" (the reference's matmuls at the
TPU's default precision), or "lost_block", which zeroes, in every layer's
latent pool, one cached block of 256 rows of every replayed stream's
history before the replay. "spoils" is the builder's shortcut: a sound
run that, after each comparison, computes the reference again under every
spoil and prints that spoil's readings beside the sound ones (one set-up
for all of them; the run's verdict is the sound comparison's).
"""

from __future__ import annotations

import sys
import types

import numpy as np

from benchmarks import harness
from benchmarks import mistral_small4_reference as reference


def build_model(ctx):
    """The compiled model, from the flags a user would put on the command
    line: the trunk builder, an inference compile."""
    from flexflow_tpu import (
        FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )
    from flexflow_tpu.fftype import CompMode
    from flexflow_tpu.models import (
        build_transformer_lm, mistral_small4_lm_config,
    )

    cell = ctx.cell
    cfg = mistral_small4_lm_config(
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


def lose_block(engine, prompts, which: int = 2) -> list:
    """Zero, in every layer's latent pool, block `which` of each prompt's
    cached prefix; -> the blocks lost."""
    mgr, dec = engine.block_manager, engine.decode_model
    lost = sorted({mgr.cache.match(p, peek=True)[1][which] for p in prompts})
    for leaves in dec._state.values():
        if "pool_c" in leaves:
            leaves["pool_c"] = leaves["pool_c"].at[np.asarray(lost)].set(0)
    return lost


def first_layer_probe(engine, config):
    """-> probe(prompt) -> (positions held, the last positions probed, the
    first layer's attention output there (n, hidden), the pool's rows of
    the prompt (held, width)): the program's decode op called once more,
    outside any step, on the first layer's pool as the loop left it, one
    row a slot (the slots' path: on a TPU the paged latent kernel) under
    the page-table row the radix cache maps for the prompt (module
    docstring). One jitted program for every prompt."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.base import OpContext
    from flexflow_tpu.ops.core import rms_norm

    mgr, dec = engine.block_manager, engine.decode_model
    node = next(nd for nd in dec.graph.topo_order() if nd.name == "l0_attn")
    n = engine.spec.slots

    @jax.jit
    def attend(params, pool, tokens, positions, table):
        x = rms_norm(params["wte"]["kernel"][tokens],
                     params["l0_ln1"]["scale"], config["rms_norm_eps"])
        (y,), _ = node.op_def.forward(
            node.params, [x[:, None], positions[:, None], table],
            {**params["l0_attn"], **pool}, None,
            OpContext(training=False, mesh=None))
        return y[:, 0].astype(jnp.float32)

    def probe(prompt):
        held, blocks = mgr.cache.match(prompt, peek=True)
        blocks = np.asarray(blocks, np.int32)
        at = np.arange(held - n, held, dtype=np.int32)
        table = np.zeros((n, mgr.table_width), np.int32)
        table[:, :len(blocks)] = blocks
        pool = dec._state["l0_attn"]
        got = np.asarray(attend(
            {name: dec._params[name] for name in ("wte", "l0_ln1",
                                                  "l0_attn")},
            pool, jnp.asarray(np.asarray(prompt, np.int32)[at]),
            jnp.asarray(at), jnp.asarray(table)))
        rows = np.asarray(pool["pool_c"][blocks], np.float32)
        return held, at, got, rows.reshape(-1, rows.shape[-1])[:held]

    return probe


def first_layer_errors(probed, get, config, prompt, pad_to, spoil=None):
    """What `first_layer_probe` read of a prompt against the reference's
    (`reference.first_layer`) -> the positions held, and max |difference|
    over max |reference| of the attention output and of the cache rows."""
    held, at, got, rows = probed
    want, want_rows = reference.first_layer(
        get, list(prompt) + [0] * (pad_to - len(prompt)), config, at,
        spoil=spoil)

    def error(mine, ref):
        return float(np.max(np.abs(mine - ref)) / np.max(np.abs(ref)))

    return {"rows": held, "attend_error": error(got, want),
            "cache_error": error(rows[:, :want_rows.shape[1]],
                                 want_rows[:held])}


def run(ctx, control=None) -> dict:
    sessions = harness.load_module("jobs", "serve_sessions.py")
    # the record of what a chunk's rows chose, kept outside the window
    ChunkChoices = harness.load_module("jobs",
                                       "serve_mediaqa.py").ChunkChoices
    scoped, engines, chunks = {}, [], []
    layers = range(ctx.config["num_hidden_layers"])
    t = ctx.traffic
    histories = sessions.traffic_gen.quantiles(t["history_tokens"],
                                               t["clients"])
    questions, replies = sessions.traffic_gen.request_sizes(t)
    # where the compared sequences' decoded rows lie: the replay decodes
    # every session, and only these rows' attention outputs are kept
    # (16 KB a row a layer)
    kept = [(n, n + sessions.CHECK_DECODED + 1)
            for n in t["check_history_tokens"]] + [
        (histories[c], histories[c] + max(questions) + max(replies))
        for c in t["check_stream_histories"]]

    class Choices(sessions.Choices):
        """What the slots' rows were given in the last call, by layer: the
        experts (`expert_ids`) and the attention's output (`attended`)."""

        def __init__(self, ctx):
            self.layers = layers
            self.attn = [f"l{i}_attn" for i in layers]
            self.moe = [f"l{i}_moe" for i in layers]

        def empty(self) -> dict:
            return {layer: {"experts": {}, "attended": {}}
                    for layer in self.layers}

        def fetch(self, state) -> dict:
            import jax

            return jax.device_get(
                {**{name: state[name]["attended"] for name in self.attn},
                 **{name: state[name]["expert_ids"] for name in self.moe}})

        def note(self, program, fetched, row: int, position: int) -> None:
            keep = any(lo <= position < hi for lo, hi in kept)
            for layer, mine in program.items():
                mine["experts"][position] = fetched[self.moe[layer]][row]
                if keep:
                    mine["attended"][position] = (
                        fetched[self.attn[layer]][row].copy())

    def decode_instructions(engine) -> list:
        """The pure-decode step's [[instruction, scope]] pairs under
        dsv32_events' scopes (returned: `decode_instructions`) and under
        ms4_events' (`ms4_instructions`), from one compiled text."""
        import jax
        import jax.numpy as jnp

        from benchmarks import dsv32_events, ms4_events

        dec, slots = engine.decode_model, engine.spec.slots
        xs = engine._stage_inputs(
            np.zeros((slots, 1), np.int32),
            np.full((slots, 1), engine.max_seq_len, np.int32))
        text = engine._step_fn.lower(
            dec._params, dec._state, xs, jnp.zeros((slots,), jnp.int32),
            jax.random.key(0), jnp.zeros((slots,), jnp.float32)
        ).compile().as_text()
        scoped["ms4_instructions"] = ms4_events.scoped_instructions(text)
        return dsv32_events.scoped_instructions(text)

    spoils = (*reference.SPOILS, "bf16")
    if control not in (*spoils, "lost_block", "spoils"):
        raise ValueError(f"unknown control {control!r}")
    spoil = control if control in spoils else None

    def padded(n: int) -> int:
        return n + -n % 256

    # one padded length a compared sequence (module docstring)
    planned = sorted({
        *(padded(n + sessions.CHECK_DECODED)
          for n in t["check_history_tokens"]),
        *(padded(histories[c] + max(questions) + max(replies))
          for c in t["check_stream_histories"])})

    def lowerings(get, config, length, **kw):
        return [pair for n in planned
                for pair in reference.lowerings(get, config, n, **kw)]

    attended = []  # a compared sequence's readings, a layer

    def compare(get, tokens, config, rows, program, pad_to=None):
        tokens = list(tokens)
        pad_to = next(n for n in planned if n >= len(tokens))
        # the prompt rows' experts beside the decoded rows' (module
        # docstring): the reference takes either at near-ties only
        found = chunks[0].experts_of(tokens)
        for layer, chosen in found.items():
            mine = program[layer]["experts"]
            for position, ids in chosen.items():
                mine.setdefault(position, ids)
        print(f"[longctx] the reference is given the experts the program "
              f"chose at {len(found[0])} prompt positions of {len(tokens)} "
              f"tokens, beside the {len(rows)} decoded rows'")
        for other in spoils[1:] if control == "spoils" else ():
            r = reference.compare(get, tokens, config, rows, program,
                                  spoil=other, pad_to=pad_to)
            print(f"[longctx] control {other} over {len(tokens)} tokens: "
                  f"logits {r['error']:.5f} off, the layers' attention "
                  f"{r['attend_errors']}, the program routed "
                  f"otherwise at {r['route_differs']} of "
                  f"{r['route_rows']}, at a gap of at most "
                  f"{r['route_gap_max']:.5f}, {r['route_bad']} beyond the "
                  f"margin")
        r = reference.compare(get, tokens, config, rows, program,
                              spoil=spoil, pad_to=pad_to)
        attended.append(r.pop("attend_errors"))
        print(f"[longctx] {len(rows)} decoded rows of {len(tokens)} tokens: "
              f"the layers' attention outputs, from the step's own "
              f"program, {attended[-1]} of the reference's norm off "
              f"it (tolerance {reference.LAYER_ATTEND_TOL})")
        return r

    logits_step, replay = sessions.logits_step, sessions.replay

    def logits_step_and_keep(engine):
        # the first the job does with its engine: kept for what follows,
        # and the record of the chunks' experts goes in here
        engines.append(engine)
        chunks.append(ChunkChoices(engine, [f"l{i}_moe" for i in layers]))
        engine._step_fn = chunks[0]
        return logits_step(engine)

    # the compared sessions' histories, as the loop draws them (its first
    # draws from the seed)
    rng = np.random.default_rng(ctx.seed)
    drawn = [rng.integers(0, ctx.config["vocab_size"], n).tolist()
             for n in histories]
    compared = [drawn[c] for c in t["check_stream_histories"]]
    checks = []

    def replay_then_check_the_attention(engine, ctx, step, served):
        """The loop's replay, then the first layer's attention and cache
        rows of the compared sessions' prompts, while the pools are still
        there (the loop deletes them after)."""
        if control == "lost_block":
            lost = lose_block(engine, [r.prompt for r in served])
            print(f"[longctx] control: latent rows of blocks {lost} zeroed "
                  f"in every layer before the replay")
        record = replay(engine, ctx, step, served)
        get = harness.param_getter(engine.decode_model)
        probe = first_layer_probe(engine, ctx.config)
        for r in served:
            if not any(r.prompt[:len(h)] == h for h in compared):
                continue
            probed = probe(r.prompt)
            pad_to = next(n for n in planned if n >= len(r.prompt))
            for other in (spoils[1:] if control == "spoils" else ()):
                c = first_layer_errors(probed, get, ctx.config, r.prompt,
                                       pad_to, other)
                print(f"[longctx] control {other} over {len(r.prompt)} "
                      f"tokens: the first layer's attention "
                      f"{c['attend_error']:.5f} off, its cache rows "
                      f"{c['cache_error']:.5f}")
            checks.append(first_layer_errors(probed, get, ctx.config,
                                             r.prompt, pad_to, spoil))
            print(f"[longctx] the first layer of a served prompt of "
                  f"{len(r.prompt)}: {checks[-1]['rows']} positions held; "
                  f"the attention of the last {engine.spec.slots} "
                  f"{checks[-1]['attend_error']:.5f} of the largest off "
                  f"the reference's (tolerance {reference.ATTEND_TOL}), "
                  f"the cache's rows {checks[-1]['cache_error']:.5f} "
                  f"(tolerance {reference.CACHE_TOL})")
        return record

    open_window, close_window, at_close = (
        ctx.open_window, ctx.close_window, {})

    def opened():
        chunks[0].on = False
        return open_window()

    def closed():
        now = close_window()
        chunks[0].on = True
        at_close.update(engines[0].stats())
        return now

    ctx.open_window, ctx.close_window = opened, closed
    sessions.build_model = build_model
    sessions.Choices = Choices
    sessions.decode_instructions = decode_instructions
    sessions.logits_step = logits_step_and_keep
    sessions.replay = replay_then_check_the_attention
    sessions.reference = types.SimpleNamespace(
        **{**vars(reference), "compare": compare, "lowerings": lowerings})
    result = sessions.run(ctx)
    sequences = len(t["check_history_tokens"]) + len(compared)
    if len(attended) != sequences or not all(
            len(a) == len(layers) and max(a) <= reference.LAYER_ATTEND_TOL
            for a in attended):
        print(f"[longctx] a layer's attention output of a compared "
              f"sequence is off the reference's, or not all {sequences} "
              f"sequences were read in all {len(layers)} layers: "
              f"{attended}")
        result["correct"] = False
        result["failed"] += sequences
    held = (len(checks) == len(compared) and all(
        c["rows"] >= len(h) and c["attend_error"] <= reference.ATTEND_TOL
        and c["cache_error"] <= reference.CACHE_TOL
        for c, h in zip(sorted(checks, key=lambda c: c["rows"]),
                        sorted(compared, key=len))))
    if not held:
        print(f"[longctx] the first layer's attention or cache rows of the "
              f"compared sessions are off the reference's, or not all "
              f"held: {checks}")
        result["correct"] = False
        result["failed"] += len(compared)
    result["counters"].update(
        scoped,
        layer_attend_error=max(map(max, attended), default=None),
        attend_error=max((c["attend_error"] for c in checks), default=None),
        cache_error=max((c["cache_error"] for c in checks), default=None),
        kv_bytes_a_token=(at_close["kv_pool_bytes"]
                          / at_close["kv_cached_tokens"]
                          if at_close.get("kv_cached_tokens") else None))
    return result
