"""The session-serving job of `cmdap-serve-agentmix`: the loop, the checks
and the replay of jobs/serve_sessions.py (sessions of one history each,
held in the prefix cache; a request is history + a fresh turn; logits of
the pre-window check and of two served streams against the reference's
full forward), over Command A+'s language model and its reference
(benchmarks/command_a_plus_reference.py), with sessions shorter and longer
than the window of 4,096 keys in one queue.

serve_sessions.py names DeepSeek-V3.2's configuration builder and
reference and is an accepted file, so this job loads a copy of that module
of its own (`harness.load_module` executes the file anew), as
jobs/serve_longdoc.py does, and gives the copy this configuration's parts:
`build_model`, `reference` (the same `compare` / `lowerings` / limits
interface; no layer selects, so the selection's readings are empty),
`Choices` (the experts a decoded row chose in each of the four layers;
beside them `ChunkChoices` of jobs/serve_mediaqa.py, the experts a prefill
chunk's rows chose, kept outside the window: a sigmoid router's scores
lie close, four tokens in five are at a near-tie of the 8th and 9th, and
a prompt token the reference routes apart from the program has another
state from there on, so the reference takes the program's experts at
near-ties at every position it has them for),
`decode_instructions` (the scopes of benchmarks/cmdap_events.py and of
mimo2_events.py beside those dsv32_events.py joins). What a cache of two
groups asks of the checks is serve_longdoc.py's, from a copy of that module
given this reference: `logit_check` over two hand-made page tables, the
global layer's rows of a served turn (`question_rows`), and the control
that zeroes the window blocks a replayed stream's admission will map
(`lose_window_block`). Everything else is serve_sessions.py's, line for
line: the traffic, the window, what `correct` needs of the logits, of the
histories and of the experts.

**The pre-window check** prefills a prompt of 4,700 tokens in the cell's
chunks of 256 (past the window by two of them) and decodes 8 rows through
tables that hold every block of both groups: it holds the kernels' band,
start page and 128 query heads on 8 KV heads against the reference. What
the block manager does to the window group (nothing given back before row
4,096, then a block every block's rows; a history under the window pinned
whole, one past it by the blocks of its last 4,096 rows; the shared tail
block copied in both groups) is held by the replayed streams, which go
through the engine's own submit(), admission and steps: one whose history
of 3,922 tokens crosses the window inside the request, one of 11,094.

**The global layer's rows of a turn** (`pool_rows`, saved after the
replay while the pools are still there, compared inside `compare`, which
runs the reference's forward anyway): layer 3's keys and values of a
compared stream's turn positions, from the global pool, against the
reference's. They are a function of three window layers' outputs, so a
window, a rotation or a norm computed wrongly when the turn was prefilled
shows there at full size, in rows no decoded row's logits are compared
at. `correct` needs both streams' within `reference.CACHE_TOL`.

`run(ctx, control=...)` is for the builder's controls, which have to come
out not correct (PERF.md section 6, PR 49): a `spoil` of the reference
(command_a_plus_reference.SPOILS), or "lost_window_block".
"""

from __future__ import annotations

import sys
import time
import types

import numpy as np

from benchmarks import command_a_plus_reference as reference
from benchmarks import harness


def build_model(ctx):
    """The compiled model, from the flags a user would put on the command
    line: the trunk builder, an inference compile."""
    from flexflow_tpu import (
        FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )
    from flexflow_tpu.fftype import CompMode
    from flexflow_tpu.models import (
        build_transformer_lm, command_a_plus_lm_config,
    )

    cell = ctx.cell
    cfg = command_a_plus_lm_config(
        ctx.config, sequence_length=cell["train_sequence_length"],
        attention_impl=cell["attention_impl"],
        initializer_range=ctx.config["initializer_range"],
        embedding_range=ctx.config["embedding_initializer_range"],
        embedding_mean=ctx.config["embedding_initializer_mean"])
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


def run(ctx, control=None) -> dict:
    sessions = harness.load_module("jobs", "serve_sessions.py")
    # serve_longdoc.py's checks over a cache of two groups, which read the
    # reference's `last_global_layer` from their module
    two_groups = harness.load_module("jobs", "serve_longdoc.py")
    two_groups.reference = reference
    # the record of what a chunk's rows chose (jobs/serve_mediaqa.py)
    chunk_choices = harness.load_module("jobs",
                                        "serve_mediaqa.py").ChunkChoices
    scoped, caches, saved, engines, chunks = {}, [], [], [], []

    class Choices(sessions.Choices):
        def __init__(self, ctx):
            self.layers = range(ctx.config["num_hidden_layers"])
            self.attn = []
            self.moe = {i: f"l{i}_moe" for i in self.layers}

        def empty(self) -> dict:
            return {layer: {"experts": {}} for layer in self.layers}

        def note(self, program, fetched, row: int, position: int) -> None:
            for layer, name in self.moe.items():
                program[layer]["experts"][position] = (
                    fetched[name]["expert_ids"][row])

    def decode_instructions(engine) -> list:
        """The pure-decode step's [[instruction, scope]] pairs under
        dsv32_events' scopes (returned: `decode_instructions`), under
        mimo2_events' (`mimo2_instructions`) and under cmdap_events'
        (`cmdap_instructions`), from one compiled text."""
        import jax
        import jax.numpy as jnp

        from benchmarks import cmdap_events, dsv32_events, mimo2_events

        dec, slots = engine.decode_model, engine.spec.slots
        xs = engine._stage_inputs(
            np.zeros((slots, 1), np.int32),
            np.full((slots, 1), engine.max_seq_len, np.int32))
        text = engine._step_fn.lower(
            dec._params, dec._state, xs, jnp.zeros((slots,), jnp.int32),
            jax.random.key(0), jnp.zeros((slots,), jnp.float32)
        ).compile().as_text()
        scoped["mimo2_instructions"] = mimo2_events.scoped_instructions(text)
        scoped["cmdap_instructions"] = cmdap_events.scoped_instructions(text)
        return dsv32_events.scoped_instructions(text)

    if control not in (*reference.SPOILS, "lost_window_block"):
        raise ValueError(f"unknown control {control!r}")
    spoil = control if control in reference.SPOILS else None
    t = ctx.traffic
    lengths = sessions.traffic_gen.quantiles(t["history_tokens"],
                                             t["clients"])
    compared = sorted(lengths[c] for c in t["check_stream_histories"])
    # the sessions' histories, as the loop draws them (its first draws)
    rng = np.random.default_rng(ctx.seed)
    histories = [rng.integers(0, ctx.config["vocab_size"], n).tolist()
                 for n in lengths]

    def logits_step_and_keep(engine):
        # the first the job does with its engine: kept for what follows,
        # and the record of the chunks' experts goes in here
        engines.append(engine)
        chunks.append(chunk_choices(engine, list(Choices(ctx).moe.values())))
        engine._step_fn = chunks[0]
        return logits_step(engine)

    def compare(get, tokens, config, rows, program, **kw):
        """reference.compare, with the pool's rows of the sequence's turn
        where the replay saved them."""
        tokens = list(tokens)
        found = chunks[0].experts_of(tokens)
        print(f"[agentmix] the reference is given the experts the program "
              f"chose at {len(found[0])} prompt positions of {len(tokens)} "
              f"tokens, beside the {len(rows)} decoded rows'")
        for layer, chosen in found.items():
            known = program.setdefault(layer, {}).setdefault("experts", {})
            for position, ids in chosen.items():
                known.setdefault(position, ids)
        mine = next((s for s in saved if tokens[:len(s[0])] == s[0]), None)
        got = reference.compare(get, tokens, config, rows, program,
                                spoil=spoil,
                                pool_rows=mine[1] if mine else None, **kw)
        if mine:
            caches.append(got.pop("cache_error"))
            print(f"[agentmix] layer {reference.last_global_layer(config)}'s "
                  f"rows of a served turn ({len(mine[1][1])} positions "
                  f"from {mine[1][0]}): {caches[-1]:.5f} of the largest off "
                  f"the reference's (tolerance {reference.CACHE_TOL})")
        return got

    replay, logits_step = sessions.replay, sessions.logits_step

    def replay_then_save_rows(engine, ctx, step, served):
        """The loop's replay, then the global layer's rows of the compared
        sessions' turns, while the pools are still there (the loop deletes
        them after)."""
        by_history = {len(two_groups.history_of(r.prompt, histories)): r
                      for r in served}
        if control == "lost_window_block":
            lost = two_groups.lose_window_block(
                engine, [r.prompt for r in served])
            print(f"[agentmix] control: window blocks {lost} zeroed in "
                  f"every window layer before the replay")
        record = replay(engine, ctx, step, served)
        said_load(record, served)
        for n in compared:
            r = by_history.get(n)
            if r is not None:
                saved.append((list(r.prompt), two_groups.question_rows(
                    engine, ctx.config, r.prompt, n)))
        return record

    def said_load(record, served):
        """What the replay's steps say of the window's: the held experts
        the streams' rows hit in one step, by layer (the j-th decoded row
        of every stream stands for a step: all were submitted at once),
        and how many distinct tokens a reply has."""
        first, held = ctx.config["experts_held"]
        rows = {r.request_id: sorted(record[r.request_id][0])
                for r in served}
        steps = min(map(len, rows.values()))
        if not steps:
            return
        hits = []
        for layer in Choices(ctx).moe:
            per_step = []
            for j in range(steps):
                ids = np.concatenate([
                    record[rid][1][layer]["experts"][at[j]]
                    for rid, at in rows.items()])
                per_step.append(len({int(e) for e in ids
                                     if first <= e < first + held}))
            hits.append(round(float(np.mean(per_step)), 2))
        distinct = sorted(len(set(r.generated)) / len(r.generated)
                          for r in served)
        scoped["experts_hit_a_step"] = hits
        print(f"[agentmix] held experts the {len(rows)} replayed streams' "
              f"rows hit in a step, by layer, mean of {steps} steps: "
              f"{hits} of {held}; distinct tokens a reply, as a share of "
              f"its length: least {distinct[0]:.2f}, median "
              f"{distinct[len(distinct) // 2]:.2f}")

    def logit_check_two_groups(engine, ctx, prompts, step, pad_to=None):
        return two_groups.logit_check(sessions, engine, ctx, prompts, step,
                                      pad_to=pad_to)

    def warm_copies(engine):
        """serve_sessions.warm_copies in both groups: the pool's
        copy-on-write programs at every width they can take."""
        from flexflow_tpu.serving.paged import SCRATCH_BLOCK, CopyPlan

        width = 1
        while width <= engine.spec.slots:
            engine._apply_copies(
                [CopyPlan(src=SCRATCH_BLOCK, dst=SCRATCH_BLOCK, group=g)
                 for g in (0, 1)] * width)
            width *= 2

    sessions.build_model = build_model
    sessions.Choices = Choices
    sessions.decode_instructions = decode_instructions
    sessions.logits_step = logits_step_and_keep
    sessions.logit_check = logit_check_two_groups
    sessions.warm_copies = warm_copies
    sessions.replay = replay_then_save_rows
    sessions.reference = types.SimpleNamespace(
        **{**vars(reference), "compare": compare})
    close_window = ctx.close_window
    at_open, at_close, timed = {}, {}, []

    def closed():
        now = close_window()
        del engines[0].step  # the class's own again
        chunks[0].on = True
        at_close.update(engines[0].stats())
        return now

    open_window = ctx.open_window

    def opened():
        at_open.update(engines[0].stats())
        chunks[0].on = False  # the window's chunks are not recorded
        engine, step = engines[0], engines[0].step

        def timed_step():
            # the window's calls by kind on the host's clock: what a run
            # without a trace can say of where its window went
            before, t0 = engine._prefill_calls, time.perf_counter()
            done = step()
            timed.append((time.perf_counter() - t0,
                          engine._prefill_calls > before))
            return done

        engine.step = timed_step
        return open_window()

    ctx.open_window, ctx.close_window = opened, closed
    result = sessions.run(ctx)
    held = (len(caches) == len(compared)
            and max(caches) <= reference.CACHE_TOL)
    if not held:
        print(f"[agentmix] the global pool's rows of the compared "
              f"sessions' turns are off the reference's, or not all held: "
              f"{caches}")
        result["correct"] = False
        result["failed"] += len(compared)
    window_keys = ("kv_pool_bytes", "kv_cached_tokens",
                   "kv_window_pool_bytes", "kv_window_pool_blocks",
                   "kv_window_blocks_held", "kv_window_blocks_in_use_peak",
                   "kv_blocks_held", "window_cow_copies",
                   "window_pins_dropped")
    result["counters"].update(
        scoped, cache_error=max(caches, default=None),
        weight_itemsize=engines[0].decode_model._params["l0_moe"][
            "shared_gate"].dtype.itemsize,
        kv_bytes_a_token=(at_close["kv_pool_bytes"]
                          / at_close["kv_cached_tokens"]
                          if at_close.get("kv_cached_tokens") else None),
        # what the window's steps gave back and read under the window
        **{k: at_close.get(k, 0) - at_open.get(k, 0)
           for k in ("window_blocks_freed", "under_window")},
        **{k: at_close.get(k) for k in window_keys})
    # assignments each layer computed since the weights were made
    # (set-up's histories among them)
    import jax

    state = jax.device_get({
        name: {k: v for k, v in engines[0].decode_model._state[name].items()
               if k == "assignments_total"}
        for name in Choices(ctx).moe.values()})
    totals = [int(leaves["assignments_total"]) for leaves in state.values()]
    result["counters"].update(moe_assignments_by_layer=totals)
    print(f"[agentmix] assignments computed by layer {totals} "
          f"({[round(n / (sum(totals) / len(totals)), 3) for n in totals]} "
          f"of their mean; the dead rows of set-up's steps, all token 0, "
          f"among them)")
    for kind, name in ((False, "that only decode"),
                       (True, "with a turn's chunk")):
        took = sorted(s for s, chunk in timed if chunk == kind) or [0.0]
        print(f"[agentmix] the window's engine steps {name}, on the host's "
              f"clock: {len(took)} in {sum(took):.3f} s, mean "
              f"{1e3 * sum(took) / len(took):.3f} ms, median "
              f"{1e3 * took[len(took) // 2]:.3f}, 99th percentile "
              f"{1e3 * took[len(took) * 99 // 100]:.3f}, the longest "
              f"{1e3 * took[-1]:.3f}")
    print(f"[agentmix] in the window: "
          f"{result['counters']['window_blocks_freed']} window blocks given "
          f"back, {result['counters']['under_window']} decoding slot-steps "
          f"with the context inside the window; the window pool holds "
          f"{at_close.get('kv_window_blocks_held')} of "
          f"{at_close.get('kv_window_pool_blocks')} blocks, "
          f"{at_close.get('window_pins_dropped')} pins dropped")
    return result
