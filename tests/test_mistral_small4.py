"""Mistral-Small-4's language model through the normal path at a small size
(PR 46): latent attention with NO selection (a row reads its whole latent
history), a position-dependent query scale, a softmax top-4 router with a
shared expert. The trunk builder's training-shaped graph and the decode
graph over the paged latent cache, each against the float32 reference
(models/mistral_small4_reference.py) on seeded weights; the tiny
`original_max_position_embeddings` of 8 is crossed twice inside a sequence
of 24, so a(t) takes three values inside these tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import small_lms
import test_latent_attention as dsv32
from flexflow_tpu.fftype import DataType, OperatorType as OT
from flexflow_tpu.models import (
    mistral_small4_lm_config, mistral_small4_reference as ref,
)
from test_latent_attention import decode_graph_logits, error, getter

# hidden 64, 4 heads, latent 32, rotary 8, 16 experts of which 4 a token,
# one shared expert, three layers, every one an expert layer
TINY = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_interleave=True,
    rope_parameters={"beta_fast": 32, "beta_slow": 1, "factor": 128,
                     "llama_4_scaling_beta": 0.1, "mscale": 1,
                     "mscale_all_dim": 1,
                     "original_max_position_embeddings": 8,
                     "rope_theta": 10000, "rope_type": "yarn",
                     "type": "yarn"},
    rms_norm_eps=1e-6, intermediate_size=96, first_k_dense_replace=0,
    num_hidden_layers=3, n_routed_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=24, n_group=1, topk_group=1, norm_topk_prob=True,
    routed_scaling_factor=1, n_shared_experts=1, vocab_size=97)
SEQ = 24
TOL = 5e-6  # float32 against float32, as a share of the largest logit


def build(config=TINY, seq=SEQ, **kw):
    return dsv32.build(lm_config=mistral_small4_lm_config(
        config, sequence_length=seq, initializer_range=0.1), seq=seq, **kw)


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 97, (2, SEQ)).astype(np.int32)


def serve(ff, **kw):
    """The shared engine of these options (tests/small_lms.py), as new."""
    spec = dict(slots=4, max_seq_len=32, prefill_chunk=8, kv_block_size=4,
                kv_num_blocks=64)
    return small_lms.engine(ff, **{**spec, **kw})


def test_the_query_scale_takes_three_values_inside_a_sequence():
    a = np.asarray(ref.query_scale(jnp.arange(SEQ), ref.dims(TINY)))
    assert len(set(a.round(6).tolist())) == 3
    np.testing.assert_allclose(
        a[[0, 7, 8, 15, 16, 23]],
        [1, 1, 1 + 0.1 * np.log(2), 1 + 0.1 * np.log(2),
         1 + 0.1 * np.log(3), 1 + 0.1 * np.log(3)], rtol=1e-6)


def test_training_shaped_graph_gives_the_references_logits(model, tokens):
    got = dsv32.forward(model, tokens)
    for b in range(2):
        want, _ = ref.forward(getter(model), tokens[b], TINY)
        assert error(got[b], want) < TOL


def test_chunked_prefill_then_decode_through_the_cache_is_the_full_forward(
        model, tokens):
    eng = serve(model)
    assert eng._chunk_rows  # a chunk rides as rows under one table row
    seq = tokens[0, :22]
    want, notes = ref.forward(getter(model), seq, TINY)
    # chunks of 8, 8 and 1, then five decoded rows: positions on both
    # sides of 8 and of 16 come from a chunk's rows and from a slot's
    got = decode_graph_logits(eng, seq, split=17)
    assert error(got, want) < TOL
    # every layer's record of the last call is its output at the slot's row
    for i, note in enumerate(notes):
        mine = eng.decode_model._state[f"l{i}_attn"]["attended"]
        assert mine.dtype == jnp.float32
        assert error(np.asarray(mine[1]), np.asarray(note["attended"][-1])
                     ) < TOL
    # the cache holds one latent row a token a layer and nothing else
    state = eng.decode_model._state["l0_attn"]
    assert set(state) == {"pool_c", "attended"}
    assert state["attended"].shape == (4, 64)      # the slots' rows' output
    assert state["pool_c"].shape == (64, 4, 128)   # 32 + 8, lane-aligned
    assert eng.kv_bytes_per_layer() == 4 * 64 * 4 * 128
    assert eng._sel_cap == 0


def test_a_latent_layer_with_no_indexer_declares_one_pool_and_no_selection():
    from flexflow_tpu.ops.base import (
        BY_BLOCK, HANDOFF, LAST_CALL, QUERIES, get_op_def,
    )
    from flexflow_tpu.ops.latent_attention import PagedLatentAttentionParams

    front = mistral_small4_lm_config(TINY, sequence_length=SEQ).latent
    assert front.index is None and front.query_scale == (0.1, 8)
    assert [w.name for w in front.weight_specs(64)] == [
        "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo"]
    assert front.kernels == ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
    assert front.cache_row_widths == {"pool_c": 128}
    p = PagedLatentAttentionParams(front, 32, 4, 64, chunk_from=4)
    state = get_op_def(OT.OP_PAGED_LATENT_ATTENTION).state(p)
    assert [(l.name, l.index, l.width) for l in state.leaves] == [
        ("pool_c", BY_BLOCK, (128,)), ("attended", LAST_CALL, (64,))]
    assert state.selected == 0 and set(state.cannot) == {HANDOFF, QUERIES}
    assert "selection" not in state.cannot[HANDOFF]


def test_a_latent_layer_with_the_indexer_is_built_as_before():
    """DeepSeek-V3.2's front end: the weights' names, the state's leaves
    and what it cannot follow are what they were before the indexer became
    an optional part."""
    from flexflow_tpu.models import deepseek_v32_lm_config
    from flexflow_tpu.ops.attention import SELECTION_CANNOT
    from flexflow_tpu.ops.base import BY_BLOCK, LAST_CALL, get_op_def
    from flexflow_tpu.ops.latent_attention import PagedLatentAttentionParams

    front = deepseek_v32_lm_config(dsv32.TINY, sequence_length=SEQ).latent
    assert (front.index.n_heads, front.index.head_dim, front.index.topk
            ) == (2, 16, 8)
    assert front.query_scale is None
    assert [w.name for w in front.weight_specs(64)] == [
        "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo", "wi_q",
        "wi_k", "wi_k_norm", "wi_k_bias", "wi_w"]
    assert front.kernels == ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "wi_q",
                             "wi_k", "wi_w")
    assert front.cache_row_widths == {"pool_c": 128, "pool_i": 16}
    p = PagedLatentAttentionParams(front, 32, 4, 64, chunk_from=4)
    state = get_op_def(OT.OP_PAGED_LATENT_ATTENTION).state(p)
    assert [(l.name, l.index) for l in state.leaves] == [
        ("pool_c", BY_BLOCK), ("pool_i", BY_BLOCK), ("sel_rows", LAST_CALL)]
    assert state.selected == 8 and state.cannot == SELECTION_CANNOT


def test_history_from_the_radix_cache_and_a_copy_on_write(model, tokens):
    """A prefix hit and a copy-on-write on the latent pool with no
    `pool_i`: two follow-ups over one cached history give the
    reference's greedy continuations."""
    history = tokens[0, :17].tolist()
    turns = [tokens[1, :5].tolist(), tokens[1, 5:9].tolist()]
    eng = serve(model)
    eng.generate([history], max_new_tokens=1)       # leaves it in the cache
    cached = [eng.generate([history + t], max_new_tokens=4)[0]
              for t in turns]
    stats = eng.stats()
    assert stats["prefix_hit_tokens"] >= 2 * 16
    assert stats["evictions"] == 0 and stats["cow_copies"] >= 2
    for t, reply in zip(turns, cached):
        seq = np.array(history + t + reply)
        want, _ = ref.forward(getter(model), seq, TINY)
        first = len(history + t) - 1
        assert np.array_equal(
            np.argmax(want[first:first + len(reply)], axis=-1), reply)


@pytest.mark.parametrize("spoil", [s for s in ref.SPOILS if s])
def test_every_spoil_moves_the_logits_past_the_tolerance(model, tokens,
                                                         spoil):
    sound, _ = ref.forward(getter(model), tokens[0], TINY)
    spoiled, _ = ref.forward(getter(model), tokens[0], TINY, spoil=spoil)
    assert error(spoiled, sound) > 1e-3
    if spoil == "query_scale_off":  # a(t) is 1 below the original extent
        assert error(spoiled[:8], sound[:8]) < TOL


def test_kv_rows_of_a_step_are_the_slots_lengths(model, tokens, monkeypatch):
    """With no selection a step's span carries `kv_rows`, the latent rows
    its attention reads, and neither `sel_rows` nor `index_rows`."""
    from flexflow_tpu import telemetry

    seen = []
    real = telemetry.span

    def span(name, **args):
        if name in ("serve.step", "serve.prefill"):
            seen.append((name, args))
        return real(name, **args)

    monkeypatch.setattr(telemetry, "span", span)
    eng = serve(model)
    eng.generate([tokens[0, :11].tolist(), tokens[1, :6].tolist()],
                 max_new_tokens=3)
    assert not any({"sel_rows", "index_rows", "ctx_rows"} & set(args)
                   for _, args in seen)
    assert all(args["kv_itemsize"] == 4 for _, args in seen)
    # the first prompt's chunks of 8 and 3 rows read 8 and 11 rows, once;
    # the second's chunk of 6 rides beside the first's decoded row at
    # position 11 (12 rows); then both slots decode, at positions 12 and
    # 6 (13 + 7 rows), and the second alone at 7 (8 rows)
    assert [(name, args["kv_rows"]) for name, args in seen] == [
        ("serve.prefill", 8), ("serve.prefill", 11), ("serve.prefill", 18),
        ("serve.step", 20), ("serve.step", 8)]


def test_what_the_latent_pool_cannot_follow_is_refused_by_name(model):
    from flexflow_tpu.serving.decode_graph import HANDOFF, QUERIES, refuse

    with pytest.raises(NotImplementedError, match="single-query rows only"):
        refuse(model, "speculative decoding", QUERIES)
    with pytest.raises(NotImplementedError,
                       match=r"latent attention \(l0_attn, \.\.\.\).*handoff"):
        serve(model, disaggregate=True)
    assert "selection" not in str(pytest.raises(
        NotImplementedError, refuse, model, "x", HANDOFF).value)
    with pytest.raises(NotImplementedError, match="paged pool"):
        serve(model, kv_layout="contiguous")


def test_the_eight_shares_add_up_to_the_uncut_expert_layer():
    """The deployment's cut (benchmarks/configs/mistral-small-4-119b.json):
    eight chips hold a layer's routed experts, an eighth each, and the
    shared expert whole. The expert op under each share (experts 0-1, 2-3,
    ...), the shared expert counted once, sums to the uncut reference's
    layer, with every assignment computed exactly once."""
    from flexflow_tpu.ops import MoEMLPParams
    from flexflow_tpu.ops.base import OpContext, get_op_def

    rng = np.random.default_rng(5)
    d, n, f, k, chips = 64, 16, 24, 4, 8
    w = {"router": rng.normal(size=(d, n)),
         "gate": 0.2 * rng.normal(size=(n, d, f)),
         "up": 0.2 * rng.normal(size=(n, d, f)),
         "down": 0.2 * rng.normal(size=(n, f, d)),
         "shared_gate": 0.2 * rng.normal(size=(d, f)),
         "shared_up": 0.2 * rng.normal(size=(d, f)),
         "shared_down": 0.2 * rng.normal(size=(f, d))}
    w = {name: jnp.asarray(a, jnp.float32) for name, a in w.items()}
    x = jnp.asarray(rng.normal(size=(40, d)), jnp.float32)
    moe = mistral_small4_lm_config(TINY, sequence_length=SEQ).moe_routing

    def held(first, count):
        return {**w, **{name: w[name][first:first + count]
                        for name in ("gate", "up", "down")}}

    with jax.default_matmul_precision("highest"):
        whole, routing = ref.expert_layer(x, w, TINY, held=(0, n))
        no_shared, _ = ref.expert_layer(x, w, TINY, held=(0, n),
                                        spoil="shared_off")
    shared = np.asarray(whole) - np.asarray(no_shared)
    fwd = get_op_def(OT.OP_MOE_MLP).forward
    ctx = OpContext(training=False, mesh=None)
    total, assignments = np.zeros_like(shared), 0
    for first in range(0, n, n // chips):
        p = MoEMLPParams(n, k, f, **{**moe, "shared_intermediate_size": f,
                                     "experts_held": (first, n // chips)})
        (y,), state = fwd(p, [x], held(first, n // chips), None, ctx)
        assert np.array_equal(np.asarray(state["expert_ids"]),
                              np.asarray(routing["ids"]))
        assert int(state["dropped_total"]) == 0
        assignments += int(state["assignments_total"])
        total += np.asarray(y) - shared
    assert assignments == 40 * k      # every assignment computed once
    assert error(total + shared, np.asarray(whole)) < TOL


def test_the_cost_model_prices_a_row_over_its_cached_rows():
    """With no selection the decode op's FLOPs are the projections and
    every cached row under 4 heads x (latent row + latent), and there are
    no indexer products."""
    from flexflow_tpu.ops.base import get_op_def
    from flexflow_tpu.ops.latent_attention import PagedLatentAttentionParams

    front = mistral_small4_lm_config(TINY, sequence_length=SEQ).latent
    p = PagedLatentAttentionParams(front, 32, 4, 64, chunk_from=4,
                                   cache_dtype=DataType.DT_FLOAT)
    flops = get_op_def(OT.OP_PAGED_LATENT_ATTENTION).flops(
        p, [(4, 1, 64), (4, 1), (4, 8)], [(4, 1, 64)])
    linear = 2.0 * 4 * (64 * (24 + 40) + 24 * 4 * 24 + 4 * 16 * 64)
    assert flops == linear + 2.0 * 4 * 32 * 4 * (40 + 32)
