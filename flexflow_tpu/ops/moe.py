"""Mixture-of-Experts operators: Group_by, Aggregate, AggregateSpec, Cache,
the fused Experts op of the MNIST classifier, and, at the end, MoEMLP: the
dropless token-routed expert layer of an LM block (OLMoE).

Reference: src/ops/group_by.cc/.cu (token→expert scatter with capacity factor
alpha), src/ops/aggregate.cc/.cu (gate-weighted combine + load-balance term in
backward), src/ops/aggregate_spec.cc (speculative variant with replicated
labels), src/ops/cache.cc (cross-batch activation cache with staleness score,
include/flexflow/ops/cache.h:14-65).

TPU design notes:
- The reference's CUDA kernels do data-dependent scatter/gather. Under jit we
  need static shapes, so expert buffers are padded to the same
  `capacity = ceil(alpha * k * batch / n)` the reference uses — its alpha
  capacity factor exists for exactly this reason (static allocation).
- Token ranking within an expert is a cumsum over a one-hot routing matrix —
  all dense VPU math, no serialization; overflow tokens are dropped exactly
  like the reference (group_by.cu drops rows beyond expert capacity).
- Both Group_by and Aggregate derive slots from the same deterministic
  (sample-major) ordering so they agree without communicating, mirroring the
  reference pair.
- The load-balance gradient the reference injects in aggregate's backward
  (lambda_bal) is exposed here as an auxiliary loss accumulated into op state
  ("aux_loss"); the loss module adds it to the scalar objective so autodiff
  produces the same gate gradients.
- Expert parallelism = sharding the stacked expert dim over the `expert`/
  `model` mesh axis; the gather in aggregate then lowers to an all-to-all.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp

from ..fftype import DataType, OperatorType as OT
from .base import OpDef, WeightSpec, register_op


def expert_capacity(n: int, k: int, batch: int, alpha: float) -> int:
    return max(1, int(math.ceil(alpha * k * batch / n)))


def _routing_slots(assign, n: int, capacity: int):
    """assign: (batch, k) int expert ids → (slot, valid) each (batch, k).

    slot[i,j] = rank of token (i,j) among tokens routed to assign[i,j], in
    sample-major order; valid = rank < capacity."""
    b, k = assign.shape
    flat = assign.reshape(-1).astype(jnp.int32)  # (b*k,)
    onehot = jax.nn.one_hot(flat, n, dtype=jnp.int32)  # (b*k, n)
    ranks = jnp.cumsum(onehot, axis=0) - onehot  # rank among same-expert tokens
    slot = jnp.take_along_axis(ranks, flat[:, None], axis=1)[:, 0]
    valid = slot < capacity
    return slot.reshape(b, k), valid.reshape(b, k)




def _scatter_to_buffers(data, assign, n: int, cap: int, slot, valid):
    """Scatter token rows into stacked (n, cap, dim) expert buffers; dropped
    tokens land in a trash slot (group_by.cu semantics). Shared by Group_by
    and the fused Experts op so routing can never desynchronize."""
    k = assign.shape[1]
    flat_assign = assign.reshape(-1).astype(jnp.int32)
    flat_slot = jnp.where(valid.reshape(-1), slot.reshape(-1), cap)
    token_rows = jnp.repeat(data, k, axis=0) if k > 1 else data
    buffers = jnp.zeros((n, cap + 1, data.shape[1]), dtype=data.dtype)
    buffers = buffers.at[flat_assign, flat_slot].set(token_rows)
    return buffers[:, :cap]


def _gather_expert_rows(stacked, assign, slot, valid):
    """Gather each token's expert output row from stacked (n, cap, dim);
    dropped tokens read as zeros (aggregate.cu semantics). Returns
    (rows (b, k, dim), expert_idx (b, k))."""
    e_idx = assign.astype(jnp.int32)
    rows = stacked[e_idx, jnp.where(valid, slot, 0)]
    return jnp.where(valid[..., None], rows, 0.0), e_idx


# ---------------------------------------------------------------- Group_by

@dataclass(frozen=True)
class GroupByParams:
    n: int
    alpha: float


def _group_by_infer(p: GroupByParams, in_shapes):
    data, assign = in_shapes
    batch, dim = data
    k = assign[1]
    cap = expert_capacity(p.n, k, batch, p.alpha)
    return [(cap, dim) for _ in range(p.n)]


def _group_by_forward(p: GroupByParams, inputs, weights, state, ctx):
    data, assign = inputs
    batch, dim = data.shape
    k = assign.shape[1]
    cap = expert_capacity(p.n, k, batch, p.alpha)
    slot, valid = _routing_slots(assign, p.n, cap)
    buffers = _scatter_to_buffers(data, assign, p.n, cap, slot, valid)
    outs = [buffers[e] for e in range(p.n)]
    return outs, state


register_op(
    OpDef(OT.OP_GROUP_BY, _group_by_infer, _group_by_forward, num_outputs=-1)
)


# ---------------------------------------------------------------- Aggregate

@dataclass(frozen=True)
class AggregateParams:
    n: int
    lambda_bal: float = 0.0


def _aggregate_infer(p: AggregateParams, in_shapes):
    # inputs: gate_preds (b,k), gate_assign (b,k), true_gate_assign (b,k),
    #         full_gate_grads (b,n), exp_pred_1..n (cap, out_dim)
    gate_preds = in_shapes[0]
    out_dim = in_shapes[4][1]
    return [(gate_preds[0], out_dim)]


def _aggregate_forward(p: AggregateParams, inputs, weights, state, ctx):
    gate_preds, gate_assign = inputs[0], inputs[1]
    exp_preds = jnp.stack(inputs[4 : 4 + p.n])  # (n, cap, dim)
    b, k = gate_assign.shape
    cap = exp_preds.shape[1]
    slot, valid = _routing_slots(gate_assign, p.n, cap)
    rows, e_idx = _gather_expert_rows(exp_preds, gate_assign, slot, valid)
    out = jnp.einsum("bk,bkd->bd", gate_preds.astype(rows.dtype), rows)

    if p.lambda_bal > 0.0:
        # load-balance auxiliary objective (reference injects the equivalent
        # gradient by hand in aggregate.cu backward): mean tokens-per-expert
        # × mean gate probability per expert, Shazeer-style.
        full_gate = inputs[3]  # (b, n) softmax over all experts
        counts = jnp.sum(
            jax.nn.one_hot(e_idx.reshape(-1), p.n, dtype=full_gate.dtype), axis=0
        )
        frac_tokens = counts / (b * k)
        frac_probs = jnp.mean(full_gate, axis=0)
        aux = p.n * jnp.sum(frac_tokens * frac_probs)
        state = dict(state or {})
        state["aux_loss"] = p.lambda_bal * aux
    return [out], state


register_op(OpDef(OT.OP_AGGREGATE, _aggregate_infer, _aggregate_forward))


# ---------------------------------------------------------------- AggregateSpec

@dataclass(frozen=True)
class AggregateSpecParams:
    n: int
    lambda_bal: float = 0.0


def _agg_spec_infer(p: AggregateSpecParams, in_shapes):
    # speculative variant: emits per-token-copy rows (k*b, dim) so each
    # expert's prediction is scored against (replicated) labels — see
    # model.cc:2875 replicating labels when last op is OP_AGG_SPEC
    gate_preds = in_shapes[0]
    out_dim = in_shapes[4][1]
    b, k = gate_preds
    return [(k * b, out_dim)]


def _agg_spec_forward(p: AggregateSpecParams, inputs, weights, state, ctx):
    gate_preds, gate_assign = inputs[0], inputs[1]
    exp_preds = jnp.stack(inputs[4 : 4 + p.n])
    b, k = gate_assign.shape
    cap = exp_preds.shape[1]
    slot, valid = _routing_slots(gate_assign, p.n, cap)
    rows, _ = _gather_expert_rows(exp_preds, gate_assign, slot, valid)
    out = rows.transpose(1, 0, 2).reshape(k * b, -1)
    return [out], state


register_op(OpDef(OT.OP_AGG_SPEC, _agg_spec_infer, _agg_spec_forward))


# ---------------------------------------------------------------- Cache

@dataclass(frozen=True)
class CacheParams:
    num_batches: int
    data_type: DataType = DataType.DT_FLOAT


def _cache_infer(p: CacheParams, in_shapes):
    return [in_shapes[0]]


def _cache_weights(p: CacheParams, in_shapes):
    return [
        WeightSpec(
            "cached", in_shapes[0], p.data_type, "zeros", trainable=False
        ),
        # staleness score of the cached activation (cache.h:14-65's
        # score function, kept on-device as a non-trainable stat)
        WeightSpec("score", (), DataType.DT_FLOAT, "zeros",
                   trainable=False),
    ]


def cache_score(x, cached) -> jnp.ndarray:
    """Staleness of `cached` w.r.t. the live activation `x` — the
    reference's CacheScore (cache.h:14-65, cache.cu score kernel): relative
    moving difference, 0 = identical, →1 = fully drifted. RecompileState
    triggers read this to decide cache invalidation / re-optimization
    (moe.cc:180-204's experiment)."""
    xf = x.astype(jnp.float32)
    cf = cached.astype(jnp.float32)
    num = jnp.sum(jnp.abs(xf - cf))
    den = jnp.sum(jnp.abs(xf)) + 1e-8
    return jnp.minimum(num / den, 1.0)


def _cache_forward(p: CacheParams, inputs, weights, state, ctx):
    (x,) = inputs
    state = dict(state or {})
    if ctx.training:
        # training: score the previous cache against the live batch, then
        # pass through and refresh (reference cache_update task); the score
        # is exposed in op state for RecompileState triggers.
        state["score"] = cache_score(x, weights["cached"])
        state["cached"] = x.astype(jnp.dtype(weights["cached"].dtype))
        return [x], state
    return [weights["cached"].astype(x.dtype)], state


register_op(
    OpDef(OT.OP_CACHE, _cache_infer, _cache_forward, _cache_weights)
)


# ---------------------------------------------------------------- Experts
# TPU-native addition (no analog in the reference training snapshot): the
# group_by → per-expert dense → aggregate trio fused into ONE op over a
# *stacked* expert weight (n, in, hidden). Why: separate per-expert Dense
# layers can only be expert-parallelized by placing whole ops on different
# devices (the reference's attribute-parallel machine views); a stacked
# weight makes expert parallelism a plain sharding of dim 0 over the
# `expert` mesh axis, so GSPMD lowers the token exchange to all_to_all over
# ICI. Routing math (capacity, slot ranking, dropping) matches
# group_by.cu/aggregate.cu semantics exactly.

@dataclass(frozen=True)
class ExpertsParams:
    n: int
    hidden_size: int
    alpha: float = 1.0
    lambda_bal: float = 0.0
    use_bias: bool = True
    activation: str = "relu"  # relu | gelu | none


def _experts_infer(p: ExpertsParams, in_shapes):
    data = in_shapes[0]  # (b, d)
    return [(data[0], p.hidden_size)]


def _experts_weights(p: ExpertsParams, in_shapes):
    d = in_shapes[0][1]
    ws = [WeightSpec("kernel", (p.n, d, p.hidden_size), DataType.DT_FLOAT)]
    if p.use_bias:
        ws.append(
            WeightSpec("bias", (p.n, p.hidden_size), DataType.DT_FLOAT, "zeros")
        )
    return ws


def _experts_forward(p: ExpertsParams, inputs, weights, state, ctx):
    data, gate_values, gate_assign = inputs  # (b,d), (b,k), (b,k)
    b, d = data.shape
    k = gate_assign.shape[1]
    cap = expert_capacity(p.n, k, b, p.alpha)
    slot, valid = _routing_slots(gate_assign, p.n, cap)
    buffers = _scatter_to_buffers(data, gate_assign, p.n, cap, slot, valid)

    # stacked expert dense — one batched MXU matmul over all experts
    kern = weights["kernel"].astype(buffers.dtype)
    h = jnp.einsum("ncd,ndh->nch", buffers, kern)
    if p.use_bias:
        h = h + weights["bias"].astype(h.dtype)[:, None, :]
    if p.activation == "relu":
        h = jax.nn.relu(h)
    elif p.activation == "gelu":
        h = jax.nn.gelu(h)

    # gather back + gate-weighted combine (aggregate semantics)
    rows, e_idx = _gather_expert_rows(h, gate_assign, slot, valid)
    out = jnp.einsum("bk,bkh->bh", gate_values.astype(rows.dtype), rows)

    if p.lambda_bal > 0.0:
        counts = jnp.sum(
            jax.nn.one_hot(e_idx.reshape(-1), p.n, dtype=jnp.float32), axis=0
        )
        frac_tokens = counts / (b * k)
        # gate_values are the top-k probabilities; renormalize as proxy
        probs = jnp.zeros((b, p.n), jnp.float32)
        probs = probs.at[jnp.arange(b)[:, None], e_idx].set(
            gate_values.astype(jnp.float32)
        )
        frac_probs = jnp.mean(probs, axis=0)
        aux = p.n * jnp.sum(frac_tokens * frac_probs)
        state = dict(state or {})
        state["aux_loss"] = p.lambda_bal * aux
    return [out], state


def _experts_flops(p: ExpertsParams, in_shapes, out_shapes):
    b, d = in_shapes[0]
    k = in_shapes[2][1]
    cap = expert_capacity(p.n, k, b, p.alpha)
    return 2.0 * p.n * cap * d * p.hidden_size


register_op(
    OpDef(
        OT.OP_EXPERTS,
        _experts_infer,
        _experts_forward,
        _experts_weights,
        _experts_flops,
    )
)


# ---------------------------------------------------------------- MoE MLP
# The token-routed expert layer of an LM block (OLMoE, and the models of
# ROADMAP Queue 2 after it): router, softmax over all experts, the k
# largest, SiLU-gated experts, gate-weighted sum. Dropless: the tokens x k
# assignments are sorted by expert and the experts run as grouped matmuls
# over the sorted rows with group sizes from the counts. There is no
# buffer of a fixed size per expert, so every assignment is computed
# however skewed the router is.

@dataclass(frozen=True)
class MoEMLPParams:
    num_experts: int  # the router's width, whatever is held here
    num_experts_per_tok: int
    intermediate_size: int
    # multiplies the load-balancing term this layer adds to the objective
    # (transformers' load_balancing_loss_func for one layer)
    aux_loss_coef: float = 0.0
    # how the router scores and chooses. "softmax" is OLMoE's (softmax
    # over all experts, the k largest; renormalised over the chosen and
    # scaled only where `norm_topk_prob` / `routed_scaling_factor` say
    # so, as Solar-Open2 does). "sigmoid" is
    # DeepSeek-V3's: sigmoid scores, a correction bias `router_bias` added
    # for the choice only, the experts in `n_group` groups of which the
    # `topk_group` with the largest two-best sums are kept, the k largest
    # among them, gates renormalised over the chosen (`norm_topk_prob`)
    # and multiplied by `routed_scaling_factor`
    scoring: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    # added to the chosen gates' sum before `norm_topk_prob` divides by it
    # (LFM2-MoE's 1e-6); 0.0 = the plain sum, and the program it was
    norm_topk_eps: float = 0.0
    # width of a SiLU-gated expert every token passes through, added to
    # the routed sum; 0 = none
    shared_intermediate_size: int = 0
    # the shared expert's output times this. n shared experts whose
    # outputs are averaged are ONE gated MLP n times as wide, the experts'
    # gate and up matrices side by side and their down matrices one under
    # the other, with its output times 1 / n: the same function
    shared_scale: float = 1.0
    # False: the sigmoid router adds no correction bias to the scores it
    # chooses by, and the layer holds no `router_bias`
    correction_bias: bool = True
    # (first expert id, count) of the experts THIS layer holds, on one
    # chip of a deployment that spreads them: the router keeps its width
    # and its k, `gate`/`up`/`down` hold these experts only, and the
    # result is the shared expert's plus the chosen-and-held experts';
    # what the absent ones would add is left out. None = all of them
    experts_held: Optional[tuple] = None
    # rows of `chunk_expert_ids`, the record of the experts chosen for
    # the rows of a call that come PAST the rows the graph was built for:
    # a prefill chunk riding as single-query rows past a decode graph's
    # slots (serving/decode_graph.py sets it to the engine's prefill
    # chunk). 0 = no such record
    chunk_rows: int = 0

    @property
    def held(self) -> tuple:
        return self.experts_held or (0, self.num_experts)


def _moe_mlp_infer(p: MoEMLPParams, in_shapes):
    return [in_shapes[0]]


def _moe_mlp_weights(p: MoEMLPParams, in_shapes):
    d, n, f = in_shapes[0][-1], p.num_experts, p.intermediate_size
    tokens = math.prod(in_shapes[0][:-1])
    held, fs = p.held[1], p.shared_intermediate_size
    extra = []
    if p.scoring == "sigmoid" and p.correction_bias:
        # e_score_correction_bias: the published one is fitted during
        # training; seeded here, so that it takes part in the choice
        extra.append(WeightSpec("router_bias", (n,), DataType.DT_FLOAT,
                                "normal"))
    if fs:
        extra += [
            WeightSpec("shared_gate", (d, fs), DataType.DT_FLOAT, "normal"),
            WeightSpec("shared_up", (d, fs), DataType.DT_FLOAT, "normal"),
            WeightSpec("shared_down", (fs, d), DataType.DT_FLOAT, "normal")]
    if p.experts_held is not None:
        # running counts since the weights were made: assignments this
        # layer computed (chosen and held; the padding rows of a serving
        # step among them) and assignments to held experts it did not
        extra += [WeightSpec(name, (), DataType.DT_INT32, "zeros",
                             trainable=False)
                  for name in ("assignments_total", "dropped_total")]
        if _by_slabs(p, tokens * p.num_experts_per_tok):
            # the row slabs of the sorted order the last forward ran
            # (docs/observability.md): x SLAB / (tokens x k) is the share
            # of the sort this layer touched
            extra.append(WeightSpec("slabs_run", (), DataType.DT_INT32,
                                    "zeros", trainable=False))
    # "normal" is N(0, 0.02), transformers' initializer_range: glorot over
    # a stacked (n, d, f) weight would count the experts into the fans
    return [
        WeightSpec("router", (d, n), DataType.DT_FLOAT, "normal"),
        WeightSpec("gate", (held, d, f), DataType.DT_FLOAT, "normal"),
        WeightSpec("up", (held, d, f), DataType.DT_FLOAT, "normal"),
        WeightSpec("down", (held, f, d), DataType.DT_FLOAT, "normal"),
        *extra,
        # the step's counters, beside the loss (read them from the model's
        # state after a step): assignments no expert computed, and the
        # largest expert's load over the mean load
        WeightSpec("dropped_tokens", (), DataType.DT_FLOAT, "zeros",
                   trainable=False),
        WeightSpec("load_max_over_mean", (), DataType.DT_FLOAT, "zeros",
                   trainable=False),
        # the experts the last forward chose for each token: routing is
        # discontinuous, so whoever compares this layer with another
        # implementation needs the choice itself
        WeightSpec("expert_ids", (tokens, p.num_experts_per_tok),
                   DataType.DT_INT32, "zeros", trainable=False),
        # the same for a chunk's rows past them (`chunk_rows`): what a
        # prompt's tokens chose, which no later step computes again
        *([WeightSpec("chunk_expert_ids",
                      (p.chunk_rows, p.num_experts_per_tok),
                      DataType.DT_INT32, "zeros", trainable=False)]
          if p.chunk_rows else []),
    ]


def moe_route(x, router, k: int):
    """(gate weights (t, k) float32, expert ids (t, k) int32, router
    probabilities (t, n) float32) of tokens x (t, d): softmax over all the
    experts in float32, then the k largest, not renormalised (OLMoE's
    norm_topk_prob false)."""
    logits = jnp.dot(x, router.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, k)
    return weights, ids.astype(jnp.int32), probs


def _gate_sum(gates, p: MoEMLPParams):
    """What `norm_topk_prob` divides the chosen gates by."""
    total = jnp.sum(gates, axis=-1, keepdims=True)
    return total + p.norm_topk_eps if p.norm_topk_eps else total


def moe_route_sigmoid(x, router, bias, p: MoEMLPParams):
    """(gate weights (t, k) float32, expert ids (t, k) int32, scores (t,
    n)) of DeepSeek-V3's Gate (MoEMLPParams.scoring)."""
    n, k, groups = p.num_experts, p.num_experts_per_tok, p.n_group
    logits = jnp.dot(x, router.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    biased = scores if bias is None else scores + bias.astype(jnp.float32)
    if groups > 1:
        grouped = biased.reshape(-1, groups, n // groups)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(group_score, p.topk_group)
        keep = jnp.any(jax.nn.one_hot(kept, groups, dtype=bool), axis=1)
        biased = jnp.where(jnp.repeat(keep, n // groups, axis=1), biased,
                           -jnp.inf)
    _, ids = jax.lax.top_k(biased, k)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    if p.norm_topk_prob:
        gates = gates / _gate_sum(gates, p)
    return gates * p.routed_scaling_factor, ids.astype(jnp.int32), scores


def load_balancing_loss(probs, group_sizes):
    """transformers' load_balancing_loss_func for one layer: num_experts x
    the sum over experts of (share of the tokens that chose the expert,
    summed over the k choices) x (mean router probability of the
    expert). `group_sizes` (n,) are the assignments each expert got."""
    t, n = probs.shape
    return n * jnp.sum((group_sizes.astype(jnp.float32) / t)
                       * jnp.mean(probs, axis=0))


def moe_sort(ids, num_experts: int):
    """The dispatch's bookkeeping for expert ids (t, k): `order` (t*k,),
    the flat assignments sorted by expert (stable, so token order holds
    inside an expert); `position` (t, k), each assignment's row in the
    sorted order; `group_sizes` (n,), the assignments of each expert."""
    flat = ids.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    position = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32)).reshape(ids.shape)
    group_sizes = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1)
    return order, position, group_sizes


@jax.custom_vjp
def _gather_sorted(x, order, position, live=None):
    """Rows of tokens x (t, d) in sorted-assignment order (t*k, d). The
    backward is a gather too (each token's k rows by `position`, summed),
    not a scatter-add over repeated rows. `live`, where given, is the
    number of sorted rows some expert computes (a held share's: the rows
    behind them belong to experts held elsewhere): the grouped matmuls
    leave the cotangent of the rows past it unwritten, so the backward
    takes it as zero there. The forward is the same with and without."""
    return x[order // position.shape[1]]


def _gather_sorted_fwd(x, order, position, live=None):
    return _gather_sorted(x, order, position), (order, position, live)


def _gather_sorted_bwd(res, g):
    order, position, live = res
    if live is not None:
        g = jnp.where((jnp.arange(g.shape[0]) < live)[:, None], g, 0)
    return (jnp.sum(g[position].astype(jnp.float32), axis=1).astype(g.dtype),
            None, None, None)


_gather_sorted.defvjp(_gather_sorted_fwd, _gather_sorted_bwd)


@jax.custom_vjp
def _gather_back(y, order, position):
    """Each token's k expert outputs (t, k, d) from the sorted rows y
    (t*k, d); the backward gathers the cotangent into sorted order."""
    return y[position]


def _gather_back_fwd(y, order, position):
    return y[position], (order, position)


def _gather_back_bwd(res, g):
    order, position = res
    return g.reshape(-1, g.shape[-1])[order], None, None


_gather_back.defvjp(_gather_back_fwd, _gather_back_bwd)


# A held share's sorted order is live prefix first: rows [0, live) belong
# to held experts and the grouped matmuls stop there. Where the sort is
# long the layer's other passes in sorted order stop there too
# (`_held_live`): they run in slabs of SLAB rows, ceil(live / SLAB) of
# them, a count the device decides each step (a `while`: no host round
# trip, one program at any load, nothing dropped). Rows past the last slab
# are never written; a row inside it and past `live` is whatever the slab
# made of unwritten kernel output. The token-order gathers mask what they
# read of either, by `position < live`.
# A multiple of the grouped matmul's row tile. On a v5e at (65536, 2048) a
# quarter live, the layer alone: PERF.md section 6, PR 62
SLAB = 2048
# the sort is worth a loop from this many slabs on: under it (a serving
# step's few thousand assignments) a loop's fixed cost a pass buys nothing
MIN_SLABS = 4


def _by_slabs(p: MoEMLPParams, m: int) -> bool:
    """Whether a layer of `m` sorted rows runs its sorted-order passes over
    the live prefix alone."""
    return p.experts_held is not None and m >= MIN_SLABS * SLAB


def _unwritten(like, after, mesh):
    """An array of `like`'s shape and dtype that nothing has written, there
    no sooner than `after` is: on one TPU device a Pallas call that reads
    nothing of `after`, allocates its output and leaves it, as megablox's
    `gmm` leaves the rows past its groups; zeros elsewhere. A loop over the
    live slabs so starts from no memset. (`lax.empty` is such a buffer
    too, but an instruction with no operand: XLA's TPU scheduler puts it
    first, and every layer's backward buffers then lie allocated through
    the whole step, 5.8 GB of them in `lfm2-train-8k`.)"""
    if jax.default_backend() != "tpu" or (mesh is not None and mesh.size > 1):
        return jnp.zeros(like.shape, like.dtype)
    from jax.experimental import pallas as pl

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        lambda after, out: None, in_specs=[anywhere], out_specs=anywhere,
        out_shape=jax.ShapeDtypeStruct(like.shape, like.dtype),
        name="moe_unwritten")(after)


def _slab(a, start):
    return jax.lax.dynamic_slice_in_dim(a, start, SLAB)


def _slabs_under(live):
    return (live + SLAB - 1) // SLAB


def _over_live_slabs(live, rows_of, like, *operands, mesh=None):
    """An unwritten array a `jax.ShapeDtypeStruct` of `like` (one or a
    tuple) with `rows_of(start, *operands)`'s (SLAB, .) rows written at
    every slab start under `live`. Where SLAB does not divide the rows the
    last slab starts SLAB short of the end, inside the slab before: every
    pass writes a row from its operands alone, so a row written twice is
    written the same (where it does, a start is i x SLAB and nothing else:
    a clamp hides from XLA that the start is a whole tile, and the slab's
    update then runs apart from the pass that makes it, 1.6 ms a layer in
    `lfm2-train-8k`). The operands are whole arrays the slabs read their
    rows from, held behind a barrier: XLA's TPU pipeline otherwise sinks
    the elementwise pass that made one into the loop's body and runs it
    whole in every slab."""
    operands = jax.lax.optimization_barrier(operands)
    # (an operand each: two calls alike are one to XLA, and one buffer)
    leaves, tree = jax.tree.flatten(like)
    out = tree.unflatten([_unwritten(o, operands[i], mesh)
                          for i, o in enumerate(leaves)])
    rows = leaves[0].shape[0]

    def body(i, out):
        start = i * SLAB
        if rows % SLAB:
            start = jnp.minimum(start, rows - SLAB)
        return jax.tree.map(
            lambda o, r: jax.lax.dynamic_update_slice_in_dim(o, r, start, 0),
            out, rows_of(start, *operands))

    return jax.lax.fori_loop(0, _slabs_under(live), body, out)


def _silu_gate(gate, up):
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


def _sorted_rows(x, order, live, k: int, mesh):
    """Rows [0, live) of tokens x (t, d) in sorted-assignment order."""
    with jax.named_scope("moe.dispatch"):
        return _over_live_slabs(
            live, lambda at, x, order: x[_slab(order, at) // k],
            jax.ShapeDtypeStruct((order.shape[0], x.shape[1]), x.dtype),
            x, order, mesh=mesh)


def _silu_gate_live(gate, up, live, mesh):
    with jax.named_scope("moe.experts"):
        return _over_live_slabs(
            live, lambda at, gate, up: _silu_gate(_slab(gate, at),
                                                  _slab(up, at)),
            jax.ShapeDtypeStruct(gate.shape, gate.dtype), gate, up,
            mesh=mesh)


def _expert_matmul(lhs, w, sizes, mesh):
    from ..kernels.grouped_matmul import grouped_matmul

    with jax.named_scope("moe.experts"):
        return grouped_matmul(lhs, w.astype(lhs.dtype), sizes, mesh)


def _expert_matmul_vjp(lhs, w, sizes, mesh, g):
    """(d lhs, d w) of `_expert_matmul` under its cotangent g: the grouped
    matmul's own backward (`gmm` for d lhs, `tgmm` for d w); the forward it
    is linearised at has no reader and is dropped."""
    return jax.vjp(lambda a, b: _expert_matmul(a, b, sizes, mesh), lhs,
                   w)[1](g)


# (jitted: the five layers of a step, and the step's several programs,
# trace the forward and the backward once, not once each)
@functools.partial(jax.jit, static_argnums=(0,))
def _held_live_fwd(mesh, x, gates, w_gate, w_up, w_down, order, position,
                   sizes):
    k, live = position.shape[1], jnp.sum(sizes)
    rows = _sorted_rows(x, order, live, k, mesh)
    gate = _expert_matmul(rows, w_gate, sizes, mesh)
    up = _expert_matmul(rows, w_up, sizes, mesh)
    out = _expert_matmul(_silu_gate_live(gate, up, live, mesh), w_down, sizes,
                         mesh)
    with jax.named_scope("moe.combine"):
        # token order, all of it, a choice a plane: (k, t, d) has no short
        # dimension under the tiles, (t, k, d) is laid out again on the way
        # to its sum. A held choice's row is under `live`; the others' are
        # whatever is there
        places = position.T
        picked = jnp.where((places < live)[..., None], out[places], 0)
        y = jnp.sum(gates.T[..., None] * picked.astype(jnp.float32), axis=0)
    # a loop's result is nothing XLA can make again when memory is short,
    # as it does the whole length's gathers and SiLU gates: of the whole-
    # length arrays `gate`, `up` and `picked` alone wait for the backward,
    # which runs the live slabs of `rows` and `hidden` again
    return y, (x, gates, w_gate, w_up, w_down, order, places, sizes, gate,
               up, picked)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_live(mesh, x, gates, w_gate, w_up, w_down, order, position, sizes):
    """The held experts' gate-weighted sum (t, d) float32 of tokens x
    (t, d) under `gates` (t, k), zero where the choice is held elsewhere:
    what `_gather_sorted`, the three grouped matmuls round the SiLU gate,
    `_gather_back` and the weighted sum give, with every pass in sorted
    order run over the slabs under the groups' sum, forward and backward
    (hand-written: a loop whose trip count the device decides has no
    reverse mode of JAX's own)."""
    return _held_live_fwd(mesh, x, gates, w_gate, w_up, w_down, order,
                          position, sizes)[0]


@functools.partial(jax.jit, static_argnums=(0,))
def _held_live_bwd(mesh, res, dy):
    (x, gates, w_gate, w_up, w_down, order, places, sizes, gate, up,
     picked) = res
    k, live, f32 = places.shape[0], jnp.sum(sizes), jnp.float32
    held = places < live
    # what the backward makes again it makes from dy's side of this
    # barrier: XLA otherwise finds the forward's two loops equal to the
    # backward's and keeps their results for it, `rows` and `hidden` of
    # every layer from its forward to its backward
    x, order, gate, up, dy = jax.lax.optimization_barrier(
        (x, order, gate, up, dy))
    with jax.named_scope("moe.combine"):
        # `out`'s cotangent in sorted order: a row's gate times its token's
        # row of dy. The (t, k, d) product JAX's reverse mode gathers from
        # (float32, and once more under the other tiling: 1 GB a layer, a
        # quarter of it read) is never made
        def weighted(at, dy, flat, order):
            at = _slab(order, at)
            return (flat[at][:, None] * dy[at // k]).astype(x.dtype)

        d_out = _over_live_slabs(
            live, weighted,
            jax.ShapeDtypeStruct((order.shape[0], x.shape[1]), x.dtype),
            dy, gates.reshape(-1), order, mesh=mesh)
        # (`picked` is zero where the choice is held elsewhere)
        d_gates = jnp.sum(picked.astype(f32) * dy[None], axis=-1).T
    d_hidden, d_w_down = _expert_matmul_vjp(
        _silu_gate_live(gate, up, live, mesh), w_down, sizes, mesh, d_out)

    def gate_backward(at, d_hidden, gate, up):
        return jax.vjp(_silu_gate, _slab(gate, at), _slab(up, at))[1](
            _slab(d_hidden, at))

    with jax.named_scope("moe.experts"):
        like = jax.ShapeDtypeStruct(gate.shape, gate.dtype)
        d_gate, d_up = _over_live_slabs(live, gate_backward, (like, like),
                                        d_hidden, gate, up, mesh=mesh)
    rows = _sorted_rows(x, order, live, k, mesh)
    by_gate, d_w_gate = _expert_matmul_vjp(rows, w_gate, sizes, mesh, d_gate)
    by_up, d_w_up = _expert_matmul_vjp(rows, w_up, sizes, mesh, d_up)
    with jax.named_scope("moe.dispatch"):
        d_rows = _over_live_slabs(
            live, lambda at, by_gate, by_up: (_slab(by_gate, at)
                                              + _slab(by_up, at)),
            jax.ShapeDtypeStruct(by_gate.shape, by_gate.dtype), by_gate,
            by_up, mesh=mesh)
        d_x = jnp.sum(jnp.where(held[..., None], d_rows[places], 0)
                      .astype(f32), axis=0).astype(x.dtype)
    # the layer's backward ends here, all of it: XLA's scheduler otherwise
    # leaves the weights' matmuls for later and their operands (d_gate,
    # d_up, d_out, rows, hidden: 1.2 GB a layer) allocated until then,
    # five layers' at once
    return (*jax.lax.optimization_barrier(
        (d_x, d_gates.astype(gates.dtype), d_w_gate, d_w_up, d_w_down)),
        None, None, None)


_held_live.defvjp(_held_live_fwd, _held_live_bwd)


def _moe_mlp_forward(p: MoEMLPParams, inputs, weights, state, ctx):
    from ..kernels.grouped_matmul import grouped_matmul

    (x,) = inputs
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    n, k = p.num_experts, p.num_experts_per_tok
    first, held = p.held
    with jax.named_scope("moe.route"):
        if p.scoring == "sigmoid":
            gates, ids, probs = moe_route_sigmoid(
                x, weights["router"], weights.get("router_bias"), p)
        else:
            gates, ids, probs = moe_route(x, weights["router"], k)
            if p.norm_topk_prob:
                gates = gates / _gate_sum(gates, p)
            if p.routed_scaling_factor != 1.0:
                gates = gates * p.routed_scaling_factor
    with jax.named_scope("moe.dispatch"):
        if p.experts_held is None:
            order, position, group_sizes = moe_sort(ids, n)
        else:
            # assignments to experts held elsewhere sort behind the last
            # group, where no expert computes them, and count for nothing
            here = (ids >= first) & (ids < first + held)
            gates = jnp.where(here, gates, 0.0)
            order, position, group_sizes = moe_sort(
                jnp.where(here, ids - first, held), held + 1)
            group_sizes = group_sizes[:held]
    slabs = _by_slabs(p, order.shape[0])
    if slabs:
        y = _held_live(ctx.mesh, x, gates, weights["gate"], weights["up"],
                       weights["down"], order, position, group_sizes)
    else:
        with jax.named_scope("moe.dispatch"):
            rows = _gather_sorted(
                x, order, position,
                None if p.experts_held is None else jnp.sum(group_sizes))
        with jax.named_scope("moe.experts"):
            gate = grouped_matmul(rows, weights["gate"].astype(x.dtype),
                                  group_sizes, ctx.mesh)
            up = grouped_matmul(rows, weights["up"].astype(x.dtype),
                                group_sizes, ctx.mesh)
            out = grouped_matmul(_silu_gate(gate, up),
                                 weights["down"].astype(x.dtype),
                                 group_sizes, ctx.mesh)
        with jax.named_scope("moe.combine"):
            picked = _gather_back(out, order, position)
            if p.experts_held is not None:
                # rows past the groups' sum are whatever the kernel left
                picked = jnp.where(here[..., None], picked, 0.0)
            y = jnp.sum(gates[..., None] * picked.astype(jnp.float32),
                        axis=1)
    if p.shared_intermediate_size:
        with jax.named_scope("moe.shared"):
            def dot(a, w):
                return jnp.dot(a, w.astype(a.dtype),
                               preferred_element_type=jnp.float32)

            h = (jax.nn.silu(dot(x, weights["shared_gate"]))
                 * dot(x, weights["shared_up"])).astype(x.dtype)
            shared = dot(h, weights["shared_down"])
            if p.shared_scale != 1.0:
                shared = shared * p.shared_scale
            y = y + shared
    state = dict(state or {})
    computed = jnp.sum(group_sizes)
    wanted = ids.size if p.experts_held is None else jnp.sum(here)
    state["dropped_tokens"] = (wanted - computed).astype(jnp.float32)
    state["load_max_over_mean"] = (
        jnp.max(group_sizes)
        * (held / jnp.maximum(computed, 1).astype(jnp.float32)))
    declared = weights.get("expert_ids")
    if declared is None or ids.shape == declared.shape:
        state["expert_ids"] = ids
    elif len(shape) == 3 and shape[1] == 1 and shape[0] > declared.shape[0]:
        # a serving step with a prefill chunk riding as single-query rows
        # past the slots': the record keeps the slots' rows, which come
        # first (a state leaf keeps its shape; any other layout leaves
        # the record as it was), and the chunk's rows beside it where the
        # graph keeps that record
        state["expert_ids"] = ids[:declared.shape[0]]
        past, n = weights.get("chunk_expert_ids"), declared.shape[0]
        if past is not None and shape[0] - n <= past.shape[0]:
            state["chunk_expert_ids"] = past.at[:shape[0] - n].set(ids[n:])
    if p.experts_held is not None:
        state["assignments_total"] = (weights.get("assignments_total", 0)
                                      + computed.astype(jnp.int32))
        state["dropped_total"] = (weights.get("dropped_total", 0)
                                  + (wanted - computed).astype(jnp.int32))
        if "slabs_run" in weights:
            # (a leaf of the layer as built: a forward at other rows than
            # the build's keeps the state's tree, 0 where it ran no slab)
            state["slabs_run"] = (
                _slabs_under(computed).astype(jnp.int32) if slabs
                else jnp.zeros((), jnp.int32))
    if p.aux_loss_coef:
        state["aux_loss"] = p.aux_loss_coef * load_balancing_loss(
            probs, group_sizes)
    return [y.astype(x.dtype).reshape(shape)], state


def _moe_mlp_flops(p: MoEMLPParams, in_shapes, out_shapes):
    d = in_shapes[0][-1]
    tokens = math.prod(in_shapes[0][:-1])
    return 2.0 * tokens * d * (p.num_experts
                               + 3 * p.num_experts_per_tok
                               * p.intermediate_size
                               + 3 * p.shared_intermediate_size)


def _moe_mlp_decode_layer(layer, ctx):
    # over the paged pool a chunk rides as rows past the slots
    # (serving/engine.py): the layer records what those rows chose too
    chunk = ctx.prefill_chunk if ctx.paged else layer.params.chunk_rows
    return OT.OP_MOE_MLP, replace(layer.params, chunk_rows=chunk), ()


register_op(OpDef(OT.OP_MOE_MLP, _moe_mlp_infer, _moe_mlp_forward,
                  _moe_mlp_weights, _moe_mlp_flops,
                  decode_layer=_moe_mlp_decode_layer))
