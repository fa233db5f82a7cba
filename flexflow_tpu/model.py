"""FFModel: the layer-builder API, compile pipeline, and training loop.

Reference: include/flexflow/model.h:326-958 + src/runtime/model.cc. The
builder surface (dense/conv2d/multihead_attention/..., model.h:336-553) is
reproduced method-for-method; `compile()` mirrors the reference pipeline
(model.cc:2803-3168):

  reference                               TPU-native
  ─────────────────────────────────────   ─────────────────────────────────
  create_operators_from_layers            Layer list → PCG OpNodes
  GRAPH_OPTIMIZE_TASK (Unity search)      search/ (DP+substitutions) or
                                          default data-parallel strategy
  deserialize optimal (graph, views)      per-node PartitionSpec assignment
  ParallelOp::create_input_partition      resharding constraints in executor
  apply_fusion (--fusion)                 XLA fusion (inherent)
  label tensor creation                   label PartitionSpec
  optimizer->init(); NCCL comms           optimizer slots; GSPMD collectives

`fit()` reproduces the cffi fit loop (flexflow_cffi.py:2058-2100): per
iteration {next_batch; forward; zero_gradients; backward; update} — fused
into one jitted step, with the granular forward()/backward()/update() API
also available for parity with C++ examples (transformer.cc:183-197).
"""

from __future__ import annotations

import math
import time
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from .config import FFConfig, FFIterationConfig
from .executor import Executor
from .fftype import (
    ActiMode,
    AggrMode,
    CompMode,
    DataType,
    LossType,
    MetricsType,
    OperatorType as OT,
    ParameterSyncType,
    PoolType,
    RegularizerMode,
)
from .initializer import Initializer, UniformInitializer
from .layer import Layer
from .loss import loss_value
from .machine import AXIS_DATA, AXIS_MODEL, AXIS_PIPE, MachineView, build_mesh
from .metrics import Metrics, PerfMetrics
from .optimizer import Optimizer, SGDOptimizer
from .ops import (
    AggregateParams,
    AggregateSpecParams,
    AttentionFrontEnd,
    BatchMatmulParams,
    BatchNormParams,
    CacheParams,
    CastParams,
    ConcatParams,
    Conv2DParams,
    DropoutParams,
    ElementBinaryParams,
    ElementUnaryParams,
    EmbeddingParams,
    GatherParams,
    GroupByParams,
    LayerNormParams,
    RMSNormParams,
    LinearParams,
    MultiHeadAttentionParams,
    Pool2DParams,
    ReduceParams,
    ReshapeParams,
    ReverseParams,
    SoftmaxParams,
    SplitParams,
    TopKParams,
    TransposeParams,
)
from .ops.base import get_op_def
from .pcg.graph import Graph, OpNode
from .tensor import ParallelTensor, ParallelTensorShape, Tensor


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        self.config = config or FFConfig()
        self.layers: list[Layer] = []
        self._input_tensors: list[Tensor] = []
        self.graph: Optional[Graph] = None
        self.mesh = None
        self.executor: Optional[Executor] = None
        self.optimizer: Optional[Optimizer] = None
        self.loss_type: Optional[LossType] = None
        self.metrics: Optional[Metrics] = None
        self.label_tensor: Optional[Tensor] = None
        self.iter_config = FFIterationConfig()
        self._params = None
        # another model's parameters this compile may take as they lie
        # (serving/decode_graph.build_decode_model sets it)
        self._shared_variables = None
        self._state = None
        self._opt_slots = None
        self._step = None
        self._counters = None
        self._rng = None
        self._current_batch = None
        self._cached_logits = None
        self._grads = None
        self._compiled = False
        self._strategy = None  # node name -> dict of spec overrides
        self._resilience = None  # ResilienceManager (resilience/manager.py)
        self._fault_hook = None  # step -> None; test-only failure injection
        self._epoch_base = 0  # absolute epochs completed across fit() calls
        self._auto_resumed = False  # auto-resume fires at most once
        self._resume_cursor = None  # (absolute epoch, batch) to resume at
        self._telemetry = None  # TelemetrySession (telemetry/session.py)
        self._diagnostics = None  # DiagnosticsManager (diagnostics/)
        # (UnitySearch, choice) of the winning plan — kept after compile so
        # diagnostics/explain can attribute the predicted makespan per op
        # and re-rank runner-up plans without re-running the search
        self._search_result = None
        self._predicted_step_s = None  # chosen plan's predicted makespan
        # warm start (warmstart/): where the applied plan came from
        # (search|cache|checkpoint|import|manual|default), the structural
        # plan fingerprint, the WarmStartManager when --warmstart-dir is
        # set, and the manifest-ready plan record checkpoints embed so
        # --auto-resume can restore the plan without searching
        self._plan_source = "none"
        self._plan_fingerprint = None
        self._warmstart = None
        self._plan_record = None
        # weight-update sharding decision (unity.choose_update_sharding):
        # whether fp32 masters + optimizer slots run ZeRO-sharded 1/dp
        # with the grad sync as an overlappable reduce-scatter; recorded
        # in checkpoint manifests + strategy_report.json
        self._update_sharding = None
        # ffcheck result (analysis.AnalysisResult) of the compile gate —
        # strategy_report.json surfaces it as its `analysis` section
        self._analysis = None
        # SPMD fingerprint-barrier verdict ({status, fingerprint} or
        # None when --spmd-barrier is off) — recorded at compile,
        # surfaced in the compile metrics record + strategy_report.json
        self._spmd_barrier = None
        # elastic re-planning (elastic/): the controller (--elastic /
        # enable_elastic) and its decision records — every replan attempt
        # (migrated/declined/dry_run/failed, both sides of the payoff
        # inequality) appends here and rides strategy_report.json's
        # `elastic` section
        self._elastic = None
        self._elastic_decisions = []

    # ================================================== tensor creation

    def create_tensor(
        self,
        dims: Sequence[int],
        dtype: DataType = DataType.DT_FLOAT,
        create_grad: bool = True,
        name: str = "",
    ) -> Tensor:
        t = Tensor(tuple(dims), dtype, name=name or f"input_{len(self._input_tensors)}",
                   create_gradients=create_grad)
        self._input_tensors.append(t)
        return t

    def create_constant(self, dims, value: float, data_type: DataType) -> Tensor:
        t = self.create_tensor(dims, data_type, create_grad=False,
                               name=f"const_{len(self._input_tensors)}")
        t.constant_value = value
        return t

    # ================================================== internal builder

    def _add_layer(
        self,
        op_type: OT,
        params,
        inputs: list[Tensor],
        name: str = "",
        initializers: Optional[dict] = None,
        data_type: DataType = DataType.DT_FLOAT,
        shared_op=None,
    ) -> Layer:
        layer = Layer(op_type, params, inputs, name=name, data_type=data_type,
                      initializers=initializers)
        if shared_op is not None:
            # tied weights (reference dense/embedding shared_op, model.h):
            # this layer reads the shared layer's parameters; autodiff sums
            # the gradients of every use into the one parameter set
            src = getattr(shared_op, "owner_layer", shared_op)
            if not isinstance(src, Layer):
                raise TypeError(
                    f"shared_op must be a Layer or one of its output "
                    f"tensors, got {type(shared_op).__name__}")
            layer.shared_layer_guid = src.layer_guid
        op_def = get_op_def(op_type)
        in_shapes = [t.dims for t in inputs]
        if shared_op is not None:
            # a tie is between weights, whatever the two operators are (a
            # head reads an embedding's table): every weight of this layer
            # is one of the source's, by name and shape
            theirs = {ws.name: ws.shape for ws in get_op_def(
                src.op_type).weights(src.params,
                                     [t.dims for t in src.inputs])}
            for ws in op_def.weights(params, in_shapes):
                if theirs.get(ws.name) != ws.shape:
                    raise ValueError(
                        f"shared_op ties {op_type.name} layer "
                        f"{layer.name!r} to {src.op_type.name} layer "
                        f"{src.name!r}, whose weight {ws.name!r} is "
                        f"{theirs.get(ws.name)}, not {ws.shape}")
        out_shapes = op_def.infer_shapes(params, in_shapes)
        for i, s in enumerate(out_shapes):
            layer.outputs.append(
                Tensor(s, data_type, owner_layer=layer, owner_idx=i,
                       name=f"{layer.name}_out{i}")
            )
        self.layers.append(layer)
        return layer

    def _unary(self, op_type: OT, x: Tensor, name: str = "", inplace: bool = True,
               scalar: float = 0.0) -> Tensor:
        p = ElementUnaryParams(op_type, inplace, scalar)
        return self._add_layer(op_type, p, [x], name, data_type=x.dtype).outputs[0]

    def _binary(self, op_type: OT, x: Tensor, y: Tensor, name: str = "",
                inplace_a: bool = False) -> Tensor:
        p = ElementBinaryParams(op_type, inplace_a)
        return self._add_layer(op_type, p, [x, y], name, data_type=x.dtype).outputs[0]

    # ================================================== ops (model.h:336-553)

    def exp(self, x, name=""):
        return self._unary(OT.OP_EXP, x, name)

    def sin(self, x, name=""):
        return self._unary(OT.OP_SIN, x, name)

    def cos(self, x, name=""):
        return self._unary(OT.OP_COS, x, name)

    def add(self, x, y, inplace_a=False, name=""):
        return self._binary(OT.OP_EW_ADD, x, y, name, inplace_a)

    def subtract(self, x, y, inplace_a=False, name=""):
        return self._binary(OT.OP_EW_SUB, x, y, name, inplace_a)

    def multiply(self, x, y, inplace_a=False, name=""):
        return self._binary(OT.OP_EW_MUL, x, y, name, inplace_a)

    def divide(self, x, y, inplace_a=False, name=""):
        return self._binary(OT.OP_EW_DIV, x, y, name, inplace_a)

    def max(self, x, y, inplace_a=False, name=""):
        return self._binary(OT.OP_EW_MAX, x, y, name, inplace_a)

    def min(self, x, y, inplace_a=False, name=""):
        return self._binary(OT.OP_EW_MIN, x, y, name, inplace_a)

    def rsqrt(self, x, inplace=True, name=""):
        return self._unary(OT.OP_RSQRT, x, name, inplace)

    def pow(self, x, exponent: float, inplace=True, name=""):
        return self._unary(OT.OP_POW, x, name, inplace, scalar=exponent)

    def scalar_multiply(self, x, scalar: float, inplace=True, name=""):
        return self._unary(OT.OP_SCALAR_MULTIPLY, x, name, inplace, scalar)

    def scalar_add(self, x, scalar: float, inplace=True, name=""):
        return self._unary(OT.OP_SCALAR_ADD, x, name, inplace, scalar)

    def scalar_sub(self, x, scalar: float, inplace=True, name=""):
        return self._unary(OT.OP_SCALAR_SUB, x, name, inplace, scalar)

    def scalar_true_divide(self, x, scalar: float, inplace=True, name=""):
        return self._unary(OT.OP_SCALAR_TRUE_DIV, x, name, inplace, scalar)

    def relu(self, x, inplace=True, name=""):
        return self._unary(OT.OP_RELU, x, name, inplace)

    def identity(self, x, name=""):
        return self._unary(OT.OP_IDENTITY, x, name)

    def gelu(self, x, name=""):
        return self._unary(OT.OP_GELU, x, name)

    def sigmoid(self, x, name=""):
        return self._unary(OT.OP_SIGMOID, x, name)

    def tanh(self, x, name=""):
        return self._unary(OT.OP_TANH, x, name)

    def elu(self, x, inplace=True, name=""):
        return self._unary(OT.OP_ELU, x, name, inplace)

    def dense(
        self,
        input: Tensor,
        out_dim: int,
        activation: ActiMode = ActiMode.AC_MODE_NONE,
        use_bias: bool = True,
        data_type: DataType = DataType.DT_FLOAT,
        shared_op=None,
        kernel_initializer: Optional[Initializer] = None,
        bias_initializer: Optional[Initializer] = None,
        kernel_regularizer: RegularizerMode = RegularizerMode.REG_MODE_NONE,
        name: str = "",
        float32_out: bool = False,
    ) -> Tensor:
        # tied to an embedding, the layer reads the table as it lies,
        # (out_dim, in_dim): ops/core.LinearParams.kernel_transposed
        tied = getattr(shared_op, "owner_layer", shared_op)
        p = LinearParams(out_dim, use_bias, ActiMode(activation), data_type,
                         kernel_transposed=(
                             getattr(tied, "op_type", None)
                             == OT.OP_EMBEDDING),
                         float32_out=float32_out)
        inits = {}
        if kernel_initializer is not None:
            inits["kernel"] = kernel_initializer
        if bias_initializer is not None:
            inits["bias"] = bias_initializer
        return self._add_layer(OT.OP_LINEAR, p, [input], name, inits,
                               data_type, shared_op=shared_op).outputs[0]

    def conv2d(
        self,
        input: Tensor,
        out_channels: int,
        kernel_h: int,
        kernel_w: int,
        stride_h: int,
        stride_w: int,
        padding_h: int,
        padding_w: int,
        activation: ActiMode = ActiMode.AC_MODE_NONE,
        groups: int = 1,
        use_bias: bool = True,
        shared_op=None,
        kernel_initializer: Optional[Initializer] = None,
        bias_initializer: Optional[Initializer] = None,
        name: str = "",
    ) -> Tensor:
        p = Conv2DParams(out_channels, kernel_h, kernel_w, stride_h, stride_w,
                         padding_h, padding_w, groups, use_bias, ActiMode(activation))
        inits = {}
        if kernel_initializer is not None:
            inits["kernel"] = kernel_initializer
        if bias_initializer is not None:
            inits["bias"] = bias_initializer
        return self._add_layer(OT.OP_CONV2D, p, [input], name, inits).outputs[0]

    def pool2d(
        self,
        input: Tensor,
        kernel_h: int,
        kernel_w: int,
        stride_h: int,
        stride_w: int,
        padding_h: int,
        padding_w: int,
        pool_type: PoolType = PoolType.POOL_MAX,
        activation: ActiMode = ActiMode.AC_MODE_NONE,
        name: str = "",
    ) -> Tensor:
        p = Pool2DParams(kernel_h, kernel_w, stride_h, stride_w, padding_h,
                         padding_w, PoolType(pool_type), ActiMode(activation))
        return self._add_layer(OT.OP_POOL2D, p, [input], name).outputs[0]

    def batch_norm(self, input: Tensor, relu: bool = True, name: str = "") -> Tensor:
        p = BatchNormParams(relu)
        return self._add_layer(OT.OP_BATCHNORM, p, [input], name).outputs[0]

    def layer_norm(
        self,
        input: Tensor,
        axes: Sequence[int],
        elementwise_affine: bool = True,
        eps: float = 1e-5,
        name: str = "",
        bias: bool = True,
    ) -> Tensor:
        """`bias` False: the affine is a learned scale alone."""
        p = LayerNormParams(tuple(axes), elementwise_affine, eps, bias)
        return self._add_layer(OT.OP_LAYERNORM, p, [input], name,
                               data_type=input.dtype).outputs[0]

    def rms_norm(self, input: Tensor, eps: float = 1e-5,
                 name: str = "", unit_offset: bool = False,
                 narrow_out: bool = False) -> Tensor:
        """RMSNorm over the last dim with a learned scale, no bias;
        `unit_offset`: the scale is 1 + g with g learned from zeros;
        `narrow_out`: the output in the compute dtype where the input is
        wider (ops/core.RMSNormParams)."""
        return self._add_layer(
            OT.OP_RMSNORM, RMSNormParams(eps, unit_offset, narrow_out),
            [input], name, data_type=input.dtype).outputs[0]

    def batch_matmul(
        self,
        A: Tensor,
        B: Tensor,
        a_seq_length_dim: int = -1,
        b_seq_length_dim: int = -1,
        name: str = "",
    ) -> Tensor:
        p = BatchMatmulParams(a_seq_length_dim, b_seq_length_dim)
        return self._add_layer(OT.OP_BATCHMATMUL, p, [A, B], name,
                               data_type=A.dtype).outputs[0]

    def dropout(self, input: Tensor, rate: float, seed: int = 0, name: str = "") -> Tensor:
        p = DropoutParams(rate, seed)
        return self._add_layer(OT.OP_DROPOUT, p, [input], name,
                               data_type=input.dtype).outputs[0]

    def embedding(
        self,
        input: Tensor,
        num_entries: int,
        out_dim: int,
        aggr: AggrMode = AggrMode.AGGR_MODE_NONE,
        dtype: DataType = DataType.DT_FLOAT,
        shared_op=None,
        kernel_initializer: Optional[Initializer] = None,
        name: str = "",
        float32_out: bool = False,
    ) -> Tensor:
        p = EmbeddingParams(num_entries, out_dim, AggrMode(aggr), dtype,
                            float32_out)
        inits = {"kernel": kernel_initializer} if kernel_initializer else {}
        return self._add_layer(OT.OP_EMBEDDING, p, [input], name, inits,
                               dtype, shared_op=shared_op).outputs[0]

    def gather(self, input: Tensor, index: Tensor, dim: int = 0, name: str = "") -> Tensor:
        p = GatherParams(dim)
        return self._add_layer(OT.OP_GATHER, p, [input, index], name,
                               data_type=input.dtype).outputs[0]

    def multihead_attention(
        self,
        query: Tensor,
        key: Tensor,
        value: Tensor,
        embed_dim: int,
        num_heads: int,
        kdim: int = 0,
        vdim: int = 0,
        dropout: float = 0.0,
        bias: bool = True,
        add_bias_kv: bool = False,
        add_zero_attn: bool = False,
        kernel_initializer: Optional[Initializer] = None,
        causal: bool = False,
        impl: str = "xla",
        name: str = "",
        positions: Optional[Tensor] = None,
        rope_theta: float = 0.0,
        qk_norm=False,
        qk_norm_eps: float = 1e-5,
        num_kv_heads: int = 0,
        head_dim: int = 0,
        output_gate: bool = False,
        index=None,
        v_head_dim: int = 0,
        rope_dim: int = 0,
        window: int = 0,
        sink: bool = False,
        value_scale: float = 1.0,
        sink_initializer: Optional[Initializer] = None,
        rope_interleaved: bool = False,
        summary_chunk: int = 0,
    ) -> Tensor:
        """`rope_theta` > 0 rotates q and k by the (batch, seq) int
        `positions`; `qk_norm` RMS-normalises the q and k projections
        ("projection" or True: whole; "head": each head);
        `num_kv_heads` < num_heads groups the query heads over fewer
        keys and values; `head_dim` is a head's size where it is not
        embed_dim / num_heads; `output_gate` multiplies the core's output
        by sigmoid(query @ wg); `index` (an ops.attention.Indexer) has
        each row attend a learned top-k selection of its past;
        `v_head_dim` is a value head's size where it is not the key
        head's; `rope_dim` rotates only the first lanes of a head;
        `window` keeps a row's nearest keys; `sink` adds a learned bias a
        head to the softmax's denominator (drawn by `sink_initializer`);
        `value_scale` multiplies the values; `rope_interleaved` rotates
        lanes 2j and 2j + 1 as a pair where the default pairs j and
        j + d / 2; `summary_chunk` > 0 aligns the `window` and has a row
        attend, beside it, one learned summary of every `summary_chunk`
        keys of the windows before (`phi` and `mu_k`, a vector a head, drawn
        uniformly within head_dim^-0.5) (ops/attention.AttentionFrontEnd)."""
        if impl not in ("xla", "flash", "ring"):
            raise ValueError(
                f"multihead_attention impl must be xla|flash|ring, got {impl!r}"
            )
        if bool(rope_theta) != (positions is not None):
            raise ValueError(
                "multihead_attention: rope_theta and positions go together")
        front = AttentionFrontEnd(embed_dim, num_heads, bias, rope_theta,
                                  qk_norm, qk_norm_eps, num_kv_heads,
                                  head_dim, output_gate, index, v_head_dim,
                                  rope_dim, window, sink, value_scale,
                                  rope_interleaved, summary_chunk)
        p = MultiHeadAttentionParams(front, kdim, vdim, dropout, add_bias_kv,
                                     add_zero_attn, causal, impl)
        inits = ({} if kernel_initializer is None
                 else dict.fromkeys(front.matrices, kernel_initializer))
        if sink and sink_initializer is not None:
            inits["sink"] = sink_initializer
        if summary_chunk:
            r = front.head_dim ** -0.5
            inits.update(dict.fromkeys(
                ("phi", "mu_k"), UniformInitializer(min_val=-r, max_val=r)))
        inputs = [query, key, value]
        if positions is not None:
            inputs.append(positions)
        return self._add_layer(OT.OP_MULTIHEAD_ATTENTION, p, inputs,
                               name, inits, query.dtype).outputs[0]

    def latent_attention(self, input: Tensor, positions: Tensor, front,
                         kernel_initializer: Optional[Initializer] = None,
                         name: str = "") -> Tensor:
        """Causal latent self-attention on (batch, seq, hidden), under
        the lightning indexer's top-k selection where `front` has one
        (`front.index`), over the whole past where not; `front` is an
        ops.latent_attention.LatentFrontEnd (ops/latent_attention.py,
        which this call imports: no other graph pays for it)."""
        from .ops.latent_attention import LatentAttentionParams

        inits = ({} if kernel_initializer is None
                 else dict.fromkeys(front.kernels, kernel_initializer))
        return self._add_layer(
            OT.OP_LATENT_ATTENTION, LatentAttentionParams(front),
            [input, positions], name, inits, input.dtype).outputs[0]

    def gated_delta_attention(self, input: Tensor, front,
                              kernel_initializer: Optional[Initializer] = None,
                              name: str = "") -> Tensor:
        """Causal gated delta-rule linear attention on (batch, seq,
        hidden); `front` is an ops.delta_attention.DeltaFrontEnd
        (ops/delta_attention.py, which this call imports: no other graph
        pays for it)."""
        from .ops.delta_attention import GatedDeltaAttentionParams

        return self._add_layer(
            OT.OP_GATED_DELTA_ATTENTION, GatedDeltaAttentionParams(front),
            [input], name, front.initializers(kernel_initializer),
            input.dtype).outputs[0]

    def mamba(self, input: Tensor, front,
              kernel_initializer: Optional[Initializer] = None,
              name: str = "") -> Tensor:
        """A causal selective state-space (Mamba-1) layer on (batch, seq,
        hidden); `front` is an ops.ssm.MambaFrontEnd (ops/ssm.py, which
        this call imports: no other graph pays for it)."""
        from .ops.ssm import SelectiveSSMParams

        return self._add_layer(
            OT.OP_SELECTIVE_SSM, SelectiveSSMParams(front), [input], name,
            front.initializers(kernel_initializer), input.dtype).outputs[0]

    def short_conv(self, input: Tensor, front,
                   kernel_initializer: Optional[Initializer] = None,
                   name: str = "") -> Tensor:
        """A gated short causal convolution (LFM2's conv mixer) on (batch,
        seq, hidden); `front` is an ops.short_conv.ShortConvFrontEnd
        (ops/short_conv.py, which this call imports: no other graph pays
        for it)."""
        from .ops.short_conv import ShortConvParams

        return self._add_layer(
            OT.OP_SHORT_CONV, ShortConvParams(front), [input], name,
            front.initializers(kernel_initializer), input.dtype).outputs[0]

    def concat(self, tensors: Sequence[Tensor], axis: int, name: str = "") -> Tensor:
        p = ConcatParams(axis, len(tensors))
        return self._add_layer(OT.OP_CONCAT, p, list(tensors), name,
                               data_type=tensors[0].dtype).outputs[0]

    def split(self, input: Tensor, sizes: Union[int, Sequence[int]], axis: int,
              name: str = "") -> list[Tensor]:
        if isinstance(sizes, int):
            # torch.split-style: n equal chunks
            total = input.dims[axis % len(input.dims)]
            if total % sizes != 0:
                raise ValueError(f"cannot split dim {total} into {sizes} equal parts")
            sizes = [total // sizes] * sizes
        p = SplitParams(tuple(sizes), axis)
        return self._add_layer(OT.OP_SPLIT, p, [input], name,
                               data_type=input.dtype).outputs

    def flat(self, input: Tensor, name: str = "") -> Tensor:
        return self._add_layer(OT.OP_FLAT, None, [input], name).outputs[0]

    def softmax(self, input: Tensor, dim: int = -1, name: str = "") -> Tensor:
        p = SoftmaxParams(dim)
        return self._add_layer(OT.OP_SOFTMAX, p, [input], name,
                               data_type=input.dtype).outputs[0]

    def transpose(self, input: Tensor, perm: Sequence[int], name: str = "") -> Tensor:
        p = TransposeParams(tuple(perm))
        return self._add_layer(OT.OP_TRANSPOSE, p, [input], name,
                               data_type=input.dtype).outputs[0]

    def reduce_sum(self, input: Tensor, axes: Sequence[int], keepdims: bool = False,
                   name: str = "") -> Tensor:
        p = ReduceParams(OT.OP_REDUCE_SUM, tuple(axes), keepdims)
        return self._add_layer(OT.OP_REDUCE_SUM, p, [input], name,
                               data_type=input.dtype).outputs[0]

    def mean(self, input: Tensor, dims: Sequence[int], keepdims: bool = False,
             name: str = "") -> Tensor:
        p = ReduceParams(OT.OP_MEAN, tuple(dims), keepdims)
        return self._add_layer(OT.OP_MEAN, p, [input], name,
                               data_type=input.dtype).outputs[0]

    def reshape(self, input: Tensor, shape: Sequence[int], name: str = "") -> Tensor:
        p = ReshapeParams(tuple(shape))
        return self._add_layer(OT.OP_RESHAPE, p, [input], name,
                               data_type=input.dtype).outputs[0]

    def reverse(self, input: Tensor, axis: int, name: str = "") -> Tensor:
        p = ReverseParams(axis)
        return self._add_layer(OT.OP_REVERSE, p, [input], name,
                               data_type=input.dtype).outputs[0]

    def top_k(self, input: Tensor, k: int, sorted: bool = True,
              name: str = "") -> tuple[Tensor, Tensor]:
        p = TopKParams(k, sorted)
        outs = self._add_layer(OT.OP_TOPK, p, [input], name,
                               data_type=input.dtype).outputs
        return outs[0], outs[1]

    def cast(self, input: Tensor, dtype: DataType, name: str = "") -> Tensor:
        p = CastParams(DataType(dtype))
        return self._add_layer(OT.OP_CAST, p, [input], name,
                               data_type=DataType(dtype)).outputs[0]

    # ------------------------------------------------ MoE family

    def group_by(self, data: Tensor, assign: Tensor, n: int, alpha: float,
                 name: str = "") -> list[Tensor]:
        p = GroupByParams(n, alpha)
        return self._add_layer(OT.OP_GROUP_BY, p, [data, assign], name,
                               data_type=data.dtype).outputs

    def aggregate(self, inputs: Sequence[Tensor], n: int, lambda_bal: float = 0.0,
                  name: str = "") -> Tensor:
        p = AggregateParams(n, lambda_bal)
        return self._add_layer(OT.OP_AGGREGATE, p, list(inputs), name,
                               data_type=inputs[4].dtype).outputs[0]

    def aggregate_spec(self, inputs: Sequence[Tensor], n: int,
                       lambda_bal: float = 0.0, name: str = "") -> Tensor:
        p = AggregateSpecParams(n, lambda_bal)
        return self._add_layer(OT.OP_AGG_SPEC, p, list(inputs), name,
                               data_type=inputs[4].dtype).outputs[0]

    def cache(self, input: Tensor, num_batches: int, name: str = "") -> Tensor:
        p = CacheParams(num_batches, input.dtype)
        return self._add_layer(OT.OP_CACHE, p, [input], name,
                               data_type=input.dtype).outputs[0]

    def experts(
        self,
        input: Tensor,
        gate_values: Tensor,
        gate_assign: Tensor,
        num_experts: int,
        hidden_size: int,
        alpha: float = 1.0,
        lambda_bal: float = 0.0,
        use_bias: bool = True,
        activation: str = "relu",
        name: str = "",
    ) -> Tensor:
        """Fused stacked-experts op (TPU-native MoE fast path; shard its
        kernel dim 0 over the expert mesh axis for expert parallelism)."""
        from .ops import ExpertsParams

        p = ExpertsParams(num_experts, hidden_size, alpha, lambda_bal,
                          use_bias, activation)
        return self._add_layer(OT.OP_EXPERTS, p,
                               [input, gate_values, gate_assign], name,
                               data_type=input.dtype).outputs[0]

    def moe_mlp(
        self,
        input: Tensor,
        num_experts: int,
        num_experts_per_tok: int,
        intermediate_size: int,
        aux_loss_coef: float = 0.0,
        name: str = "",
        kernel_initializer: Optional[Initializer] = None,
        router_bias_initializer: Optional[Initializer] = None,
        **routing,
    ) -> Tensor:
        """The token-routed expert layer of an LM block on (.., hidden):
        router, the k largest of a softmax over all experts (not
        renormalised), SiLU-gated
        experts, gate-weighted sum; dropless (ops/moe.py). `routing`:
        the further fields of MoEMLPParams (DeepSeek-V3's sigmoid
        group-limited router, a shared expert, the experts held here).
        `router_bias_initializer` draws the sigmoid router's correction
        bias where the layer's own draw, N(0, 0.02), is not wanted: beside
        scores a few thousandths apart that draw decides which experts are
        loaded."""
        from .ops import MoEMLPParams

        p = MoEMLPParams(num_experts, num_experts_per_tok, intermediate_size,
                         aux_loss_coef, **routing)
        inits = ({} if kernel_initializer is None else dict.fromkeys(
            ("router", "gate", "up", "down", "shared_gate", "shared_up",
             "shared_down"), kernel_initializer))
        if router_bias_initializer is not None:
            inits["router_bias"] = router_bias_initializer
        return self._add_layer(OT.OP_MOE_MLP, p, [input], name, inits,
                               data_type=input.dtype).outputs[0]

    def moe(
        self,
        input: Tensor,
        num_exp: int,
        num_select: int,
        expert_hidden_size: int,
        alpha: float,
        lambda_bal: float,
        fused: bool = False,
    ) -> Tensor:
        """MoE composite (reference src/ops/moe.cc:20-50): gate dense → topk →
        group_by → per-expert dense → aggregate. With fused=True the
        group_by/expert/aggregate trio is the single stacked Experts op."""
        gate_preds = self.dense(input, num_exp, ActiMode.AC_MODE_RELU)
        gate_probs = self.softmax(gate_preds)
        topk_values, topk_assign = self.top_k(gate_probs, num_select)
        if fused:
            return self.experts(input, topk_values, topk_assign, num_exp,
                                expert_hidden_size, alpha, lambda_bal)
        expert_inputs = self.group_by(input, topk_assign, num_exp, alpha)
        expert_outputs = []
        for ei in expert_inputs:
            h = self.dense(ei, expert_hidden_size, ActiMode.AC_MODE_RELU)
            expert_outputs.append(h)
        agg_inputs = [topk_values, topk_assign, topk_assign, gate_probs] + expert_outputs
        return self.aggregate(agg_inputs, num_exp, lambda_bal)

    def pipeline_blocks(
        self,
        input: Tensor,
        num_layers: int,
        num_heads: int,
        mlp_ratio: int = 4,
        num_microbatches: int = 0,
        causal: bool = True,
        attention_impl: str = "xla",
        name: str = "",
    ) -> Tensor:
        """L stacked pre-LN transformer blocks as one op whose layer dim
        shards over the `pipe` mesh axis — working pipeline parallelism
        (ppermute fill/drain schedule, parallel/pipeline.py), exceeding the
        reference's enum-only OP_PIPELINE (ffconst.h:159)."""
        from .ops import PipelineBlocksParams

        p = PipelineBlocksParams(num_layers, num_heads, mlp_ratio,
                                 num_microbatches, causal, attention_impl)
        return self._add_layer(OT.OP_PIPE_BLOCKS, p, [input], name,
                               data_type=input.dtype).outputs[0]

    # ------------------------------------------------ parallel ops
    # (reference src/parallel_ops/*; inserted explicitly or by Unity search)

    def repartition(self, input: Tensor, dim: int, degree: int,
                    name: str = "") -> Tensor:
        from .parallel import RepartitionParams

        p = RepartitionParams(dim, degree)
        return self._add_layer(OT.OP_REPARTITION, p, [input], name,
                               data_type=input.dtype).outputs[0]

    def combine(self, input: Tensor, dim: int, degree: int,
                name: str = "") -> Tensor:
        from .parallel import CombineParams

        p = CombineParams(dim, degree)
        return self._add_layer(OT.OP_COMBINE, p, [input], name,
                               data_type=input.dtype).outputs[0]

    def replicate(self, input: Tensor, degree: int, name: str = "") -> Tensor:
        from .parallel import ReplicateParams

        p = ReplicateParams(degree)
        return self._add_layer(OT.OP_REPLICATE, p, [input], name,
                               data_type=input.dtype).outputs[0]

    def reduction(self, input: Tensor, degree: int, name: str = "") -> Tensor:
        from .parallel import ReductionParams

        p = ReductionParams(degree)
        return self._add_layer(OT.OP_REDUCTION, p, [input], name,
                               data_type=input.dtype).outputs[0]

    # ================================================== strategy

    def set_strategy(self, strategy):
        """Install a parallelization strategy (a parallel.Strategy or raw
        override dict) applied on top of the data-parallel default at
        compile. The `--import-strategy` analog (model.cc:3599-3608)."""
        self._strategy = getattr(strategy, "overrides", strategy)

    # ================================================== compile

    def compile(
        self,
        optimizer: Optional[Optimizer] = None,
        loss_type: LossType = LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics: Sequence[MetricsType] = (),
        comp_mode: CompMode = CompMode.COMP_MODE_TRAINING,
    ):
        """Lower layers → PCG, choose a parallelization strategy, build the
        executor (pipeline parity: model.cc:2803-3168)."""
        from . import telemetry

        if self._telemetry is None and self.config.telemetry_dir:
            self.enable_telemetry(self.config.telemetry_dir)
        tel = self._telemetry
        try:
            if tel is not None:
                # the global sink is active only while ITS model is inside
                # an instrumented operation — another model compiled in the
                # same process must not write into this model's artifacts
                telemetry.activate(tel)
                # manifest FIRST — before any search events the body emits
                tel.write_manifest(self)
            t_compile0 = time.perf_counter()
            if tel is not None:
                # time-to-first-step accounting: the fit summary reports
                # first-step completion relative to this instant
                tel.note_compile_start(t_compile0)
            with telemetry.phase(
                    "compile",
                    comp_mode=("inference"
                               if comp_mode == CompMode.COMP_MODE_INFERENCE
                               else "training")):
                self._compile_impl(optimizer, loss_type, metrics, comp_mode)
            if tel is not None:
                # the COMPILED outcome (a mesh-shape search may have
                # replaced the configured mesh; strategy_nodes = ops
                # deviating from pure data parallel)
                tel.recorder.record(
                    "compile",
                    duration_s=time.perf_counter() - t_compile0,
                    num_nodes=len(self.graph.topo_order()),
                    mesh_axes={k: int(v)
                               for k, v in self.mesh.shape.items()},
                    strategy_nodes=sorted(self._strategy)
                    if self._strategy else [],
                    plan_source=self._plan_source,
                    plan_fingerprint=self._plan_fingerprint,
                    # ffsan state: whether this compile's step carries
                    # the numerics probes, and the fingerprint-barrier
                    # verdict (run_doctor --check gates on both)
                    sanitize_numerics=bool(
                        self.config.sanitize_numerics),
                    spmd_barrier=(self._spmd_barrier or {}).get(
                        "status", "off"),
                )
                diag = self._maybe_enable_diagnostics()
                if diag is not None:
                    # strategy explain + drift-monitor arming, inside the
                    # active-session window so its spans/events land here
                    diag.on_compile()
        finally:
            if tel is not None:
                # flush in the finally: a compile/search crash is exactly
                # when the buffered spans are wanted on disk
                tel.flush()
                telemetry.deactivate(tel)

    def _compile_impl(self, optimizer, loss_type, metrics, comp_mode):
        from . import telemetry

        self.optimizer = optimizer or SGDOptimizer(lr=self.config.learning_rate)
        self.loss_type = LossType(loss_type)
        self.metrics = Metrics.from_list(self.loss_type, list(metrics))
        # the raw metrics argument, kept so an elastic replan can drive
        # this same compile pipeline again with identical arguments
        self._metrics_arg = tuple(metrics)
        self.config.computation_mode = comp_mode

        # --- create_operators_from_layers
        with telemetry.phase("compile.graph"):
            g = Graph()
            tensor_to_out = {}  # Tensor guid -> (OpNode, out idx)
            for t in self._input_tensors:
                node = OpNode(OT.OP_INPUT, None, name=t.name)
                shape = ParallelTensorShape.from_shape(t.dims, t.dtype)
                pt = ParallelTensor(shape, name=t.name)
                node.outputs = [pt]
                g.add_node(node)
                tensor_to_out[t.tensor_guid] = (node, 0)

            guid_to_node: dict[int, OpNode] = {}
            self._weight_alias: dict[str, str] = {}  # tied node name -> owner
            for layer in self.layers:
                node = OpNode(layer.op_type, layer.params, name=layer.name,
                              layer_guid=layer.layer_guid,
                              initializers=layer.initializers)
                g.add_node(node)
                guid_to_node[layer.layer_guid] = node
                for dst_idx, t_in in enumerate(layer.inputs):
                    src_node, src_idx = tensor_to_out[t_in.tensor_guid]
                    g.add_edge(src_node, node, src_idx, dst_idx)
                    node.inputs.append(src_node.outputs[src_idx])
                in_shapes = [t.dims for t in layer.inputs]
                node.weight_specs = node.op_def.weights(
                    layer.params, in_shapes)
                if layer.shared_layer_guid >= 0:
                    # tied weights: this node reads the source node's
                    # parameter set; the executor creates no variables for
                    # it and autodiff sums gradients across all uses
                    # (reference shared_op)
                    src = guid_to_node.get(layer.shared_layer_guid)
                    if src is None:
                        raise ValueError(
                            f"{layer.name}: shared_op layer must be built "
                            f"before the layer sharing it")
                    # (`_add_layer` held each of its weights to the source's,
                    # by name and shape, when the tie was made)
                    node.weight_source = src.name
                    self._weight_alias[node.name] = src.name
                for i, t_out in enumerate(layer.outputs):
                    shape = ParallelTensorShape.from_shape(
                        t_out.dims, t_out.dtype)
                    pt = ParallelTensor(shape, name=t_out.name)
                    pt.owner_op = node
                    pt.owner_idx = i
                    node.outputs.append(pt)
                    tensor_to_out[t_out.tensor_guid] = (node, i)
            self.graph = g

        # --- mesh + strategy
        self.mesh = self._build_mesh(self.config.mesh_shape())
        used_substitutions = False
        search_cost_model = None  # set by the search branch (calibrated)
        if self.config.warmstart_dir and self._warmstart is None:
            # attach the warm-start subsystem early: pointing JAX's
            # persistent compilation cache under the warm-start dir must
            # precede the first jit of this compile (executor build,
            # init_variables) so those executables land in / load from it
            from .warmstart import WarmStartManager

            self._warmstart = WarmStartManager(
                self, self.config.warmstart_dir)
        if self._strategy is not None:
            self._plan_source = "manual"  # set_strategy()
        elif self.config.import_strategy_file:
            # replay a previously searched/exported plan instead of
            # re-searching (--import-strategy, model.cc:3599-3608) —
            # validated against THIS graph and mesh first, so a stale
            # plan fails loudly instead of silently degrading node by
            # node to data parallel
            from .parallel.strategies import Strategy

            imported = Strategy.load(self.config.import_strategy_file)
            try:
                imported.validate(g, self.mesh)
            except ValueError as e:
                raise ValueError(
                    f"--import-strategy "
                    f"{self.config.import_strategy_file}: {e}") from e
            self._strategy = imported.overrides
            self._plan_source = "import"
        n_devices = 1
        for v in self.mesh.shape.values():
            n_devices *= v
        do_search = (
            self._strategy is None
            and not self.config.only_data_parallel
            and n_devices > 1
            and (
                self.config.search_budget > 0
                or self.config.enable_parameter_parallel
                or self.config.enable_attribute_parallel
                or self.config.enable_substitutions
                or bool(self.config.substitution_json_path)
            )
        )
        if do_search:
            # ONE joint Unity search (GRAPH_OPTIMIZE_TASK analog): GraphXfer
            # rewrites and per-node placements optimized together — every
            # rewritten candidate is costed by the placement DP
            # (substitution.cc:2229-2311 + graph.cc:1742-1843). The winning
            # graph (possibly rewritten, with explicit parallel ops) replaces
            # the layer-built one and arrives with every tensor's mesh axes +
            # weight shardings materialized; the searched placements are also
            # kept as a Strategy for --export-strategy.
            from .search.cost_model import CostModel
            from .search.joint import joint_graph_optimize
            from .search.machine_model import (
                machine_model_for_mesh,
                machine_model_from_file,
            )

            machine = (
                machine_model_from_file(
                    self.config.machine_model_file, self.mesh)
                if self.config.machine_model_file
                else machine_model_for_mesh(
                    self.mesh, num_hosts=self.config.num_nodes)
            )
            cost_model = CostModel(
                machine, opt_slots=self.optimizer.num_slots)
            if (self.config.weight_update_sharding
                    and self.config.computation_mode
                    == CompMode.COMP_MODE_TRAINING):
                # forced sharded update: the placement search itself must
                # price sync as the overlappable RS+AG + 1/dp state (auto
                # mode decides after the placements are materialized —
                # choose_update_sharding below); a forced stage 3 also
                # prices weights 1/shards-at-rest + the just-in-time
                # gather pair. Inference compiles — a serving replay
                # inherits the trainer's config — have no grad sync or
                # optimizer state to price.
                cost_model.update_sharding = True
                cost_model.param_gather = (
                    self.config.weight_update_stage == 3)
                cost_model.overlap_update = bool(
                    self.config.overlap_collectives)
            search_cost_model = cost_model

            _calibrated = [False]

            def _calibrate():
                # measure the dominant ops on the local chip so the search
                # costs candidates from measurements, not the mfu guess
                # (Simulator::measure_operator_cost, model.cu:38-75).
                # Idempotent: the warm-start fingerprinting runs it before
                # the search branches do, and it must not emit two spans.
                if _calibrated[0] or self.config.search_calibrate <= 0:
                    return
                _calibrated[0] = True
                with telemetry.phase("compile.calibrate"):
                    cost_model.calibrate_graph(
                        g, top_k=self.config.search_calibrate)
                    # ring-capable axes: measure the real ppermute hop so
                    # the overlap-aware sp pricing (and the warm-start DB)
                    # uses the chip's hop, not the datasheet guess
                    from .machine import AXIS_SEQ

                    ring_axes = [
                        ax for ax in (AXIS_SEQ,)
                        if dict(self.mesh.shape).get(ax, 1) > 1]
                    if ring_axes:
                        hops = cost_model.calibrate_collectives(
                            self.mesh, ring_axes)
                        telemetry.event("calibrate_collectives",
                                        axes=ring_axes, measured=hops)
                    stats = getattr(cost_model, "calib_stats", None)
                    if stats is not None:
                        # measured-vs-cache-hit split (the calibration
                        # twin of the search evals/cache_hits counters):
                        # with a warm calibration DB, measured → 0 and
                        # cache_hits → candidates — drift in that reuse
                        # is visible per compile in metrics.jsonl
                        telemetry.event(
                            "calibrate",
                            top_k=self.config.search_calibrate, **stats)

            tensor_to_out[self.layers[-1].outputs[0].tensor_guid][0]._is_logits = True
            restored = None
            if jax.process_count() == 1:
                # warm start: adopt a cached/checkpointed plan when its
                # fingerprint matches everything this search would consume
                # — a hit replays through the same strategy machinery
                # --import-strategy uses, with ZERO search evaluations
                from .warmstart import restore_plan

                restored = restore_plan(self, g, cost_model, _calibrate)
            if restored is not None:
                overrides, plan_mesh_axes, source = restored
                cur_axes = {k: int(v) for k, v in self.mesh.shape.items()}
                if plan_mesh_axes and plan_mesh_axes != cur_axes:
                    # a mesh-shape-searched plan carries its winning
                    # factorization — rebuild the mesh it was found for
                    from .machine import MeshShape

                    ms = self.config.mesh_shape()
                    sizes = {a: 1 for a in ms.axis_names}
                    sizes.update(plan_mesh_axes)
                    self.mesh = self._build_mesh(MeshShape(
                        tuple(sizes[a] for a in ms.axis_names),
                        ms.axis_names))
                self._strategy = overrides
                self._plan_source = source
                self._search_result = None  # plan replayed, not searched
                self._assign_strategy()
            elif jax.process_count() > 1:
                # multi-host: search on process 0 only, broadcast the plan,
                # and apply it to the ORIGINAL graph on every process (the
                # reference's search-on-GPU0 + serialize pattern,
                # mapper.cc:291-306 / model.cc:2830-2872) — rewritten-graph
                # materialization is skipped because the broadcast Strategy
                # expresses the same placements in logical-rank form
                from .distributed import run_search_on_host0

                def _search():
                    # calibration only where its measurements are consumed
                    # (process 0) — the other hosts' device time is not
                    # wasted on benchmarks whose results get discarded.
                    # Warm start also lives entirely on process 0: only
                    # host 0 reads/writes the shared warm-start dir, and a
                    # plan-cache hit reaches the other hosts through the
                    # same broadcast a searched plan would
                    from .parallel.strategies import Strategy
                    from .telemetry import log as fflog
                    from .warmstart import restore_plan, store_plan

                    warm = restore_plan(self, g, cost_model, _calibrate)
                    if warm is not None:
                        cur = {k: int(v)
                               for k, v in self.mesh.shape.items()}
                        if warm[1] and warm[1] != cur:
                            # the fleet's mesh is already built on every
                            # process — a plan for a different
                            # factorization cannot be adopted here; treat
                            # as a miss rather than mis-apply it
                            fflog.warning(
                                "warmstart: cached plan's mesh %s != "
                                "fleet mesh %s — re-searching",
                                warm[1], cur)
                            warm = None
                    if warm is not None:
                        self._plan_source = warm[2]
                        return Strategy(warm[0])
                    _calibrate()
                    orig_names = {n.name for n in g.topo_order()}
                    _, choice, us = joint_graph_optimize(
                        g, self.mesh, self.config, cost_model)
                    strategy = us.to_strategy(choice)
                    self._strategy = strategy.overrides
                    store_plan(self, meta={"mode": "multihost",
                                           "evals": us.evals},
                               replay_names=orig_names)
                    return strategy

                with telemetry.phase("compile.search", mode="multihost"):
                    self._strategy = run_search_on_host0(_search)
                if self._plan_source == "none":
                    # host 0 knows whether the plan was searched or served
                    # warm; the other hosts only know it arrived over the
                    # broadcast — label it that way rather than guessing
                    from .distributed import is_coordinator

                    self._plan_source = ("search" if is_coordinator()
                                         else "broadcast")
                self._assign_strategy()
                self._search_result = None  # plan arrived as a broadcast
            elif self.config.search_mesh_shapes:
                # also search the mesh factorization itself (the MachineView
                # grid-shape half of Unity, search/mesh_search.py): divisor
                # degrees — a 2×4 hybrid on 8 chips — are reached by
                # re-factorizing the data/model split, then the joint search
                # runs per candidate shape. Calibration transfers: the
                # measurements are per-op, mesh-independent.
                from .machine import AXIS_SEQ, MeshShape
                from .search.mesh_search import search_mesh_shapes

                # a PIPE_BLOCKS stack makes the pipe axis searchable too:
                # the dp-vs-pp decision is taken ACROSS factorizations
                # (each candidate's costing matches its execution)
                search_axes = (AXIS_DATA, AXIS_MODEL)
                if any(n.op_type == OT.OP_PIPE_BLOCKS
                       for n in g.topo_order()):
                    search_axes = search_axes + (AXIS_PIPE,)
                ms = self.config.mesh_shape()
                fixed = {a: s for a, s in zip(ms.axis_names, ms.axis_sizes)
                         if s > 1 and a not in search_axes}
                if fixed:
                    # factorizing around a pinned dcn/seq axis is not
                    # modeled — refuse loudly rather than silently collapse
                    # the configured axes to 1
                    raise ValueError(
                        f"--search-mesh-shapes factorizes the chip count "
                        f"over {search_axes} on a single slice; drop the "
                        f"flag or the extra mesh axes {sorted(fixed)}")
                machine_factory = None
                if self.config.machine_model_file:
                    # candidate machines must keep the file's topology/
                    # congestion fidelity, not fall back to the analytic
                    # defaults
                    from .search.machine_model import machine_model_from_file

                    machine_factory = lambda mesh: machine_model_from_file(  # noqa: E731
                        self.config.machine_model_file, mesh)
                _calibrate()
                orig_names = {n.name for n in g.topo_order()}
                with telemetry.phase("compile.search", mode="mesh_shapes"):
                    shape, g, choice, us, _ = search_mesh_shapes(
                        g, n_devices, self.config, axes=search_axes,
                        chip=machine.chip,
                        num_hosts=self.config.num_nodes,
                        calibrated=cost_model,
                        machine_factory=machine_factory)
                sizes = {a: 1 for a in ms.axis_names}
                sizes.update(shape)
                self.mesh = self._build_mesh(MeshShape(
                    tuple(sizes[a] for a in ms.axis_names), ms.axis_names))
                self.graph = g
                self._strategy = us.to_strategy(choice).overrides
                self._search_result = (us, choice)
                self._plan_source = "search"
                used_substitutions = True
                from .warmstart import store_plan

                store_plan(self, meta={"mode": "mesh_shapes",
                                       "evals": us.evals},
                           replay_names=orig_names)
            else:
                _calibrate()
                orig_names = {n.name for n in g.topo_order()}
                with telemetry.phase("compile.search", mode="joint"):
                    g, choice, us = joint_graph_optimize(
                        g, self.mesh, self.config, cost_model)
                self.graph = g
                self._strategy = us.to_strategy(choice).overrides
                self._search_result = (us, choice)
                self._plan_source = "search"
                used_substitutions = True
                from .warmstart import store_plan

                store_plan(self, meta={"mode": "joint", "evals": us.evals},
                           replay_names=orig_names)
        else:
            if self._plan_source == "none":
                self._plan_source = "default"  # data-parallel fallback
            self._assign_strategy()
        hint = getattr(self, "_plan_source_hint", None)
        if hint is not None:
            # elastic replan: the recompile's outcome is relabeled so
            # every consumer (plan record, compile event, report,
            # ffcheck context) sees plan_source "replan"; the underlying
            # origin (search/cache/broadcast/...) is kept for the
            # decision record
            self._plan_origin = self._plan_source
            self._plan_source = hint
            self._plan_source_hint = None
        if self._plan_fingerprint is not None:
            # manifest-ready plan record: every checkpoint this model
            # writes carries the applied plan + its structural
            # fingerprint, so --auto-resume restores the plan from the
            # manifest (warmstart._checkpoint_plan) instead of paying a
            # from-scratch search after the weights already loaded
            from .parallel.strategies import Strategy

            self._plan_record = {
                "structural_fingerprint": self._plan_fingerprint,
                "plan_source": self._plan_source,
                "strategy": Strategy(self._strategy or {}).to_json(),
                "mesh_axes": {k: int(v)
                              for k, v in self.mesh.shape.items()},
            }
        if self.config.export_strategy_file:
            # persist the plan in effect (searched or imported) for replay
            # (--export-strategy, model.cc:3599-3608); only the coordinator
            # writes — in a multi-host run every process reaches this point
            # and all hosts would race on the same shared-filesystem path
            from .distributed import is_coordinator

            if is_coordinator():
                from .parallel.strategies import Strategy

                Strategy(self._strategy or {}).save(
                    self.config.export_strategy_file)
        if self.config.export_strategy_computation_graph_file:
            from .pcg.graph import export_dot

            export_dot(g, self.config.export_strategy_computation_graph_file)

        # --- logits node = last layer's op (rewrites may have replaced it:
        # the mapped output's producer is then the unique sink)
        if used_substitutions:
            marked = [n for n in g.topo_order()
                      if getattr(n, "_is_logits", False)]
            sinks = g.sinks()
            if marked:
                logits_node = marked[0]
            elif len(sinks) == 1:
                logits_node = sinks[0]
            else:
                raise RuntimeError(
                    "cannot identify logits node after substitution rewrite")
        else:
            logits_node = tensor_to_out[
                self.layers[-1].outputs[0].tensor_guid][0]

        # --- label sharding matches logits batch sharding (model.cc:3086-3124)
        label_spec = logits_node.outputs[0].partition_spec()
        batch_axes = label_spec[0] if len(label_spec) > 0 else None
        self.label_spec = PartitionSpec(batch_axes)

        # --- weight-update sharding: the update-dimension half of the
        # search, decided AFTER every branch materialized its placements
        # (the decision prices the live graph's assignments). The chosen
        # mode is what the executor places/pins and what the explain
        # report / drift monitor price.
        from .search.unity import choose_update_sharding

        with telemetry.phase("compile.update_sharding"):
            if search_cost_model is None and self._warmstart is not None:
                # no local search ran (warm-start plan hit / checkpoint /
                # import / dp fallback): price the decision with the SAME
                # persisted calibration a cold --calibrate run consumed —
                # a roofline-only cost model could flip the auto decision
                # between a cold run and a warm restart of the identical job
                # (parity with the replayed strategy report, explain.py)
                from .search.cost_model import CostModel
                from .search.machine_model import machine_model_for_mesh

                search_cost_model = CostModel(
                    machine_model_for_mesh(
                        self.mesh, num_hosts=self.config.num_nodes),
                    opt_slots=self.optimizer.num_slots)
                self._warmstart.calibration_db.load_into(search_cost_model)
            self._update_sharding = choose_update_sharding(
                g, self.mesh, self.config, cost_model=search_cost_model,
                opt_slots=self.optimizer.num_slots)
            if jax.process_count() > 1:
                # the auto verdict prices with process-divergent cost models
                # (calibration + the warm-start DB live on process 0 only) and
                # its thresholds can land on opposite sides across hosts —
                # adopt the coordinator's decision everywhere so every process
                # pins the same update layout into the one jitted step
                from .distributed import broadcast_json, is_coordinator

                self._update_sharding = broadcast_json(
                    self._update_sharding if is_coordinator() else None)
                if search_cost_model is not None:
                    # keep the local cost model pricing the ADOPTED mode (the
                    # strategy report / drift monitor must describe what runs)
                    search_cost_model.update_sharding = (
                        self._update_sharding["enabled"])
                    search_cost_model.param_gather = (
                        self._update_sharding.get("stage", 0) == 3)
                    search_cost_model.overlap_update = (
                        self._update_sharding["enabled"]
                        and bool(self.config.overlap_collectives))

        with telemetry.phase("compile.executor"):
            self.executor = Executor(
                g, self.mesh, self.config, self.loss_type, self.metrics,
                self.optimizer, logits_node, self.label_spec,
                update_sharding=self._update_sharding,
            )
        # adopt the REALIZED record (the executor resolves the decision
        # into per-weight specs and may widen shards/axes beyond the dp
        # default, e.g. over `seq`): manifests, the strategy report, and
        # the decision event below must describe what runs
        self._update_sharding = self.executor.update_sharding
        telemetry.event(
            "weight_update_decision",
            enabled=self._update_sharding["enabled"],
            stage=self._update_sharding.get("stage", 0),
            shards=self._update_sharding["shards"],
            reason=self._update_sharding.get("reason", ""))
        # --- ffcheck compile gate (analysis/): static verification of the
        # materialized plan — sharding dataflow, memory liveness,
        # collective uniformity, donation/aliasing — on EVERY plan source
        # (all six adoption paths funnel through this point), BEFORE
        # init_variables touches device memory, so a predicted OOM or an
        # invalid sharding fails fast with a structured report instead of
        # a device error. Errors raise unless --no-verify-plan.
        from .analysis import verify_plan

        verify_plan(self, cost_model=search_cost_model)
        # --- SPMD fingerprint barrier (analysis/spmd.py, --spmd-barrier):
        # cross-host uniformity check of the step-executable ingredients
        # BEFORE the first step — a diverged process raises a structured
        # SPMDDivergenceError here instead of deadlocking a collective or
        # silently training a different program. The verdict rides into
        # the compile metrics record and strategy_report.json so
        # run_doctor --check can gate on it.
        self._spmd_barrier = None
        if self.config.spmd_barrier:
            from .analysis import spmd

            with telemetry.phase("compile.spmd_barrier"):
                self._spmd_barrier = spmd.fingerprint_barrier(self)
            telemetry.event("spmd_barrier", **self._spmd_barrier)
        with telemetry.phase("compile.init"):
            self._rng = jax.random.key(self.config.seed)
            self._params, self._state = self.executor.init_variables(
                self._rng, self._shared_variables)
            self._shared_variables = None
            # optimizer slots inherit the (possibly update-sharded) param
            # placement via zeros_like; place_update_sharded is the explicit
            # guarantee (momentum-off scalar slots pass through untouched)
            # fresh-init placement of just-built zeros at compile — not a
            # plan transition, nothing pre-existing to verify a mapping for
            self._opt_slots = self.executor.place_update_sharded(  # fflint: ok unverified_transition
                self.executor.replicate(self.optimizer.init(self._params)))
            self._state = (self.executor.replicate(self._state)
                           if self._state else self._state)
            self._step = self.executor.replicate(jnp.zeros((), jnp.int32))
            self._counters = self.executor.replicate(
                self.metrics.zero_counters())
        # --- ffpulse goodput anchor: cost-model forward FLOPs summed over
        # the compiled graph (x3 for fwd+bwd, the standard training
        # estimate) against the aggregate peak of the mesh's chips (the
        # machine model's table; an unknown device raises) — the two MFU
        # factors record_step divides by measured step time.
        from .search.cost_model import _NON_COMPUTE
        from .search.machine_model import chip_for

        fwd = 0.0
        for node in self.graph.topo_order():
            if (node.op_type in _NON_COMPUTE or not node.outputs
                    or not node.inputs):
                continue
            fwd += node.op_def.flops(
                node.params,
                [pt.shape.logical_shape for pt in node.inputs],
                [pt.shape.logical_shape for pt in node.outputs])
        self._goodput_anchor = None
        if fwd > 0:
            num_chips = int(self.mesh.devices.size)
            self._goodput_anchor = {
                "flops_per_step": 3.0 * fwd,
                "peak_flops": (chip_for(self.mesh.devices.flat[0]).peak_flops
                               * num_chips),
                "num_chips": num_chips,
            }
        self._compiled = True

    def _assign_strategy(self):
        """Assign mesh axes to every op output / weight.

        Default = data parallel: batch dim (0) of every activation sharded
        over the `data` axis, weights replicated — the reference's
        data-parallel fallback (graph.cc:1939-1964). A searched or imported
        strategy overrides per-node specs via self._strategy."""
        from .machine import batch_axes_for
        from .parallel.ops import derive_parallel_assignment

        batch_axes = batch_axes_for(dict(self.mesh.shape))
        batch_deg = 1
        for ax in batch_axes:
            batch_deg *= self.mesh.shape.get(ax, 1)
        if self._strategy:
            # a broadcast/imported plan can carry names from a REWRITTEN
            # graph (e.g. the fused Experts node from fuse_moe_trio) that
            # don't exist in this graph; silently dropping them would fall
            # back to data parallel for those ops with no sign anything was
            # lost — make the mismatch visible
            present = {n.name for n in self.graph.topo_order()}
            dropped = sorted(set(self._strategy) - present)
            if dropped:
                import warnings

                warnings.warn(
                    "strategy contains placements for nodes not in this "
                    f"graph (dropped, falling back to data parallel): "
                    f"{dropped}", stacklevel=2)
        for node in self.graph.topo_order():
            ov = (self._strategy or {}).get(node.name, {})
            if node.is_parallel_op and node.inputs:
                # explicit parallel op: output placement derived from the
                # input's placement + the op's dim/degree params (unless the
                # strategy pins it explicitly below)
                if 0 not in ov.get("outputs", {}):
                    node.outputs[0].assign_axes(
                        derive_parallel_assignment(
                            node.op_type, node.params,
                            node.inputs[0].axis_assignment, self.mesh,
                        )
                    )
            else:
                for pt in node.outputs:
                    dims = pt.shape.dims
                    assignment = [()] * len(dims)
                    if (
                        batch_deg > 1
                        and len(dims) > 0
                        and dims[0].size % batch_deg == 0
                        and not _is_expert_buffer(node)
                    ):
                        # multi-host meshes compose (dcn, data) on the batch
                        assignment[0] = batch_axes
                    pt.assign_axes(tuple(assignment))
            if (node.op_type == OT.OP_INC_MULTIHEAD_ATTENTION
                    and batch_deg > 1):
                # default KV-cache placement: the slot dim rides the data
                # axes with the batch it serves — a replicated cache would
                # multiply per-chip HBM by the data degree. A searched/
                # imported plan (e.g. head-parallel attention also sharding
                # the cache feature dim over `model`) overrides below.
                # (The PAGED op takes no such default on purpose: its
                # pool's leading dim is physical blocks shared across
                # slots by prefix reuse, so it stays whole on the batch
                # axes — only a head-parallel plan shards its feature dim.)
                for ws in node.weight_specs:
                    if not ws.trainable and ws.shape[0] % batch_deg == 0:
                        node.weight_axes.setdefault(
                            ws.name,
                            PartitionSpec(
                                batch_axes[0] if len(batch_axes) == 1
                                else tuple(batch_axes),
                                *([None] * (len(ws.shape) - 1))))
            if (node.op_type == OT.OP_PIPE_BLOCKS
                    and self.mesh.shape.get(AXIS_PIPE, 1) > 1):
                # default pipe-axis sharding of the stacked block weights:
                # each stage stores only its layers (+ optimizer slots),
                # and the shard_map schedule consumes exactly this layout —
                # no per-step weight collectives
                for ws in node.weight_specs:
                    node.weight_axes.setdefault(
                        ws.name,
                        PartitionSpec(AXIS_PIPE, *([None] * (len(ws.shape) - 1))),
                    )
            for i, spec_axes in ov.get("outputs", {}).items():
                node.outputs[i].assign_axes(spec_axes)
            node.weight_axes.update(ov.get("weights", {}))

    # ================================================== training API

    def _input_partition_spec(self, name: str):
        """PartitionSpec of the graph input named `name`, or None when no
        OP_INPUT source carries that name (callers place replicated). The
        ONE resolution point for input placement — the fit loop, the
        dataloader, and the pipelined engine all go through here."""
        for node in self.graph.sources():
            if node.op_type == OT.OP_INPUT and node.name == name:
                return node.outputs[0].partition_spec()
        return None

    def _make_batch(self, x_arrays: dict, labels):
        specs = {}
        for name in x_arrays:
            spec = self._input_partition_spec(name)
            if spec is not None:
                specs[name] = spec
        xs = self.executor.shard_batch(x_arrays, specs)
        y = jax.device_put(
            labels, jax.sharding.NamedSharding(self.mesh, self.label_spec)
        )
        return xs, y

    def enable_checkpointing(self, directory: str, every_n_steps: int = 0,
                             every_t_seconds: float = 0.0, keep: int = 3):
        """Attach the resilience subsystem (resilience/): async snapshots
        every N steps / T seconds during fit, SIGTERM-drains to a final
        snapshot, and `auto_resume`-able committed checkpoints. The
        programmatic twin of --checkpoint-dir/--checkpoint-every."""
        from .resilience import CheckpointPolicy, ResilienceManager

        self._resilience = ResilienceManager(
            self, directory,
            CheckpointPolicy(every_n_steps=every_n_steps,
                             every_t_seconds=every_t_seconds),
            keep=keep)
        return self._resilience

    def enable_telemetry(self, directory: str):
        """Attach the observability subsystem (telemetry/): Chrome-trace
        spans + JSONL run metrics under `directory`. The session becomes
        the process-wide sink only WHILE this model is inside compile/fit
        (so search/resilience/dataloader hooks land in the same files
        without other models leaking events in between). The programmatic
        twin of --telemetry-dir."""
        from . import telemetry
        from .telemetry import log as fflog

        if self._telemetry is None:
            self._telemetry = telemetry.TelemetrySession(directory)
        else:
            import os

            if os.path.abspath(directory) != self._telemetry.directory:
                # e.g. --telemetry-dir A at compile + Telemetry("B")
                # callback: the first session wins; say so instead of
                # letting the user tail an empty directory
                fflog.warning(
                    "enable_telemetry(%r) ignored: this model's telemetry "
                    "session already writes to %s",
                    directory, self._telemetry.directory)
        return self._telemetry

    def get_telemetry(self):
        """The model's TelemetrySession, or None when telemetry is off."""
        return self._telemetry

    def enable_diagnostics(self, directory: str = "",
                           drift_threshold: Optional[float] = None,
                           abort_on: Optional[Sequence[str]] = None,
                           recalibrate: bool = False, rules=None):
        """Attach the diagnostics subsystem (diagnostics/): strategy
        explain report at compile, online cost-model drift monitoring and
        run-health anomaly rules during fit, artifacts next to the
        telemetry session's (strategy_report.json/md, alerts.jsonl). The
        programmatic twin of --diagnostics; `directory` enables telemetry
        there first when no session exists yet."""
        from .diagnostics import DiagnosticsManager

        if directory:
            self.enable_telemetry(directory)
        elif self._telemetry is None and self.config.telemetry_dir:
            self.enable_telemetry(self.config.telemetry_dir)
        if self._telemetry is None:
            raise ValueError(
                "diagnostics requires telemetry: pass a directory, set "
                "--telemetry-dir, or call enable_telemetry() first")
        if self._diagnostics is None:
            self._diagnostics = DiagnosticsManager(
                self, self._telemetry,
                drift_threshold=(self.config.drift_threshold
                                 if drift_threshold is None
                                 else drift_threshold),
                abort_on=tuple(self.config.health_abort_on
                               if abort_on is None else abort_on),
                recalibrate=recalibrate, rules=rules)
        elif (drift_threshold is not None or abort_on is not None
                or recalibrate or rules is not None):
            # e.g. --diagnostics attached a manager at compile and a keras
            # Diagnostics(abort_on=...) callback asks for different
            # settings later: apply what can be applied live (abort set,
            # drift threshold) rather than silently dropping an explicit
            # abort request; rule objects are already running, so a new
            # rule set can't be swapped in — say so
            from .telemetry import log as fflog

            diag = self._diagnostics
            if abort_on is not None:
                diag.health.set_abort_on(tuple(abort_on))
            if drift_threshold is not None:
                diag.drift_threshold = float(drift_threshold)
                if diag.drift is not None:
                    diag.drift.threshold = float(drift_threshold)
            if recalibrate:
                from .diagnostics.drift import make_recalibration_state

                diag._recalibrate = True
                if diag.drift is not None \
                        and diag.drift.recompile_state is None:
                    diag.drift.recompile_state = \
                        make_recalibration_state(self)
            if rules is not None:
                fflog.warning(
                    "enable_diagnostics: custom rules ignored — this "
                    "model's diagnostics manager already runs its rule "
                    "set (pass rules on the FIRST enable_diagnostics "
                    "call)")
        return self._diagnostics

    def get_diagnostics(self):
        """The model's DiagnosticsManager, or None when diagnostics is
        off."""
        return self._diagnostics

    def _maybe_enable_diagnostics(self):
        """Config-driven lazy attach (mirrors the telemetry lazy attach);
        --diagnostics without --telemetry-dir warns once instead of
        silently doing nothing."""
        from .telemetry import log as fflog

        if self._diagnostics is not None or not self.config.diagnostics:
            return self._diagnostics
        if self._telemetry is None and not self.config.telemetry_dir:
            if not getattr(self, "_diag_warned", False):
                self._diag_warned = True
                fflog.warning(
                    "--diagnostics ignored: no --telemetry-dir (the "
                    "report/alert artifacts need a telemetry directory)")
            return None
        return self.enable_diagnostics()

    def _ensure_step_profiler(self):
        """The model's ffscope StepProfiler (scope/profile.py), created
        on first use from config (--profile-every; trace dirs live
        under <telemetry-dir>/ffscope when a telemetry dir exists)."""
        prof = getattr(self, "_scope_prof", None)
        if prof is None:
            import os

            from .scope.profile import StepProfiler

            root = (os.path.join(self.config.telemetry_dir, "ffscope")
                    if self.config.telemetry_dir else None)
            prof = self._scope_prof = StepProfiler(
                every=self.config.profile_every, trace_root=root)
        return prof

    def profile_step(self):
        """Arm a one-shot op-grain profile capture: the next fit step
        runs under `jax.profiler` tracing and its attributed per-op
        device time lands in strategy_report.json's `profile` section
        (the programmatic twin of --profile-every K)."""
        self._ensure_step_profiler().arm()

    def enable_elastic(self, **kwargs):
        """Attach the elastic re-planning controller (elastic/) to this
        model — the programmatic twin of --elastic. kwargs pass through
        to ElasticController (cooldown_steps, horizon_steps, dry_run,
        visible_devices_fn for tests). Reuses/attaches diagnostics when
        configured so the drift trigger stream is live."""
        from .elastic import ElasticController

        diag = self._maybe_enable_diagnostics()
        self._elastic = ElasticController(self, diag, **kwargs)
        return self._elastic

    def _maybe_enable_elastic(self, diag):
        """Config-driven lazy attach (--elastic), mirroring the
        diagnostics lazy attach; an existing controller (enable_elastic)
        is reused, picking up diagnostics if it attached later."""
        if self._elastic is not None:
            if diag is not None and self._elastic.diag is None:
                self._elastic.attach_diagnostics(diag)
            return self._elastic
        if not self.config.elastic:
            return None
        from .elastic import ElasticController

        self._elastic = ElasticController(self, diag)
        return self._elastic

    def _py_step(self) -> int:
        """The device step counter as a host int — THE checkpoint step
        numbering convention (fit's policy decisions, explicit saves, and
        the keras ModelCheckpoint all go through here)."""
        return int(np.asarray(jax.device_get(self._step)))

    def _nonfinite_localization(self, loss_val) -> dict:
        """The sanitizer's (op, phase, step) attribution for a
        non-finite loss, as extra keys for the health-rule step record
        (NaNLossRule folds them into its alert). Empty when the loss is
        finite, the sanitizer is off, or nothing was localized. The one
        effects_barrier drains the probe callbacks of the step that
        produced the NaN — paid only on the already-dead path."""
        import math as _math

        if (loss_val is None or _math.isfinite(loss_val)
                or not self.config.sanitize_numerics):
            return {}
        from . import sanitize

        jax.effects_barrier()
        info = sanitize.get_monitor().first_nonfinite()
        if info is None:
            return {}
        return {"nonfinite_op": info["op"],
                "nonfinite_phase": info["phase"],
                "nonfinite_step": info["step"]}

    def set_fault_hook(self, hook):
        """Install a per-step failure-injection hook (resilience/fault.py):
        called with the global step after each optimizer step + checkpoint
        decision; raising simulates mid-fit death. Test-only."""
        self._fault_hook = hook

    def _epoch_order(self, num_samples: int, epoch: int,
                     shuffle: bool) -> np.ndarray:
        """Sample order for one epoch. Shuffles are keyed on (config.seed,
        absolute epoch) — NOT the global numpy RNG — so a preempted run
        that resumes mid-epoch replays the exact order the uninterrupted
        run saw, making resume bit-exact. The absolute index includes
        `_epoch_base` (epochs completed by previous fit() calls), so
        repeated fit(epochs=1) calls — the keras per-epoch loop — get a
        fresh order every epoch instead of re-training one fixed order."""
        if not shuffle:
            return np.arange(num_samples)
        rs = np.random.RandomState(
            (self.config.seed * 1_000_003
             + self._epoch_base + epoch) % (2 ** 32))
        return rs.permutation(num_samples)

    def fit(self, x: Union[np.ndarray, Sequence[np.ndarray], dict], y: np.ndarray,
            epochs: int = -1, batch_size: int = -1, shuffle: bool = True,
            verbose: bool = True, pipeline_steps: Optional[int] = None):
        """Training loop (parity: flexflow_cffi.py:2058-2100), made
        preemption-safe: policy-gated async checkpoints between steps, a
        SIGTERM drain-and-final-snapshot path, and --auto-resume restart
        from the newest committed checkpoint's (epoch, batch) cursor.

        With `pipeline_steps > 1` (or --pipeline-steps) the loop routes
        through the pipelined execution engine (engine/): chunks of N
        steps run as one donated lax.scan dispatch over batches a
        background thread prefetched onto the mesh, with checkpoints/
        preemption at chunk boundaries — bit-identical losses/params to
        the default eager loop (docs/performance.md).

        With telemetry on (--telemetry-dir / enable_telemetry) every step
        emits a trace span and a JSONL record splitting wall time into
        data-wait vs device time plus the blocking slice of any checkpoint
        save (reconstructed per step from the chunk window in pipelined
        mode); `verbose=False` drops the epoch progress lines to debug
        level (they also honor FF_LOG_LEVEL and emit on host 0 only)."""
        assert self._compiled, "call compile() before fit()"
        from . import telemetry
        from .telemetry import log as fflog

        if self._telemetry is None and self.config.telemetry_dir:
            self.enable_telemetry(self.config.telemetry_dir)
        tel = self._telemetry
        if tel is not None:
            # active only for the duration of THIS model's fit (the
            # matching deactivate is in the loop's finally below) —
            # another model training afterwards in the same process must
            # not leak events into this model's artifacts
            telemetry.activate(tel)
            # idempotent: covers sessions attached after compile (keras
            # Telemetry callback, manual enable_telemetry)
            tel.write_manifest(self)
            # ffpulse: MFU/tokens-per-sec anchors from the compile-time
            # cost model, and continuous export when configured
            anchor = getattr(self, "_goodput_anchor", None)
            if anchor is not None:
                tel.set_goodput(anchor["flops_per_step"],
                                anchor["peak_flops"])
            if self.config.metrics_interval or self.config.metrics_port:
                tel.start_exporter(
                    interval_s=self.config.metrics_interval,
                    port=self.config.metrics_port)
        if self.config.sanitize_numerics:
            # a fresh fit gets a fresh provenance window: stale
            # non-finite reports from an earlier (diverged) fit in the
            # same process must not win the min-step localization of
            # THIS run's first NaN
            from . import sanitize

            jax.effects_barrier()
            sanitize.get_monitor().reset()
        diag = self._maybe_enable_diagnostics()
        if diag is not None and diag.report is None:
            # diagnostics attached after compile (keras Diagnostics
            # callback, manual enable): write the explain report and arm
            # the drift monitor now
            diag.on_compile()
        elastic = self._maybe_enable_elastic(diag)
        # ffscope (scope/): flight-recorder sizing, sampled op-grain
        # profiling, hang watchdog. The recorder itself is always on —
        # config only resizes/disables the ring.
        from .scope import flightrec
        flightrec.configure(capacity=self.config.flight_events or None,
                            enabled=self.config.flight_events > 0)
        scope_prof = getattr(self, "_scope_prof", None)
        if scope_prof is None and self.config.profile_every > 0:
            scope_prof = self._ensure_step_profiler()
        watchdog = None
        if self.config.watchdog_timeout > 0:
            from .scope.watchdog import HangWatchdog

            try:
                host_idx = jax.process_index()
            except Exception:
                host_idx = 0
            wd_dir = (tel.directory if tel is not None
                      else self.config.telemetry_dir
                      or self.config.checkpoint_dir or None)

            def _wd_alert(info, _diag=diag):
                if _diag is not None:
                    _diag._alerts.record(
                        "alert", rule="hang_watchdog", level="error",
                        step=info.get("last_step"),
                        stalled_s=info.get("stalled_s"),
                        deadline_s=info.get("deadline_s"),
                        lagging_host=info.get("lagging_host"),
                        message="hang watchdog fired: no step-boundary "
                                "progress (flight.json dumped)")

            watchdog = HangWatchdog(
                timeout_s=self.config.watchdog_timeout,
                multiplier=self.config.watchdog_multiplier,
                directory=wd_dir, host_index=host_idx,
                abort=self.config.watchdog_abort,
                on_fire=_wd_alert).start()
        epoch_log = fflog.info if verbose else fflog.debug
        if self.config.profiling and not getattr(self, "_profiled", False):
            # --profiling: per-op kernel table, printed once per compile
            # (the reference prints per-kernel times every launch under
            # m->profiling, linear_kernels.cu:95-117); the rows also land
            # in the report's `profile` section (source: standalone) so
            # the doctor renders one measured-vs-predicted table for both
            # this and the ffscope xplane source
            from .profiling import (print_operator_profile,
                                    profile_section_from_rows)

            rows = print_operator_profile(self.graph)
            self._profiled = True
            if diag is not None and rows:
                diag.on_profile(profile_section_from_rows(rows))
        if epochs < 0:
            epochs = self.config.epochs
        if batch_size < 0:
            batch_size = self.config.batch_size
        x_dict = self._as_input_dict(x)
        num_samples = y.shape[0]
        num_batches = num_samples // batch_size
        if pipeline_steps is None:
            pipeline_steps = self.config.pipeline_steps
        pipeline_steps = max(1, int(pipeline_steps))
        engine = None
        step_fn = None
        health_every = max(1, int(self.config.health_sample_every))
        health_win = [0.0, 0.0, 0.0, 0]  # step/data-wait/save sums, count
        if pipeline_steps > 1:
            from .engine import PipelinedEngine

            engine = PipelinedEngine(self, pipeline_steps)
        else:
            step_fn = (self.executor._train_step
                       or self.executor.build_train_step())

        resil = self._resilience
        if resil is None and self.config.checkpoint_dir:
            from .resilience import ResilienceManager

            resil = self._resilience = ResilienceManager.from_config(self)
        start_epoch = 0
        if (resil is not None and self.config.auto_resume
                and not self._auto_resumed):
            # at most once per model object: a second fit() (keras drives
            # one fit(epochs=1) per epoch) must NOT rewind live training
            # state back to the on-disk checkpoint
            self._auto_resumed = True
            # peek the manifest BEFORE restoring: a stale checkpoint
            # (older than this model's live progress) must be rejected
            # without first rewinding params/opt state to it
            peek = resil.peek_latest()
            if peek is not None:
                path, extras = peek
                cur = extras.get("cursor") or {}
                # cursor epochs are ABSOLUTE (epochs completed since
                # compile); this fit call's within-loop index is relative
                # to the epochs this model object already ran
                abs_epoch = int(cur.get("epoch", 0))
                if abs_epoch < self._epoch_base:
                    import warnings

                    warnings.warn(
                        f"auto-resume: checkpoint {path} is older than "
                        f"this model's live progress (epoch {abs_epoch} < "
                        f"{self._epoch_base}) — ignored", stacklevel=2)
                else:
                    with telemetry.phase("resume.restore", path=path):
                        resil.restore_path(path)
                    start_epoch = abs_epoch - self._epoch_base
                    # the batch offset sticks to its ABSOLUTE epoch: when
                    # fit is driven one epoch at a time (keras), the epoch
                    # containing it may only be reached by a later call
                    self._resume_cursor = (
                        abs_epoch, int(cur.get("batch", 0)))
                    telemetry.instant("resume", path=path, epoch=abs_epoch)
                    telemetry.event(
                        "resume", path=path, epoch=abs_epoch,
                        batch=int(cur.get("batch", 0)))
        py_step = self._py_step()
        if elastic is not None and elastic.maybe_replan(py_step):
            # fit-entry capacity check: a preempted/restored fleet
            # re-plans BEFORE the first step so the whole epoch runs on
            # the new mesh (the pipelined engine re-reads the model's
            # executor/mesh per chunk; the eager step_fn is rebuilt here)
            if engine is None:
                step_fn = (self.executor._train_step
                           or self.executor.build_train_step())
        # derived token rate: labels shaped (N, seq, ...) carry seq tokens
        # per example (trailing size-1 dims collapse; plain (N, 1) labels
        # degenerate to 1 token = 1 example)
        tokens_per_example = int(np.prod(y.shape[1:])) if y.ndim > 1 else 1
        # ffscope attribution joins trace scopes back to these names;
        # the report's op set (when diagnostics wrote one) is the
        # contract — every report op gets a measured column
        prof_names = None
        if scope_prof is not None:
            if diag is not None and diag.report is not None:
                prof_names = [o["name"] for o in diag.report["ops"]]
            else:
                prof_names = [n.name for n in self.graph.topo_order()]

        import contextlib

        from .diagnostics.health import HealthAbort
        from .resilience.fault import SimulatedPreemption
        from .resilience.policy import PreemptionHandler

        if diag is not None and resil is not None:
            # staleness clock starts at fit start; every commit re-feeds it
            diag.note_checkpoint_commit(time.time())
        preempt = PreemptionHandler() if resil is not None else None
        preempted = False
        with contextlib.ExitStack() as stack:
            if preempt is not None:
                stack.enter_context(preempt)
            if self.config.xprof_dir:
                # opt-in device-level timeline: the whole fit runs under
                # jax.profiler.trace, so XProf/TensorBoard shows the XLA
                # step right where the host-side trace shows its dispatch
                stack.enter_context(
                    jax.profiler.trace(self.config.xprof_dir))
            fit_span = telemetry.span(
                "fit", steps=max(0, epochs - start_epoch) * num_batches,
                batch_size=batch_size)
            fit_span.__enter__()
            # a program built again from here on is a recompile
            telemetry.startup.steps_began()
            try:
                for epoch in range(start_epoch, epochs):
                    abs_e = self._epoch_base + epoch
                    order = self._epoch_order(num_samples, epoch, shuffle)
                    t0 = time.time()
                    b0 = 0
                    if (self._resume_cursor is not None
                            and abs_e >= self._resume_cursor[0]):
                        if abs_e == self._resume_cursor[0]:
                            b0 = self._resume_cursor[1]
                            if b0 >= num_batches and b0 > 0:
                                import warnings

                                warnings.warn(
                                    f"resume cursor batch {b0} does not "
                                    f"fit {num_batches} batches (batch "
                                    f"size changed?) — restarting the "
                                    f"epoch", stacklevel=2)
                                b0 = 0
                        self._resume_cursor = None
                    if engine is not None:
                        # pipelined engine: fused chunk dispatches with
                        # prefetch; raises HealthAbort/SimulatedPreemption
                        # into the same handlers as the eager loop below
                        py_step, preempted = engine.run_epoch(
                            x_dict=x_dict, y=y, order=order, b0=b0,
                            num_batches=num_batches,
                            batch_size=batch_size, abs_e=abs_e,
                            py_step=py_step, tel=tel, diag=diag,
                            resil=resil, preempt=preempt,
                            fault_hook=self._fault_hook,
                            tokens_per_example=tokens_per_example)
                        if preempted:
                            fflog.warning(
                                "preempted at step %d (chunk boundary): "
                                "final checkpoint committed, stopping "
                                "fit", py_step)
                            flightrec.dump("sigterm")
                            return
                        b0_eager = num_batches  # epoch fully covered
                    else:
                        b0_eager = b0
                    for b in range(b0_eager, num_batches):
                        t_it0 = time.perf_counter() if tel is not None else 0.0
                        with telemetry.span("step", step=py_step + 1):
                            with telemetry.span("data_wait"):
                                idx = order[b * batch_size : (b + 1) * batch_size]
                                xb = {k: v[idx] for k, v in x_dict.items()}
                                yb = y[idx]
                                batch = self._make_batch(xb, yb)
                            data_wait = (time.perf_counter() - t_it0
                                         if tel is not None else 0.0)
                            self._rng, sub = jax.random.split(self._rng)
                            capturing = (
                                scope_prof is not None
                                and scope_prof.should_capture(py_step + 1)
                                and scope_prof.begin(py_step + 1))
                            (
                                self._params,
                                self._state,
                                self._opt_slots,
                                self._step,
                                self._counters,
                                lval,
                            ) = step_fn(
                                self._params, self._state, self._opt_slots,
                                self._step, self._counters, sub, batch,
                            )
                            py_step += 1
                            if capturing:
                                # drain before stop_trace so the step's
                                # device work lands inside the capture
                                jax.block_until_ready(self._params)
                                section = scope_prof.end(
                                    py_step, prof_names)
                                if section is not None and diag is not None:
                                    diag.on_profile(section)
                            flightrec.note_step(py_step)
                            if watchdog is not None:
                                watchdog.beat(py_step)
                            # the cursor names the NEXT batch to run on
                            # resume; epochs are ABSOLUTE (since compile)
                            if b + 1 >= num_batches:
                                cursor = {"epoch": abs_e + 1, "batch": 0}
                            else:
                                cursor = {"epoch": abs_e, "batch": b + 1}
                            t_save0 = (time.perf_counter()
                                       if tel is not None else 0.0)
                            if resil is not None:
                                if preempt.preempted:
                                    # preemption notice: drain the in-flight
                                    # async save, then one final synchronous
                                    # snapshot — the only blocking save
                                    telemetry.instant("preempted",
                                                      step=py_step)
                                    resil.finalize(py_step, cursor,
                                                   final_save=True)
                                    preempted = True
                                else:
                                    resil.maybe_save(py_step, cursor)
                        if tel is not None:
                            save_lat = time.perf_counter() - t_save0
                            loss_val = None
                            sampled = (diag is not None
                                       and py_step % health_every == 0)
                            if sampled:
                                # the scalar loss fetch is a device sync
                                # and happens ONLY with diagnostics on —
                                # BEFORE step_time is read, so the drained
                                # device work lands inside this step's own
                                # timed window (fetching after it would
                                # leave every window measuring dispatch
                                # only, and the drift monitor would
                                # compare the predicted makespan against
                                # host overhead)
                                loss_val = float(np.asarray(
                                    jax.device_get(lval)))
                            step_time = time.perf_counter() - t_it0
                            tel.record_step(
                                py_step, abs_e, step_time, data_wait,
                                save_lat, batch_size, tokens_per_example)
                            if diag is not None:
                                if resil is not None:
                                    diag.note_checkpoint_commit(
                                        resil.last_commit_walltime())
                                # --health-sample-every K: with the drain
                                # thinned to every K-th step, the steps in
                                # between measure dispatch only while the
                                # sampled step absorbs the drained device
                                # work — feeding rules that raw bimodal
                                # stream would seed spike/stall/drift
                                # baselines on dispatch-only windows. So
                                # rules see ONE record per window with
                                # the K-step AVERAGE (the pipelined
                                # chunk/N attribution applied to the
                                # eager loop); K=1 reduces to the
                                # per-step record exactly.
                                hw = health_win
                                hw[0] += step_time
                                hw[1] += data_wait
                                hw[2] += save_lat
                                hw[3] += 1
                                if sampled:
                                    k = hw[3]
                                    w_t, w_dw, w_sv = (hw[0] / k,
                                                       hw[1] / k,
                                                       hw[2] / k)
                                    health_win = [0.0, 0.0, 0.0, 0]
                                    rec = {
                                        "step": py_step, "epoch": abs_e,
                                        "t": time.time(),
                                        "step_time_s": w_t,
                                        "data_wait_s": w_dw,
                                        "save_latency_s": w_sv,
                                        "device_time_s": max(
                                            0.0, w_t - w_dw - w_sv),
                                        "loss": loss_val,
                                    }
                                    rec.update(
                                        self._nonfinite_localization(
                                            loss_val))
                                    diag.on_step(rec)
                        if self._fault_hook is not None:
                            self._fault_hook(py_step)
                        if (elastic is not None and not preempted
                                and elastic.maybe_replan(py_step)):
                            # the re-plan migrated executor + state in
                            # place at this step boundary — the captured
                            # step callable belongs to the old executor
                            step_fn = (self.executor._train_step
                                       or self.executor.build_train_step())
                        if preempted:
                            telemetry.event("preempted", step=py_step)
                            fflog.warning(
                                "preempted at step %d: final checkpoint "
                                "committed, stopping fit", py_step)
                            flightrec.dump("sigterm")
                            return
                    with telemetry.span("fit.drain"):
                        jax.block_until_ready(self._params)
                    dt = time.time() - t0
                    thru = (num_batches - b0) * batch_size / dt
                    epoch_log(
                        f"epoch {epoch}: {self.get_perf_metrics()} "
                        f"ELAPSED TIME = {dt:.4f}s, "
                        f"THROUGHPUT = {thru:.2f} samples/s"
                    )
                    telemetry.event("epoch", epoch=abs_e, duration_s=dt,
                                    examples_per_sec=thru)
            except SimulatedPreemption:
                # injected death: die exactly as a real kill would — no
                # drain, no final save, and the in-flight async write must
                # not commit after the "kill"; only checkpoints already
                # committed at this instant survive for auto_resume
                flightrec.dump("SimulatedPreemption")
                if resil is not None:
                    resil.checkpointer.abort()
                raise
            except HealthAbort:
                # a health rule listed in --health-abort-on fired: stop
                # training with artifacts intact. Drain the in-flight
                # async save but do NOT final-snapshot — a NaN'd model is
                # not worth committing over the last good checkpoint
                flightrec.dump("HealthAbort")
                if resil is not None:
                    resil.finalize()
                fflog.error(
                    "fit aborted by diagnostics at step %d (see %s)",
                    py_step, diag.alerts_path if diag else "alerts.jsonl")
                raise
            except BaseException as e:
                # anything else that kills the fit (executor exception,
                # SPMDDivergenceError, the watchdog's interrupt) leaves
                # the flight record behind — the post-mortem artifact a
                # crash otherwise never writes
                flightrec.dump(type(e).__name__)
                raise
            else:
                # the next fit() call continues the absolute epoch count
                # (fresh shuffle orders for keras's repeated fit(epochs=1))
                self._epoch_base += epochs
                if resil is not None:
                    resil.finalize()
            finally:
                # closed by hand, not by the stack: the session's trace is
                # written below and has to hold this span
                fit_span.__exit__(None, None, None)
                if watchdog is not None:
                    watchdog.stop()
                if scope_prof is not None:
                    scope_prof.abandon()  # a capture left open by a raise
                if tel is not None:
                    # artifacts must exist however fit ends (normal return,
                    # preemption, injected death): summary then trace dump.
                    # The in-flight checkpoint writer was already drained
                    # on every exit path, so no late events are lost by
                    # deactivating here.
                    if diag is not None:
                        diag.on_fit_end()
                    tel.write_summary()
                    tel.write_metrics_snapshot(reason="fit_end")
                    tel.flush()
                    telemetry.deactivate(tel)

    def eval(self, x, y, batch_size: int = -1):
        assert self._compiled
        if batch_size < 0:
            batch_size = self.config.batch_size
        x_dict = self._as_input_dict(x)
        num_batches = y.shape[0] // batch_size
        eval_fn = self.executor._eval_step or self.executor.build_eval_step()
        counters = self.metrics.zero_counters()
        for b in range(num_batches):
            sl = slice(b * batch_size, (b + 1) * batch_size)
            xb = {k: v[sl] for k, v in x_dict.items()}
            batch = self._make_batch(xb, y[sl])
            counters = eval_fn(self._params, self._state, counters, batch)
        return PerfMetrics(counters, self.metrics)

    def _as_input_dict(self, x) -> dict:
        input_names = [t.name for t in self._input_tensors
                       if not hasattr(t, "constant_value")]
        if isinstance(x, dict):
            return x
        if isinstance(x, np.ndarray) or hasattr(x, "shape"):
            x = [x]
        if len(x) != len(input_names):
            raise ValueError(
                f"model has {len(input_names)} inputs {input_names}, got {len(x)} arrays"
            )
        return dict(zip(input_names, x))

    # ------------------------------------------------ granular API (parity
    # with C++ train loops: transformer.cc:183-197)

    def start_batch(self, x, y):
        self._current_batch = self._make_batch(self._as_input_dict(x), y)

    def forward(self, seq_length: int = -1):
        assert self._current_batch is not None, "call start_batch first"
        fwd = self.executor._forward_fn or self.executor.build_forward()
        xs, _ = self._current_batch
        self._cached_logits, new_state = fwd(
            self._params, self._state,
            xs, self.config.computation_mode == CompMode.COMP_MODE_TRAINING,
        )
        self._state = new_state
        return self._cached_logits

    def zero_gradients(self):
        self._grads = None

    def backward(self, seq_length: int = -1):
        assert self._current_batch is not None
        xs, labels = self._current_batch
        inner = self.executor.make_loss_fn(self._state, xs, labels, self._rng)

        def loss_fn(p):
            l, (logits, _, ce_sum) = inner(p)
            return l, (logits, ce_sum)

        (lval, (logits, ce_sum)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(self._params)
        self._grads = grads
        self._cached_logits = logits
        self._counters = self.metrics.compute(
            self._counters, logits, self.executor.expand_labels(labels),
            from_logits=not self.executor.last_op_is_softmax,
            scce_sum=ce_sum,
        )
        return lval

    def update(self):
        assert self._grads is not None, "call backward first"
        self._params, self._opt_slots = self.optimizer.update(
            self._grads, self._params, self._opt_slots, self._step
        )
        self._step = self._step + 1
        self._grads = None

    def init_operators(self):
        """No-op on TPU: per-device OpMeta initialization (reference
        init_operators → per-op INIT tasks) has no analog — jit handles it."""

    def reset_metrics(self):
        # placed on the mesh as compile places them: counters that arrive
        # unplaced give the next train step a second argument signature,
        # and with it a second compilation of the whole step
        self._counters = self.executor.replicate(self.metrics.zero_counters())

    def set_learning_rate(self, lr: float):
        """Change the optimizer's learning rate mid-training (the keras
        LearningRateScheduler hook; reference optimizer.cc set_learning_rate
        swaps the kernel constant the same way). The rate is a trace-time
        constant of the fused train step, so the cached executable is
        dropped — the next batch retraces with the new rate (one compile per
        distinct rate, amortized over the epoch that scheduled it)."""
        assert self._compiled, "call compile() before set_learning_rate()"
        if float(lr) == float(self.optimizer.lr):
            return
        self.optimizer.set_learning_rate(lr)
        self.executor._train_step = None
        # chunked executables bake in the same rate constant
        self.executor._chunk_steps.clear()

    def get_perf_metrics(self) -> PerfMetrics:
        return PerfMetrics(jax.device_get(self._counters), self.metrics)

    # ------------------------------------------------ weights I/O
    # (reference ParallelTensorBase::set_tensor/get_tensor)

    def _resolve_weight_owner(self, layer_name: str) -> str:
        """Tied-weight nodes (shared_op) store no parameters of their own —
        reads/writes go to the source layer's set (O(1) via the alias map
        built at compile)."""
        return getattr(self, "_weight_alias", {}).get(layer_name, layer_name)

    def get_weight(self, layer_name: str, weight_name: str) -> np.ndarray:
        layer_name = self._resolve_weight_owner(layer_name)
        return np.asarray(self._params[layer_name][weight_name])

    def set_weight(self, layer_name: str, weight_name: str, value: np.ndarray):
        layer_name = self._resolve_weight_owner(layer_name)
        old = self._params[layer_name][weight_name]
        self._params[layer_name][weight_name] = jax.device_put(
            jnp.asarray(value, old.dtype), old.sharding
        )

    def create_data_loader(self, batch_tensor: Tensor, full_array: np.ndarray):
        from .dataloader import SingleDataLoader

        return SingleDataLoader(self, batch_tensor, full_array)

    def _build_mesh(self, shape):
        """Build this model's mesh, honouring `mesh_device_offset`: a
        nonzero offset carves the mesh out of jax.devices()[offset:], so
        two compiles with disjoint (offset, shape) windows place on
        disjoint chips — the disaggregated serving sub-meshes."""
        off = int(getattr(self.config, "mesh_device_offset", 0) or 0)
        devices = jax.devices()
        if off:
            if off >= len(devices):
                raise ValueError(
                    f"mesh_device_offset {off} >= device count "
                    f"{len(devices)}")
            devices = devices[off:]
        return build_mesh(shape, devices=devices)

    # ------------------------------------------------ serving (serving/)

    def serve(self, **kwargs):
        """Build a ServingEngine on this trained model: compiles the
        single-token *decode* graph from the same PCG (causal attention
        becomes incremental attention over sharded KV-cache state, priced
        and placed by the same Unity search + warm-start plan cache the
        trainer uses), adopts this model's weights by name, and runs
        Orca-style continuous batching over a fixed slot set, with a
        paged block-pool KV cache (COW prefix sharing, chunked prefill
        interleaved with decode) by default (docs/serving.md). kwargs
        override ServingSpec fields — slots, max_seq_len, prefill_chunk,
        kv_layout ("paged"|"contiguous"), kv_block_size, kv_num_blocks,
        prefix_sharing, config_overrides, strategy, ...

        `disaggregate=True` (or --serve-disaggregate) instead builds a
        DisaggregatedServingEngine: prefill and decode compile as TWO
        independent Unity plans on disjoint sub-meshes (serve_prefill_chips
        sizes the prefill side), with each request's KV handed off
        through a verified, priced fftrans transfer program
        (docs/serving.md "Disaggregated serving").

        `speculate=True, draft_model=<small compiled LM>` builds a
        SpeculativeServingEngine: the drafter proposes K tokens per
        round and the target verifies them in one batched call, gated
        by an acceptance-calibrated payoff inequality — token streams
        stay bit-identical to plain decode (serve_draft_chips places
        the drafter on a disjoint sub-mesh; docs/serving.md
        "Speculative decoding")."""
        assert self._compiled, "call compile() before serve()"
        # fail fast on chip-budget flags that exceed THIS process's
        # visible devices, naming the flag — a bad sub-mesh carve
        # otherwise surfaces as an opaque mesh-factorization error
        n_dev = len(jax.devices())
        for flag, field in (("--serve-prefill-chips", "serve_prefill_chips"),
                            ("--serve-draft-chips", "serve_draft_chips")):
            chips = int(getattr(self.config, field, 0) or 0)
            if chips >= n_dev:
                raise ValueError(
                    f"{flag}={chips} but only {n_dev} device(s) are "
                    f"visible; both sides of the split need at least "
                    f"one chip")
        disaggregate = kwargs.pop(
            "disaggregate",
            bool(getattr(self.config, "serve_disaggregate", False)))
        speculate = kwargs.pop("speculate", False)
        if disaggregate and speculate:
            raise ValueError(
                "serve(): disaggregate=True and speculate=True are "
                "mutually exclusive for now (speculative decoding of "
                "the disaggregated decode pool is a ROADMAP item)")
        if disaggregate:
            kwargs.pop("draft_model", None)
            from .serving import DisaggregatedServingEngine

            return DisaggregatedServingEngine(self, **kwargs)
        if speculate:
            from .serving import SpeculativeServingEngine

            return SpeculativeServingEngine(self, **kwargs)
        kwargs.pop("draft_model", None)
        from .serving import ServingEngine

        return ServingEngine(self, **kwargs)

    # ------------------------------------------------ checkpoint / export

    def save_checkpoint(self, path: str):
        """Synchronous atomic checkpoint of the full training state into
        the checkpoint root `path` (resilience/checkpointer.py). Capability
        beyond the reference, which has none (SURVEY §5)."""
        from .resilience import ResilienceManager

        # keep=0: explicit save_checkpoint calls never prune — a user
        # saving milestones must not silently lose all but the newest few
        mgr = ResilienceManager(self, path, keep=0)
        mgr.save(self._py_step(), blocking=True)
        return mgr.checkpointer.last_committed

    def load_checkpoint(self, path: str):
        """Restore the newest committed checkpoint under root `path` (or a
        single checkpoint dir), resharding onto this model's mesh/Strategy
        — the saving run's mesh may differ (resilience/reshard.py)."""
        from .resilience import latest_checkpoint, restore_model

        target = path
        import os

        if not os.path.exists(os.path.join(path, "manifest.json")):
            found = latest_checkpoint(path)
            if found is None:
                raise FileNotFoundError(
                    f"no committed checkpoint under {path!r} (expected a "
                    f"step_*/manifest.json layout; checkpoints written by "
                    f"the pre-resilience orbax format are not readable — "
                    f"re-save with save_checkpoint)")
            target = found
        restore_model(self, target)
        return self

    def export_dot(self, path: str = "") -> str:
        """PCG DOT export (reference --compgraph flag / print_dot)."""
        from .pcg.graph import export_dot

        assert self.graph is not None, "call compile() first"
        return export_dot(self.graph, path or None)

    def print_layers(self, id: int = -1):
        for i, l in enumerate(self.layers):
            if id < 0 or i == id:
                print(f"[{i}] {l.name} {l.op_type.name} "
                      f"in={[t.dims for t in l.inputs]} "
                      f"out={[t.dims for t in l.outputs]}")


from .pcg.graph import is_expert_buffer as _is_expert_buffer  # noqa: E402
