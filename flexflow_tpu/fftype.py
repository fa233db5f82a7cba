"""Core enums and type constants for the TPU-native FlexFlow framework.

Mirrors the *surface* of the reference's constant vocabulary
(/root/reference/include/flexflow/ffconst.h) so user code written against the
reference's Python API maps one-to-one, while the values behind them drive a
JAX/XLA execution model instead of Legion tasks.
"""

from __future__ import annotations

import enum

import jax.numpy as jnp


class ActiMode(enum.IntEnum):
    AC_MODE_NONE = 10
    AC_MODE_RELU = 11
    AC_MODE_SIGMOID = 12
    AC_MODE_TANH = 13
    AC_MODE_GELU = 14


class RegularizerMode(enum.IntEnum):
    REG_MODE_NONE = 17
    REG_MODE_L1 = 18
    REG_MODE_L2 = 19


class AggrMode(enum.IntEnum):
    AGGR_MODE_NONE = 20
    AGGR_MODE_SUM = 21
    AGGR_MODE_AVG = 22


class PoolType(enum.IntEnum):
    POOL_MAX = 30
    POOL_AVG = 31


class DataType(enum.IntEnum):
    DT_BOOLEAN = 40
    DT_INT32 = 41
    DT_INT64 = 42
    DT_HALF = 43
    DT_BFLOAT16 = 46  # TPU-native addition: bf16 is the MXU's home dtype
    DT_FLOAT = 44
    DT_DOUBLE = 45
    DT_NONE = 49


_DTYPE_TO_JNP = {
    DataType.DT_BOOLEAN: jnp.bool_,
    DataType.DT_INT32: jnp.int32,
    DataType.DT_INT64: jnp.int64,
    DataType.DT_HALF: jnp.float16,
    DataType.DT_BFLOAT16: jnp.bfloat16,
    DataType.DT_FLOAT: jnp.float32,
    DataType.DT_DOUBLE: jnp.float64,
}

_JNP_TO_DTYPE = {
    jnp.dtype("bool"): DataType.DT_BOOLEAN,
    jnp.dtype("int32"): DataType.DT_INT32,
    jnp.dtype("int64"): DataType.DT_INT64,
    jnp.dtype("float16"): DataType.DT_HALF,
    jnp.dtype("bfloat16"): DataType.DT_BFLOAT16,
    jnp.dtype("float32"): DataType.DT_FLOAT,
    jnp.dtype("float64"): DataType.DT_DOUBLE,
}


def dtype_to_jnp(dt: DataType):
    return _DTYPE_TO_JNP[DataType(dt)]


def jnp_to_dtype(dt) -> DataType:
    return _JNP_TO_DTYPE[jnp.dtype(dt)]


def size_of_datatype(dt: DataType) -> int:
    return jnp.dtype(dtype_to_jnp(dt)).itemsize


class LossType(enum.IntEnum):
    LOSS_CATEGORICAL_CROSSENTROPY = 50
    LOSS_SPARSE_CATEGORICAL_CROSSENTROPY = 51
    LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE = 52
    LOSS_MEAN_SQUARED_ERROR_SUM_REDUCE = 53
    LOSS_IDENTITY = 54


class CompMode(enum.IntEnum):
    COMP_MODE_TRAINING = 70
    COMP_MODE_INFERENCE = 71


class ParameterSyncType(enum.IntEnum):
    """Kept for API parity (reference: include/flexflow/ffconst.h:52-56).
    On TPU NCCL-mode sync lowers to an XLA psum over the data axes, chosen
    by GSPMD from shardings. PS (hub-and-spoke parameter server,
    optimizer_kernel.cu:48-76) is rejected at tensor construction: a psum
    riding ICI strictly dominates it on TPU (SURVEY §7)."""

    NONE = 80
    PS = 81
    NCCL = 82


class MetricsType(enum.IntEnum):
    METRICS_ACCURACY = 1001
    METRICS_CATEGORICAL_CROSSENTROPY = 1002
    METRICS_SPARSE_CATEGORICAL_CROSSENTROPY = 1004
    METRICS_MEAN_SQUARED_ERROR = 1008
    METRICS_ROOT_MEAN_SQUARED_ERROR = 1016
    METRICS_MEAN_ABSOLUTE_ERROR = 1032


class OperatorType(enum.IntEnum):
    """Operator vocabulary (reference: include/flexflow/ffconst.h:69-162)."""

    OP_INPUT = enum.auto()
    OP_WEIGHT = enum.auto()
    OP_NOOP = enum.auto()
    OP_CONV2D = enum.auto()
    OP_DROPOUT = enum.auto()
    OP_LINEAR = enum.auto()
    OP_BATCHMATMUL = enum.auto()
    OP_POOL2D = enum.auto()
    OP_SCALAR_MULTIPLY = enum.auto()
    OP_SCALAR_ADD = enum.auto()
    OP_SCALAR_FLOOR_DIV = enum.auto()
    OP_SCALAR_TRUE_DIV = enum.auto()
    OP_SCALAR_SUB = enum.auto()
    OP_RELU = enum.auto()
    OP_IDENTITY = enum.auto()
    OP_SIGMOID = enum.auto()
    OP_TANH = enum.auto()
    OP_ELU = enum.auto()
    OP_FLAT = enum.auto()
    OP_SOFTMAX = enum.auto()
    OP_BATCHNORM = enum.auto()
    OP_CONCAT = enum.auto()
    OP_SPLIT = enum.auto()
    OP_EMBEDDING = enum.auto()
    OP_GROUP_BY = enum.auto()
    OP_CACHE = enum.auto()
    OP_AGGREGATE = enum.auto()
    OP_AGG_SPEC = enum.auto()
    # TPU-native addition: stacked-experts op enabling expert-axis sharding
    OP_EXPERTS = enum.auto()
    # TPU-native addition: stacked transformer blocks runnable as a
    # ppermute pipeline over the `pipe` mesh axis (parallel/pipeline.py)
    OP_PIPE_BLOCKS = enum.auto()
    OP_RESHAPE = enum.auto()
    OP_REVERSE = enum.auto()
    OP_TRANSPOSE = enum.auto()
    OP_EW_ADD = enum.auto()
    OP_EW_MUL = enum.auto()
    OP_MATMUL = enum.auto()
    OP_MUL = enum.auto()
    OP_ENLARGE = enum.auto()
    OP_SQUEEZE = enum.auto()
    OP_UNSQUEEZE = enum.auto()
    OP_EW_SUB = enum.auto()
    OP_EW_DIV = enum.auto()
    OP_EW_EQUAL = enum.auto()
    OP_EW_GREATER = enum.auto()
    OP_EW_LESS = enum.auto()
    OP_EW_MAX = enum.auto()
    OP_EW_MIN = enum.auto()
    OP_REDUCE_ARGMAX = enum.auto()
    OP_REDUCE_ARGMIN = enum.auto()
    OP_REDUCE_MAX = enum.auto()
    OP_REDUCE_MEAN = enum.auto()
    OP_REDUCE_MIN = enum.auto()
    OP_REDUCE_PROD = enum.auto()
    OP_REDUCE_SUM = enum.auto()
    OP_PAD = enum.auto()
    OP_SHAPE = enum.auto()
    OP_SIZE = enum.auto()
    OP_TOPK = enum.auto()
    OP_WHERE = enum.auto()
    OP_CEIL = enum.auto()
    OP_CAST = enum.auto()
    OP_EXP = enum.auto()
    OP_ROUND = enum.auto()
    OP_LOG = enum.auto()
    OP_LOGICAL_NOT = enum.auto()
    OP_SQRT = enum.auto()
    OP_SIN = enum.auto()
    OP_COS = enum.auto()
    OP_LEAKYRELU = enum.auto()
    OP_SLICE = enum.auto()
    OP_RESIZE = enum.auto()
    OP_PRELU = enum.auto()
    OP_GELU = enum.auto()
    OP_MULTIHEAD_ATTENTION = enum.auto()
    # incremental (decode-phase) self-attention over a stateful KV cache —
    # the serving-engine op the reference snapshot predates (its later
    # serving rewrite added IncMultiHeadSelfAttention; PAPER.md §0)
    OP_INC_MULTIHEAD_ATTENTION = enum.auto()
    # paged variant: the KV cache is a shared block pool + per-slot page
    # tables (vLLM/PagedAttention, SOSP '23) instead of a contiguous
    # per-slot region — the serving memory lever (docs/serving.md)
    OP_PAGED_INC_MULTIHEAD_ATTENTION = enum.auto()
    OP_FUSED = enum.auto()
    OP_RSQRT = enum.auto()
    OP_POW = enum.auto()
    OP_MEAN = enum.auto()
    OP_LAYERNORM = enum.auto()
    OP_GATHER = enum.auto()
    # Parallelization operators — first-class PCG nodes
    # (reference: src/parallel_ops/*)
    OP_REPARTITION = enum.auto()
    OP_COMBINE = enum.auto()
    OP_REPLICATE = enum.auto()
    OP_REDUCTION = enum.auto()
    OP_PIPELINE = enum.auto()
    OP_FUSED_PARALLEL = enum.auto()
    OP_INVALID = enum.auto()
    # the post-2020 LM block's vocabulary, appended so the members above
    # keep their values: RMSNorm, and the token-routed expert layer
    # (router, dropless top-k dispatch, SiLU-gated experts, combine)
    OP_RMSNORM = enum.auto()
    OP_MOE_MLP = enum.auto()
    # latent attention with a learned sparse selection (DeepSeek-V3.2):
    # the training-shaped op in the expanded form, and its decode op in
    # the absorbed form over a paged latent cache (ops/latent_attention.py)
    OP_LATENT_ATTENTION = enum.auto()
    OP_PAGED_LATENT_ATTENTION = enum.auto()
    # gated delta-rule linear attention (ops/delta_attention.py): the
    # training-shaped op, and its decode op over per-slot recurrent state
    OP_GATED_DELTA_ATTENTION = enum.auto()
    OP_GATED_DELTA_ATTENTION_DECODE = enum.auto()
    # the selective state-space layer (Mamba-1; ops/ssm.py): the
    # training-shaped op, and its decode op over per-slot recurrent state
    OP_SELECTIVE_SSM = enum.auto()
    OP_SELECTIVE_SSM_DECODE = enum.auto()
    # the gated short convolution (LFM2's conv mixer; ops/short_conv.py),
    # training-shaped; it has no decode op
    OP_SHORT_CONV = enum.auto()


PARALLEL_OP_TYPES = frozenset(
    {
        OperatorType.OP_REPARTITION,
        OperatorType.OP_COMBINE,
        OperatorType.OP_REPLICATE,
        OperatorType.OP_REDUCTION,
        OperatorType.OP_PIPELINE,
        OperatorType.OP_FUSED_PARALLEL,
    }
)


# guid ranges (reference: ffconst.h:230-239) — kept so tooling that keys on
# guid ranges (e.g. layer-vs-op discrimination) behaves identically.
LAYER_GUID_FIRST_VALID = 1000000
LAYER_GUID_LAST_VALID = 1999999
OP_GUID_FIRST_VALID = 2000000
OP_GUID_LAST_VALID = 2999999
TENSOR_GUID_FIRST_VALID = 3000000
TENSOR_GUID_LAST_VALID = 3999999
PARALLEL_TENSOR_GUID_FIRST_VALID = 4000000
NODE_GUID_FIRST_VALID = 5000000
