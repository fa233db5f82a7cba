"""Operator library: importing this package populates the op registry."""

from .base import OpContext, OpDef, WeightSpec, get_op_def, register_op, registered_ops
from . import elementwise  # noqa: F401
from . import core  # noqa: F401
from . import shape_ops  # noqa: F401
from . import attention  # noqa: F401
from . import inc_attention  # noqa: F401
from . import moe  # noqa: F401
from . import pipeline_blocks  # noqa: F401

from .core import (
    BatchMatmulParams,
    BatchNormParams,
    Conv2DParams,
    DropoutParams,
    EmbeddingParams,
    LayerNormParams,
    LinearParams,
    Pool2DParams,
    RMSNormParams,
    SoftmaxParams,
)
from .attention import AttentionFrontEnd, MultiHeadAttentionParams
from .inc_attention import (
    IncMultiHeadAttentionParams,
    PagedIncMultiHeadAttentionParams,
)
from .elementwise import ElementBinaryParams, ElementUnaryParams
from .moe import (
    AggregateParams,
    AggregateSpecParams,
    CacheParams,
    ExpertsParams,
    GroupByParams,
    MoEMLPParams,
)
from .pipeline_blocks import PipelineBlocksParams
from .shape_ops import (
    CastParams,
    ConcatParams,
    GatherParams,
    ReduceParams,
    ReshapeParams,
    ReverseParams,
    SplitParams,
    TopKParams,
    TransposeParams,
)
