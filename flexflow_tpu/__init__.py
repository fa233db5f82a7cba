"""flexflow_tpu: a TPU-native distributed DNN training framework.

Same capabilities as FlexFlow (PCG parallelism IR + Unity strategy search +
full operator/model surface), re-designed for TPU: JAX/XLA/Pallas compute,
GSPMD sharding over an ICI mesh, collectives instead of task-based data
movement. See SURVEY.md for the capability map against the reference.
"""

import time as _time

# the `import` phase of the start-up record (telemetry/startup.py): what
# this package's own imports cost, from here to the last line
_IMPORT_T0 = _time.perf_counter()

from .config import FFConfig, FFIterationConfig
from .fftype import (
    ActiMode,
    AggrMode,
    CompMode,
    DataType,
    LossType,
    MetricsType,
    OperatorType,
    ParameterSyncType,
    PoolType,
    RegularizerMode,
)
from .initializer import (
    ConstantInitializer,
    GlorotUniformInitializer,
    Initializer,
    NormInitializer,
    UniformInitializer,
    ZeroInitializer,
)
from .machine import MachineResource, MachineView, MeshShape, build_mesh
from .metrics import Metrics, PerfMetrics
from .model import FFModel
from . import parallel  # registers parallel-op OpDefs
from . import resilience  # checkpointing / elastic resume / preemption
from . import serving  # decode-graph inference + continuous batching
from . import telemetry  # tracer + run metrics + leveled logging
from .parallel import Strategy
from .optimizer import AdamOptimizer, Optimizer, SGDOptimizer
from .tensor import ParallelDim, ParallelTensor, ParallelTensorShape, Tensor

__version__ = "0.1.0"

telemetry.startup.complete("import", _IMPORT_T0, _time.perf_counter())
