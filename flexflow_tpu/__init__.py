"""flexflow_tpu: a TPU-native automatic-parallelization DNN framework.

Brand-new design with the capability surface of FlexFlow/Unity (see SURVEY.md):
a layer API builds a Parallel Computation Graph whose tensors carry
per-dimension partition degrees; a Unity-style search chooses the
parallelization strategy against a profiling-based cost model of the TPU pod;
execution lowers to JAX/XLA (jit over a jax.sharding.Mesh, Pallas kernels,
lax collectives) instead of Legion tasks + cuDNN/NCCL.
"""
import time as _time

_T_IMPORT = _time.perf_counter()   # first line: phase `startup.import`

from .config import FFConfig, FFIterationConfig
from .ffconst import (
    ActiMode,
    AggrMode,
    CompMode,
    DataType,
    LossType,
    MetricsType,
    OpType,
    ParameterSyncType,
    PoolType,
)
from .model import FFModel
from .core.tensor import ParallelDim, ParallelTensorShape, Tensor
from .core.machine import MachineResource, MachineView, make_mesh
from .core.graph import Graph
from . import ops  # registers all operator types
from . import parallel  # registers parallel ops
from .runtime.optimizers import AdamOptimizer, Optimizer, SGDOptimizer
from .runtime.losses import Loss
from .runtime.metrics import Metrics, PerfMetrics
from .runtime.dataloader import SingleDataLoader
from .runtime.recompile import RecompileState
from .runtime.initializers import (
    ConstantInitializer,
    GlorotUniformInitializer,
    NormInitializer,
    UniformInitializer,
    ZeroInitializer,
)

from .obs.startup import record_phase as _record_phase

# last line of the import: the package's own modules, and jax's where this
# is its first importer (docs/observability.md "Cold start")
_record_phase("startup.import", _T_IMPORT, _time.perf_counter() - _T_IMPORT)

__version__ = "0.1.0"

__all__ = [
    "FFConfig",
    "FFIterationConfig",
    "FFModel",
    "Tensor",
    "ParallelDim",
    "ParallelTensorShape",
    "MachineView",
    "MachineResource",
    "make_mesh",
    "Graph",
    "ActiMode",
    "AggrMode",
    "CompMode",
    "DataType",
    "LossType",
    "MetricsType",
    "OpType",
    "ParameterSyncType",
    "PoolType",
    "Optimizer",
    "SGDOptimizer",
    "AdamOptimizer",
    "Loss",
    "Metrics",
    "PerfMetrics",
    "SingleDataLoader",
    "GlorotUniformInitializer",
    "ZeroInitializer",
    "UniformInitializer",
    "NormInitializer",
    "ConstantInitializer",
]
