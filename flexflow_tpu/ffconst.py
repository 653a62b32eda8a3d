"""Framework-wide enums.

Mirrors the capability surface of the reference's include/flexflow/ffconst.h
(OperatorType, DataType, LossType, MetricsType, ActiMode, PoolType, AggrMode,
ParameterSyncType, CompMode) re-expressed for a TPU/JAX-native framework.
"""
from __future__ import annotations

import enum


class DataType(enum.Enum):
    DT_BOOLEAN = "bool"
    DT_INT32 = "int32"
    DT_INT64 = "int64"
    DT_HALF = "float16"
    DT_BFLOAT16 = "bfloat16"
    DT_FLOAT = "float32"
    DT_DOUBLE = "float64"
    DT_NONE = "none"

    @property
    def np_dtype(self):
        import numpy as np

        return np.dtype(self.value)

    @classmethod
    def from_numpy(cls, dt) -> "DataType":
        import numpy as np

        return cls(np.dtype(dt).name)

    @property
    def jnp_dtype(self):
        import jax.numpy as jnp

        return jnp.dtype(self.value)


class ActiMode(enum.Enum):
    AC_MODE_NONE = 0
    AC_MODE_RELU = 1
    AC_MODE_SIGMOID = 2
    AC_MODE_TANH = 3
    AC_MODE_GELU = 4
    AC_MODE_SILU = 5


class PoolType(enum.Enum):
    POOL_MAX = 0
    POOL_AVG = 1


class AggrMode(enum.Enum):
    AGGR_MODE_NONE = 0
    AGGR_MODE_SUM = 1
    AGGR_MODE_AVG = 2


class LossType(enum.Enum):
    LOSS_CATEGORICAL_CROSSENTROPY = 0
    LOSS_SPARSE_CATEGORICAL_CROSSENTROPY = 1
    LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE = 2
    LOSS_MEAN_SQUARED_ERROR_SUM_REDUCE = 3
    LOSS_IDENTITY = 4


class MetricsType(enum.Enum):
    METRICS_ACCURACY = 0
    METRICS_CATEGORICAL_CROSSENTROPY = 1
    METRICS_SPARSE_CATEGORICAL_CROSSENTROPY = 2
    METRICS_MEAN_SQUARED_ERROR = 3
    METRICS_ROOT_MEAN_SQUARED_ERROR = 4
    METRICS_MEAN_ABSOLUTE_ERROR = 5


class CompMode(enum.Enum):
    COMP_MODE_TRAINING = 0
    COMP_MODE_INFERENCE = 1


class ParameterSyncType(enum.Enum):
    """Reference distinguishes PS vs NCCL gradient sync (config.h:55-59).

    On TPU both collapse to a psum over the data-parallel mesh axis inside the
    jitted update step; the enum is kept for API compatibility.
    """

    NONE = 0
    PS = 1
    NCCL = 2


class OpType(enum.Enum):
    """Operator types (reference: ffconst.h OperatorType)."""

    NOOP = "noop"
    INPUT = "input"
    WEIGHT = "weight"
    CONV2D = "conv2d"
    DROPOUT = "dropout"
    LINEAR = "linear"
    BATCHMATMUL = "batch_matmul"
    POOL2D = "pool2d"
    SCALAR_MULTIPLY = "scalar_multiply"
    SCALAR_ADD = "scalar_add"
    SCALAR_SUB = "scalar_sub"
    SCALAR_TRUE_DIV = "scalar_true_div"
    RELU = "relu"
    IDENTITY = "identity"
    SIGMOID = "sigmoid"
    TANH = "tanh"
    ELU = "elu"
    GELU = "gelu"
    RSQRT = "rsqrt"
    POW = "pow"
    EXP = "exp"
    SIN = "sin"
    COS = "cos"
    FLAT = "flat"
    SOFTMAX = "softmax"
    BATCHNORM = "batchnorm"
    LAYERNORM = "layernorm"
    RMSNORM = "rmsnorm"
    CONCAT = "concat"
    SPLIT = "split"
    EMBEDDING = "embedding"
    GATHER = "gather"
    CACHE = "cache"
    AGGREGATE = "aggregate"
    AGGREGATE_SPEC = "aggregate_spec"
    RESHAPE = "reshape"
    REVERSE = "reverse"
    TRANSPOSE = "transpose"
    EW_ADD = "ew_add"
    EW_MUL = "ew_mul"
    EW_SUB = "ew_sub"
    EW_DIV = "ew_div"
    EW_MAX = "ew_max"
    EW_MIN = "ew_min"
    REDUCE_SUM = "reduce_sum"
    MEAN = "mean"
    CAST = "cast"
    MULTIHEAD_ATTENTION = "multihead_attention"
    # latent attention (ops/latent_attention.py); the value is its device
    # scope prefix, `mla:<name>`
    LATENT_ATTENTION = "mla"
    TOPK = "topk"
    GROUP_BY = "group_by"
    EXPERTS = "experts"
    # dropless gated experts, told which experts they hold (ops/moe.py);
    # device scopes `moe_router:<name>` and `moe:<name>`
    MOE_ROUTER = "moe_router"
    GATED_EXPERTS = "moe"
    FUSED = "fused"
    LSTM = "lstm"
    # Mamba-2 state-space mixer (ops/ssm.py); device scope `ssm:<name>`
    SSM = "ssm"
    # Parallel ops (reference: src/parallel_ops)
    REPARTITION = "repartition"
    COMBINE = "combine"
    REPLICATE = "replicate"
    REDUCTION = "reduction"
    ALLREDUCE = "allreduce"
    FUSED_PARALLEL = "fused_parallel"
    PIPELINE = "pipeline"
    # TPU-native new capability: sequence/context parallel attention
    RING_ATTENTION = "ring_attention"


# Parallel-dimension kinds used by the strategy layer / search.
class ParallelDimKind(enum.Enum):
    SAMPLE = "sample"  # batch dim (data parallelism)
    CHANNEL = "channel"  # feature dims (tensor/"parameter" parallelism)
    ATTRIBUTE = "attribute"  # spatial/attribute dims
    SEQUENCE = "sequence"  # sequence dim (context parallelism — new on TPU)
    REPLICA = "replica"  # replication dim
    EXPERT = "expert"  # expert dim (MoE)
