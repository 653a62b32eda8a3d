"""FFModel: the user-facing model container and layer API.

TPU-native re-design of the reference's FFModel (include/flexflow/model.h:326-958,
src/runtime/model.cc). The layer-building methods mirror model.h:336-554 /
python flexflow_cffi.py:887+ signatures; `compile()` (reference model.cc:2803)
chooses a parallelization strategy, builds the device mesh, and compiles the
whole training iteration with XLA; `fit()` mirrors flexflow_cffi.py:2062.
"""
from __future__ import annotations

import logging
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .config import FFConfig
from .core.graph import Graph
from .core.machine import MachineView, make_mesh
from .core.op import OP_REGISTRY, Op
from .core.tensor import ParallelDim, ParallelTensorShape, Tensor
from .ffconst import (
    ActiMode,
    AggrMode,
    CompMode,
    DataType,
    LossType,
    MetricsType,
    OpType,
    ParallelDimKind,
    PoolType,
)
from .runtime.executor import Executor
from .runtime.losses import Loss
from .runtime.metrics import Metrics, PerfMetrics
from .runtime.optimizers import Optimizer, SGDOptimizer

_log = logging.getLogger("flexflow_tpu.model")


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        self.config = config or FFConfig()
        self.ops: List[Op] = []
        self.input_ops: List[Op] = []
        self.optimizer: Optional[Optimizer] = None
        self.loss: Optional[Loss] = None
        self.metrics: Optional[Metrics] = None
        self.label_tensor: Optional[Tensor] = None
        self.final_tensor: Optional[Tensor] = None
        self.graph: Optional[Graph] = None
        self.executor: Optional[Executor] = None
        self.mesh = None
        self.params = None
        self.opt_state = None
        self.state = None
        self.perf_metrics = PerfMetrics()
        self._rng_seed = self.config.seed
        self._step_count = 0
        self._name_counts: Dict[OpType, int] = {}
        self._used_names: set = set()
        self._compiled = False
        self._recompile_state = None
        self._op_strategies = None
        self.search_result = None
        # per-step observability ring (obs/stepstats.py), populated by fit()
        self.step_stats = None
        self._dataloaders: List[Any] = []
        self._accum_grad = self._accum_add = self._accum_update = None
        # (op_name, weight_name, fn) regularization terms added to the loss
        self.weight_regularizers: List[Tuple[str, str, Any]] = []
        # node-key cache (reference: get_or_create_node, model.h:678-706)
        self._op_cache: Dict[Tuple, Op] = {}

    def add_weight_regularizer(self, op_name: str, weight_name: str, fn) -> None:
        """Add a per-weight regularization term fn(weight)->scalar to the
        training loss (keras kernel_regularizer support)."""
        self.weight_regularizers.append((op_name, weight_name, fn))

    # ------------------------------------------------------------------
    # tensor & op creation
    # ------------------------------------------------------------------
    def create_tensor(
        self,
        dims: Sequence[int],
        dtype: DataType = DataType.DT_FLOAT,
        create_grad: bool = True,
        name: str = "",
    ) -> Tensor:
        op = OP_REGISTRY[OpType.INPUT](
            self, [], name=name or f"input_{len(self.input_ops)}",
            dims=tuple(dims), dtype=dtype,
        )
        self.ops.append(op)
        self.input_ops.append(op)
        t = op.outputs[0]
        t.create_gradients = create_grad
        t._model = self
        return t

    def _add_op(self, op_type: OpType, inputs: Sequence[Tensor], name: str = "", **params) -> Op:
        cls = OP_REGISTRY[op_type]
        if not name:
            # per-model sequential names: two identical model definitions get
            # identical op names regardless of process history, so
            # checkpoints key params stably (guids stay globally unique);
            # skip names the user already took — params are keyed by name
            while True:
                idx = self._name_counts.get(op_type, 0)
                self._name_counts[op_type] = idx + 1
                name = f"{op_type.value}_{idx}"
                if name not in self._used_names:
                    break
        elif name in self._used_names:
            raise ValueError(f"duplicate op name {name!r}")
        self._used_names.add(name)
        op = cls(self, list(inputs), name=name, **params)
        self.ops.append(op)
        for t in op.outputs:
            t._model = self
        return op

    def _unary(self, op_type, x, name="", **params) -> Tensor:
        return self._add_op(op_type, [x], name, **params).outputs[0]

    def _binary(self, op_type, x, y, name="") -> Tensor:
        return self._add_op(op_type, [x, y], name).outputs[0]

    # -- elementwise (reference model.h:336-400) ------------------------
    def exp(self, x, name=""):
        return self._unary(OpType.EXP, x, name)

    def sin(self, x, name=""):
        return self._unary(OpType.SIN, x, name)

    def cos(self, x, name=""):
        return self._unary(OpType.COS, x, name)

    def pow(self, x, exponent, name=""):
        return self._unary(OpType.POW, x, name, exponent=exponent)

    def rsqrt(self, x, name=""):
        return self._unary(OpType.RSQRT, x, name)

    def add(self, x, y, name=""):
        return self._binary(OpType.EW_ADD, x, y, name)

    def subtract(self, x, y, name=""):
        return self._binary(OpType.EW_SUB, x, y, name)

    def multiply(self, x, y, name=""):
        return self._binary(OpType.EW_MUL, x, y, name)

    def divide(self, x, y, name=""):
        return self._binary(OpType.EW_DIV, x, y, name)

    def max(self, x, y, name=""):
        return self._binary(OpType.EW_MAX, x, y, name)

    def min(self, x, y, name=""):
        return self._binary(OpType.EW_MIN, x, y, name)

    def scalar_multiply(self, x, scalar, inplace=True, name=""):
        return self._unary(OpType.SCALAR_MULTIPLY, x, name, scalar=scalar)

    def scalar_add(self, x, scalar, inplace=True, name=""):
        return self._unary(OpType.SCALAR_ADD, x, name, scalar=scalar)

    def scalar_sub(self, x, scalar, inplace=True, name=""):
        return self._unary(OpType.SCALAR_SUB, x, name, scalar=scalar)

    def scalar_true_divide(self, x, scalar, inplace=True, name=""):
        return self._unary(OpType.SCALAR_TRUE_DIV, x, name, scalar=scalar)

    def relu(self, x, name=""):
        return self._unary(OpType.RELU, x, name)

    def identity(self, x, name=""):
        return self._unary(OpType.IDENTITY, x, name)

    def sigmoid(self, x, name=""):
        return self._unary(OpType.SIGMOID, x, name)

    def tanh(self, x, name=""):
        return self._unary(OpType.TANH, x, name)

    def elu(self, x, inplace=True, name=""):
        return self._unary(OpType.ELU, x, name)

    def gelu(self, x, name=""):
        return self._unary(OpType.GELU, x, name)

    # -- dense / conv / pool / norm (reference model.h:401-470) ----------
    def dense(
        self,
        input: Tensor,
        out_dim: int,
        activation: ActiMode = ActiMode.AC_MODE_NONE,
        use_bias: bool = True,
        datatype: Optional[DataType] = None,
        shared_op=None,
        kernel_initializer=None,
        bias_initializer=None,
        name: str = "",
        kernel_datatype: Optional[DataType] = None,
    ) -> Tensor:
        """`datatype`: the output's (and, unless `kernel_datatype` says
        otherwise, the kernel's) type; default the input's."""
        return self._add_op(
            OpType.LINEAR,
            [input],
            name,
            out_dim=out_dim,
            activation=activation,
            use_bias=use_bias,
            dtype=datatype,
            kernel_dtype=kernel_datatype,
            kernel_initializer=kernel_initializer,
            bias_initializer=bias_initializer,
        ).outputs[0]

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
        kernel_initializer=None,
        bias_initializer=None,
        name: str = "",
    ) -> Tensor:
        return self._add_op(
            OpType.CONV2D,
            [input],
            name,
            out_channels=out_channels,
            kernel_h=kernel_h,
            kernel_w=kernel_w,
            stride_h=stride_h,
            stride_w=stride_w,
            padding_h=padding_h,
            padding_w=padding_w,
            activation=activation,
            groups=groups,
            use_bias=use_bias,
            kernel_initializer=kernel_initializer,
            bias_initializer=bias_initializer,
        ).outputs[0]

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
        return self._add_op(
            OpType.POOL2D,
            [input],
            name,
            kernel_h=kernel_h,
            kernel_w=kernel_w,
            stride_h=stride_h,
            stride_w=stride_w,
            padding_h=padding_h,
            padding_w=padding_w,
            pool_type=pool_type,
            activation=activation,
        ).outputs[0]

    def batch_norm(self, input: Tensor, relu: bool = True, name: str = "") -> Tensor:
        return self._add_op(OpType.BATCHNORM, [input], name, relu=relu).outputs[0]

    def layer_norm(
        self,
        input: Tensor,
        axes: Sequence[int],
        elementwise_affine: bool = True,
        eps: float = 1e-5,
        name: str = "",
    ) -> Tensor:
        axes = [a if a >= 0 else input.num_dims + a for a in axes]
        return self._add_op(
            OpType.LAYERNORM, [input], name,
            axes=tuple(axes), elementwise_affine=elementwise_affine, eps=eps,
        ).outputs[0]

    def rms_norm(
        self,
        input: Tensor,
        axes: Sequence[int],
        elementwise_affine: bool = True,
        eps: float = 1e-6,
        name: str = "",
    ) -> Tensor:
        axes = [a if a >= 0 else input.num_dims + a for a in axes]
        return self._add_op(
            OpType.RMSNORM, [input], name,
            axes=tuple(axes), elementwise_affine=elementwise_affine, eps=eps,
        ).outputs[0]

    def softmax(self, input: Tensor, axis: int = -1, name: str = "") -> Tensor:
        return self._add_op(OpType.SOFTMAX, [input], name, axis=axis).outputs[0]

    def flat(self, input: Tensor, name: str = "") -> Tensor:
        return self._add_op(OpType.FLAT, [input], name).outputs[0]

    def dropout(self, input: Tensor, rate: float = 0.5, seed: int = 0, name: str = "") -> Tensor:
        return self._add_op(OpType.DROPOUT, [input], name, rate=rate, seed=seed).outputs[0]

    # -- embedding / attention ------------------------------------------
    def embedding(
        self,
        input: Tensor,
        num_entries: int,
        out_dim: int,
        aggr: AggrMode = AggrMode.AGGR_MODE_NONE,
        dtype: DataType = DataType.DT_FLOAT,
        shared_op=None,
        kernel_initializer=None,
        name: str = "",
    ) -> Tensor:
        return self._add_op(
            OpType.EMBEDDING,
            [input],
            name,
            num_entries=num_entries,
            out_dim=out_dim,
            aggr=aggr,
            dtype=dtype,
            kernel_initializer=kernel_initializer,
        ).outputs[0]

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
        causal: bool = False,
        sequence_parallel: bool = False,
        sequence_parallel_mode: str = "ring",
        use_flash: Optional[bool] = None,
        kernel_initializer=None,
        name: str = "",
        kv_heads: int = 0,
        rope_parameters=None,
        key_multiplier: float = 1.0,
        window: int = 0,
        head_gate: bool = False,
    ) -> Tensor:
        """`kv_heads` (0 = `num_heads`): grouped KV heads, query head j
        reading KV head j // (num_heads / kv_heads); `rope_parameters`: the
        model's published rotary group (ops/rope.py), queries and keys
        rotated at their positions before the cache write (half-split
        pairs); `key_multiplier` scales the projected keys; `window` W
        (0 = none; needs `causal`): query t sees the keys s with
        0 <= t - s < W, and the serving cache is a ring of W rows;
        `head_gate`: each head's context times the sigmoid of its own
        logit, a projection (`wg`) of the op's query input."""
        # only what departs from plain multi-head attention becomes an op
        # parameter: the keys of the cost caches and of stored strategies
        # for every model without them stay what they were
        extra = {}
        if kv_heads and kv_heads != num_heads:
            extra["kv_heads"] = int(kv_heads)
        if rope_parameters:
            extra["rope_parameters"] = dict(rope_parameters)
        if float(key_multiplier) != 1.0:
            extra["key_multiplier"] = float(key_multiplier)
        if window:
            extra["window"] = int(window)
        if head_gate:
            extra["head_gate"] = True
        return self._add_op(
            OpType.MULTIHEAD_ATTENTION,
            [query, key, value],
            name,
            embed_dim=embed_dim,
            num_heads=num_heads,
            kdim=kdim or None,
            vdim=vdim or None,
            dropout=dropout,
            bias=bias,
            add_bias_kv=add_bias_kv,
            add_zero_attn=add_zero_attn,
            causal=causal,
            sequence_parallel=sequence_parallel,
            sequence_parallel_mode=sequence_parallel_mode,
            use_flash=use_flash,
            kernel_initializer=kernel_initializer,
            **extra,
        ).outputs[0]

    # -- shape ops -------------------------------------------------------
    def concat(self, tensors: Sequence[Tensor], axis: int, name: str = "") -> Tensor:
        return self._add_op(OpType.CONCAT, list(tensors), name, axis=axis).outputs[0]

    def split(self, input: Tensor, sizes, axis: int, name: str = "") -> List[Tensor]:
        if isinstance(sizes, int):
            assert input.dims[axis] % sizes == 0
            sizes = [input.dims[axis] // sizes] * sizes
        return self._add_op(
            OpType.SPLIT, [input], name, sizes=tuple(sizes), axis=axis
        ).outputs

    def reshape(self, input: Tensor, shape: Sequence[int], name: str = "") -> Tensor:
        return self._add_op(OpType.RESHAPE, [input], name, shape=tuple(shape)).outputs[0]

    def transpose(self, input: Tensor, perm: Sequence[int], name: str = "") -> Tensor:
        return self._add_op(OpType.TRANSPOSE, [input], name, perm=tuple(perm)).outputs[0]

    def reverse(self, input: Tensor, axis: int, name: str = "") -> Tensor:
        return self._add_op(OpType.REVERSE, [input], name, axis=axis).outputs[0]

    def cast(self, input: Tensor, dtype: DataType, name: str = "") -> Tensor:
        return self._add_op(OpType.CAST, [input], name, dtype=dtype).outputs[0]

    def gather(self, input: Tensor, index: Tensor, dim: int = 0, name: str = "") -> Tensor:
        return self._add_op(OpType.GATHER, [input, index], name, axis=dim).outputs[0]

    def reduce_sum(self, input: Tensor, axes: Sequence[int], keepdims: bool = False, name: str = "") -> Tensor:
        return self._add_op(
            OpType.REDUCE_SUM, [input], name, axes=tuple(axes), keepdims=keepdims
        ).outputs[0]

    def mean(self, input: Tensor, dims: Sequence[int], keepdims: bool = False, name: str = "") -> Tensor:
        return self._add_op(
            OpType.MEAN, [input], name, axes=tuple(dims), keepdims=keepdims
        ).outputs[0]

    def batch_matmul(
        self, A: Tensor, B: Tensor,
        a_seq_length_dim: int = -1, b_seq_length_dim: int = -1, name: str = "",
    ) -> Tensor:
        return self._add_op(
            OpType.BATCHMATMUL, [A, B], name,
            a_seq_length_dim=a_seq_length_dim, b_seq_length_dim=b_seq_length_dim,
        ).outputs[0]

    # -- MoE (reference model.h:509-514, src/ops/{topk,group_by,aggregate,cache}.cc)
    def lstm(self, input: Tensor, hidden_size: int,
             return_sequences: bool = True, name: str = "") -> Tensor:
        """Scan-based LSTM layer (reference capability: nmt/lstm.cu)."""
        return self._add_op(
            OpType.LSTM, [input], name,
            hidden_size=hidden_size, return_sequences=return_sequences,
        ).outputs[0]

    def top_k(self, input: Tensor, k: int, sorted: bool = False, name: str = "") -> Tuple[Tensor, Tensor]:
        outs = self._add_op(OpType.TOPK, [input], name, k=k, sorted=sorted).outputs
        return outs[0], outs[1]

    def group_by(self, input: Tensor, assign: Tensor, n: int, alpha: float = 1.0, name: str = "") -> List[Tensor]:
        return self._add_op(
            OpType.GROUP_BY, [input, assign], name, n=n, alpha=alpha
        ).outputs

    def aggregate(
        self, gate_preds, gate_assign, true_gate_assign, full_gate_grads,
        exp_preds: Sequence[Tensor], n: int, lambda_bal: float = 0.0, name: str = "",
    ) -> Tensor:
        ins = [gate_preds, gate_assign, true_gate_assign, full_gate_grads] + list(exp_preds)
        return self._add_op(
            OpType.AGGREGATE, ins, name, n=n, lambda_bal=lambda_bal
        ).outputs[0]

    def aggregate_spec(self, inputs: Sequence[Tensor], n: int, lambda_bal: float = 0.0, name: str = "") -> Tensor:
        return self._add_op(OpType.AGGREGATE_SPEC, list(inputs), name, n=n, lambda_bal=lambda_bal).outputs[0]

    def cache(self, input: Tensor, num_batches: int = 1, name: str = "") -> Tensor:
        return self._add_op(OpType.CACHE, [input], name, num_batches=num_batches).outputs[0]

    # -- explicit parallel ops (reference: src/parallel_ops/) ------------
    def repartition(self, input: Tensor, dim: int, degree: int,
                    axis: Optional[str] = None, name: str = "") -> Tensor:
        return self._add_op(OpType.REPARTITION, [input], name, dim=dim,
                            degree=degree, axis=axis).outputs[0]

    def combine(self, input: Tensor, dim: int, degree: int = 1, name: str = "") -> Tensor:
        return self._add_op(OpType.COMBINE, [input], name, dim=dim, degree=degree).outputs[0]

    def replicate(self, input: Tensor, degree: int = 1, name: str = "") -> Tensor:
        return self._add_op(OpType.REPLICATE, [input], name, degree=degree).outputs[0]

    def reduction(self, input: Tensor, degree: int = 1, name: str = "") -> Tensor:
        return self._add_op(OpType.REDUCTION, [input], name, degree=degree).outputs[0]

    def allreduce(self, input: Tensor, axis_name: str = "data", name: str = "") -> Tensor:
        return self._add_op(OpType.ALLREDUCE, [input], name, axis_name=axis_name).outputs[0]

    def fused_parallel(self, input: Tensor, descriptors: Sequence[dict],
                       name: str = "") -> Tensor:
        """Chain of parallel-op descriptors applied as ONE reshard
        (reference: src/parallel_ops/fused_parallel_op.cc). Each descriptor:
        {"type": "partition"|"combine"|"replicate", "dim": int,
        "degree": int, "axis": Optional[str]} — see parallel/parallel_ops.py
        FusedParallelOp."""
        return self._add_op(OpType.FUSED_PARALLEL, [input], name,
                            descriptors=list(descriptors)).outputs[0]

    def create_constant(self, value, trainable: bool = False,
                        dtype: Optional[DataType] = None,
                        name: str = "") -> Tensor:
        """Fixed tensor value as a graph source (torch-frontend get_attr
        support; reference: torch/model.py:2427+ attribute access).
        trainable=True makes it a real parameter."""
        value = np.asarray(value)
        if dtype is not None:
            value = value.astype(dtype.np_dtype)
        return self._add_op(OpType.WEIGHT, [], name, value=value,
                            trainable=trainable, dtype=dtype).outputs[0]

    def experts(
        self,
        input: Tensor,
        gate_preds: Tensor,
        assign: Tensor,
        num_exp: int,
        out_dim: int,
        alpha: float = 2.0,
        lambda_bal: float = 0.0,
        full_gate: Optional[Tensor] = None,
        activation: ActiMode = ActiMode.AC_MODE_RELU,
        kernel_initializer=None,
        name: str = "",
    ) -> Tensor:
        """Fused expert block with device-level expert parallelism (see
        ops/moe.py ExpertsOp; reference: search-placed expert ops,
        src/ops/group_by.cc + examples/cpp/mixture_of_experts/moe.cc)."""
        ins = [input, gate_preds, assign]
        if full_gate is not None:
            ins.append(full_gate)
        return self._add_op(
            OpType.EXPERTS, ins, name, n=num_exp, out_dim=out_dim,
            alpha=alpha, lambda_bal=lambda_bal, activation=activation,
            kernel_initializer=kernel_initializer,
        ).outputs[0]

    def moe_router(self, input: Tensor, num_exp: int, num_select: int,
                   scale: float = 1.0, kernel_initializer=None,
                   name: str = "",
                   scoring: str = "softmax") -> Tuple[Tensor, Tensor]:
        """Router of a dropless expert layer (ops/moe.py MoERouterOp):
        float32 logits over all `num_exp` experts, the `num_select`
        largest, softmax over those (`scoring="sigmoid"`: their sigmoids
        over the sum of those). Returns (weights, expert ids)."""
        extra = {} if scoring == "softmax" else {"scoring": scoring}
        outs = self._add_op(
            OpType.MOE_ROUTER, [input], name, n=num_exp, k=num_select,
            scale=scale, kernel_initializer=kernel_initializer,
            **extra).outputs
        return outs[0], outs[1]

    def gated_experts(self, input: Tensor, gate_weights: Tensor,
                      assign: Tensor, experts_total: int,
                      expert_hidden_size: int,
                      local_experts: Optional[Tuple[int, int]] = None,
                      kernel_initializer=None, name: str = "") -> Tensor:
        """Routed part of a gated (SiLU) expert layer for the experts held
        here, dropless (ops/moe.py GatedExpertsOp). `local_experts` =
        (first, count) names the range of the `experts_total` this holder
        has; None = all of them."""
        local = (0, int(experts_total)) if local_experts is None \
            else (int(local_experts[0]), int(local_experts[1]))
        return self._add_op(
            OpType.GATED_EXPERTS, [input, gate_weights, assign], name,
            experts_total=int(experts_total),
            expert_hidden_size=int(expert_hidden_size), local_experts=local,
            kernel_initializer=kernel_initializer).outputs[0]

    def latent_attention(self, input: Tensor, num_heads: int,
                         q_lora_rank: int, kv_lora_rank: int,
                         qk_nope_head_dim: int, qk_rope_head_dim: int,
                         v_head_dim: int, rope_parameters=None,
                         eps: float = 1e-6, kernel_initializer=None,
                         name: str = "") -> Tensor:
        """Causal multi-head latent self-attention with rotary positions
        (ops/latent_attention.py): low-rank q and kv projections, one
        rotary key shared by the heads, a latent serving cache.
        `rope_parameters`: the model's published group (ops/rope.py)."""
        return self._add_op(
            OpType.LATENT_ATTENTION, [input], name, num_heads=num_heads,
            q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_parameters=dict(rope_parameters) if rope_parameters else None,
            eps=eps, kernel_initializer=kernel_initializer).outputs[0]

    def ssm_mixer(self, input: Tensor, d_ssm: int, n_heads: int,
                  d_state: int, n_groups: int = 1, d_conv: int = 4,
                  chunk_size: int = 128, slice_multipliers=None,
                  conv_bias: bool = True, eps: float = 1e-5,
                  state_dtype: DataType = DataType.DT_FLOAT,
                  kernel_initializer=None, name: str = "") -> Tensor:
        """Mamba-2 state-space mixer (ops/ssm.py): a gated selective
        recurrence over `n_heads` heads of `d_ssm / n_heads` channels with a
        `d_state`-wide state each, B and C shared inside each of `n_groups`
        groups, behind a causal depthwise convolution of `d_conv` taps; the
        multi-token entries scan in blocks of `chunk_size`.
        `slice_multipliers`: five scalars on the input projection's
        [z | x | B | C | dt] slices. Serving keeps its state per SEQUENCE
        (`Op.sequence_state_arrays`), the recurrent state stored in
        `state_dtype` and stepped in float32."""
        return self._add_op(
            OpType.SSM, [input], name, d_ssm=int(d_ssm),
            n_heads=int(n_heads), d_state=int(d_state),
            n_groups=int(n_groups), d_conv=int(d_conv),
            chunk_size=int(chunk_size),
            slice_multipliers=(tuple(float(m) for m in slice_multipliers)
                               if slice_multipliers is not None else None),
            conv_bias=bool(conv_bias), eps=float(eps),
            state_dtype=state_dtype,
            kernel_initializer=kernel_initializer).outputs[0]

    def moe(
        self,
        input: Tensor,
        num_exp: int,
        num_select: int,
        expert_hidden_size: int,
        alpha: float = 2.0,
        lambda_bal: float = 0.0,
        fused: bool = False,
        name: str = "",
    ) -> Tensor:
        """MoE block (reference: FFModel::moe, model.h:509-514 / moe.cc):
        gating softmax → topk → group_by → per-expert dense → aggregate.
        For the unfused path, inputs of rank > 2 are flattened to
        [tokens, features] for dispatch and restored afterwards (the
        capacity-factor dispatch is per-token). The fused path keeps
        rank-3 inputs NATIVE: ExpertsOp flattens tokens inside its own
        lowering, so the graph stays shape-polymorphic over the leading
        dims and the serving decode path (seq=1) re-runs it unchanged —
        a fixed reshape op here would pin the build-time token count."""
        orig_dims = None
        if len(input.dims) > 2 and not fused:
            orig_dims = input.dims
            tokens = 1
            for d in input.dims[:-1]:
                tokens *= d
            input = self.reshape(input, [tokens, input.dims[-1]],
                                 name=f"{name}_tokens")
        gate = self.dense(input, num_exp, ActiMode.AC_MODE_NONE, name=f"{name}_gate")
        gate = self.softmax(gate)
        topk_out, topk_idx = self.top_k(gate, num_select)
        if fused:
            # fused dispatch->batched FFN->combine; expert-parallel capable
            out = self.experts(input, topk_out, topk_idx, num_exp,
                               expert_hidden_size, alpha, lambda_bal,
                               full_gate=gate, name=f"{name}_experts")
        else:
            grouped = self.group_by(input, topk_idx, num_exp, alpha)
            exp_preds = [
                self.dense(g, expert_hidden_size, ActiMode.AC_MODE_RELU,
                           name=f"{name}_exp{i}")
                for i, g in enumerate(grouped)
            ]
            out = self.aggregate(topk_out, topk_idx, topk_idx, gate, exp_preds,
                                 num_exp, lambda_bal)
        if orig_dims is not None:
            out = self.reshape(
                out, list(orig_dims[:-1]) + [expert_hidden_size],
                name=f"{name}_untokens")
        return out

    # ------------------------------------------------------------------
    # compile / strategy
    # ------------------------------------------------------------------
    def compile(
        self,
        optimizer: Optional[Optimizer] = None,
        loss_type: LossType = LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics: Sequence[MetricsType] = (),
        comp_mode: CompMode = CompMode.COMP_MODE_TRAINING,
        parallel_axes: Optional[Dict[str, int]] = None,
    ) -> None:
        """reference: FFModel::compile (model.cc:2803) — create operators
        from layers, run the strategy search, build partitions/comms. Here:
        build the PCG, pick a strategy (data-parallel default; Unity search
        when search_budget > 0), build the mesh and compile the step
        functions. The whole pass is one `compile` phase (obs/tracing.py
        `Tracer.phase`: a span that also lands in `ff_startup_seconds`),
        with the search, plan-analysis, weight-init and step-build phases
        nested inside it."""
        from .obs.startup import watch_compiles
        from .obs.tracing import get_tracer

        watch_compiles()
        with get_tracer().phase("compile", ops=len(self.ops)):
            self._compile_inner(optimizer, loss_type, metrics, comp_mode,
                                parallel_axes)

    def _compile_inner(
        self,
        optimizer: Optional[Optimizer],
        loss_type: LossType,
        metrics: Sequence[MetricsType],
        comp_mode: CompMode,
        parallel_axes: Optional[Dict[str, int]],
    ) -> None:
        self.optimizer = optimizer or SGDOptimizer(self, lr=self.config.learning_rate)
        # memory model input for the search: per-param optimizer state factor
        # (Adam: param+m+v, momentum-SGD: param+v, SGD: param)
        from .runtime.optimizers import AdamOptimizer as _Adam

        self.config.optimizer_state_factor = (
            3.0 if isinstance(self.optimizer, _Adam)
            else 2.0 if getattr(self.optimizer, "momentum", 0.0) else 1.0
        )
        self.loss = Loss(loss_type) if not isinstance(loss_type, Loss) else loss_type
        self.metrics = Metrics(self.loss.loss_type, list(metrics))
        self.comp_mode = comp_mode

        self.graph = Graph(self.ops)
        order = self.graph.topo_order()
        self.final_tensor = self.final_tensor or order[-1].outputs[0]

        # label tensor mirrors final op's shape (model.cc:3086-3124)
        self.label_tensor = Tensor(self._label_dims(), name="label")
        self.label_tensor._model = self

        # -- strategy selection (reference: GRAPH_OPTIMIZE task model.cc:2826)
        n_dev = self.config.total_devices
        self.search_result = None
        self._op_strategies = None
        if parallel_axes is None:
            if self.config.import_strategy_file:
                from .search.unity import rewrite_and_import_strategy

                strategies, axes = rewrite_and_import_strategy(
                    self.graph, self.config,
                    self.config.import_strategy_file)
                self._op_strategies = strategies
                parallel_axes = axes
            elif (
                (self.config.search_budget > 0
                 or (self.config.strategy_search == "mcmc"
                     and (self.config.mcmc_budget or 0) > 0))
                and n_dev > 1
                and not self.config.only_data_parallel
            ):
                from .search.machine_model import make_machine_model
                from .search.unity import export_strategy, unity_optimize

                machine = make_machine_model(self.config, n_dev)
                if self.config.strategy_search == "mcmc":
                    from .search.mcmc import mcmc_search

                    self.search_result = mcmc_search(
                        self.graph, self.config, machine,
                        self.config.batch_size, n_dev,
                    )
                else:
                    self.search_result = unity_optimize(
                        self.graph, self.config, machine,
                        self.config.batch_size, n_dev,
                    )
                self._op_strategies = self.search_result.strategies
                parallel_axes = self.search_result.mesh_axes
                if self.config.export_strategy_file:
                    export_strategy(
                        self.search_result, self.graph,
                        self.config.export_strategy_file,
                    )
            else:
                parallel_axes = {"data": n_dev} if n_dev > 1 else {}
        if self.config.only_data_parallel:
            parallel_axes = {"data": n_dev} if n_dev > 1 else {}
        # substitutions may have removed/fused/created ops: follow tensor
        # aliases and rebuild the op list from the (rewritten) graph so a
        # re-compile() sees the rewritten graph, not the original op list
        self.final_tensor = self.graph.resolve_tensor(self.final_tensor)
        self.ops = list(self.graph.topo_order())
        self.parallel_axes = dict(parallel_axes)
        self._assign_strategy(self.parallel_axes)

        # hierarchical machines (docs/machine.md): synthesize the per-tier
        # reduction decomposition for every synced tensor of the CHOSEN
        # plan — searched, imported, or the mesh-wide default alike — so
        # the FFTA07x gate below, the executor, and any exported artifact
        # all see the one decomposition the simulator priced
        self._reduction_plan = None
        # predicted grad-sync overlap split of the compiled plan
        # (docs/machine.md "Overlap"): {total/overlapped/exposed_sync_us,
        # buckets} — exported on the ff_grad_sync_overlap_us gauge
        self._sync_overlap = None
        if (self.search_result is not None
                and self.search_result.reduction_strategies):
            # the Unity search already synthesized the plan for these
            # exact strategies — reuse it rather than re-pricing
            self._reduction_plan = self.search_result.reduction_strategies
            if self.search_result.exposed_sync_us is not None:
                self._sync_overlap = {
                    "overlapped_sync_us":
                        self.search_result.overlapped_sync_us,
                    "exposed_sync_us": self.search_result.exposed_sync_us,
                    "buckets": self.search_result.sync_buckets,
                }
        elif n_dev > 1:
            from .search.machine_model import make_machine_model as _mk

            _machine = _mk(self.config, n_dev)
            if hasattr(_machine, "tier_path"):
                from .analysis.passes import default_strategies_for
                from .search.simulator import CostModel

                _strats = self._op_strategies or default_strategies_for(
                    self.graph, self.parallel_axes, self.config.batch_size)
                self._reduction_plan = CostModel(
                    _machine, self.config).reduction_plan(self.graph,
                                                          _strats)
                if any(e.get("bucket") is not None
                       for e in self._reduction_plan.values()):
                    # a bucketed plan's overlap split is a property of
                    # the schedule, not just the record — simulate the
                    # pinned strategies once so the gauge and the bench
                    # surfaces report the split this compile priced
                    from .search.simulator import Simulator as _Sim

                    _sim = _Sim(_machine, self.config)
                    _sim.simulate(self.graph, _strats)
                    _st = _sim.last_sync_stats or {}
                    self._sync_overlap = {
                        "overlapped_sync_us":
                            _st.get("overlapped_sync_us"),
                        "exposed_sync_us": _st.get("exposed_sync_us"),
                        "buckets": len(_st.get("buckets") or []),
                    }
        if self._sync_overlap is not None:
            from .obs.registry import REGISTRY as _REG

            _g = _REG.gauge(
                "ff_grad_sync_overlap_us",
                "Predicted grad-sync overlap split of the compiled plan",
                labels=("kind",))
            _g.set(float(self._sync_overlap["overlapped_sync_us"] or 0.0),
                   kind="overlapped")
            _g.set(float(self._sync_overlap["exposed_sync_us"] or 0.0),
                   kind="exposed")

        # pre-flight plan sanitizer (analysis/): statically prove the chosen
        # plan legal before any XLA trace sees it — errors reject the plan,
        # warnings go to the analysis event log (profiling.print_event_log)
        # and the process-wide counters the serving /metrics endpoint exports
        self._run_plan_analysis()

        # explicit device subset (elastic: compile onto the survivors of a
        # chip loss rather than jax.devices()'s prefix)
        mesh_devices = None
        if self.config.device_ids is not None:
            import jax as _jax

            all_devices = _jax.devices()
            mesh_devices = [all_devices[i] for i in self.config.device_ids]
        self.mesh = (make_mesh(self.parallel_axes, mesh_devices)
                     if self.parallel_axes else None)

        self.executor = Executor(self.graph, self.config, self.mesh,
                                 reduction_plan=self._reduction_plan)
        # FFTA072: with the explicit collective lowering active, what
        # the executor will actually run must match what the gate above
        # just proved and the simulator priced — fail loudly, not drift
        self._verify_executed_reductions()
        import jax

        from .obs.tracing import get_tracer

        with get_tracer().phase("compile.init_params"):
            self.params, self.state = self.executor.init_params(
                jax.random.PRNGKey(self.config.seed)
            )
            # mesh-less compile with an explicit device subset (elastic: a
            # single-survivor recovery): commit params to the chosen device
            # so jitted steps execute there — jax.devices()[0], the
            # default, may be the lost chip. opt_state inherits the
            # placement via init_state(params) below.
            if self.mesh is None and mesh_devices:
                self.params = jax.device_put(self.params, mesh_devices[0])
                self.state = jax.device_put(self.state, mesh_devices[0])
        reg_fn = None
        if self.weight_regularizers:
            regs = list(self.weight_regularizers)

            def reg_fn(params):
                total = 0.0
                for op_name, w_name, fn in regs:
                    if op_name in params and w_name in params[op_name]:
                        total = total + fn(params[op_name][w_name])
                return total

        self._reg_fn = reg_fn
        self._comp_mode_used = comp_mode
        self._build_step_functions()
        self.opt_state = self.optimizer.init_state(self.params)
        self._compiled = True
        self._manual: Dict[str, Any] = {}

        if self.config.export_strategy_computation_graph_file:
            self.graph.export_dot(self.config.export_strategy_computation_graph_file)
        if self.config.export_strategy_task_graph_file:
            self._export_task_graph(self.config.export_strategy_task_graph_file)

    def _build_step_functions(self) -> None:
        from .obs.tracing import get_tracer

        with get_tracer().phase("compile.build_steps"):
            self._build_step_functions_inner()

    def _build_step_functions_inner(self) -> None:
        # stale accumulation closures would capture the OLD executor/optimizer
        self._accum_grad = self._accum_add = self._accum_update = None
        input_names = [op.name for op in self.input_ops]
        self._train_step = self.executor.build_train_step(
            self.optimizer, self.loss.fn, self.metrics, self.final_tensor,
            input_names, reg_fn=self._reg_fn,
        )
        self._eval_step = self.executor.build_eval_step(
            self.loss.fn, self.metrics, self.final_tensor
        )
        self._forward_fn = self.executor.build_forward(
            self.final_tensor, self._comp_mode_used)
        self._infer_fn = self.executor.build_forward(self.final_tensor)
        self._grad_step = self.executor.build_grad_step(
            self.loss.fn, self.final_tensor)
        self._multi_step = None  # built lazily (fit(steps_per_execution=K))

    def _get_multi_step(self):
        """Jitted K-steps-per-dispatch train fn (lazy — most models never
        need it; see Executor.build_multi_step)."""
        if self._multi_step is None:
            input_names = [op.name for op in self.input_ops]
            self._multi_step = self.executor.build_multi_step(
                self.optimizer, self.loss.fn, self.metrics,
                self.final_tensor, input_names, reg_fn=self._reg_fn)
        return self._multi_step

    def _build_accum_fns(self) -> None:
        """Jitted pieces of gradient accumulation: the executor's shared
        grad+metrics core, a (donating) tree add, and a
        divide-then-optimizer-update (fit(accum_steps=k))."""
        import jax

        optimizer = self.optimizer
        gstep = self.executor.build_grad_metrics_step(
            self.loss.fn, self.metrics, self.final_tensor, self._reg_fn)
        self._accum_grad_state = jax.jit(gstep)

        def accum_grad(params, state, inputs, label, rng):
            grads, mvals, new_state = self._accum_grad_state(
                params, state, inputs, label, rng)
            self.state = new_state  # BN running stats advance per microbatch
            return grads, mvals

        self._accum_grad = accum_grad
        # donate the accumulator / the consumed params+grads+opt_state:
        # accumulation is used when memory is tight
        self._accum_add = jax.jit(
            lambda a, b: jax.tree.map(lambda x, y: x + y, a, b),
            donate_argnums=(0,))

        def upd(params, grads, opt_state, k):
            grads = jax.tree.map(lambda g: g / k, grads)
            with jax.named_scope("optimizer:update"):
                return optimizer.update(params, grads, opt_state)

        self._accum_update = jax.jit(upd, donate_argnums=(0, 1, 2))

    def invalidate_compiled_steps(self) -> None:
        """Rebuild the jitted step functions after a graph/op-param mutation
        (the RecompileState alter path — reference: the 'recompile' in
        recompile_on_condition). The next step re-traces with the new
        dataflow; weights and optimizer state carry over."""
        self._build_step_functions()
        # per-seq_length jits were lowered from the old graph
        if getattr(self, "_manual", None):
            self._manual.pop("seq_fns", None)

    def analyze_plan(self, passes=None):
        """Run the plan sanitizer (analysis/) over this model's PCG + chosen
        strategies + machine spec; returns the DiagnosticReport (never
        raises). Usable mid-compile and after compile()."""
        from .analysis import analyze_plan as _analyze
        from .search.machine_model import make_machine_model

        n_dev = self.config.total_devices
        final = (self.graph.resolve_tensor(self.final_tensor)
                 if self.final_tensor is not None else None)
        final_guid = (final.owner_op.guid
                      if final is not None and final.owner_op is not None
                      and final.owner_op.guid in self.graph.ops else None)
        # an active explicit lowering makes the analysis compare against
        # the EXECUTED schedule (FFTA072), not just the plan record
        lowering = getattr(getattr(self, "executor", None),
                           "grad_sync_lowering", None)
        return _analyze(
            self.graph,
            strategies=self._op_strategies,
            machine=make_machine_model(self.config, n_dev),
            config=self.config,
            batch_size=self.config.batch_size,
            n_devices=n_dev,
            mesh_axes=getattr(self, "parallel_axes", None),
            final_guid=final_guid,
            reduction_strategies=getattr(self, "_reduction_plan", None),
            executed_reductions=(lowering.executed_plan()
                                 if lowering is not None else None),
            executed_buckets=(lowering.executed_buckets()
                              if lowering is not None else None),
            passes=passes,
        )

    def _verify_executed_reductions(self) -> None:
        """The compile-time executed-schedule gate: with the explicit
        collective lowering active, fail loudly (under
        plan_analysis="error") if the lowering dropped or renamed any
        tensor the priced reduction_plan names (FFTA072) — and, beyond
        name matching, if the priced plan and the executed schedule do
        not *interpret* to the same discharged gradient state: the
        sharding-flow verifier re-derives each weight's pending
        partial-sum axes from the graph + strategies and requires the
        executed schedule to discharge them all (FFTA090,
        docs/analysis.md "Verifier")."""
        lowering = getattr(self.executor, "grad_sync_lowering", None)
        mode = getattr(self.config, "plan_analysis", "error")
        if lowering is None or mode == "off" or not self._reduction_plan:
            return
        from .analysis import PlanAnalysisError, record_report
        from .analysis.diagnostics import DiagnosticReport
        from .analysis.interp import semantic_reduction_diagnostics
        from .analysis.passes import (AnalysisContext,
                                      check_executed_reductions)

        ctx = AnalysisContext(
            graph=self.graph,
            strategies=getattr(self, "_op_strategies", None),
            reduction_strategies=self._reduction_plan,
            executed_reductions=lowering.executed_plan(),
            executed_buckets=lowering.executed_buckets())
        report = DiagnosticReport(passes_run=["tiers", "flow"])
        report.extend(check_executed_reductions(ctx))
        report.extend(semantic_reduction_diagnostics(ctx))
        if not report.diagnostics:
            return
        record_report(report)
        for d in report.errors():
            _log.error("plan analysis: %s", d.format())
        if mode == "error" and report.errors():
            raise PlanAnalysisError(report)

    def _run_plan_analysis(self) -> None:
        """The compile()/re-plan pre-flight gate: plan_analysis="error"
        raises PlanAnalysisError on error diagnostics, "warn" only records,
        "off" skips. Every diagnostic lands in self.analysis_events (an
        elastic-style EventLog profiling.print_event_log renders) and the
        process-wide per-code counters."""
        mode = getattr(self.config, "plan_analysis", "error")
        if mode == "off":
            return
        from .analysis import PlanAnalysisError, record_report
        from .elastic.events import EventLog
        from .obs.tracing import get_tracer

        with get_tracer().phase("compile.analysis"):
            report = self.analyze_plan()
        # stashed so post-compile consumers (the elastic coordinator's
        # recovery event) reuse this run instead of re-running the pipeline
        self._analysis_report = report
        record_report(report)
        if not hasattr(self, "analysis_events"):
            self.analysis_events = EventLog()
        for d in report.diagnostics:
            self.analysis_events.record(
                f"analysis.{d.severity.value}", code=d.code,
                op=d.op_name, message=d.message)
        for d in report.warnings():
            _log.warning("plan analysis: %s", d.format())
        for d in report.errors():
            _log.error("plan analysis: %s", d.format())
        if report.errors() and mode == "error":
            raise PlanAnalysisError(report)

    def _export_task_graph(self, path: str) -> None:
        """Cost-annotated task-graph dot (reference: --export-strategy-
        task-graph-file + --include-costs-dot-graph, simulator.cc's task
        graph dump). Nodes carry the chosen strategy and the cost model's
        fwd/bwd estimates."""
        from .search.machine_model import make_machine_model
        from .search.simulator import CostModel, OpStrategy

        n_dev = self.config.total_devices
        cost = CostModel(make_machine_model(self.config, n_dev), self.config)
        strategies = getattr(self, "_op_strategies", None) or {}
        costs = {}
        labels = {}
        for op in self.graph.ops.values():
            s = strategies.get(op.guid, OpStrategy(dp=1, tp=1))
            try:
                f = cost.forward_time_us(op, s)
                b = cost.backward_time_us(op, s)
            except Exception:
                f = b = 0.0
            costs[op.guid] = f + b
            labels[op.guid] = f"dp={s.dp},tp={s.tp} fwd={f:.1f}us bwd={b:.1f}us"
        self.graph.export_dot(path, include_costs=True, costs=costs,
                              labels=labels)

    def _label_dims(self):
        from .ffconst import LossType as LT

        fd = self.final_tensor.dims
        if self.loss.loss_type == LT.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY:
            return fd[:-1] + (1,)
        return fd

    def _assign_strategy(self, axes: Dict[str, int]) -> None:
        """Assign ParallelTensorShapes: batch dim over the 'data' axis
        (reference: only_data_parallel path model.cc:2638-2642) and — when a
        'model' axis is present — Megatron-style tensor parallelism: linear
        out-features, attention heads, and embedding features sharded over
        'model' (reference analog: create_partition_linear_combine /
        create_partition_attention_combine substitutions, substitution.cc:
        1755-1770). The Unity search overrides per-op views when enabled."""
        batch = self.config.batch_size
        dp = axes.get("data", 1)
        tp = axes.get("model", 1)
        view = MachineView(axes=tuple(axes.items()))
        ap_axis = axes.get("attr", 1)
        sp_axis = axes.get("seq", 1)
        from .search.simulator import AP_CAPABLE, sp_shardable

        for op in self.graph.topo_order():
            # per-op search result overrides the mesh-wide default
            s = (self._op_strategies or {}).get(op.guid)
            op_dp = min(s.dp, dp) if s else dp
            op_tp = min(s.tp, tp) if s else tp
            op_ap = min(s.ap, ap_axis) if s else ap_axis
            op_sp = min(s.sp, sp_axis) if s else sp_axis
            spatial = (op_ap > 1 and op.op_type in AP_CAPABLE)
            # search-selected sequence parallelism: position dims shard over
            # 'seq' and attention switches to the ring kernel (the manual
            # sequence_parallel=True op param is the same machinery)
            seq_sharded = op_sp > 1 and sp_shardable(op, op_sp)
            if seq_sharded and op.op_type == OpType.MULTIHEAD_ATTENTION:
                if op.params.get("dropout", 0.0) > 0:
                    # the SP kernels have no attention-prob dropout
                    # (ops/attention.py fails loudly on the explicit
                    # combination) — this op stays unsharded rather than
                    # silently changing regularization
                    seq_sharded = False
                else:
                    # a 'seq' axis with sp>1 on this op means SP executes
                    # here: the attention must run its sequence-parallel
                    # kernel (the builder default is False; the axis only
                    # exists when the user passed parallel_axes={'seq': n}
                    # or the search chose SP, both of which own this
                    # decision)
                    op.params["sequence_parallel"] = True
            op.machine_view = view
            for t in list(op.outputs):
                dims = []
                for i, size in enumerate(t.dims):
                    if i == 0 and op_dp > 1 and size == batch and size % op_dp == 0:
                        dims.append(
                            ParallelDim(size, op_dp, "data", kind=ParallelDimKind.SAMPLE)
                        )
                    elif (i == 1 and seq_sharded and len(t.dims) >= 3
                          and size % op_sp == 0):
                        # sequence/context parallelism: position dim over
                        # 'seq' (attention runs the ring kernel; GSPMD keeps
                        # position-wise ops local)
                        dims.append(
                            ParallelDim(size, op_sp, "seq",
                                        kind=ParallelDimKind.SEQUENCE)
                        )
                    elif (i == 2 and spatial and len(t.dims) == 4
                          and size % op_ap == 0):
                        # attribute/spatial parallelism: H over 'attr'
                        # (GSPMD inserts the conv halo exchanges)
                        dims.append(
                            ParallelDim(size, op_ap, "attr",
                                        kind=ParallelDimKind.ATTRIBUTE)
                        )
                    else:
                        dims.append(ParallelDim(size, 1, None))
                t.parallel_shape = ParallelTensorShape(dims, t.dtype)
            op_ep = min(s.ep, axes.get("expert", 1)) if s else axes.get("expert", 1)
            if op.op_type == OpType.EXPERTS and op_ep > 1:
                # expert-parallel: stacked expert weights shard dim 0 over
                # the 'expert' mesh axis (device-level expert parallelism);
                # per-op searched ep overrides the mesh-wide default
                ep = op_ep
                for w in op.weights:
                    dims = [ParallelDim(sz, 1, None) for sz in w.dims]
                    if w.dims[0] % ep == 0:
                        dims[0] = ParallelDim(
                            w.dims[0], ep, "expert",
                            kind=ParallelDimKind.EXPERT,
                        )
                    w.parallel_shape = ParallelTensorShape(dims, w.dtype)
            elif op_tp > 1:
                row = bool(s and s.tp_row and op.op_type == OpType.LINEAR)
                self._assign_tp_weights(op, op_tp, row=row)
                if row and op.inputs and op.inputs[0].parallel_shape is not None:
                    # Megatron pairing: the row-parallel linear consumes its
                    # input sharded on the contraction (feature) dim — the
                    # column-parallel producer's output then never gathers
                    t_in = op.inputs[0]
                    if t_in.dims[-1] % op_tp == 0:
                        pdims = list(t_in.parallel_shape.dims)
                        pdims[-1] = ParallelDim(
                            t_in.dims[-1], op_tp, "model",
                            kind=ParallelDimKind.CHANNEL)
                        t_in.parallel_shape = ParallelTensorShape(
                            pdims, t_in.dtype)
            elif tp > 1:
                # non-TP op under a TP mesh: weights replicated
                for w in op.weights:
                    w.parallel_shape = ParallelTensorShape(
                        [ParallelDim(sz, 1, None) for sz in w.dims], w.dtype
                    )
            # explicit parallel ops override the default output sharding
            if op.op_type == OpType.REPARTITION:
                from .parallel.parallel_ops import resolve_partition_axis

                axis = resolve_partition_axis(
                    op.name, op.params["dim"], op.params["degree"], axes,
                    axis=op.params.get("axis"))
                if axis is not None:
                    op.apply_parallel_shape(axis)
            elif op.op_type == OpType.COMBINE:
                op.apply_parallel_shape()
            elif op.op_type == OpType.REPLICATE:
                op.apply_parallel_shape()
            elif op.op_type == OpType.FUSED_PARALLEL:
                op.apply_parallel_shape(axes)

    def _assign_tp_weights(self, op: Op, tp: int, row: bool = False) -> None:
        """Shard weight dims over the 'model' axis where the op supports TP.
        row=True (LINEAR only): kernel shards the INPUT-feature dim and the
        bias stays replicated — the reduction-parallel half of Megatron."""
        from .search.simulator import TP_WEIGHT_SHARD_DIMS

        shard_dim = ({"kernel": 0} if row
                     else TP_WEIGHT_SHARD_DIMS.get(op.op_type))
        for w in op.weights:
            ws = w._weight_spec
            dims = [ParallelDim(s, 1, None) for s in w.dims]
            if shard_dim and ws.name in shard_dim:
                d = shard_dim[ws.name] % len(w.dims)
                if w.dims[d] % tp == 0:
                    dims[d] = ParallelDim(
                        w.dims[d], tp, "model", kind=ParallelDimKind.CHANNEL
                    )
            w.parallel_shape = ParallelTensorShape(dims, w.dtype)

    # ------------------------------------------------------------------
    # training loop (reference: flexflow_cffi.py fit :2062 / eval :2106)
    # ------------------------------------------------------------------
    def _next_rng(self, advance: int = 1):
        """Fresh dropout key; advances the step counter by `advance`.

        A K-steps-per-dispatch chunk passes advance=K so _step_count
        counts OPTIMIZER steps, not dispatches — RecompileState warmup
        and checkpointed step_count stay comparable across
        steps_per_execution settings (the per-chunk key is derived from
        the pre-increment count; the rng-stream difference vs K single
        steps is documented at fit())."""
        import jax

        self._step_count += advance
        return jax.random.PRNGKey(
            self._rng_seed + self._step_count - advance + 1)

    def _prep_inputs(self, arrays: Sequence[np.ndarray], lo: int, hi: int):
        out = {}
        for op, arr in zip(self.input_ops, arrays):
            batch = np.ascontiguousarray(arr[lo:hi])
            out[op.name] = self.executor.shard_batch(
                batch.astype(op.outputs[0].dtype.np_dtype)
            )
        return out

    def _label_dtype(self) -> DataType:
        """Loss-driven label dtype: sparse-categorical labels are int class
        ids, everything else trains against float targets."""
        return (
            DataType.DT_INT32
            if self.loss.loss_type == LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY
            else DataType.DT_FLOAT
        )

    def _prep_step_batch(self, x: Sequence[np.ndarray], y: np.ndarray,
                         lo: int, hi: int):
        """Sharded (inputs, label) for one step — the single batch-prep
        rule shared by fit/eval and the elastic coordinator's loop."""
        inputs = self._prep_inputs(x, lo, hi)
        label = self.executor.shard_batch(
            np.ascontiguousarray(y[lo:hi]).astype(
                self._label_dtype().np_dtype)
        )
        return inputs, label

    def _assert_trainable(self) -> None:
        if getattr(self, "_inference_only", None):
            raise RuntimeError(
                f"model was optimized for inference "
                f"({self._inference_only}); training is no longer valid — "
                "rebuild and compile a fresh model to train")

    def fit(
        self,
        x: Union[np.ndarray, Sequence[np.ndarray], None] = None,
        y: Optional[np.ndarray] = None,
        batch_size: Optional[int] = None,
        epochs: Optional[int] = None,
        accum_steps: int = 1,
        steps_per_execution: int = 1,
        verbose: bool = False,
        watchdog=None,
        drift_detector=None,
    ) -> List[Dict[str, float]]:
        """accum_steps > 1: gradient accumulation — each optimizer update
        averages the gradients of `accum_steps` consecutive microbatches of
        the compiled batch size (static shapes stay fixed; effective batch =
        batch_size * accum_steps). The per-microbatch loss mean makes the
        accumulated average exactly the full-effective-batch gradient.

        steps_per_execution > 1 (tf.keras role): K optimizer steps run in
        ONE device dispatch (a jitted lax.scan) — the same optimizer math
        as K single steps (bit-identical for dropout-free models), with the
        host->device dispatch latency paid once per K (what that is worth
        is not measured on the current set-up). Two documented
        differences from plain fit: the dropout rng stream differs (keys
        are split(key, K) per chunk rather than drawn per step), and any
        trailing n mod (bs*K) samples run through the single-step path to
        keep updates-per-epoch identical. Mutually exclusive with
        accum_steps > 1.

        watchdog: an optional elastic.TrainingWatchdog. Every committed
        loss is health-checked (NaN/Inf, EMA spike); bad steps are flagged
        in the watchdog's event log, and after max_consecutive_bad of them
        in a row fit raises the typed NumericBlowup. This plain loop
        CANNOT skip or roll back a bad update — its jitted step donates
        the previous params, and there are no checkpoints here; train
        under an ElasticCoordinator for skip-and-rollback recovery.

        drift_detector: an optional obs.DriftDetector. Every committed
        step's wall time feeds its measured-vs-predicted EMA
        (`ff_calibration_drift` gauge / `ff_drift_breaches_total`
        counter); a breach here only marks an `obs.drift` trace instant —
        this plain loop cannot re-plan (same contract as the no-rollback
        watchdog guard). Train under an ElasticCoordinator with a drift
        detector for the budgeted refit + re-search path."""
        import jax

        assert self._compiled, "call compile() first"
        self._assert_trainable()
        if steps_per_execution > 1 and accum_steps > 1:
            raise ValueError(
                "steps_per_execution and accum_steps are mutually exclusive "
                "(one batches optimizer steps per dispatch, the other "
                "microbatches per optimizer step)")
        if accum_steps > 1 and self._accum_update is None:
            self._build_accum_fns()
        bs = batch_size or self.config.batch_size
        epochs = epochs or self.config.epochs
        dls = y_dl = None
        if x is None:
            # dataloader-driven fit: batches are PULLED through next_batch()
            # so the native prefetch ring overlaps the gather with compute
            # and shuffle=True is honored (loaders sharing a seed shuffle in
            # lockstep — the seed+epoch reseeding scheme keeps x/y aligned)
            dls, y_dl = self._dataloader_handles()
            if y_dl is None:
                raise RuntimeError(
                    "fit() without x/y requires a dataloader attached to the "
                    "label tensor")
            if bs != dls[0].batch_size:
                raise ValueError(
                    f"fit(batch_size={bs}) differs from the attached "
                    f"dataloaders' batch size {dls[0].batch_size}")
            sizes = {dl.num_samples for dl in dls + [y_dl]}
            if len(sizes) > 1:
                # mismatched loader lengths would silently decorrelate x/y
                # (each loader shuffles/wraps over its OWN num_samples)
                raise ValueError(
                    f"attached dataloaders disagree on num_samples: {sizes}")
            n = sizes.pop()
        else:
            if isinstance(x, np.ndarray):
                x = [x]
            n = x[0].shape[0]
        label_dtype = self._label_dtype()
        if n < bs * accum_steps:
            raise ValueError(
                f"dataset has {n} samples but batch_size*accum_steps is "
                f"{bs * accum_steps}; fit needs at least one full update"
            )
        if n < bs * steps_per_execution:
            raise ValueError(
                f"dataset has {n} samples but batch_size*steps_per_execution "
                f"is {bs * steps_per_execution}; fit needs at least one full "
                "dispatch"
            )
        def _wd_guard(mv: Dict[str, float]) -> None:
            # watchdog health check on the committed loss; raises
            # NumericBlowup after max_consecutive_bad bad steps
            if watchdog is not None and "loss" in mv:
                watchdog.guard(self._step_count, mv["loss"])

        def _drift_guard(rec: Dict[str, float]) -> None:
            # feed the committed step's wall time to the drift detector;
            # a breach verdict here can only be MARKED (trace instant +
            # gauge/counter, done inside observe) — re-planning needs the
            # ElasticCoordinator's loop
            if drift_detector is None or rec.get("step_ms", 0) <= 0:
                return
            if drift_detector.observe(rec["step_ms"] * 1e3):
                from .obs.tracing import get_tracer

                get_tracer().instant("obs.drift", step=self._step_count,
                                     drift=drift_detector.drift)

        history = []
        # per-step observability: every committed optimizer step (or
        # K-step dispatch chunk) lands in a StepStats ring buffer — wall
        # ms, samples/s, achieved TFLOP/s, MFU vs the machine spec's peak,
        # loss — summarized at fit end and exported on the metrics
        # registry. This subsumes the old IterationTimer: with
        # config.profiling the same periodic samples/s line prints.
        from .obs.stepstats import (StepStats, model_peak_tflops,
                                    model_train_flops_per_step)
        from .obs.tracing import get_tracer

        # every dispatch is one `fit.chunk` span (a step marker of the
        # profiler) whose children — fit.load, fit.stage, the executor's
        # dispatch span, fit.absorb — name where its host time went
        tracer = get_tracer()
        stats = StepStats(
            flops_per_step=model_train_flops_per_step(self),
            peak_tflops=model_peak_tflops(self),
            print_freq=(max(1, self.config.print_freq)
                        if self.config.profiling else 0),
        )
        self.step_stats = stats
        stats.start()
        for epoch in range(epochs):
            self.reset_metrics()
            t0 = time.time()
            mvals: Dict[str, float] = {}
            def load_host(it):
                """One host batch (no device placement). Sequential pull on
                the dataloader branch — called exactly once per batch index
                in order, so the streams stay aligned. steps_per_execution
                stacks K of these, then shards once with the K axis
                leading."""
                if dls is not None:
                    inputs = {
                        op.name: dl.next_batch().astype(
                            op.outputs[0].dtype.np_dtype)
                        for op, dl in zip(self.input_ops, dls)
                    }
                    label = y_dl.next_batch().astype(label_dtype.np_dtype)
                    return inputs, label
                lo, hi = it * bs, (it + 1) * bs
                inputs = {
                    op.name: np.ascontiguousarray(arr[lo:hi]).astype(
                        op.outputs[0].dtype.np_dtype)
                    for op, arr in zip(self.input_ops, x)
                }
                label = np.ascontiguousarray(y[lo:hi]).astype(
                    label_dtype.np_dtype)
                return inputs, label

            def load(it):
                with tracer.span("fit.load"):
                    inputs, label = load_host(it)
                with tracer.span("fit.stage"):
                    return (
                        {k2: self.executor.shard_batch(v)
                         for k2, v in inputs.items()},
                        self.executor.shard_batch(label),
                    )

            def absorb_step(mvals, samples, scale=1.0):
                """Fetch one update's metric values (the sync), commit
                them, and hand the step to the health checks."""
                with tracer.span("fit.absorb"):
                    mv = {k2: float(v) / scale for k2, v in mvals.items()}
                    self.perf_metrics.update(samples, mv)
                    rec = stats.record_step(samples, loss=mv.get("loss"))
                _drift_guard(rec)
                _wd_guard(mv)
                return mv

            if steps_per_execution > 1:
                K = steps_per_execution
                chunks = n // (bs * K)
                prev_mvals_k = None

                def _absorb(mvals_k):
                    # stacked (K,) per-step values -> per-step mean, weighted
                    # by the K*bs samples that dispatch consumed
                    with tracer.span("fit.absorb"):
                        per_step = {k2: np.asarray(v)  # the sync
                                    for k2, v in mvals_k.items()}
                        mv = {k2: float(v.mean())
                              for k2, v in per_step.items()}
                        self.perf_metrics.update(K * bs, mv)
                        # one record per K-step dispatch; StepStats divides
                        # the interval by K for the per-optimizer-step wall
                        # time and keeps the K single losses beside the mean
                        rec = stats.record_step(
                            K * bs, loss=mv.get("loss"), steps=K,
                            losses=per_step.get("loss"))
                    _drift_guard(rec)
                    _wd_guard(mv)  # per-chunk: the K-step mean loss
                    return mv

                for chunk_i in range(chunks):
                    if self._recompile_state is not None:
                        self._recompile_state.step(self)
                    with tracer.step("fit.chunk", self._step_count,
                                     chunk=chunk_i, steps=K, samples=K * bs):
                        with tracer.span("fit.load"):
                            batches = [load_host(chunk_i * K + j)
                                       for j in range(K)]
                            host_k = {name: np.stack([b[0][name]
                                                      for b in batches])
                                      for name in batches[0][0]}
                            host_label_k = np.stack([b[1] for b in batches])
                        with tracer.span("fit.stage"):
                            inputs_k = {
                                name: self.executor.shard_batch(
                                    v, batch_axis=1)
                                for name, v in host_k.items()
                            }
                            label_k = self.executor.shard_batch(
                                host_label_k, batch_axis=1)
                            rng_k = jax.random.split(
                                self._next_rng(advance=K), K)
                        # re-resolved every chunk: a recompile trigger
                        # (elastic graph alteration) invalidates and
                        # rebuilds the jitted steps mid-epoch
                        (self.params, self.opt_state, self.state,
                         mvals_k) = self._get_multi_step()(
                            self.params, self.opt_state, self.state,
                            inputs_k, label_k, rng_k)
                        # one-deep pipeline: absorb the PREVIOUS dispatch's
                        # metrics after queuing this one, so host-side
                        # metric fetches and the next chunk's batch staging
                        # overlap device execution instead of serializing
                        # with it
                        if prev_mvals_k is not None:
                            mvals = _absorb(prev_mvals_k)
                        prev_mvals_k = mvals_k
                if prev_mvals_k is not None:
                    mvals = _absorb(prev_mvals_k)
                # trailing n mod (bs*K) samples: single-step path, so an
                # epoch performs the same n // bs updates as plain fit
                for step_i in range(chunks * K, n // bs):
                    with tracer.step("fit.chunk", self._step_count,
                                     chunk=step_i, steps=1, samples=bs):
                        inputs, label = load(step_i)
                        (self.params, self.opt_state, self.state,
                         mvals) = self._train_step(
                            self.params, self.opt_state, self.state, inputs,
                            label, self._next_rng())
                        mvals = absorb_step(mvals, bs)
                dt = time.time() - t0
                summ = self.perf_metrics.summary()
                summ["epoch"] = epoch
                summ["throughput"] = (n // bs) * bs / dt
                history.append(summ)
                self._publish_moe_metrics()
                if verbose:
                    print(
                        f"epoch {epoch}: loss={mvals.get('loss', 0):.4f} "
                        f"acc={summ['accuracy']:.4f} "
                        f"{summ['throughput']:.1f} samples/s"
                    )
                continue

            # with accumulation, each update consumes accum_steps microbatches
            for step_i in range(n // (bs * accum_steps)):
                if self._recompile_state is not None:
                    self._recompile_state.step(self)
                base = step_i * accum_steps
                with tracer.step("fit.chunk", self._step_count, chunk=step_i,
                                 steps=1, samples=accum_steps * bs):
                    inputs, label = load(base)
                    if accum_steps > 1:
                        # ONE counter advance per optimizer update
                        # (microbatches are sub-steps, not steps); each
                        # microbatch still gets a distinct dropout key via
                        # split
                        micro_keys = jax.random.split(self._next_rng(),
                                                      accum_steps)
                        grads, mvals = self._accum_grad(
                            self.params, self.state, inputs, label,
                            micro_keys[0])
                        for k in range(1, accum_steps):
                            inputs, label = load(base + k)
                            g2, mv2 = self._accum_grad(
                                self.params, self.state, inputs, label,
                                micro_keys[k])
                            grads = self._accum_add(grads, g2)
                            mvals = {k2: mvals[k2] + mv2[k2] for k2 in mvals}
                        self.params, self.opt_state = self._accum_update(
                            self.params, grads, self.opt_state,
                            float(accum_steps))
                        mvals = absorb_step(mvals, accum_steps * bs,
                                            scale=accum_steps)
                    else:
                        (self.params, self.opt_state, self.state,
                         mvals) = self._train_step(
                            self.params, self.opt_state, self.state, inputs,
                            label, self._next_rng())
                        mvals = absorb_step(mvals, bs)
            dt = time.time() - t0
            summ = self.perf_metrics.summary()
            summ["epoch"] = epoch
            summ["throughput"] = (n // (bs * accum_steps)) * bs * accum_steps / dt
            history.append(summ)
            self._publish_moe_metrics()
            if verbose:
                print(
                    f"epoch {epoch}: loss={mvals.get('loss', 0):.4f} "
                    f"acc={summ['accuracy']:.4f} {summ['throughput']:.1f} samples/s"
                )
        # fit-end step summary (wall ms percentiles, samples/s, TFLOP/s,
        # MFU) — kept OFF the history records so their schema is unchanged
        if len(stats):
            _log.info(stats.format_summary())
            if self.config.profiling:
                print(stats.format_summary())
        return history

    def _publish_moe_metrics(self) -> None:
        """End-of-epoch MoE router health: mirror every EXPERTS op's
        dropped/load state into the ff_moe_* metric families
        (obs/moe.py). No-op (no registry touch) for expert-free graphs."""
        if not any(op.op_type in (OpType.EXPERTS, OpType.GATED_EXPERTS)
                   for op in self.graph.ops.values()):
            return
        from .obs.moe import publish_moe_metrics

        publish_moe_metrics(self)

    def eval(self, x, y, batch_size: Optional[int] = None) -> Dict[str, float]:
        assert self._compiled
        if isinstance(x, np.ndarray):
            x = [x]
        bs = batch_size or self.config.batch_size
        n = x[0].shape[0]
        pm = PerfMetrics()

        def absorb(pending):
            cnt, mv = pending
            pm.update(cnt, {k: float(v) for k, v in mv.items()})

        num_batches = (n + bs - 1) // bs  # include the tail partial batch
        pending = None  # one-deep pipeline: the host-side float() fetch of
        #                 batch i happens after batch i+1 is dispatched, so
        #                 metric transfers overlap device execution
        for it in range(num_batches):
            lo, hi = it * bs, min((it + 1) * bs, n)
            if hi <= lo:
                break
            inputs, label = self._prep_step_batch(x, y, lo, hi)
            mvals, _ = self._eval_step(self.params, self.state, inputs, label)
            if pending is not None:
                absorb(pending)
            pending = (hi - lo, mvals)
        if pending is not None:
            absorb(pending)
        return pm.summary()

    # -- manual loop parity (reference: forward/zero_gradients/backward/update)
    def set_iteration_batch(self, inputs: Sequence[np.ndarray], label: np.ndarray):
        self._manual["inputs"] = self._prep_inputs(list(inputs), 0, inputs[0].shape[0])
        self._manual["label"] = np.asarray(label)

    def _seq_fn(self, kind: str, seq_length: Optional[int]):
        """Per-seq_length jitted step cache (FFIterationConfig parity,
        reference config.h:162-167: forward(seq_length) truncates seq-dim
        compute). Each distinct length traces once; XLA caches it."""
        if seq_length is None:
            return self._forward_fn if kind == "fwd" else self._grad_step
        cache = self._manual.setdefault("seq_fns", {})
        key = (kind, seq_length)
        if key not in cache:
            if kind == "fwd":
                cache[key] = self.executor.build_forward(
                    self.final_tensor, self._comp_mode_used,
                    seq_length=seq_length)
            else:
                cache[key] = self.executor.build_grad_step(
                    self.loss.fn, self.final_tensor, seq_length=seq_length)
        return cache[key]

    def forward(self, seq_length: Optional[int] = None):
        # one rng per iteration, shared with backward() so the differentiated
        # forward sees the identical dropout masks
        self._manual["rng"] = self._next_rng()
        pred, self.state = self._seq_fn("fwd", seq_length)(
            self.params, self.state, self._manual["inputs"], self._manual["rng"]
        )
        self._manual["pred"] = pred
        return pred

    def zero_gradients(self):
        self._manual.pop("grads", None)

    def backward(self, seq_length: Optional[int] = None):
        import jax.numpy as jnp

        self._assert_trainable()
        label = jnp.asarray(self._manual["label"])
        rng = self._manual.get("rng")
        if rng is None:
            rng = self._next_rng()
        self._manual["grads"] = self._seq_fn("grad", seq_length)(
            self.params, self.state, self._manual["inputs"], label, rng
        )

    def update(self):
        self.params, self.opt_state = self.optimizer.update(
            self.params, self._manual["grads"], self.opt_state
        )

    def set_learning_rate(self, lr: float) -> None:
        """Change the learning rate without recompiling (lr is carried as a
        traced scalar in opt_state)."""
        self.opt_state = self.optimizer.set_lr(self.opt_state, lr)

    def predict(self, x, batch_size: Optional[int] = None) -> np.ndarray:
        """Inference-mode forward over a dataset, batched. Returns the final
        tensor's values stacked over all samples."""
        assert self._compiled
        if isinstance(x, np.ndarray):
            x = [x]
        bs = batch_size or self.config.batch_size
        n = x[0].shape[0]
        def fetch(pred):
            arr = np.asarray(pred)
            if arr.dtype.kind == "V":  # bf16 (ml_dtypes) under mixed precision
                arr = arr.astype(np.float32)
            return arr

        outs = []
        pending = None  # one-deep pipeline: fetch batch i's output after
        #                 batch i+1 is dispatched (device->host transfer
        #                 overlaps device execution)
        for lo in range(0, n, bs):
            hi = min(lo + bs, n)
            inputs = self._prep_inputs(x, lo, hi)
            pred, _ = self._infer_fn(self.params, self.state, inputs,
                                     self._next_rng())
            if pending is not None:
                outs.append(fetch(pending))
            pending = pred
        if pending is not None:
            outs.append(fetch(pending))
        return np.concatenate(outs, axis=0)

    def reset_metrics(self):
        self.perf_metrics = PerfMetrics()

    def get_perf_metrics(self) -> PerfMetrics:
        return self.perf_metrics

    # -- recompile hook (reference: RecompileState, recompile.h:28-44) ----
    def recompile_on_condition(self, recompile_state) -> None:
        """Install a per-iteration trigger/alter hook (reference:
        FFModel::recompile_on_condition, model.cc:2422 — used by the MoE
        example to swap to cached expert assignments mid-training,
        moe.cc:64-98)."""
        self._recompile_state = recompile_state

    def get_cache_score(self, cache_tensor: Tensor) -> float:
        op = cache_tensor.owner_op
        return float(self.state[op.name]["score"])

    # ------------------------------------------------------------------
    # tensor value access (reference: ParallelTensor set_tensor/get_tensor)
    # ------------------------------------------------------------------
    def _find_weight(self, tensor: Tensor):
        op = tensor.owner_op
        if op is None or not hasattr(tensor, "_weight_spec"):
            return None
        return op.name, tensor._weight_spec.name

    def _pp_slot(self, op_name: str):
        ex = getattr(self, "executor", None)
        return ex.pipeline_weight_slot(op_name) if ex is not None else None

    def _get_tensor_value(self, tensor: Tensor):
        loc = self._find_weight(tensor)
        if loc and self.params is not None:
            if loc[0] in self.params:
                return self.params[loc[0]][loc[1]]
            slot = self._pp_slot(loc[0])
            if slot is not None:
                key, s = slot
                return self.params["__pipeline__"][key][loc[1]][s]
            # a weight tensor that resolves nowhere is a stale handle
            # (e.g. its op was removed by a rewrite) — fail loudly rather
            # than letting callers fall back to pre-compile host values
            raise KeyError(
                f"no compiled parameters for op {loc[0]!r} (stale tensor "
                "handle after a graph rewrite?)")
        return None

    def _set_tensor_value(self, tensor: Tensor, value: np.ndarray):
        loc = self._find_weight(tensor)
        if loc and self.params is not None:
            import jax.numpy as jnp

            if loc[0] in self.params:
                self.params[loc[0]][loc[1]] = jnp.asarray(value)
                return
            slot = self._pp_slot(loc[0])
            if slot is not None:
                key, s = slot
                stack = self.params["__pipeline__"][key][loc[1]]
                self.params["__pipeline__"][key][loc[1]] = (
                    stack.at[s].set(jnp.asarray(value, dtype=stack.dtype)))
                return
            raise KeyError(
                f"no compiled parameters for op {loc[0]!r} (stale tensor "
                "handle after a graph rewrite?)")

    def get_parameter_by_id(self, op_name: str, weight_name: str):
        """Weight value by (op, weight) name — pipelined ops resolve into
        their stage's slice of the stacked '__pipeline__' tree."""
        if op_name in self.params:
            return np.asarray(self.params[op_name][weight_name])
        slot = self._pp_slot(op_name)
        if slot is not None:
            key, s = slot
            entry = self.params.get("__pipeline__", {}).get(key, {})
            if weight_name in entry:
                return np.asarray(entry[weight_name][s])
        raise KeyError(f"no parameters for op {op_name!r}")

    def adopt_params_from(self, other: "FFModel") -> None:
        """Copy a sequentially-compiled model's parameters into this model,
        restacking per-layer weights into the pipeline-stage tree when this
        model is pipeline-parallel.

        Use case: migrate trained weights onto a different parallelization
        of the same graph (reference role: strategies are re-mapped onto new
        MachineViews without re-initializing, model.cc recompile path); also
        how GPipe == sequential numerics is asserted in tests/dryrun.
        `other` must not itself be pipeline-parallel. The optimizer state is
        re-initialized to match the adopted tree."""
        import jax.numpy as jnp

        if getattr(other.executor, "pipeline_plan", None) is not None:
            raise ValueError("adopt_params_from needs a sequential source "
                             "model (the stacked stage tree is not "
                             "unstacked in this direction)")
        params = dict(self.params)
        for name in params:
            if name == "__pipeline__":
                continue
            if name not in other.params:
                raise KeyError(
                    f"adopt_params_from: op {name!r} has no counterpart in "
                    "the source model (same-graph models only)")
            # copy, not alias: the source model's fit() may donate
            params[name] = {k: jnp.array(np.asarray(v))
                            for k, v in other.params[name].items()}
        plan = getattr(self.executor, "pipeline_plan", None)
        if plan is not None:
            stacked = {}
            for j in range(plan.segs_per_stage):
                for r, template in enumerate(plan.segments[j]):
                    if not template.weights:
                        continue
                    entry = {}
                    for w in template.weights:
                        wname = w._weight_spec.name
                        slices = []
                        for s in range(plan.n_stages):
                            op_s = plan.segments[
                                s * plan.segs_per_stage + j][r]
                            slices.append(np.asarray(
                                other.params[op_s.name][wname]))
                        entry[wname] = jnp.stack(slices)
                    stacked[self.executor._pp_key(j, r, template)] = entry
            params["__pipeline__"] = stacked
        self.params = params
        self.opt_state = self.optimizer.init_state(self.params)

    def summary(self, print_fn=print) -> str:
        """Keras-style model summary: one row per op with output shape and
        parameter count; columns size to content (reference analog: the
        layer listing FFModel prints under verbose compile)."""
        rows = [("Op (type)", "Output shape", "Params")]
        total = 0
        for op in self.ops:
            if op.op_type == OpType.INPUT:
                shape = str(tuple(op.outputs[0].dims))
                rows.append((f"{op.name} (input)", shape, "0"))
                continue
            n = sum(w.num_elements() for w in op.weights)
            total += n
            shape = str(tuple(op.outputs[0].dims)) if op.outputs else "-"
            rows.append((f"{op.name} ({op.op_type.value})", shape, f"{n:,}"))
        w0 = max(len(r[0]) for r in rows) + 2
        w1 = max(len(r[1]) for r in rows) + 2
        lines = [f"{r[0]:<{w0}}{r[1]:<{w1}}{r[2]:>10}" for r in rows]
        sep = "=" * (w0 + w1 + 10)
        out = "\n".join(
            [sep, lines[0], sep] + lines[1:]
            + [sep, f"Total params: {total:,}", sep])
        if print_fn is not None:
            print_fn(out)
        return out

    def get_layers(self) -> List[Op]:
        return list(self.ops)

    def get_layer_by_id(self, layer_id: int) -> Op:
        """reference: FFModel.get_layer_by_id (flexflow_cffi.py)."""
        return self.ops[layer_id]

    def get_layer_by_name(self, name: str) -> Op:
        for op in self.ops:
            if op.name == name:
                return op
        raise KeyError(f"no layer named {name!r}")

    def _attach_dataloader(self, dl) -> None:
        self._dataloaders.append(dl)

    def _dataloader_handles(self):
        """fit() without x/y: the attached SingleDataLoaders ordered by input
        op, plus the label loader (reference: dataloaders created per tensor,
        flexflow_cffi.py:2451). fit() pulls batches through next_batch()."""
        if not self._dataloaders:
            raise RuntimeError("fit() without x/y requires attached dataloaders")
        by_tensor = {dl.input_tensor.guid: dl for dl in self._dataloaders}
        xs = []
        for op in self.input_ops:
            dl = by_tensor.get(op.outputs[0].guid)
            if dl is None:
                raise RuntimeError(
                    f"no dataloader attached for input {op.name!r}")
            xs.append(dl)
        y_dl = None
        if self.label_tensor is not None:
            y_dl = by_tensor.get(self.label_tensor.guid)
        return xs, y_dl

    def print_layers(self, id: int = -1) -> None:
        for op in self.ops:
            print(op)
