"""Operator base class and registry.

TPU-native re-design of the reference's `Op` (include/flexflow/operator.h:51-277).
The reference Op carries Legion task launchers (init/forward/backward) plus
profiling hooks; here an Op is a pure description: it computes output shapes at
construction, declares its weights, and provides a single `lower()` that emits
jax ops inside the traced train/inference step (forward only — backward comes
from jax.grad, the TPU-native replacement for hand-written backward kernels).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..ffconst import DataType, OpType, ParameterSyncType
from .machine import MachineView
from .tensor import Parameter, Tensor

_op_guid = itertools.count(1)


def _freeze(v):
    """Hashable deep-freeze of op params (lists/dicts/arrays/callables)."""
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, np.ndarray):
        return (v.shape, v.dtype.str, v.tobytes())
    if callable(v):
        return getattr(v, "__name__", repr(v))
    return v


@dataclasses.dataclass
class WeightSpec:
    """Declaration of one weight tensor of an op."""

    name: str
    dims: Tuple[int, ...]
    dtype: DataType = DataType.DT_FLOAT
    initializer: Optional[Any] = None  # runtime.initializers.Initializer
    sync_type: ParameterSyncType = ParameterSyncType.NCCL


class LoweringContext:
    """State threaded through PCG lowering into a jax computation."""

    def __init__(self, config, mode, mesh=None, rng_key=None,
                 iter_seq_length=None):
        self.config = config
        self.mode = mode  # CompMode
        self.mesh = mesh
        self.rng_key = rng_key
        # FFIterationConfig.seq_length (reference config.h:162-167): ops with
        # a sequence dim truncate their compute to the first L positions
        self.iter_seq_length = iter_seq_length
        self._rng_count = 0
        # tensor guid -> traced jax value
        self.values: Dict[int, Any] = {}

        # non-trainable per-op state (e.g. batchnorm running stats):
        # (op_name, var_name) -> traced value; lower() may write updates here.
        self.state: Dict[Tuple[str, str], Any] = {}
        self.state_updates: Dict[Tuple[str, str], Any] = {}
        # auxiliary loss terms ops contribute (e.g. MoE load-balance loss);
        # summed into the training objective by the executor.
        self.aux_losses: List[Any] = []
        # true while lowering inside a shard_map manual-collective region
        # (ring attention, expert all_to_all) where lax collectives are legal
        self.in_shard_map: bool = False
        # mesh axes the enclosing shard_map holds MANUAL (the explicit
        # grad-sync lowering, runtime/collectives.py): sharding
        # constraints naming a manual axis are illegal inside the body,
        # so constrain() strips them (the data is already the shard)
        self.manual_axes: frozenset = frozenset()

    def gspmd_partitioned(self) -> bool:
        """True while lowering into a step GSPMD partitions over a mesh —
        not off a mesh, and not inside an already-manual shard_map region.
        A Mosaic (Pallas TPU) kernel has no GSPMD partitioning rule: there
        the chip's compiler refuses it ("cannot be automatically
        partitioned"), so a lowering either wraps the kernel in shard_map
        (ops/attention.py `_on_mesh`) or keeps its reference lowering
        (norm and decode families)."""
        return self.mesh is not None and not self.in_shard_map

    def next_rng(self):
        import jax

        if self.rng_key is None:
            raise RuntimeError("op needs an rng key but none was provided")
        self._rng_count += 1
        return jax.random.fold_in(self.rng_key, self._rng_count)

    def constrain(self, value, tensor: Tensor):
        """Apply the tensor's sharding as a constraint, if meshed + partitioned."""
        if self.mesh is None or tensor.parallel_shape is None:
            return value
        spec = tensor.parallel_shape.partition_spec()
        if self.manual_axes:
            from jax.sharding import PartitionSpec

            def drop_manual(p):
                if isinstance(p, (tuple, list)):
                    kept = tuple(q for q in p if q not in self.manual_axes)
                    return kept if kept else None
                return None if p in self.manual_axes else p

            spec = PartitionSpec(*[drop_manual(p) for p in spec])
        if all(p is None for p in spec):
            return value
        import jax

        from jax.sharding import NamedSharding

        return jax.lax.with_sharding_constraint(
            value, NamedSharding(self.mesh, spec)
        )


class Op:
    """Base operator. Subclasses implement shape inference + lowering."""

    op_type: OpType = OpType.NOOP

    def __init__(
        self,
        model,
        inputs: Sequence[Tensor],
        name: str = "",
        **params,
    ):
        self.guid = next(_op_guid)
        self.model = model
        self.inputs: List[Tensor] = list(inputs)
        self.params: Dict[str, Any] = params
        self.name = name or f"{self.op_type.value}_{self.guid}"
        self.machine_view: Optional[MachineView] = None
        self.profiling = bool(model is not None and model.config.profiling)

        out_dims, out_dtypes = self.output_shapes()
        self.outputs: List[Tensor] = [
            Tensor(dims, dtype, name=f"{self.name}.out{i}", owner_op=self, owner_idx=i)
            for i, (dims, dtype) in enumerate(zip(out_dims, out_dtypes))
        ]
        self.weights: List[Parameter] = []
        for ws in self.weight_specs():
            p = Parameter(
                ws.dims,
                ws.dtype,
                name=f"{self.name}.{ws.name}",
                owner_op=self,
                sync_type=ws.sync_type,
                initializer=ws.initializer,
            )
            p._weight_spec = ws
            self.weights.append(p)
        self.state_vars: List[WeightSpec] = list(self.state_specs())

    # -- subclass API -----------------------------------------------------
    def output_shapes(self) -> Tuple[List[Tuple[int, ...]], List[DataType]]:
        """Return (list of output dims, list of output dtypes)."""
        raise NotImplementedError

    def weight_specs(self) -> List[WeightSpec]:
        return []

    def state_specs(self) -> List[WeightSpec]:
        """Non-trainable per-op state (e.g. running statistics)."""
        return []

    # The two serving-cache capabilities. An op may have either or both;
    # serving/sched/kvpool.py `kv_cache_spec` finds caching ops by them and
    # is the one home of how each kind is shaped, allocated and installed.
    def kv_cache_arrays(self) -> Optional[Dict[str, int]]:
        """What the op keeps PER TOKEN: {array name: values a token stores
        in it} (`ctx.state[(op name, array name)]`, shaped (rows,
        max_len — or `kv_ring_rows()` —, width): an attention's keys and values, a
        latent attention's latent rows). Rows past a sequence's position are hidden by the
        op's own `<= position` mask, so a reused slot needs no reset. None
        for an op that keeps nothing per token."""
        return None

    def kv_ring_rows(self) -> Optional[int]:
        """None where each of those arrays keeps one row a position, up to
        the holder's `max_len`. An op that never looks further back than a
        window says how many rows it needs instead, and keeps a RING of
        them: position p in row p mod R, written and masked by the op by
        the position each row holds (ops/attention.py). Whoever allocates
        asks here (kvpool.py `kv_cache_spec`); what addresses rows by
        token position (pages of a prefix cache, a rollback, an export)
        cannot address a ring (`kvpool.RingCacheUnsupported`)."""
        return None

    def sequence_state_arrays(self) -> Optional[Dict[str, tuple]]:
        """What the op keeps PER SEQUENCE, whatever the sequence's length:
        {array name: (shape after the row axis, DataType or None for the
        op's cache type)} (`ctx.state[(op name, array name)]`, shaped
        (rows,) + shape: a state-space mixer's recurrent state and
        convolution tail). No mask hides a previous tenant's state: whoever
        admits a sequence into a row starts it from zeros and OVERWRITES
        the row (the continuous batcher's batch-1 prefill state does), and
        nothing addresses such state by token position — no page of it can
        be shared, rolled back or shipped by rows. None for every other
        op."""
        return None

    def acts_per_position(self) -> bool:
        """Whether position p of axis 1 (the token axis of a (rows, tokens,
        ...) value) of every output is computed from position p of the
        inputs alone, so that the op gives the same row whether it is
        handed every position or that one. A serving step that feeds one
        token a sequence relies on it for every op that keeps no cache;
        `Executor.row_cut` checks it. An op that does not say is taken to
        read across positions."""
        return False

    def _off_token_axis(self, axes) -> bool:
        """None of `axes` (negative ones counted from the end) is axis 1
        of the first input."""
        ndim = len(self.inputs[0].dims)
        return all(a % ndim != 1 for a in axes)

    # state vars the continuous batcher threads from one decode iteration
    # to the next and hands back (small counters, never caches)
    serving_counters: Tuple[str, ...] = ()

    def lower(self, ctx: LoweringContext, inputs: List[Any], weights: Dict[str, Any]):
        """Emit jax ops; return list of output values (one per output tensor)."""
        raise NotImplementedError

    # -- cost/analysis hooks (used by the simulator/search) ---------------
    def flops(self) -> float:
        """Forward FLOPs estimate; default 0 (elementwise ops dominated by BW)."""
        return 0.0

    def bytes_accessed(self) -> float:
        n = sum(t.num_elements() * t.dtype.np_dtype.itemsize for t in self.inputs)
        n += sum(t.num_elements() * t.dtype.np_dtype.itemsize for t in self.outputs)
        n += sum(w.num_elements() * w.dtype.np_dtype.itemsize for w in self.weights)
        return float(n)

    def is_parallel_op(self) -> bool:
        return False

    # -- identity/caching (reference: per-op Params structs + get_or_create_node)
    def param_key(self) -> Tuple:
        return (
            self.op_type,
            tuple(t.guid for t in self.inputs),
            _freeze(self.params),
        )

    def cost_key(self) -> Tuple:
        """Shape-based identity for cost caching: unlike param_key (whose
        input guids are unique per model), identical ops — the 12 identical
        layers of a BERT stack, or the same op in a fresh compile — share one
        key (reference: measured-cost hash cache, simulator.h:750-752)."""
        return (
            self.op_type,
            tuple((t.dims, t.dtype) for t in self.inputs),
            _freeze(self.params),
        )

    def __repr__(self):
        ins = ",".join(str(t.dims) for t in self.inputs)
        outs = ",".join(str(t.dims) for t in self.outputs)
        return f"{self.op_type.value}[{self.name}]({ins})->({outs})"


# registry: OpType -> Op subclass
OP_REGISTRY: Dict[OpType, type] = {}


def register_op(cls):
    OP_REGISTRY[cls.op_type] = cls
    return cls
