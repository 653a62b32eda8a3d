"""Parallel ops: explicit resharding nodes in the PCG.

Reference: src/parallel_ops/{partition,combine,replicate,reduction,
fused_parallel_op}.cc — there, each op builds a Legion LogicalPartition of its
input region in the output's index space and Legion's region runtime performs
the data movement (partition.cc:132-145); the kernels are identity copies.

TPU-native design: each parallel op is an *identity on values* that changes
the tensor's ParallelTensorShape; the executor applies the output sharding as
a `with_sharding_constraint`, and XLA GSPMD emits the actual collective:

| op          | shape change                    | collective XLA emits        |
|-------------|---------------------------------|-----------------------------|
| Repartition | degree 1->k on a dim            | dynamic-slice (scatter)     |
| Combine     | degree k->1 on a dim            | all_gather                  |
| Replicate   | add replica dim (replicated)    | broadcast                   |
| Reduction   | sum over a partial/replica dim  | reduce_scatter / psum       |
| AllReduce   | partial -> replicated           | all_reduce (psum)           |

Reduction/AllReduce over *partial* values only arise inside manual-collective
regions (shard_map, e.g. ring attention, expert all_to_all) — under GSPMD
semantics tensors are always logically global, so here Reduction sums an
explicit leading replica axis instead (matching the reference's
reduction.cc:230 kernel which adds num_replicas buffers).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..core.op import Op, register_op
from ..core.tensor import ParallelDim, ParallelTensorShape
from ..ffconst import OpType, ParallelDimKind


def resolve_partition_axis(op_name: str, dim: int, degree: int,
                           axes: Dict[str, int],
                           axis: Optional[str] = None) -> Optional[str]:
    """Mesh axis a partition descriptor shards over: an explicit axis param
    wins; else the dim-kind convention (dim 0 = batch -> 'data', others ->
    'model'); else any axis whose size matches. Raises when no axis of the
    required size exists (degree > 1 under a non-empty mesh)."""
    if axis is None:
        cand = "data" if dim == 0 else "model"
        if axes.get(cand) == degree:
            axis = cand
        else:
            axis = next((n for n, s in axes.items() if s == degree), None)
    if axis is None:
        if degree > 1 and axes:
            raise ValueError(
                f"partition {op_name}: no mesh axis of size {degree} in {axes}")
        return None
    if axes.get(axis) != degree:
        raise ValueError(
            f"partition {op_name}: axis {axis!r} has size "
            f"{axes.get(axis)}, need {degree}")
    return axis


class ParallelOpBase(Op):
    """Base for parallel ops (reference: parallel_op.h:17)."""

    def is_parallel_op(self) -> bool:
        return True

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def acts_per_position(self):
        return True

    def lower(self, ctx, inputs, weights):
        # identity on the value; the executor's constrain() on the output
        # tensor (whose parallel_shape this op changed) triggers the reshard
        return [inputs[0]]


@register_op
class RepartitionOp(ParallelOpBase):
    """Partition a dim to degree k (reference: partition.cc)."""

    op_type = OpType.REPARTITION

    def apply_parallel_shape(self, axis_name: str):
        dim = self.params["dim"]
        degree = self.params["degree"]
        t = self.outputs[0]
        src = self.inputs[0].parallel_shape
        dims = [ParallelDim(d.size, d.degree, d.axis, d.is_replica_dim, d.kind)
                for d in src.dims]
        dims[dim] = ParallelDim(
            dims[dim].size, degree, axis_name,
            kind=ParallelDimKind.SAMPLE if dim == 0 else ParallelDimKind.ATTRIBUTE,
        )
        t.parallel_shape = ParallelTensorShape(dims, t.dtype)


@register_op
class CombineOp(ParallelOpBase):
    """Gather a partitioned dim back to degree 1 (reference: combine.cc)."""

    op_type = OpType.COMBINE

    def apply_parallel_shape(self):
        dim = self.params["dim"]
        t = self.outputs[0]
        src = self.inputs[0].parallel_shape
        dims = [ParallelDim(d.size, d.degree, d.axis, d.is_replica_dim, d.kind)
                for d in src.dims]
        dims[dim] = ParallelDim(dims[dim].size, 1, None)
        t.parallel_shape = ParallelTensorShape(dims, t.dtype)


@register_op
class ReplicateOp(ParallelOpBase):
    """Broadcast to `degree` replicas (reference: replicate.cc). Under GSPMD
    a replicated tensor is simply unsharded, so this clears partitioning."""

    op_type = OpType.REPLICATE

    def apply_parallel_shape(self):
        t = self.outputs[0]
        src = self.inputs[0].parallel_shape
        dims = [ParallelDim(d.size, 1, None) for d in src.dims]
        t.parallel_shape = ParallelTensorShape(dims, t.dtype)


@register_op
class ReductionOp(Op):
    """Sum over an explicit leading replica axis (reference: reduction.cc:230
    sums num_replicas buffers). Input dims: (k, ...) -> output (...)."""

    op_type = OpType.REDUCTION

    def is_parallel_op(self) -> bool:
        return True

    def output_shapes(self):
        (x,) = self.inputs
        return [x.dims[1:]], [x.dtype]

    def lower(self, ctx, inputs, weights):
        return [jnp.sum(inputs[0], axis=0)]


@register_op
class AllReduceOp(Op):
    """All-reduce marker.

    Under the default GSPMD executor this is an identity *by design*, not a
    missing feature: GSPMD tensors are logically global, so there are no
    partial values to reduce at the PCG level — the gradient all-reduce the
    reference issues explicitly (optimizer_kernel.cu:88) is emitted by XLA
    from the sharded loss-mean. The lax.psum branch only fires inside manual
    shard_map regions (ctx.in_shard_map), where partial values do exist."""

    op_type = OpType.ALLREDUCE

    def is_parallel_op(self) -> bool:
        return True

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def acts_per_position(self):
        return True

    def lower(self, ctx, inputs, weights):
        axis = self.params.get("axis_name")
        if axis is not None and ctx.in_shard_map:
            return [jax.lax.psum(inputs[0], axis)]
        return [inputs[0]]


# descriptor extraction for the parallel ops a FusedParallelOp can absorb
# (reference: FusedParallelOp's ParallelOpInfo{op_type, parallel_dim,
# parallel_degree}, include/flexflow/parallel_ops/parallel_op.h)
def descriptors_of(op: Op) -> List[dict]:
    if op.op_type == OpType.REPARTITION:
        return [{"type": "partition", "dim": op.params["dim"],
                 "degree": op.params["degree"],
                 "axis": op.params.get("axis")}]
    if op.op_type == OpType.COMBINE:
        return [{"type": "combine", "dim": op.params["dim"]}]
    if op.op_type == OpType.REPLICATE:
        return [{"type": "replicate"}]
    if op.op_type == OpType.FUSED_PARALLEL:
        return [dict(d) for d in op.params["descriptors"]]
    raise ValueError(f"{op.op_type} has no parallel descriptor")


@register_op
class FusedParallelOp(ParallelOpBase):
    """Composition of parallel-op descriptors applied as one reshard
    (reference: fused_parallel_op.cc — FusedParallelOp carries a
    ParallelOpInfo chain and its kernel forwards data once). The output's
    ParallelTensorShape is the chain's FINAL state, so the executor's single
    sharding constraint emits one GSPMD reshard for the whole chain —
    intermediate reshards are elided by construction.

    params["descriptors"]: list of {"type": "partition"|"combine"|
    "replicate", "dim": int, "degree": int, "axis": Optional[str]} applied
    in order (dim/degree/axis per type as in the standalone ops)."""

    op_type = OpType.FUSED_PARALLEL

    def apply_parallel_shape(self, axes: Dict[str, int]) -> None:
        t = self.outputs[0]
        src = self.inputs[0].parallel_shape
        dims = [ParallelDim(d.size, d.degree, d.axis, d.is_replica_dim, d.kind)
                for d in src.dims]
        for desc in self.params["descriptors"]:
            kind = desc["type"]
            if kind == "partition":
                dim, degree = desc["dim"], desc["degree"]
                axis = resolve_partition_axis(self.name, dim, degree, axes,
                                              axis=desc.get("axis"))
                if axis is not None:
                    dims[dim] = ParallelDim(
                        dims[dim].size, degree, axis,
                        kind=ParallelDimKind.SAMPLE if dim == 0
                        else ParallelDimKind.ATTRIBUTE)
            elif kind == "combine":
                dim = desc["dim"]
                dims[dim] = ParallelDim(dims[dim].size, 1, None)
            elif kind == "replicate":
                dims = [ParallelDim(d.size, 1, None) for d in dims]
            else:
                raise ValueError(
                    f"{self.name}: unknown parallel descriptor type {kind!r}")
        t.parallel_shape = ParallelTensorShape(dims, t.dtype)
