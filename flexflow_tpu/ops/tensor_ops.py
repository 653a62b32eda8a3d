"""Shape/data-movement ops + reductions + TopK + BatchMatmul.

Reference: src/ops/{reshape,transpose,reverse,concat,split,gather,reduce,mean,
topk,batch_matmul}.cc with CUDA kernels; all are direct jax/lax primitives here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.op import Op, register_op
from ..ffconst import DataType, OpType


@register_op
class ReshapeOp(Op):
    op_type = OpType.RESHAPE

    def output_shapes(self):
        (x,) = self.inputs
        shape = tuple(self.params["shape"])
        if -1 in shape:
            known = int(np.prod([s for s in shape if s != -1]))
            shape = tuple(
                x.num_elements() // known if s == -1 else s for s in shape
            )
        assert int(np.prod(shape)) == x.num_elements(), (shape, x.dims)
        return [shape], [x.dtype]

    def lower(self, ctx, inputs, weights):
        return [inputs[0].reshape(self.outputs[0].dims)]


@register_op
class TransposeOp(Op):
    op_type = OpType.TRANSPOSE

    def output_shapes(self):
        (x,) = self.inputs
        perm = self.params["perm"]
        return [tuple(x.dims[p] for p in perm)], [x.dtype]

    def lower(self, ctx, inputs, weights):
        return [jnp.transpose(inputs[0], self.params["perm"])]


@register_op
class ReverseOp(Op):
    op_type = OpType.REVERSE

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def lower(self, ctx, inputs, weights):
        return [jnp.flip(inputs[0], axis=self.params["axis"])]


@register_op
class ConcatOp(Op):
    op_type = OpType.CONCAT

    def output_shapes(self):
        axis = self.params["axis"]
        base = list(self.inputs[0].dims)
        base[axis] = sum(t.dims[axis] for t in self.inputs)
        return [tuple(base)], [self.inputs[0].dtype]

    def acts_per_position(self):
        return self._off_token_axis([self.params["axis"]])

    def lower(self, ctx, inputs, weights):
        return [jnp.concatenate(inputs, axis=self.params["axis"])]


@register_op
class SplitOp(Op):
    op_type = OpType.SPLIT

    def output_shapes(self):
        (x,) = self.inputs
        axis = self.params["axis"]
        sizes = self.params["sizes"]
        assert sum(sizes) == x.dims[axis]
        outs = []
        for s in sizes:
            d = list(x.dims)
            d[axis] = s
            outs.append(tuple(d))
        return outs, [x.dtype] * len(sizes)

    def acts_per_position(self):
        return self._off_token_axis([self.params["axis"]])

    def lower(self, ctx, inputs, weights):
        axis = self.params["axis"]
        sizes = self.params["sizes"]
        offs = np.cumsum([0] + list(sizes))
        return [
            jax.lax.slice_in_dim(inputs[0], int(offs[i]), int(offs[i + 1]), axis=axis)
            for i in range(len(sizes))
        ]


@register_op
class GatherOp(Op):
    """Gather along a dim with an index tensor of the same rank
    (reference: src/ops/gather.cc, torch.gather semantics)."""

    op_type = OpType.GATHER

    def output_shapes(self):
        _, idx = self.inputs
        return [idx.dims], [self.inputs[0].dtype]

    def lower(self, ctx, inputs, weights):
        x, idx = inputs
        axis = self.params.get("axis", 0)
        return [jnp.take_along_axis(x, idx.astype(jnp.int32), axis=axis)]


@register_op
class ReduceSumOp(Op):
    op_type = OpType.REDUCE_SUM

    def output_shapes(self):
        (x,) = self.inputs
        axes = tuple(self.params["axes"])
        keepdims = self.params.get("keepdims", False)
        dims = []
        for i, d in enumerate(x.dims):
            if i in axes:
                if keepdims:
                    dims.append(1)
            else:
                dims.append(d)
        return [tuple(dims)], [x.dtype]

    def lower(self, ctx, inputs, weights):
        return [
            jnp.sum(
                inputs[0],
                axis=tuple(self.params["axes"]),
                keepdims=self.params.get("keepdims", False),
            )
        ]


@register_op
class MeanOp(Op):
    op_type = OpType.MEAN

    def output_shapes(self):
        (x,) = self.inputs
        axes = tuple(self.params["axes"])
        keepdims = self.params.get("keepdims", False)
        dims = []
        for i, d in enumerate(x.dims):
            if i in axes:
                if keepdims:
                    dims.append(1)
            else:
                dims.append(d)
        return [tuple(dims)], [x.dtype]

    def lower(self, ctx, inputs, weights):
        return [
            jnp.mean(
                inputs[0],
                axis=tuple(self.params["axes"]),
                keepdims=self.params.get("keepdims", False),
            )
        ]


@register_op
class TopKOp(Op):
    """Top-k values+indices along last dim (reference: src/ops/topk.cc — the
    MoE router)."""

    op_type = OpType.TOPK

    def output_shapes(self):
        (x,) = self.inputs
        k = self.params["k"]
        out = x.dims[:-1] + (k,)
        return [out, out], [x.dtype, DataType.DT_INT32]

    def acts_per_position(self):
        return self._off_token_axis([-1])

    def lower(self, ctx, inputs, weights):
        values, indices = jax.lax.top_k(inputs[0], self.params["k"])
        return [values, indices.astype(jnp.int32)]


@register_op
class BatchMatmulOp(Op):
    """Batched matmul (reference: src/ops/batch_matmul.cc). Carries optional
    a_seq_length_dim/b_seq_length_dim attributes like the reference
    (batch_matmul.cc:77-90); when the iteration carries a seq_length
    (FFModel.forward(seq_length), FFIterationConfig config.h:162-167) the
    declared seq dims are truncated to it before the GEMM — a static slice,
    so each distinct length compiles once and XLA caches it — and the output
    is zero-padded back to its declared shape."""

    op_type = OpType.BATCHMATMUL

    def output_shapes(self):
        a, b = self.inputs
        assert a.dims[:-2] == b.dims[:-2], (a.dims, b.dims)
        assert a.dims[-1] == b.dims[-2]
        return [a.dims[:-1] + (b.dims[-1],)], [a.dtype]

    def lower(self, ctx, inputs, weights):
        from .common import matmul_dtype

        a, b = inputs
        L = getattr(ctx, "iter_seq_length", None)
        a_dim = self.params.get("a_seq_length_dim")
        b_dim = self.params.get("b_seq_length_dim")
        if L is not None and a_dim is not None and a_dim >= 0 and L < a.shape[a_dim]:
            a = jax.lax.slice_in_dim(a, 0, L, axis=a_dim)
        if L is not None and b_dim is not None and b_dim >= 0 and L < b.shape[b_dim]:
            b = jax.lax.slice_in_dim(b, 0, L, axis=b_dim)
        cdt = matmul_dtype(ctx.config, a.dtype)
        y = jnp.matmul(
            a.astype(cdt), b.astype(cdt), preferred_element_type=jnp.float32
        )
        out = self.outputs[0]
        if y.shape != out.dims:
            pad = [(0, full - got) for full, got in zip(out.dims, y.shape)]
            y = jnp.pad(y, pad)
        return [y.astype(out.dtype.jnp_dtype)]

    def flops(self) -> float:
        a, b = self.inputs
        batch = int(np.prod(a.dims[:-2]))
        return 2.0 * batch * a.dims[-2] * a.dims[-1] * b.dims[-1]
