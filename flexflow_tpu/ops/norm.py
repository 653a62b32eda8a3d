"""LayerNorm / RMSNorm / Softmax / Dropout.

Reference: src/ops/layer_norm.cc (custom CUDA kernels), softmax.cc (cuDNN),
dropout.cc (cuDNN dropout states). Dropout here uses jax PRNG threaded through
the LoweringContext — functional replacement for cuDNN's stateful RNG.

The norm/softmax ops are kernel-tier families (docs/kernels.md): when the
KernelRegistry selects `pallas` — trailing-axis normalization only — the
lowering emits the fused Pallas kernel from kernels/pallas/norm.py (one
VMEM pass, f32 statistics, custom fwd+bwd); otherwise the unfused jnp
reference below, which doubles as the parity oracle. On a mesh
(`ctx.gspmd_partitioned()`) they always keep the reference lowering, which
XLA partitions; CostModel.kernel_time_factor prices the same gate.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..core.op import Op, WeightSpec, register_op
from ..ffconst import CompMode, OpType
from ..runtime.initializers import ConstantInitializer, ZeroInitializer
from ..runtime.platform import pallas_interpret


def _trailing_axis_only(op: Op, axes) -> bool:
    """The fused kernels normalize the trailing axis with leading dims
    flattened; anything else stays on the reference lowering."""
    nd = len(op.inputs[0].dims)
    return tuple(axes) == (nd - 1,)


@register_op
class LayerNormOp(Op):
    op_type = OpType.LAYERNORM

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def _norm_shape(self):
        axes = self.params["axes"]
        return tuple(self.inputs[0].dims[a] for a in axes)

    def weight_specs(self) -> List[WeightSpec]:
        if not self.params.get("elementwise_affine", True):
            return []
        shape = self._norm_shape()
        return [
            WeightSpec("gamma", shape, self.inputs[0].dtype, ConstantInitializer(1.0)),
            WeightSpec("beta", shape, self.inputs[0].dtype, ZeroInitializer()),
        ]

    def lower(self, ctx, inputs, weights):
        x = inputs[0]
        axes = tuple(self.params["axes"])
        eps = self.params.get("eps", 1e-5)
        from ..kernels.registry import KERNELS

        if (_trailing_axis_only(self, axes)
                and not ctx.gspmd_partitioned()
                and KERNELS.select("layernorm", config=ctx.config)):
            from ..kernels.pallas.norm import fused_layernorm

            return [fused_layernorm(x, weights.get("gamma"),
                                    weights.get("beta"), eps=eps,
                                    interpret=pallas_interpret())]
        # statistics in f32 even when activations flow bf16; the result is
        # stored back in the activation dtype
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=axes, keepdims=True)
        var = jnp.var(xf, axis=axes, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps)
        if "gamma" in weights:
            # broadcast affine params over the normalized axes
            shape = [1] * x.ndim
            for a in axes:
                shape[a] = x.shape[a]
            y = (y * weights["gamma"].astype(jnp.float32).reshape(shape)
                 + weights["beta"].astype(jnp.float32).reshape(shape))
        return [y.astype(x.dtype)]


@register_op
class RMSNormOp(Op):
    """Root-mean-square norm (no mean-centering, no beta) — the
    LayerNorm variant of LLaMA-family decoders, added with the kernel
    tier so the serving models it matters for can use the fused path."""

    op_type = OpType.RMSNORM

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def _norm_shape(self):
        axes = self.params["axes"]
        return tuple(self.inputs[0].dims[a] for a in axes)

    def weight_specs(self) -> List[WeightSpec]:
        if not self.params.get("elementwise_affine", True):
            return []
        return [WeightSpec("gamma", self._norm_shape(),
                           self.inputs[0].dtype, ConstantInitializer(1.0))]

    def lower(self, ctx, inputs, weights):
        x = inputs[0]
        axes = tuple(self.params["axes"])
        eps = self.params.get("eps", 1e-6)
        from ..kernels.registry import KERNELS

        if (_trailing_axis_only(self, axes)
                and not ctx.gspmd_partitioned()
                and KERNELS.select("rmsnorm", config=ctx.config)):
            from ..kernels.pallas.norm import fused_rmsnorm

            return [fused_rmsnorm(x, weights.get("gamma"), eps=eps,
                                  interpret=pallas_interpret())]
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(
            jnp.mean(jnp.square(xf), axis=axes, keepdims=True) + eps)
        if "gamma" in weights:
            shape = [1] * x.ndim
            for a in axes:
                shape[a] = x.shape[a]
            y = y * weights["gamma"].astype(jnp.float32).reshape(shape)
        return [y.astype(x.dtype)]


@register_op
class SoftmaxOp(Op):
    op_type = OpType.SOFTMAX

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def lower(self, ctx, inputs, weights):
        axis = self.params.get("axis", -1)
        x = inputs[0]
        from ..kernels.pallas.norm import fused_softmax, softmax_block_rows
        from ..kernels.registry import KERNELS

        # the fused kernel keeps whole rows resident in VMEM: a row too
        # wide for that stays on the reference lowering (the same gate
        # CostModel.kernel_time_factor prices with)
        if (axis in (-1, x.ndim - 1) and softmax_block_rows(x.shape[-1])
                and not ctx.gspmd_partitioned()
                and KERNELS.select("softmax", config=ctx.config)):
            return [fused_softmax(x, interpret=pallas_interpret())]
        # f32 exp/sum even for bf16 activations
        return [jax.nn.softmax(x.astype(jnp.float32), axis=axis).astype(x.dtype)]


@register_op
class DropoutOp(Op):
    op_type = OpType.DROPOUT

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def lower(self, ctx, inputs, weights):
        x = inputs[0]
        rate = self.params.get("rate", 0.5)
        if ctx.mode != CompMode.COMP_MODE_TRAINING or rate <= 0.0:
            return [x]
        keep = 1.0 - rate
        mask = jax.random.bernoulli(ctx.next_rng(), keep, x.shape)
        return [jnp.where(mask, x / keep, 0.0).astype(x.dtype)]
