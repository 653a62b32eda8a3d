"""LayerNorm / RMSNorm / Softmax / Dropout.

Reference: src/ops/layer_norm.cc (custom CUDA kernels), softmax.cc (cuDNN),
dropout.cc (cuDNN dropout states). Dropout here uses jax PRNG threaded through
the LoweringContext — functional replacement for cuDNN's stateful RNG.

The norm/softmax ops have one lowering each: plain jnp with f32 statistics,
which XLA fuses and partitions on any backend and mesh.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..core.op import Op, WeightSpec, register_op
from ..ffconst import CompMode, OpType
from ..runtime.initializers import ConstantInitializer, ZeroInitializer


@register_op
class LayerNormOp(Op):
    op_type = OpType.LAYERNORM

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def _norm_shape(self):
        axes = self.params["axes"]
        return tuple(self.inputs[0].dims[a] for a in axes)

    def acts_per_position(self):
        return self._off_token_axis(self.params["axes"])

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
    LayerNorm variant of LLaMA-family decoders."""

    op_type = OpType.RMSNORM

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def _norm_shape(self):
        axes = self.params["axes"]
        return tuple(self.inputs[0].dims[a] for a in axes)

    def acts_per_position(self):
        return self._off_token_axis(self.params["axes"])

    def weight_specs(self) -> List[WeightSpec]:
        if not self.params.get("elementwise_affine", True):
            return []
        return [WeightSpec("gamma", self._norm_shape(),
                           self.inputs[0].dtype, ConstantInitializer(1.0))]

    def lower(self, ctx, inputs, weights):
        x = inputs[0]
        axes = tuple(self.params["axes"])
        eps = self.params.get("eps", 1e-6)
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

    def acts_per_position(self):
        return self._off_token_axis([self.params.get("axis", -1)])

    def lower(self, ctx, inputs, weights):
        axis = self.params.get("axis", -1)
        x = inputs[0]
        # f32 exp/sum even for bf16 activations
        return [jax.nn.softmax(x.astype(jnp.float32), axis=axis).astype(x.dtype)]


@register_op
class DropoutOp(Op):
    op_type = OpType.DROPOUT

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def acts_per_position(self):
        return True

    def lower(self, ctx, inputs, weights):
        x = inputs[0]
        rate = self.params.get("rate", 0.5)
        if ctx.mode != CompMode.COMP_MODE_TRAINING or rate <= 0.0:
            return [x]
        keep = 1.0 - rate
        mask = jax.random.bernoulli(ctx.next_rng(), keep, x.shape)
        return [jnp.where(mask, x / keep, 0.0).astype(x.dtype)]
