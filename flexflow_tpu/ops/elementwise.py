"""Elementwise unary/binary ops + cast.

Reference: src/ops/element_unary.cc, element_binary.cc (broadcast support),
cast.cc. All are bandwidth-bound; XLA fuses them into neighboring matmuls —
the TPU replacement for the reference's `can_inplace_output`/FusedOp machinery.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.op import Op, register_op
from ..ffconst import DataType, OpType


_UNARY_FNS = {
    OpType.RELU: jax.nn.relu,
    OpType.SIGMOID: jax.nn.sigmoid,
    OpType.TANH: jnp.tanh,
    OpType.GELU: jax.nn.gelu,
    OpType.ELU: jax.nn.elu,
    OpType.RSQRT: jax.lax.rsqrt,
    OpType.EXP: jnp.exp,
    OpType.SIN: jnp.sin,
    OpType.COS: jnp.cos,
    OpType.IDENTITY: lambda x: x,
}


def _make_unary(op_type):
    class _Unary(Op):
        pass

    _Unary.op_type = op_type
    _Unary.__name__ = f"Unary_{op_type.value}"

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def lower(self, ctx, inputs, weights):
        x = inputs[0]
        t = self.op_type
        if t == OpType.POW:
            return [jnp.power(x, self.params["exponent"])]
        if t == OpType.SCALAR_MULTIPLY:
            return [x * self.params["scalar"]]
        if t == OpType.SCALAR_ADD:
            return [x + self.params["scalar"]]
        if t == OpType.SCALAR_SUB:
            return [x - self.params["scalar"]]
        if t == OpType.SCALAR_TRUE_DIV:
            return [x / self.params["scalar"]]
        return [_UNARY_FNS[t](x)]

    _Unary.output_shapes = output_shapes
    _Unary.lower = lower
    _Unary.acts_per_position = lambda self: True
    return register_op(_Unary)


for _t in (
    OpType.RELU,
    OpType.SIGMOID,
    OpType.TANH,
    OpType.GELU,
    OpType.ELU,
    OpType.RSQRT,
    OpType.EXP,
    OpType.SIN,
    OpType.COS,
    OpType.POW,
    OpType.SCALAR_MULTIPLY,
    OpType.SCALAR_ADD,
    OpType.SCALAR_SUB,
    OpType.SCALAR_TRUE_DIV,
):
    _make_unary(_t)


_BINARY_FNS = {
    OpType.EW_ADD: jnp.add,
    OpType.EW_SUB: jnp.subtract,
    OpType.EW_MUL: jnp.multiply,
    OpType.EW_DIV: jnp.divide,
    OpType.EW_MAX: jnp.maximum,
    OpType.EW_MIN: jnp.minimum,
}


def _broadcast_dims(a, b):
    import numpy as np

    return tuple(np.broadcast_shapes(a, b))


def _make_binary(op_type):
    class _Binary(Op):
        pass

    _Binary.op_type = op_type
    _Binary.__name__ = f"Binary_{op_type.value}"

    def output_shapes(self):
        a, b = self.inputs
        return [_broadcast_dims(a.dims, b.dims)], [a.dtype]

    def lower(self, ctx, inputs, weights):
        return [_BINARY_FNS[self.op_type](inputs[0], inputs[1])]

    _Binary.output_shapes = output_shapes
    _Binary.lower = lower
    _Binary.acts_per_position = lambda self: True
    return register_op(_Binary)


for _t in (
    OpType.EW_ADD,
    OpType.EW_SUB,
    OpType.EW_MUL,
    OpType.EW_DIV,
    OpType.EW_MAX,
    OpType.EW_MIN,
):
    _make_binary(_t)


@register_op
class CastOp(Op):
    op_type = OpType.CAST

    def output_shapes(self):
        return [self.inputs[0].dims], [self.params["dtype"]]

    def acts_per_position(self):
        return True

    def lower(self, ctx, inputs, weights):
        return [inputs[0].astype(self.params["dtype"].jnp_dtype)]
