"""PCG source/sink ops: Input, Weight, NoOp (reference: src/ops/noop.cc)."""
from __future__ import annotations

from ..core.op import Op, register_op
from ..ffconst import DataType, OpType


@register_op
class InputOp(Op):
    """Graph input placeholder (reference NoOp with OP_INPUT)."""

    op_type = OpType.INPUT

    def output_shapes(self):
        return [tuple(self.params["dims"])], [self.params.get("dtype", DataType.DT_FLOAT)]

    def lower(self, ctx, inputs, weights):
        # value injected by the executor before lowering
        raise RuntimeError("InputOp is resolved by the executor, not lowered")


@register_op
class ConstantOp(Op):
    """Source op holding a fixed tensor value (reference: OP_WEIGHT NoOp +
    get_attr parameter access in the torch frontend, torch/model.py:2427+).
    trainable=True registers the value as a weight (an fx get_attr on an
    nn.Parameter); otherwise it is baked into the program as a constant."""

    op_type = OpType.WEIGHT

    def output_shapes(self):
        v = self.params["value"]
        dtype = self.params.get("dtype") or DataType.from_numpy(v.dtype)
        return [tuple(v.shape)], [dtype]

    def weight_specs(self):
        if not self.params.get("trainable", False):
            return []
        from ..core.op import WeightSpec

        v = self.params["value"]

        def init(key, dims, dtype):
            import jax.numpy as jnp

            return jnp.asarray(v, dtype)

        return [WeightSpec("value", tuple(v.shape), self.outputs[0].dtype, init)]

    def lower(self, ctx, inputs, weights):
        import jax.numpy as jnp

        if "value" in weights:
            return [weights["value"]]
        return [jnp.asarray(self.params["value"],
                            self.outputs[0].dtype.jnp_dtype)]


@register_op
class NoOp(Op):
    op_type = OpType.NOOP

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def acts_per_position(self):
        return True

    def lower(self, ctx, inputs, weights):
        return [inputs[0]]


@register_op
class IdentityOp(Op):
    op_type = OpType.IDENTITY

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def acts_per_position(self):
        return True

    def lower(self, ctx, inputs, weights):
        return [inputs[0]]
