"""Linear / Dense (reference: src/ops/linear.cc:1-1184, kernels/linear_kernels.cu).

The reference lowers to cuBLAS GEMM + fused activation; here it is jnp.dot,
which XLA tiles onto the MXU and fuses the bias/activation epilogue into.
Weight layout is (in_dim, out_dim) — row-major matmul-friendly — rather than
the reference's transposed cuBLAS layout.
"""
from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np

from ..core.op import Op, WeightSpec, register_op
from ..ffconst import ActiMode, OpType
from ..runtime.initializers import DefaultInitializer, ZeroInitializer
from .common import apply_activation, emit_dtype, matmul_dtype


@register_op
class LinearOp(Op):
    op_type = OpType.LINEAR

    def output_shapes(self):
        (x,) = self.inputs
        out_dim = self.params["out_dim"]
        dtype = self.params.get("dtype") or x.dtype
        return [x.dims[:-1] + (out_dim,)], [dtype]

    def acts_per_position(self):
        return self._off_token_axis([-1])   # the contracted axis

    def weight_specs(self) -> List[WeightSpec]:
        (x,) = self.inputs
        out_dim = self.params["out_dim"]
        dtype = self.params.get("dtype") or x.dtype
        specs = [
            WeightSpec(
                "kernel",
                (x.dims[-1], out_dim),
                # a kernel may be stored narrower than the output it
                # feeds (a bf16 vocabulary head emitting float32 logits)
                self.params.get("kernel_dtype") or dtype,
                self.params.get("kernel_initializer") or DefaultInitializer(),
            )
        ]
        if self.params.get("use_bias", True):
            specs.append(
                WeightSpec(
                    "bias",
                    (out_dim,),
                    dtype,
                    self.params.get("bias_initializer") or ZeroInitializer(),
                )
            )
        return specs

    def lower(self, ctx, inputs, weights):
        x = inputs[0]
        k = weights["kernel"]
        cdt = matmul_dtype(ctx.config, x.dtype)
        # the bias+activation epilogue runs in the boundary storage dtype:
        # under mixed precision the pre-activation residual autodiff saves
        # for the activation's backward is then bf16, not f32 — at BERT
        # scale that is ~64 MB of f32 per FFN layer otherwise
        odt = emit_dtype(ctx.config, self.outputs[0].dtype)
        y = jnp.dot(
            x.astype(cdt), k.astype(cdt), preferred_element_type=jnp.float32
        ).astype(odt)
        if "bias" in weights:
            y = y + weights["bias"].astype(odt)
        y = apply_activation(y, self.params.get("activation", ActiMode.AC_MODE_NONE))
        return [y]

    def flops(self) -> float:
        x = self.inputs[0]
        batch = int(np.prod(x.dims[:-1]))
        return 2.0 * batch * x.dims[-1] * self.params["out_dim"]
