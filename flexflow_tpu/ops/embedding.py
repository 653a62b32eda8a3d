"""Embedding lookup (reference: src/ops/embedding.cc, kernels/embedding_kernels.cu).

aggr modes mirror the reference: NONE keeps a per-token vector dim, SUM/AVG
reduce over the token positions dim. Lookup lowers to jnp.take, which XLA
turns into a dynamic-gather — shardable over the entries dim for
attribute-parallel embedding tables (the DLRM strategy)."""
from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np

from ..core.op import Op, WeightSpec, register_op
from ..ffconst import AggrMode, DataType, OpType
from ..runtime.initializers import NormInitializer


@register_op
class EmbeddingOp(Op):
    op_type = OpType.EMBEDDING

    def output_shapes(self):
        (ids,) = self.inputs
        out_dim = self.params["out_dim"]
        aggr = self.params.get("aggr", AggrMode.AGGR_MODE_NONE)
        dtype = self.params.get("dtype", DataType.DT_FLOAT)
        if aggr == AggrMode.AGGR_MODE_NONE:
            return [ids.dims + (out_dim,)], [dtype]
        return [ids.dims[:-1] + (out_dim,)], [dtype]

    def acts_per_position(self):
        # SUM / AVG reduce over the ids' last axis
        return self.params.get("aggr", AggrMode.AGGR_MODE_NONE) \
            == AggrMode.AGGR_MODE_NONE or self._off_token_axis([-1])

    def weight_specs(self) -> List[WeightSpec]:
        return [
            WeightSpec(
                "weight",
                (self.params["num_entries"], self.params["out_dim"]),
                self.params.get("dtype", DataType.DT_FLOAT),
                self.params.get("kernel_initializer")
                or NormInitializer(stddev=0.05),
            )
        ]

    def lower(self, ctx, inputs, weights):
        ids = inputs[0].astype(jnp.int32)
        table = weights["weight"]
        vecs = jnp.take(table, ids, axis=0)
        aggr = self.params.get("aggr", AggrMode.AGGR_MODE_NONE)
        if aggr == AggrMode.AGGR_MODE_SUM:
            vecs = jnp.sum(vecs, axis=-2)
        elif aggr == AggrMode.AGGR_MODE_AVG:
            vecs = jnp.mean(vecs, axis=-2)
        return [vecs]
