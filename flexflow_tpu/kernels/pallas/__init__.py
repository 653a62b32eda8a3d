"""Pallas fused-kernel tier (docs/kernels.md).

Fused TPU kernels with einsum/jnp reference fallbacks, selected per op
family by kernels/registry.py from backend capability plus the per-op-
family residuals `obs.calibrate()`/refit record. Every kernel also runs
under the Pallas interpreter (`interpret=True`) so the CPU parity suite
exercises fwd and bwd without a TPU.
"""
from .decode import (fused_decode_attention,
                     fused_multiquery_decode_attention)
from .norm import fused_layernorm, fused_rmsnorm, fused_softmax
from .reduction import fused_reduce

__all__ = [
    "fused_layernorm",
    "fused_rmsnorm",
    "fused_softmax",
    "fused_reduce",
    "fused_decode_attention",
    "fused_multiquery_decode_attention",
]
