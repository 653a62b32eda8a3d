"""Pallas decode-attention kernels (docs/kernels.md).

Reachable through `KERNELS.override` alone (kernels/registry.py) until a
serving cell times them. Both also run under the Pallas interpreter
(`interpret=True`), which is how the CPU parity suite exercises them.
"""
from .decode import (fused_decode_attention,
                     fused_multiquery_decode_attention)

__all__ = [
    "fused_decode_attention",
    "fused_multiquery_decode_attention",
]
