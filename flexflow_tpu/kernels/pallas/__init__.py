"""Pallas decode-attention kernels (docs/kernels.md).

One query a slot reads the rows each slot has FILLED: the latent core
(latent_decode.py) wherever a TPU runs it, the dense one (decode.py
`fused_decode_attention`) where kernels/registry.py admits the shapes;
the dense multi-query one is reachable through `KERNELS.override` alone.
All also run under the Pallas interpreter (`interpret=True`), which is
how the CPU parity suite exercises them.
"""
from .decode import (fused_decode_attention,
                     fused_multiquery_decode_attention)
from .latent_decode import latent_decode_attention

__all__ = [
    "fused_decode_attention",
    "fused_multiquery_decode_attention",
    "latent_decode_attention",
]
