"""Pallas decode-attention kernels (docs/kernels.md).

The dense two (decode.py) are reachable through `KERNELS.override` alone
(kernels/registry.py) until a serving cell times them; the latent one
(latent_decode.py) is what a TPU runs for one query a slot. All also run
under the Pallas interpreter (`interpret=True`), which is how the CPU
parity suite exercises them.
"""
from .decode import (fused_decode_attention,
                     fused_multiquery_decode_attention)
from .latent_decode import latent_decode_attention

__all__ = [
    "fused_decode_attention",
    "fused_multiquery_decode_attention",
    "latent_decode_attention",
]
