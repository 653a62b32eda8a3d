"""TPU kernels: manual-collective (shard_map) and Pallas implementations of
the hot ops. The reference has no equivalent — cuDNN/cuBLAS play this role
there; here ring attention (sequence/context parallelism over ICI) is a new
capability required by BASELINE.md's north star. flash_attention.py is the
Pallas kernel the BERT training cell runs; kernels/pallas/decode.py holds the
decode-attention kernels. kernels/registry.py alone decides kernel or
reference lowering, from platform, mesh and shape (docs/kernels.md)."""
from .registry import KERNELS, KernelChoice, KernelRegistry
from .ring_attention import ring_attention, ring_attention_sharded

__all__ = ["ring_attention", "ring_attention_sharded", "KERNELS",
           "KernelChoice", "KernelRegistry"]

