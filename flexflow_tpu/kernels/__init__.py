"""TPU kernels: manual-collective (shard_map) and Pallas implementations of
the hot ops. The reference has no equivalent — cuDNN/cuBLAS play this role
there; here ring attention (sequence/context parallelism over ICI) is a new
capability required by BASELINE.md's north star. kernels/pallas/ holds the
fused-kernel tier (norm/softmax/reduction/decode) selected per op family by
kernels/registry.py (docs/kernels.md)."""
from .registry import KERNELS, KernelChoice, KernelRegistry
from .ring_attention import ring_attention, ring_attention_sharded

__all__ = ["ring_attention", "ring_attention_sharded", "KERNELS",
           "KernelChoice", "KernelRegistry"]

