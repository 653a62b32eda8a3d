"""Shared harness bits for the kernel benchmark scripts (sweep_flash,
bench_longcontext): one warm+sync timing idiom, so a fix applies to every
script. The scripts measure the attached TPU, one process per chip; on the
CPU backend the kernels run interpreted and the numbers mean nothing."""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def timeit_grad(loss_fn, operands, iters: int, argnums=(0, 1, 2)) -> float:
    """fwd+bwd ms/iter of `loss_fn(*operands)`: jit(grad(...)), one warm
    call, then `iters` timed calls ending in one block_until_ready."""
    import jax

    g = jax.jit(jax.grad(loss_fn, argnums=argnums))

    jax.block_until_ready(g(*operands))  # warm / compile
    t0 = time.perf_counter()
    r = None
    for _ in range(iters):
        r = g(*operands)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters * 1e3
