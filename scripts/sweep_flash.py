"""Flash-attention block-size sweep at the bench config (ROADMAP S2).
Times fwd+bwd of the Pallas kernel across
block_q x block_k combinations against the einsum reference, on the real
chip. Prints one JSON line with the per-config ms and the winner.

Usage: python scripts/sweep_flash.py
Env: SWEEP_B/H/L/D shape knobs; SWEEP_BLOCKS comma list (default 128,256,512).
"""
from __future__ import annotations

import itertools
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from _bench_util import timeit_grad  # noqa: E402

B = int(os.environ.get("SWEEP_B", 8))
H = int(os.environ.get("SWEEP_H", 16))
L = int(os.environ.get("SWEEP_L", 512))
D = int(os.environ.get("SWEEP_D", 64))
BLOCKS = [int(x) for x in os.environ.get("SWEEP_BLOCKS", "128,256,512").split(",")]
ITERS = int(os.environ.get("SWEEP_ITERS", 20))


def main():
    import jax.numpy as jnp

    from flexflow_tpu.kernels.flash_attention import flash_attention
    from flexflow_tpu.runtime.platform import pallas_interpret

    interpret = pallas_interpret()
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.bfloat16)

    def timeit(f, operands=None):
        ops_ = operands if operands is not None else (q, k, v)
        return timeit_grad(
            lambda q_, k_, v_: jnp.sum(f(q_, k_, v_).astype(jnp.float32) ** 2),
            ops_, ITERS)

    from flexflow_tpu.kernels.flash_attention import flash_attention_packed

    qp = q.reshape(B, L, H * D)
    kp = k.reshape(B, L, H * D)
    vp = v.reshape(B, L, H * D)

    def timeit_packed(f):
        return timeit(f, operands=(qp, kp, vp))

    results = {}
    for bq, bk in itertools.product(BLOCKS, BLOCKS):
        if bq > L or bk > L:
            continue

        # packed layout: the production path (ops/attention.py use_packed,
        # which runs the kernel's own 512x512 default)
        def fp(q_, k_, v_, bq=bq, bk=bk):
            return flash_attention_packed(q_, k_, v_, H, block_q=bq,
                                          block_k=bk, interpret=interpret)

        try:
            results[f"packed_{bq}x{bk}"] = round(timeit_packed(fp), 3)
        except Exception as e:  # a tiling the backend rejects: record, move on
            results[f"packed_{bq}x{bk}"] = f"error: {type(e).__name__}"
        print(f"packed {bq}x{bk}: {results[f'packed_{bq}x{bk}']}",
              file=sys.stderr)

        # bhld layout kept for comparison (the TP-sharded path)
        def fa(q_, k_, v_, bq=bq, bk=bk):
            return flash_attention(q_, k_, v_, block_q=bq, block_k=bk,
                                   interpret=interpret)

        try:
            results[f"flash_{bq}x{bk}"] = round(timeit(fa), 3)
        except Exception as e:
            results[f"flash_{bq}x{bk}"] = f"error: {type(e).__name__}"
        print(f"flash {bq}x{bk}: {results[f'flash_{bq}x{bk}']}", file=sys.stderr)

    def einsum_attn(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) / np.sqrt(D)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

    results["einsum"] = round(timeit(einsum_attn), 3)
    numeric = {k2: v2 for k2, v2 in results.items() if isinstance(v2, float)}
    print(json.dumps({
        "shape": {"B": B, "H": H, "L": L, "D": D},
        "fwd_bwd_ms": results,
        "best": min(numeric, key=numeric.get) if numeric else None,
    }))


if __name__ == "__main__":
    main()
