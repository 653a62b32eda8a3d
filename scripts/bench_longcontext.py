"""Long-context attention benchmark: packed flash kernel vs the einsum
path across sequence lengths, single chip.

Backs PARITY.md's long-context claim with a measured artifact: the einsum
path materializes the f32 L x L score matrix (O(L^2) HBM) and falls over
as L grows, while the packed flash kernel streams K/V blocks through VMEM
(O(L) HBM). Prints one JSON line with fwd+bwd ms and achieved TF/s per
sequence length; einsum entries record OOM/slowdown honestly.

Usage: python scripts/bench_longcontext.py          (on the TPU)
Env: SWEEP_B/H/D shape knobs, SWEEP_LENS comma list, SWEEP_ITERS.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from _bench_util import timeit_grad  # noqa: E402

B = int(os.environ.get("SWEEP_B", 1))
H = int(os.environ.get("SWEEP_H", 16))
D = int(os.environ.get("SWEEP_D", 64))
LENS = [int(x) for x in os.environ.get(
    "SWEEP_LENS", "2048,4096,8192,16384").split(",")]
ITERS = int(os.environ.get("SWEEP_ITERS", 10))


def attn_flops(l: int) -> float:
    # fwd core 2*B*H*L^2*(D+D); bwd ~2.5x (dq/dkv recompute included)
    return 3.5 * 2.0 * B * H * l * l * 2 * D


def main():
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels.flash_attention import flash_attention_packed
    from flexflow_tpu.runtime.platform import pallas_interpret

    dev = jax.devices()[0]

    interpret = pallas_interpret()
    rng = np.random.RandomState(0)
    results = {}

    for L in LENS:
        q = jnp.asarray(rng.randn(B, L, H * D), jnp.bfloat16)
        k = jnp.asarray(rng.randn(B, L, H * D), jnp.bfloat16)
        v = jnp.asarray(rng.randn(B, L, H * D), jnp.bfloat16)

        def loss_flash(q, k, v):
            o = flash_attention_packed(q, k, v, H, causal=True,
                                       interpret=interpret)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        try:
            ms = timeit_grad(loss_flash, (q, k, v), ITERS)
            results[f"flash_L{L}"] = {
                "ms": round(ms, 2),
                "tflops": round(attn_flops(L) / (ms * 1e-3) / 1e12, 1),
            }
        except Exception as e:
            results[f"flash_L{L}"] = f"error: {type(e).__name__}"
        print(f"flash L={L}: {results[f'flash_L{L}']}", file=sys.stderr)

        q4 = q.reshape(B, L, H, D)
        k4 = k.reshape(B, L, H, D)
        v4 = v.reshape(B, L, H, D)

        def loss_einsum(q4, k4, v4):
            s = jnp.einsum("bqhd,bkhd->bhqk", q4, k4,
                           preferred_element_type=jnp.float32) / np.sqrt(D)
            mask = jnp.tril(jnp.ones((q4.shape[1], k4.shape[1]), bool))
            s = jnp.where(mask, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v4.dtype), v4)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        try:
            ms = timeit_grad(loss_einsum, (q4, k4, v4), ITERS)
            results[f"einsum_L{L}"] = {
                "ms": round(ms, 2),
                "tflops": round(attn_flops(L) / (ms * 1e-3) / 1e12, 1),
            }
        except Exception as e:  # expected to OOM at long L
            results[f"einsum_L{L}"] = f"error: {type(e).__name__}"
        print(f"einsum L={L}: {results[f'einsum_L{L}']}", file=sys.stderr)

    print(json.dumps({"shape": {"B": B, "H": H, "D": D},
                      "platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "fwd_bwd": results}))


if __name__ == "__main__":
    main()
