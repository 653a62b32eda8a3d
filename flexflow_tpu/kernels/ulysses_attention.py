"""Ulysses-style all-to-all sequence parallelism for attention.

The second of the two sequence/context-parallel designs (SURVEY.md §5 calls
for "ring attention or all-to-all sequence parallelism"; ring lives in
kernels/ring_attention.py). Instead of rotating K/V blocks around the ICI
ring, one `all_to_all` re-shards the activations from sequence-sharded to
HEAD-sharded: each chip then holds the FULL sequence for H/s of the heads,
computes ordinary (exact, fused) attention locally, and a second all_to_all
restores sequence sharding.

Trade-offs vs ring (why both exist):
- Ulysses moves q+k+v+o once each (4 tensor volumes) in two all_to_alls;
  ring moves k+v (axis_size-1) times in neighbor ppermutes. For large axis
  sizes ring's traffic is higher but stays on neighbor links; Ulysses'
  all_to_all crosses the full axis but totals less bytes and keeps the
  attention core a single dense local computation (better MXU utilization,
  and the local core can use the Pallas flash kernel).
- Ulysses requires num_heads % axis_size == 0; ring has no head constraint.

The all_to_alls are reverse-differentiable (their transpose is the opposite
all_to_all), so jax.grad gives the backward pass.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      scale: Optional[float] = None, use_flash: bool = False,
                      block_q: int = 512, block_k: int = 512,
                      interpret: bool = False):
    """Runs INSIDE shard_map: q,k,v are local sequence blocks
    (B, L_local, H, D). Returns the local output block (B, L_local, H, Dv).
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])

    # seq-sharded (B, L/s, H, D) -> head-sharded (B, L, H/s, D):
    # split the heads axis across the mesh, concatenate the seq axis
    def to_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)  # (B, L, H/s, D)

    if use_flash:
        # packed kernel on the free (B, L, (H/s)*D) view — the trailing
        # head/depth dims are contiguous, so the reshape is a bitcast and
        # the custom call needs no [b,h,l,d] transposes (the r4 finding
        # that motivated the packed kernels applies per shard here too)
        from .flash_attention import flash_attention_packed

        b, l, hh, d = qh.shape
        ctx = flash_attention_packed(
            qh.reshape(b, l, hh * d), kh.reshape(b, l, hh * d),
            vh.reshape(b, l, hh * d), hh, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret,
        ).reshape(b, l, hh, d)
    else:
        logits = jnp.einsum("bqhd,bkhd->bhqk", qh, kh,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            lq, lk = qh.shape[1], kh.shape[1]
            mask = jnp.tril(jnp.ones((lq, lk), dtype=bool), lk - lq)
            logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vh.dtype), vh)

    return to_seq(ctx.astype(q.dtype))  # back to (B, L/s, H, D)


def ulysses_attention_sharded(q, k, v, mesh, axis_name: str = "seq",
                              causal: bool = False,
                              scale: Optional[float] = None,
                              use_flash: bool = False,
                              block_q: int = 512, block_k: int = 512,
                              interpret: bool = False):
    """GSPMD-land entry: q,k,v are GLOBAL (B, L, H, D) values; shard_map
    partitions L over `axis_name`, one all_to_all re-shards to heads, exact
    local attention runs per chip, and a second all_to_all restores the
    sequence sharding. Call inside jit.

    Requires H % axis_size == 0 and L % axis_size == 0.
    """
    from jax.sharding import PartitionSpec as P

    # the flash local core is a pallas_call, whose outputs carry no vma
    # annotation — disable the varying-mesh-axes check only on that path
    shard_map = functools.partial(jax.shard_map, check_vma=not use_flash)

    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    if q.shape[2] % axis_size:
        raise ValueError(
            f"ulysses attention needs num_heads ({q.shape[2]}) divisible by "
            f"the '{axis_name}' axis size ({axis_size}); use ring attention "
            "for head counts that don't divide")
    if q.shape[1] % axis_size:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by '{axis_name}' "
            f"axis size {axis_size}")

    batch_axis = "data" if "data" in mesh.axis_names else None
    spec = P(batch_axis, axis_name, None, None)
    fn = functools.partial(ulysses_attention, axis_name=axis_name,
                           causal=causal, scale=scale, use_flash=use_flash,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)
