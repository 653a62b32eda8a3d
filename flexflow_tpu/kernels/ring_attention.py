"""Ring attention: sequence/context-parallel attention over a mesh axis.

New capability vs the reference (SURVEY.md §5 "Long-context / sequence
parallelism: Absent — the TPU build must design this fresh"): Q/K/V are
sharded over a `seq` mesh axis; each chip holds one sequence block, computes
blockwise attention against its local K/V, then rotates the K/V blocks around
the ICI ring with `lax.ppermute`, accumulating with a numerically-stable
online (flash-style) softmax. After `axis_size` steps every query block has
attended to every key block while K/V traffic stayed on neighbor ICI links —
overlap of compute with the permute is XLA's job (it pipelines the collective
with the einsum when latency hiding is on).

The ring loop uses lax.scan (reverse-differentiable) so jax.grad provides the
backward ring pass without a hand-written kernel.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _block_attend(q, k, v, scale, q_offset, k_offset, causal):
    """One blockwise attention contribution.

    q: (B, Lq, H, D), k/v: (B, Lk, H, D/Dv). Returns (numerator (B,Lq,H,Dv),
    row max (B,H,Lq), row denom (B,H,Lq)) of the *unnormalized* softmax for
    this block only.
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        qpos = q_offset + jnp.arange(lq)[:, None]
        kpos = k_offset + jnp.arange(lk)[None, :]
        logits = jnp.where(qpos >= kpos, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)  # (B,H,Lq)
    # guard fully-masked rows: exp(-inf - -inf) -> exp(0); zero them via l
    p = jnp.exp(logits - jnp.where(jnp.isinf(m), 0.0, m)[..., None])
    p = jnp.where(jnp.isinf(logits), 0.0, p)
    l = jnp.sum(p, axis=-1)  # (B,H,Lq)
    num = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return num, m, l


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None,
                   vary_axes: Optional[Tuple[str, ...]] = None):
    """Runs INSIDE shard_map: q,k,v are local sequence blocks
    (B, L_local, H, D). Returns the local output block (B, L_local, H, Dv).
    vary_axes: all manual mesh axes of the enclosing shard_map (the scan
    carry must be marked varying over them for the vma type check)."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if vary_axes is None:
        vary_axes = (axis_name,)
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    l_local = q.shape[1]
    b, _, h, dv = v.shape

    # accumulators for the online softmax; marked varying over the ring axis
    # (shard_map's vma check requires carry in/out types to agree)
    def varying(x):
        return jax.lax.pcast(x, vary_axes, to="varying")

    acc0 = varying(jnp.zeros((b, l_local, h, dv), jnp.float32))
    m0 = varying(jnp.full((b, h, l_local), -jnp.inf, jnp.float32))
    l0 = varying(jnp.zeros((b, h, l_local), jnp.float32))

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def accumulate(carry_acc, k_blk, v_blk, i):
        acc, m, l = carry_acc
        src_idx = (my_idx - i) % axis_size  # whose block we currently hold
        num, m_blk, l_blk = _block_attend(
            q, k_blk, v_blk, scale,
            q_offset=my_idx * l_local, k_offset=src_idx * l_local,
            causal=causal,
        )
        m_new = jnp.maximum(m, m_blk)
        m_new_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        corr_old = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_new_safe))
        corr_blk = jnp.where(jnp.isinf(m_blk), 0.0, jnp.exp(m_blk - m_new_safe))
        l_new = l * corr_old + l_blk * corr_blk
        # corr shapes (B,H,Lq) -> broadcast to (B,Lq,H,1)
        co = jnp.transpose(corr_old, (0, 2, 1))[..., None]
        cb = jnp.transpose(corr_blk, (0, 2, 1))[..., None]
        acc_new = acc * co + num * cb
        return (acc_new, m_new, l_new)

    def step(carry, i):
        acc, m, l, k_blk, v_blk = carry
        acc, m, l = accumulate((acc, m, l), k_blk, v_blk, i)
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (acc, m, l, k_next, v_next), ()

    # scan the first axis_size-1 steps (attend + rotate), then attend the
    # final resident block outside the loop — avoids a wasted trailing
    # ppermute pair that XLA cannot DCE out of the scan body
    if axis_size > 1:
        (acc, m, l, k_last, v_last), _ = jax.lax.scan(
            step, (acc0, m0, l0, k, v), jnp.arange(axis_size - 1)
        )
        acc, m, l = accumulate((acc, m, l), k_last, v_last, axis_size - 1)
    else:
        acc, m, l = accumulate((acc0, m0, l0), k, v, 0)
    denom = jnp.transpose(jnp.maximum(l, 1e-20), (0, 2, 1))[..., None]
    return (acc / denom).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name: str = "seq",
                           causal: bool = False,
                           scale: Optional[float] = None):
    """GSPMD-land entry: q,k,v are GLOBAL (B, L, H, D) values; shard_map
    partitions L over `axis_name` and runs the ring. Call inside jit."""
    from jax.sharding import PartitionSpec as P

    shard_map = jax.shard_map

    # keep the batch dim sharded over 'data' when that axis exists, so DP x SP
    # composes without an all-gather + redundant compute at the region edge
    batch_axis = "data" if "data" in mesh.axis_names else None
    spec = P(batch_axis, axis_name, None, None)
    vary = tuple(a for a in (batch_axis, axis_name) if a is not None)
    fn = functools.partial(ring_attention, axis_name=axis_name, causal=causal,
                           scale=scale, vary_axes=vary)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)
