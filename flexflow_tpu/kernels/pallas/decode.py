"""Fused KV-cache attention decode steps (Pallas, fwd-only).

The continuous batcher's per-iteration hot loop (serving/sched/
continuous.py `decode_all`) runs ops/attention.py `_decode_step` with a
(B,) VECTOR of per-slot positions: every active slot attends its new
query token(s) against its own span of the paged KV cache. The reference
lowering materializes the (B, h, C, M) logits and probs in HBM every
iteration; these kernels run QK^T -> masked softmax -> V in ONE pass
with the queries resident and the cache streamed through VMEM in
`block_k` rows (online softmax across blocks, f32 accumulation).

Two entry points over ONE kernel body:

 - `fused_decode_attention` — C = 1, the plain decode iteration (one new
   token per slot), kernel family `attention_decode`;
 - `fused_multiquery_decode_attention` — C >= 1 query tokens per slot
   per dispatch, kernel family `attention_decode_mq`. Query j of slot b
   sits at absolute position pos[b] + j and attends cache rows
   `k_pos <= pos[b] + j` — causal over the already-filled prefix PLUS
   the in-flight query window itself. This is what lets (a) chunked
   prefill lower its C-token chunks through the same kernel as decode
   instead of materializing (B, h, C, M) logits in HBM, and (b)
   speculative decoding score a draft's k proposals plus the pending
   token in one dispatch (docs/serving.md).

Inference-only, so no VJP. Layout is packed (heads iterated over lane
slices inside the body, like kernels/flash_attention.py's packed
variant): q (B, C, heads*d), caches (B, M, heads*d) — the KV cache as the
pool stores it (serving/sched/kvpool.py `kv_cache_spec`), taken in place;
only the small per-head (B, C, h, d) query is reshaped.

Token parity: when the whole cache fits one block the kernel computes
max/exp/sum/divide in exactly the reference einsum path's order and
dtypes, so greedy decode is token-identical to the reference. The
multi-block path streams blocks through the online softmax — the same
math reassociated, equal to float rounding; greedy argmax parity across
block boundaries is pinned by tests/test_pallas_kernels.py (ragged
positions, slot reuse, bf16 caches) for BOTH entry points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale, block_k, kv_len, heads, head_dim, c):
    """Grid = (B, n_k_blocks); k innermost, the C query rows resident.
    pos_ref is the (B,) position vector, scalar-prefetched into SMEM (a
    (1, 1) VMEM block of a (B, 1) array is not a legal TPU tile)."""
    ik = pl.program_id(1)
    n_kb = pl.num_programs(1)
    single = n_kb == 1

    if not single:
        @pl.when(ik == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                          # (C, e)
    k = k_ref[0].astype(q.dtype)                          # (bk, e)
    v = v_ref[0].astype(q.dtype)
    pos = pos_ref[pl.program_id(0)]
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    # query j sits at absolute position pos + j: causal over the filled
    # prefix plus the query window itself (C = 1 degenerates to the
    # plain <= pos decode mask)
    q_off = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    mask = (k_pos < kv_len) & (k_pos <= pos + q_off)      # (C, bk)

    for h in range(heads):
        sl = slice(h * head_dim, (h + 1) * head_dim)
        s = jnp.dot(q[:, sl], k[:, sl].T,
                    preferred_element_type=jnp.float32) * scale  # (C, bk)
        s = jnp.where(mask, s, NEG_INF)
        if single:
            # plain softmax in the reference path's exact op order, so
            # greedy decode stays token-identical to the einsum lowering
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, :, sl] = jnp.dot(
                (p / l_safe).astype(q.dtype), v[:, sl],
                preferred_element_type=jnp.float32).astype(o_ref.dtype)
            continue
        m_prev = m_ref[:, h:h + 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        m_ref[:, h:h + 1] = m_new
        l_ref[:, h:h + 1] = (l_ref[:, h:h + 1] * correction
                             + jnp.sum(p, axis=1, keepdims=True))
        acc_ref[:, sl] = acc_ref[:, sl] * correction + jnp.dot(
            p.astype(q.dtype), v[:, sl],
            preferred_element_type=jnp.float32)

    if not single:
        @pl.when(ik == n_kb - 1)
        def _emit():
            l = l_ref[:]                                  # (C, heads)
            l_safe = jnp.where(l == 0.0, 1.0, l)
            for h in range(heads):
                sl = slice(h * head_dim, (h + 1) * head_dim)
                o_ref[0, :, sl] = (acc_ref[:, sl]
                                   / l_safe[:, h:h + 1]).astype(o_ref.dtype)


def _call_decode(q, k_cache, v_cache, pos, *, scale, block_k, interpret):
    b, c, heads, head_dim = q.shape
    m = k_cache.shape[1]
    e = heads * head_dim
    if k_cache.shape != (b, m, e) or v_cache.shape != (b, m, e):
        raise ValueError(
            f"decode kernels take the packed (B, M, heads*head_dim) caches"
            f" {(b, m, e)}, got k {k_cache.shape} v {v_cache.shape}")
    qp = q.reshape(b, c, e)
    block_k = max(1, min(block_k, m))
    m_pad = -(-m // block_k) * block_k
    if m_pad != m:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, m_pad - m), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, m_pad - m), (0, 0)))
    n_kb = m_pad // block_k

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale),
                          block_k=block_k, kv_len=m, heads=heads,
                          head_dim=head_dim, c=c),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_kb),
            in_specs=[
                pl.BlockSpec((1, c, e), lambda ib, ik, pos: (ib, 0, 0)),
                pl.BlockSpec((1, block_k, e), lambda ib, ik, pos: (ib, ik, 0)),
                pl.BlockSpec((1, block_k, e), lambda ib, ik, pos: (ib, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, c, e), lambda ib, ik, pos: (ib, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((c, e), jnp.float32),
                pltpu.VMEM((c, heads), jnp.float32),
                pltpu.VMEM((c, heads), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, c, e), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), qp, k_cache, v_cache)
    return out.reshape(b, c, heads, head_dim)


def fused_decode_attention(q, k_cache, v_cache, pos, *, scale: float,
                           block_k: int = 512, interpret: bool = False):
    """One decode step for every slot: q (B, 1, h, d) new-token
    projections, caches (B, M, h*d) ALREADY updated at pos, pos (B,)
    per-slot positions. Returns the context (B, 1, h, d) in q.dtype —
    the output projection stays outside (a plain matmul XLA handles)."""
    if q.shape[1] != 1:
        raise ValueError(
            f"fused decode takes one query token per slot, got "
            f"C={q.shape[1]}; use fused_multiquery_decode_attention")
    return _call_decode(q, k_cache, v_cache, pos, scale=scale,
                        block_k=block_k, interpret=interpret)


def fused_multiquery_decode_attention(q, k_cache, v_cache, pos, *,
                                      scale: float, block_k: int = 512,
                                      interpret: bool = False):
    """C query tokens per slot in one dispatch: q (B, C, h, d)
    projections of the tokens at absolute positions pos[b] + j, caches
    (B, M, h*d) ALREADY updated at those rows, pos (B,) per-slot base
    positions. Query j attends rows `k_pos <= pos[b] + j` — causal over
    prefix + query window. Returns the context (B, C, h, d) in q.dtype.

    The two in-tree consumers (ops/attention.py `_decode_step`): the
    chunk-offset PREFILL entry (C chunk tokens at a shared scalar
    offset, broadcast to (B,)) and speculative decoding's verify step
    (C = k + 1 per-slot candidate tokens)."""
    if q.shape[1] < 1:
        raise ValueError(f"need >= 1 query token per slot, got q {q.shape}")
    return _call_decode(q, k_cache, v_cache, pos, scale=scale,
                        block_k=block_k, interpret=interpret)
