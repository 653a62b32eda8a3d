"""Fused KV-cache attention decode steps (Pallas, fwd-only).

The continuous batcher's per-iteration hot loop (serving/sched/
continuous.py `decode_all`) runs ops/attention.py `_decode_step` with a
(B,) VECTOR of per-slot positions: every active slot attends its new
query token(s) against its own span of the KV cache. The reference
lowering contracts over every row the pool ALLOCATED under a `<= pos` mask
and materializes the (B, h, C, M) logits and probs in HBM every iteration;
these kernels run QK^T -> masked softmax -> V in ONE pass (online softmax
across blocks, f32 accumulation). Caches are taken as the pool stores them
(serving/sched/kvpool.py `kv_cache_spec`), packed (B, M, kv_heads*d).

Two entry points, two bodies:

 - `fused_decode_attention` — C = 1, the plain decode iteration (one new
   token per slot), kernel family `attention_decode`: what a TPU runs
   where kernels/registry.py `filled_rows_decode` admits the shapes. It
   reads the rows each slot has FILLED, in latent_decode.py's form and
   with its helpers (`block_rows`, `filled_blocks`, the online softmax):
   grid (slots,), the caches left in HBM, a loop INSIDE the kernel over
   the `pos // block + 1` blocks that hold a filled row, two VMEM buffers
   for K and for V, the next slot's first block started by the slot
   before it; no block past `pos[slot]` is copied, and the one edge block
   masks its scores and zeroes its V rows past `pos` (stale rows may hold
   anything, NaN included). Grouped KV heads (n < h): query head j reads
   KV head j // (h/n) — its query sits in that head's lanes of an
   n*d-wide row and zeros elsewhere, so ONE product over the packed block
   is every head's QK^T, and of the n*d context lanes a head's
   probabilities give, its KV head's d are kept (the form XLA's chain
   takes; no key or value is repeated or relaid out). On a v5e the block's
   copy sets the pace, not its products: 730 GB/s of rows read at 40 x
   12,288 x 1,024 lanes, one product a block or one a KV head alike
   (PERF.md section 6, PR 34).
 - `fused_multiquery_decode_attention` — C >= 1 query tokens per slot
   per dispatch, kernel family `attention_decode_mq`, behind
   `KERNELS.override` alone. Query j of slot b sits at absolute position
   pos[b] + j and attends cache rows `k_pos <= pos[b] + j` — causal over
   the already-filled prefix PLUS the in-flight query window itself: a
   chunked prefill's C-token chunks and speculative decoding's k
   proposals plus the pending token (docs/serving.md). One K/V head a
   query head; its grid walks ALL `max_len` blocks in `block_k` rows.

Inference-only, so no VJP. Scores and softmax in float32, probabilities
cast to the query's type before the value product, accumulation in
float32: the reference chain's types (`_masked_core`). The online softmax
is the reference's math reassociated, equal to float rounding; greedy
argmax parity across block boundaries is pinned by
tests/test_pallas_kernels.py (ragged positions, slot reuse, bf16 caches)
for BOTH entry points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .latent_decode import (NEG_INF, _nt, block_rows, filled, filled_blocks,
                            softmax_start, softmax_step)


def _filled_kernel(pos_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem,
                   turn_ref, acc_ref, m_ref, l_ref, *, scale, block, slots,
                   group, head_dim):
    """Grid = (slots,): one slot's block-diagonal query (heads, e) resident,
    its filled blocks of K and V (block, e) walked by `filled_blocks`."""
    pos = pos_ref[pl.program_id(0)]
    softmax_start(acc_ref, m_ref, l_ref)
    q = q_ref[0]

    def compute(buf, first, edge):
        k = k_buf[buf].astype(q.dtype)
        v = v_buf[buf].astype(q.dtype)
        s = _nt(q, k) * scale                            # (heads, block) f32
        if edge:
            # rows past `pos` may hold anything a retired tenant or a
            # padded prefill left, NaN included
            s = jnp.where(filled(first, block, pos, 1), s, NEG_INF)
            v = jnp.where(filled(first, block, pos, 0), v, jnp.zeros_like(v))
        softmax_step(s, v, acc_ref, m_ref, l_ref)

    filled_blocks(pos, (k_hbm, v_hbm), (k_buf, v_buf), sem, turn_ref,
                  block=block, slots=slots, compute=compute)
    wide = acc_ref[...] / l_ref[...]                     # (heads, e)
    heads, e = wide.shape
    # of the e context lanes a head's probabilities give, its KV head's
    kv_head = jax.lax.broadcasted_iota(jnp.int32, (heads, 1), 0) // group
    out = jnp.zeros((heads, head_dim), jnp.float32)
    for n in range(e // head_dim):
        out = jnp.where(kv_head == n,
                        wide[:, n * head_dim:(n + 1) * head_dim], out)
    o_ref[0] = out.astype(o_ref.dtype)


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
                           interpret: bool = False):
    """One decode step for every slot: q (B, 1, h, d) new-token
    projections, caches (B, M, n*d) ALREADY updated at pos — n KV heads,
    query head j reads KV head j // (h/n) —, pos (B,) per-slot positions
    (row `pos` is the last one attended; 0 <= pos < M). Returns the
    context (B, 1, h, d) in q.dtype — the output projection stays outside
    (a plain matmul XLA handles)."""
    b, c, heads, d = q.shape
    if c != 1:
        raise ValueError(
            f"fused decode takes one query token per slot, got "
            f"C={c}; use fused_multiquery_decode_attention")
    m, e = k_cache.shape[1:]
    kv_heads = e // d
    if (k_cache.shape != (b, m, e) or v_cache.shape != (b, m, e)
            or kv_heads * d != e or heads % kv_heads):
        raise ValueError(
            f"fused decode takes the packed (B, M, kv_heads*head_dim) caches"
            f" for q {q.shape}, got k {k_cache.shape} v {v_cache.shape}")
    block = block_rows(m)
    if block is None:
        raise ValueError(f"fused decode: no block of whole 16-row tiles"
                         f" divides a cache of {m} rows")
    group = heads // kv_heads
    # a position outside the cache would copy rows that are not there
    pos = jnp.clip(pos.astype(jnp.int32), 0, m - 1)
    # head j's query in the lanes of ITS KV head of an e-wide row, zeros
    # elsewhere: one product over the packed row is every head's QK^T
    own = (jnp.arange(heads)[:, None] // group
           == jnp.arange(kv_heads)[None, :])[None, :, :, None]
    q_wide = jnp.where(own, q[:, 0, :, None, :], 0).reshape(b, heads, e)
    slot = lambda ib, pos: (ib, 0, 0)
    out = pl.pallas_call(
        functools.partial(_filled_kernel, scale=float(scale), block=block,
                          slots=b, group=group, head_dim=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, heads, e), slot),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, heads, d), slot),
            scratch_shapes=[
                pltpu.VMEM((2, block, e), k_cache.dtype),
                pltpu.VMEM((2, block, e), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((heads, e), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, d), q.dtype),
        # the slots run in order: each starts the next one's first copy
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pos, q_wide, k_cache, v_cache)
    return out.reshape(b, 1, heads, d)


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
