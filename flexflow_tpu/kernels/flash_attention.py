"""Flash attention as a Pallas TPU kernel (fwd + bwd).

Role in the framework: the reference wraps cuDNN's fused multi-head-attention
kernels (src/ops/attention.cu); on TPU the softmax(QK^T)V core is the one op
where manual fusion beats XLA *at long context* — materializing the L x L
score matrix in HBM is what OOMs/slows the einsum path. This kernel keeps
scores in VMEM with the standard online-softmax streaming:

  forward:  grid (b, h, q_block, k_block), k innermost. The q block stays
            resident (constant index map on the inner axis), k/v blocks
            stream through VMEM; rowmax m / rowsum l / output accumulator
            live in VMEM scratch that persists across the inner axis;
            the final k step normalizes and emits O and logsumexp.
  backward: recompute p = exp(qk - lse) per block pair (no stored probs).
            dq kernel streams k blocks per resident q block; dkv kernel
            streams q blocks per resident k/v block, using
            D = rowsum(dO * O) for the softmax Jacobian.

Nothing of size L x L ever touches HBM, and VMEM holds only
O(block_q x block_k + block x d) — so sequence length is bounded by HBM
(q/k/v themselves), not VMEM. MXU inputs stay in the stored dtype (bf16
under mixed precision — f32 inputs would run the MXU at 1/4 rate); all
accumulation and the softmax/normalization math are f32
(preferred_element_type + f32 scratch).

Layout is [batch, heads, len, head_dim] internally; the public wrapper takes
the attention op's [batch, len, heads, head_dim] and transposes.

`interpret=True` runs the same kernels in the Pallas interpreter so CPU tests
cover them (SURVEY.md §4's align-test strategy applied to kernels).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pad_to(x, multiple: int, axis: int):
    size = x.shape[axis]
    rem = size % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, multiple - rem)
    return jnp.pad(x, pad)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_k, kv_len, q_offset):
    """Grid = (b, h, n_q_blocks, n_k_blocks); the k axis is innermost."""
    iq, ik = pl.program_id(2), pl.program_id(3)
    n_kb = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # MXU inputs stay in the stored dtype (bf16 under mixed precision —
    # f32 inputs would run the MXU at 1/4 rate); accumulation is f32 via
    # preferred_element_type, and the softmax/normalization math is f32.
    q = q_ref[0, 0]                                       # (bq, d)
    k = k_ref[0, 0]                                       # (bk, d)
    v = v_ref[0, 0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < kv_len
    if causal:
        # cross-length semantics match tril(ones(lq, lk), lk - lq):
        # query i attends keys j <= i + (lk - lq)
        mask = mask & (k_pos <= q_pos + q_offset)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    correction = jnp.exp(m_prev - m_new)
    m_ref[:] = m_new
    l_ref[:] = l_ref[:] * correction + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[:] = acc_ref[:] * correction + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(ik == n_kb - 1)
    def _emit():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        # lse carried as [.., lq, 1]: a lane dim of exactly 1 matches the
        # array, satisfying the TPU (8k, 128)-or-full tiling rule
        lse_ref[0, 0] = (m_ref[:] + jnp.log(l_safe)).astype(jnp.float32)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    """q,k,v: [b, h, l, d] → (o [b,h,lq,d], lse [b,h,lq,1])."""
    b, h, lq, d = q.shape
    kv_len = k.shape[2]
    block_q = min(block_q, max(lq, 1))
    block_k = min(block_k, max(kv_len, 1))
    qp = _pad_to(q, block_q, axis=2)
    kp = _pad_to(k, block_k, axis=2)
    vp = _pad_to(v, block_k, axis=2)
    lq_pad, kv_pad = qp.shape[2], kp.shape[2]
    grid = (b, h, lq_pad // block_q, kv_pad // block_k)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=kv_len, q_offset=kv_len - lq)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, lq_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, lq_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return o[:, :, :lq], lse[:, :, :lq]


# ---------------------------------------------------------------------------
# packed layout: (b, l, heads*d), heads iterated inside the kernel
# ---------------------------------------------------------------------------
#
# The bhld kernels above need their operands physically laid out [b,h,l,d];
# a custom call can't absorb a layout change, so XLA materializes real
# transposes between the (b,l,e)-shaped projections and the kernel —
# measured ~5 ms/step (13%) on the BERT bench config (r4 xprof). The packed
# variant takes q/k/v exactly as the projection matmuls emit them,
# (b, l, heads*head_dim), and loops the heads over static lane slices
# inside the body: no transpose, no copy, contiguous DMA rows. The grid
# drops the head axis — (b, q_blocks, k_blocks) — so each program computes
# every head of its block pair; rowmax/rowsum scratch carries one lane per
# head, (block_q, heads).


def _packed_compiler_params(io_blocks, scratch, block_q, block_k):
    """Scoped-VMEM request for one packed kernel, derived from its blocks.

    Mosaic's default scoped limit (16 MiB on v5e) is below what the packed
    kernels need once a block spans every head: at the BERT bench shape
    (512x512 blocks, 1024 lanes) the forward kernel wants 17.8 MiB in bf16
    and 25.9 MiB in f32, and the compiler refuses it. The need is the
    double-buffered I/O blocks, the scratch, and about ten live f32
    (block_q, block_k) score temporaries of the unrolled head loop —
    counted as twelve. It is a ceiling, not an allocation, so it is rounded
    up generously and never set below 32 MiB.

    io_blocks / scratch: (shape, dtype) of every blocked operand+result /
    scratch buffer."""
    def nbytes(shape, dtype):
        return int(np.prod(shape)) * jnp.dtype(dtype).itemsize

    need = (2 * sum(nbytes(*b) for b in io_blocks)
            + sum(nbytes(*b) for b in scratch)
            + 12 * 4 * block_q * block_k)
    return pltpu.CompilerParams(
        vmem_limit_bytes=max(32 * 2 ** 20, int(1.25 * need)))


def _block_mask(iq, ik, *, causal, block_q, block_k, kv_len, q_len, q_offset,
                check_q=False):
    """Mask for one (q_block, k_block) pair, or None when every position is
    live — full blocks in a non-causal kernel. The None case matters: the
    kernels are VPU-bound (the r4 trace put them at ~30 TF/s while the exp/
    select work dwarfs the d=64 MXU dots), so skipping a dead
    iota+compare+select per head is a real win on encoder models."""
    need_kv = kv_len % block_k != 0
    need_q = check_q and q_len % block_q != 0
    if not (causal or need_kv or need_q):
        return None
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = None
    if need_kv:
        mask = k_pos < kv_len
    if need_q:
        qm = q_pos < q_len
        mask = qm if mask is None else mask & qm
    if causal:
        cm = k_pos <= q_pos + q_offset
        mask = cm if mask is None else mask & cm
    return mask


def _fwd_kernel_packed(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                       l_ref, *, scale, causal, block_q, block_k, kv_len,
                       q_offset, heads, head_dim):
    """Grid = (b, n_q_blocks, n_k_blocks); k innermost."""
    iq, ik = pl.program_id(1), pl.program_id(2)
    n_kb = pl.num_programs(2)
    single = n_kb == 1  # whole kv length in one block: plain softmax, no
    #                     online running state (the seq<=block case)

    if not single:
        @pl.when(ik == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                          # (bq, e)
    k = k_ref[0]                                          # (bk, e)
    v = v_ref[0]
    mask = _block_mask(iq, ik, causal=causal, block_q=block_q,
                       block_k=block_k, kv_len=kv_len, q_len=0,
                       q_offset=q_offset)

    for h in range(heads):
        sl = slice(h * head_dim, (h + 1) * head_dim)
        s = jnp.dot(q[:, sl], k[:, sl].T,
                    preferred_element_type=jnp.float32) * scale
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        if single:
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, :, sl] = (jnp.dot(
                p.astype(v.dtype), v[:, sl],
                preferred_element_type=jnp.float32) / l_safe
            ).astype(o_ref.dtype)
            lse_ref[0, :, h:h + 1] = m + jnp.log(l_safe)
            continue
        m_prev = m_ref[:, h:h + 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        m_ref[:, h:h + 1] = m_new
        l_ref[:, h:h + 1] = (l_ref[:, h:h + 1] * correction
                             + jnp.sum(p, axis=1, keepdims=True))
        acc_ref[:, sl] = acc_ref[:, sl] * correction + jnp.dot(
            p.astype(v.dtype), v[:, sl], preferred_element_type=jnp.float32)

    if not single:
        @pl.when(ik == n_kb - 1)
        def _emit():
            l = l_ref[:]                                  # (bq, heads)
            l_safe = jnp.where(l == 0.0, 1.0, l)
            for h in range(heads):
                sl = slice(h * head_dim, (h + 1) * head_dim)
                o_ref[0, :, sl] = (acc_ref[:, sl]
                                   / l_safe[:, h:h + 1]).astype(o_ref.dtype)
            lse_ref[0] = m_ref[:] + jnp.log(l_safe)


def _flash_fwd_packed(q, k, v, heads, scale, causal, block_q, block_k,
                      interpret):
    """q,k,v: [b, l, heads*d] → (o [b,lq,e], lse [b,lq,heads] f32)."""
    b, lq, e = q.shape
    head_dim = e // heads
    kv_len = k.shape[1]
    block_q = min(block_q, max(lq, 1))
    block_k = min(block_k, max(kv_len, 1))
    qp = _pad_to(q, block_q, axis=1)
    kp = _pad_to(k, block_k, axis=1)
    vp = _pad_to(v, block_k, axis=1)
    lq_pad, kv_pad = qp.shape[1], kp.shape[1]
    grid = (b, lq_pad // block_q, kv_pad // block_k)

    kernel = functools.partial(
        _fwd_kernel_packed, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_len=kv_len, q_offset=kv_len - lq, heads=heads,
        head_dim=head_dim)
    # single k block -> the kernel's plain-softmax path never touches the
    # online-softmax scratch; don't reserve real VMEM for it
    single = kv_pad // block_k == 1
    f32 = jnp.float32
    scratch = [((8, 128) if single else (block_q, e), f32),
               ((8, heads) if single else (block_q, heads), f32),
               ((8, heads) if single else (block_q, heads), f32)]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, e), lambda ib, iq, ik: (ib, iq, 0)),
            pl.BlockSpec((1, block_k, e), lambda ib, iq, ik: (ib, ik, 0)),
            pl.BlockSpec((1, block_k, e), lambda ib, iq, ik: (ib, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, e), lambda ib, iq, ik: (ib, iq, 0)),
            pl.BlockSpec((1, block_q, heads), lambda ib, iq, ik: (ib, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, lq_pad, e), q.dtype),
            jax.ShapeDtypeStruct((b, lq_pad, heads), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM(*sc) for sc in scratch],
        compiler_params=_packed_compiler_params(
            [((block_q, e), q.dtype)] * 2 + [((block_k, e), k.dtype)] * 2
            + [((block_q, 128), f32)], scratch, block_q, block_k),
        interpret=interpret,
    )(qp, kp, vp)
    return o[:, :lq], lse[:, :lq]


def _bwd_dq_kernel_packed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, dq_acc, *, scale, causal, block_q, block_k,
                          kv_len, q_offset, heads, head_dim):
    """Grid = (b, n_q_blocks, n_k_blocks); k innermost."""
    iq, ik = pl.program_id(1), pl.program_id(2)
    n_kb = pl.num_programs(2)

    if n_kb > 1:
        @pl.when(ik == 0)
        def _init():
            dq_acc[:] = jnp.zeros_like(dq_acc)

    q = q_ref[0]
    do = do_ref[0]
    kf = k_ref[0]
    v = v_ref[0]
    lse = lse_ref[0]          # (bq, heads)
    delta = delta_ref[0]      # (bq, heads)
    single = n_kb == 1
    mask = _block_mask(iq, ik, causal=causal, block_q=block_q,
                       block_k=block_k, kv_len=kv_len, q_len=0,
                       q_offset=q_offset)

    for h in range(heads):
        sl = slice(h * head_dim, (h + 1) * head_dim)
        s = jnp.dot(q[:, sl], kf[:, sl].T,
                    preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse[:, h:h + 1])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jnp.dot(do[:, sl], v[:, sl].T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, h:h + 1])
        if single:
            dq_ref[0, :, sl] = (jnp.dot(
                ds.astype(kf.dtype), kf[:, sl],
                preferred_element_type=jnp.float32) * scale
            ).astype(dq_ref.dtype)
            continue
        dq_acc[:, sl] = dq_acc[:, sl] + jnp.dot(
            ds.astype(kf.dtype), kf[:, sl],
            preferred_element_type=jnp.float32)

    if not single:
        @pl.when(ik == n_kb - 1)
        def _emit():
            dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel_packed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                           block_q, block_k, q_len, kv_len, q_offset, heads,
                           head_dim):
    """Grid = (b, n_k_blocks, n_q_blocks); q innermost."""
    ik, iq = pl.program_id(1), pl.program_id(2)
    n_qb = pl.num_programs(2)
    single = n_qb == 1

    if not single:
        @pl.when(iq == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

    k = k_ref[0]
    v = v_ref[0]
    qf = q_ref[0]
    dof = do_ref[0]
    lse = lse_ref[0]
    delta = delta_ref[0]
    mask = _block_mask(iq, ik, causal=causal, block_q=block_q,
                       block_k=block_k, kv_len=kv_len, q_len=q_len,
                       q_offset=q_offset, check_q=True)

    for h in range(heads):
        sl = slice(h * head_dim, (h + 1) * head_dim)
        s = jnp.dot(qf[:, sl], k[:, sl].T,
                    preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse[:, h:h + 1])                  # (bq, bk)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jnp.dot(dof[:, sl], v[:, sl].T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, h:h + 1])
        if single:
            dv_ref[0, :, sl] = jnp.dot(
                p.T.astype(dof.dtype), dof[:, sl],
                preferred_element_type=jnp.float32).astype(dv_ref.dtype)
            dk_ref[0, :, sl] = (jnp.dot(
                ds.T.astype(qf.dtype), qf[:, sl],
                preferred_element_type=jnp.float32) * scale
            ).astype(dk_ref.dtype)
            continue
        dv_acc[:, sl] = dv_acc[:, sl] + jnp.dot(
            p.T.astype(dof.dtype), dof[:, sl],
            preferred_element_type=jnp.float32)
        dk_acc[:, sl] = dk_acc[:, sl] + jnp.dot(
            ds.T.astype(qf.dtype), qf[:, sl],
            preferred_element_type=jnp.float32)

    if not single:
        @pl.when(iq == n_qb - 1)
        def _emit():
            dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_packed(heads, scale, causal, block_q, block_k, interpret,
                      residuals, g):
    q, k, v, o, lse = residuals
    b, lq, e = q.shape
    head_dim = e // heads
    kv_len = k.shape[1]
    block_q = min(block_q, max(lq, 1))
    block_k = min(block_k, max(kv_len, 1))

    do = g.astype(q.dtype)
    # delta[b, l, h] = sum_d dO * O per head — small fused reduce outside
    delta = jnp.sum(
        (g.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
            b, lq, heads, head_dim),
        axis=-1)                                          # (b, lq, heads)

    qp = _pad_to(q, block_q, axis=1)
    dop = _pad_to(do, block_q, axis=1)
    lsep = _pad_to(lse, block_q, axis=1)
    deltap = _pad_to(delta, block_q, axis=1)
    kp = _pad_to(k, block_k, axis=1)
    vp = _pad_to(v, block_k, axis=1)
    lq_pad, kv_pad = qp.shape[1], kp.shape[1]

    q_spec = pl.BlockSpec((1, block_q, e), lambda ib, iq, ik: (ib, iq, 0))
    k_spec = pl.BlockSpec((1, block_k, e), lambda ib, iq, ik: (ib, ik, 0))
    qvec_spec = pl.BlockSpec((1, block_q, heads),
                             lambda ib, iq, ik: (ib, iq, 0))
    # VMEM accounting (see _packed_compiler_params): the (., heads) lse /
    # delta blocks pad to a full 128-lane tile
    f32 = jnp.float32
    q_blocks = [((block_q, e), q.dtype)]
    k_blocks = [((block_k, e), k.dtype)]
    vec_blocks = [((block_q, 128), f32)] * 2
    dq_scratch = [((8, 128) if kv_pad // block_k == 1 else (block_q, e),
                   f32)]
    dkv_scratch = [((8, 128) if lq_pad // block_q == 1 else (block_k, e),
                    f32)] * 2

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_packed, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, kv_len=kv_len,
                          q_offset=kv_len - lq, heads=heads,
                          head_dim=head_dim),
        grid=(b, lq_pad // block_q, kv_pad // block_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, qvec_spec, qvec_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, lq_pad, e), q.dtype),
        scratch_shapes=[pltpu.VMEM(*sc) for sc in dq_scratch],
        compiler_params=_packed_compiler_params(
            q_blocks * 3 + k_blocks * 2 + vec_blocks, dq_scratch,
            block_q, block_k),
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)[:, :lq]

    q_spec2 = pl.BlockSpec((1, block_q, e), lambda ib, ik, iq: (ib, iq, 0))
    k_spec2 = pl.BlockSpec((1, block_k, e), lambda ib, ik, iq: (ib, ik, 0))
    qvec_spec2 = pl.BlockSpec((1, block_q, heads),
                              lambda ib, ik, iq: (ib, iq, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_packed, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, q_len=lq,
                          kv_len=kv_len, q_offset=kv_len - lq, heads=heads,
                          head_dim=head_dim),
        grid=(b, kv_pad // block_k, lq_pad // block_q),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, qvec_spec2, qvec_spec2],
        out_specs=[k_spec2, k_spec2],
        out_shape=[
            jax.ShapeDtypeStruct((b, kv_pad, e), k.dtype),
            jax.ShapeDtypeStruct((b, kv_pad, e), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM(*sc) for sc in dkv_scratch],
        compiler_params=_packed_compiler_params(
            q_blocks * 2 + k_blocks * 4 + vec_blocks, dkv_scratch,
            block_q, block_k),
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)
    return dq, dk[:, :kv_len], dv[:, :kv_len]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_packed(q, k, v, heads, scale, causal, block_q, block_k,
                            interpret):
    o, _ = _flash_fwd_packed(q, k, v, heads, scale, causal, block_q, block_k,
                             interpret)
    return o


def _flash_packed_fwd_rule(q, k, v, heads, scale, causal, block_q, block_k,
                           interpret):
    o, lse = _flash_fwd_packed(q, k, v, heads, scale, causal, block_q,
                               block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_packed_bwd_rule(heads, scale, causal, block_q, block_k, interpret,
                           residuals, g):
    return _flash_bwd_packed(heads, scale, causal, block_q, block_k,
                             interpret, residuals, g)


_flash_attention_packed.defvjp(_flash_packed_fwd_rule, _flash_packed_bwd_rule)


def flash_attention_packed(q, k, v, num_heads: int, *,
                           scale: Optional[float] = None,
                           causal: bool = False, block_q: int = 512,
                           block_k: int = 512, interpret: bool = False):
    """Flash attention on packed (b, l, num_heads*head_dim) tensors.

    Takes q/k/v exactly as (b, l, e) projection matmuls emit them and
    returns the context in the same layout — no [b,h,l,d] transposes on
    either side of the custom call (the packed kernels loop heads over
    static lane slices internally).
    """
    e = q.shape[-1]
    if e % num_heads:
        raise ValueError(f"embed dim {e} not divisible by heads {num_heads}")
    if scale is None:
        scale = 1.0 / np.sqrt(e // num_heads)
    return _flash_attention_packed(q, k, v, int(num_heads), float(scale),
                                   bool(causal), int(block_q), int(block_k),
                                   bool(interpret))


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, block_q, block_k, kv_len,
                   q_offset):
    """Grid = (b, h, n_q_blocks, n_k_blocks); k innermost, dq accumulates in
    scratch across the k axis."""
    iq, ik = pl.program_id(2), pl.program_id(3)
    n_kb = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q = q_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]        # (bq, 1)
    delta = delta_ref[0, 0]    # (bq, 1)
    kf = k_ref[0, 0]
    v = v_ref[0, 0]

    s = jnp.dot(q, kf.T, preferred_element_type=jnp.float32) * scale
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < kv_len
    if causal:
        mask = mask & (k_pos <= q_pos + q_offset)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dq_acc[:] = dq_acc[:] + jnp.dot(
        ds.astype(kf.dtype), kf, preferred_element_type=jnp.float32)

    @pl.when(ik == n_kb - 1)
    def _emit():
        dq_ref[0, 0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, q_len, kv_len, q_offset):
    """Grid = (b, h, n_k_blocks, n_q_blocks); q innermost, dk/dv accumulate
    in scratch across the q axis."""
    ik, iq = pl.program_id(2), pl.program_id(3)
    n_qb = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    k = k_ref[0, 0]                                     # (bk, d)
    v = v_ref[0, 0]
    qf = q_ref[0, 0]                                    # (bq, d)
    dof = do_ref[0, 0]
    lse = lse_ref[0, 0]        # (bq, 1)
    delta = delta_ref[0, 0]    # (bq, 1)

    s = jnp.dot(qf, k.T, preferred_element_type=jnp.float32) * scale
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = (k_pos < kv_len) & (q_pos < q_len)
    if causal:
        mask = mask & (k_pos <= q_pos + q_offset)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)          # (bq, bk)
    dv_acc[:] = dv_acc[:] + jnp.dot(
        p.T.astype(dof.dtype), dof, preferred_element_type=jnp.float32)
    dp = jnp.dot(dof, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dk_acc[:] = dk_acc[:] + jnp.dot(
        ds.T.astype(qf.dtype), qf, preferred_element_type=jnp.float32)

    @pl.when(iq == n_qb - 1)
    def _emit():
        dk_ref[0, 0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(scale, causal, block_q, block_k, interpret, residuals, g):
    q, k, v, o, lse = residuals
    b, h, lq, d = q.shape
    kv_len = k.shape[2]
    block_q = min(block_q, max(lq, 1))
    block_k = min(block_k, max(kv_len, 1))

    do = g.astype(q.dtype)  # MXU input dtype; the kernels accumulate f32
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)                          # (b, h, lq, 1)

    qp = _pad_to(q, block_q, axis=2)
    dop = _pad_to(do, block_q, axis=2)
    lsep = _pad_to(lse, block_q, axis=2)
    deltap = _pad_to(delta, block_q, axis=2)
    kp = _pad_to(k, block_k, axis=2)
    vp = _pad_to(v, block_k, axis=2)
    lq_pad, kv_pad = qp.shape[2], kp.shape[2]

    # dq: q-block resident over the inner (k) axis
    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, iq, ik: (ib, ih, ik, 0))
    qvec_spec = pl.BlockSpec((1, 1, block_q, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, kv_len=kv_len,
                          q_offset=kv_len - lq),
        grid=(b, h, lq_pad // block_q, kv_pad // block_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, qvec_spec, qvec_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lq_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)[:, :, :lq]

    # dkv: k/v-block resident over the inner (q) axis
    q_spec2 = pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, ik, iq: (ib, ih, iq, 0))
    k_spec2 = pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, ik, iq: (ib, ih, ik, 0))
    qvec_spec2 = pl.BlockSpec((1, 1, block_q, 1), lambda ib, ih, ik, iq: (ib, ih, iq, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          q_len=lq, kv_len=kv_len, q_offset=kv_len - lq),
        grid=(b, h, kv_pad // block_k, lq_pad // block_q),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, qvec_spec2, qvec_spec2],
        out_specs=[k_spec2, k_spec2],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, kv_pad, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, kv_pad, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)
    return dq, dk[:, :, :kv_len], dv[:, :, :kv_len]


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_bhld(q, k, v, scale, causal, block_q, block_k, interpret):
    o, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return o


def _flash_attention_fwd_rule(q, k, v, scale, causal, block_q, block_k,
                              interpret):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_attention_bwd_rule(scale, causal, block_q, block_k, interpret,
                              residuals, g):
    return _flash_bwd(scale, causal, block_q, block_k, interpret, residuals, g)


_flash_attention_bhld.defvjp(_flash_attention_fwd_rule,
                             _flash_attention_bwd_rule)


def flash_attention(q, k, v, *, scale: Optional[float] = None,
                    causal: bool = False, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False,
                    layout: str = "blhd"):
    """softmax(QK^T * scale)V with VMEM-tiled online softmax.

    layout="blhd" (default): q [batch, q_len, heads, d], k/v
    [batch, kv_len, heads, d] — the attention op's logical layout; the
    wrapper transposes to the kernel's [b, h, l, d] and back.
    layout="bhld": inputs are already [b, h, l, d] and the result is
    returned in that layout. Callers that can emit their projections
    directly in bhld (a free epilogue re-index inside the projection
    matmul) should: the r4 xprof trace showed the blhd swapaxes pairs cost
    ~5 ms/step (13%) on the BERT bench config as standalone transposes.
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if layout == "bhld":
        return _flash_attention_bhld(q, k, v, float(scale), bool(causal),
                                     int(block_q), int(block_k),
                                     bool(interpret))
    if layout != "blhd":
        raise ValueError(
            f"layout={layout!r}: expected 'blhd' or 'bhld'")
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = _flash_attention_bhld(qt, kt, vt, float(scale), bool(causal),
                              int(block_q), int(block_k), bool(interpret))
    return jnp.swapaxes(o, 1, 2)


def attention_reference(q, k, v, *, scale: Optional[float] = None,
                        causal: bool = False):
    """Naive jnp attention in the same [b, l, h, d] layout — the align-test
    oracle for the kernel."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((lq, lk), dtype=bool), lk - lq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)
