"""Absorbed latent-attention decode core (Pallas, fwd-only): ONE pass over
each slot's latent rows that stops at the slot's own position.

ops/latent_attention.py `_absorbed` takes scores and context on the latent
cache as stored — `c_kv` (slots, max_len, kv_lora_rank) and `k_rope`
(slots, max_len, 128) — over every row the pool ALLOCATED under a
`<= pos` mask, materialises (slots, heads, max_len) f32 scores and reads
`c_kv` twice. This kernel reads the rows each slot has FILLED, once:

  grid (slots,); per slot the queries carried into the latent space,
  `q_lat` (heads, kv_lora_rank), and the lane-padded `q_rope` (heads, 128)
  are resident — all heads share the latent rows, so the heads are the
  ROWS of one product. The two caches stay in HBM where they lie, and a
  loop over the blocks of rows that hold a filled row, `pos // block + 1`
  of them, copies each block into one of two VMEM buffers while the one
  before it is computed: scores `q_lat c_kv^T + q_rope k_rope^T` in f32,
  times the slot's scale, online softmax in f32, context `p c_kv`
  accumulated in f32 from the SAME block. The copy of a slot's first
  block is started by the slot before it, so no slot waits for its own.

  A block past `pos[slot]` is never copied. The one block that holds row
  `pos` masks its scores and zeroes its latent rows past `pos`, so nothing
  a retired request or a padded prefill left there reaches the output;
  the blocks before it are filled whole and take no mask at all.

The loop stands inside the kernel and not in the grid (with the row blocks
as an inner grid axis whose index map is clamped to `pos // block`, a
skipped step still costs 0.3 us: 1.2 ms of a decode iteration's 2.8 at 128
slots x 8 blocks x 6 layers, PERF.md section 6, PR 30).

The walk over a slot's filled blocks (`block_rows`, `rows_read`,
`filled_blocks`, `filled`) and the online softmax (`softmax_start`,
`softmax_step`) are shared with the dense twin, decode.py
`fused_decode_attention` (K and V in place of the two latent arrays).

Positions and scales are scalar-prefetched to SMEM. Inference-only, so no
VJP. `W_kvb` stays outside on both sides (the absorb and the value
products are plain matmuls XLA handles).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_ROWS = 512


def block_rows(max_len: int):
    """Rows of one copied block for a cache of `max_len` rows: a cache no
    longer than BLOCK_ROWS is one block, a longer one is cut into the
    largest blocks of whole 16-row tiles that divide it (the copies take
    whole blocks, so none may overhang the cache). None where there is no
    such block: the op then keeps its reference lowering."""
    if max_len <= BLOCK_ROWS:
        return max_len
    block = math.gcd(max_len, BLOCK_ROWS)
    return block if block % 16 == 0 else None


def rows_read(pos, max_len: int):
    """Cache rows the kernel copies for positions `pos` (B,): every block
    up to the one that holds row `pos`, summed over the slots."""
    block = block_rows(max_len)
    return jnp.sum((pos // block + 1) * block)


def _nt(a, b):
    """a (m, k) against b (n, k) over k, f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def filled_blocks(pos, hbm, bufs, sem, turn_ref, *, block, slots, compute):
    """Grid step `ib` is slot `ib`: walk the blocks of `block` rows that
    hold a filled row of the slot, `pos // block + 1` of them, each copied
    from every cache of `hbm` ((slots, max_len, width), left where it
    lies) into one of the two halves of its `bufs` entry ((2, block,
    width) VMEM) while the block before it is computed. `compute(buf,
    first, edge)` is handed the half that holds the block, the block's
    first row and whether it is the EDGE block — the one that holds row
    `pos` and rows past it; the blocks before it are filled whole. No
    block past `pos` is ever copied.

    The slots take turns at the two halves across the whole grid, not
    within a slot (`turn_ref`, SMEM (1,)): each slot starts the copy of the
    next slot's first block beside its own last, so no slot waits for its
    own. `sem` is DMA (len(hbm), 2); the grid must run in order
    (`dimension_semantics=("arbitrary",)`)."""
    ib = pl.program_id(0)
    n_full = (pos + 1) // block     # blocks filled whole
    n_blocks = pos // block + 1     # blocks that hold a filled row

    def copies(buf, slot, blk):
        rows = pl.ds(pl.multiple_of(blk * block, block), block)
        return [pltpu.make_async_copy(cache.at[slot, rows], held.at[buf],
                                      sem.at[which, buf])
                for which, (cache, held) in enumerate(zip(hbm, bufs))]

    @pl.when(ib == 0)
    def _first_copy():
        turn_ref[0] = 0
        for copy in copies(0, 0, 0):
            copy.start()

    # which half this slot's first block was copied into
    turn = turn_ref[0]

    def step(i, edge):
        buf = (turn + i) % 2
        more = i + 1 < n_blocks

        # the block after this one: the slot's own next, else the next
        # slot's first
        @pl.when(more | (ib + 1 < slots))
        def _start_next():
            for copy in copies(1 - buf, jnp.where(more, ib, ib + 1),
                               jnp.where(more, i + 1, 0)):
                copy.start()

        for copy in copies(buf, ib, i):
            copy.wait()
        compute(buf, i * block, edge)

    jax.lax.fori_loop(0, n_full, lambda i, _: step(i, False), None)
    pl.when(n_full < n_blocks)(lambda: step(n_full, True))
    turn_ref[0] = (turn + n_blocks) % 2


def filled(first, block, pos, axis):
    """Whether each row of the block that starts at row `first` lies at or
    before `pos`: (block, 1) along axis 0, (1, block) along axis 1."""
    shape = (1, block) if axis else (block, 1)
    return first + jax.lax.broadcasted_iota(jnp.int32, shape, axis) <= pos


def softmax_start(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def softmax_step(s, values, acc_ref, m_ref, l_ref):
    """One block of the online softmax: scores `s` (queries, block) in
    float32 against the running maximum and sum ((queries, 1) float32),
    the probabilities cast to the values' type before their product, the
    context accumulated in float32 (queries, width)."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    fix = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * fix + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * fix + jnp.dot(
        p.astype(values.dtype), values, preferred_element_type=jnp.float32)


def _kernel(pos_ref, scale_ref, ql_ref, qr_ref, c_hbm, r_hbm, o_ref,
            c_buf, r_buf, sem, turn_ref, acc_ref, m_ref, l_ref, *, block,
            slots):
    ib = pl.program_id(0)
    pos = pos_ref[ib]
    softmax_start(acc_ref, m_ref, l_ref)
    ql, qr = ql_ref[0], qr_ref[0]                        # (H, kvr), (H, 128)
    scale = scale_ref[ib]

    def compute(buf, first, edge):
        c = c_buf[buf].astype(ql.dtype)                  # (block, kvr)
        r = r_buf[buf].astype(ql.dtype)
        s = (_nt(ql, c) + _nt(qr, r)) * scale            # (H, block) f32
        if edge:
            s = jnp.where(filled(first, block, pos, 1), s, NEG_INF)
            c = jnp.where(filled(first, block, pos, 0), c, jnp.zeros_like(c))
        softmax_step(s, c, acc_ref, m_ref, l_ref)

    filled_blocks(pos, (c_hbm, r_hbm), (c_buf, r_buf), sem, turn_ref,
                  block=block, slots=slots, compute=compute)
    o_ref[0] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)


def latent_decode_attention(q_lat, q_rope, c_kv, k_rope, pos, scale, *,
                            interpret: bool = False):
    """One decode step's absorbed core for every slot: q_lat (B, H, kvr)
    and q_rope (B, H, lanes) the new token's queries, c_kv (B, M, kvr) and
    k_rope (B, M, lanes) the caches ALREADY updated at `pos`, pos (B,) the
    per-slot positions (row `pos` is the last one attended; 0 <= pos < M),
    scale a float or (B,) per-slot factor on the scores. Returns the
    context in the latent space, (B, H, kvr) in q_lat.dtype."""
    b, heads, kvr = q_lat.shape
    m, lanes = c_kv.shape[1], k_rope.shape[2]
    if (c_kv.shape != (b, m, kvr) or k_rope.shape != (b, m, lanes)
            or q_rope.shape != (b, heads, lanes)):
        raise ValueError(
            f"latent decode takes q_lat (B, H, kvr), q_rope (B, H, lanes)"
            f" and the caches (B, M, kvr), (B, M, lanes) as stored; got"
            f" {q_lat.shape}, {q_rope.shape}, {c_kv.shape}, {k_rope.shape}")
    block = block_rows(m)
    if block is None:
        raise ValueError(f"latent decode: no block of whole 16-row tiles"
                         f" divides a cache of {m} rows")
    # a position outside the cache would copy rows that are not there
    pos = jnp.clip(pos.astype(jnp.int32), 0, m - 1)
    scale = jnp.broadcast_to(jnp.asarray(scale, jnp.float32), (b,))

    query = lambda ib, pos, scale: (ib, 0, 0)
    return pl.pallas_call(
        functools.partial(_kernel, block=block, slots=b),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, heads, kvr), query),
                pl.BlockSpec((1, heads, lanes), query),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, heads, kvr), query),
            scratch_shapes=[
                pltpu.VMEM((2, block, kvr), c_kv.dtype),
                pltpu.VMEM((2, block, lanes), k_rope.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((heads, kvr), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, kvr), q_lat.dtype),
        # the slots run in order: each starts the next one's first copy
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pos, scale, q_lat, q_rope.astype(q_lat.dtype), c_kv, k_rope)
