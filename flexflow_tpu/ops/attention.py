"""Multi-head attention (reference: src/ops/attention.cc:1-926, cuDNN MHA API).

The reference wraps cuDNN's multi-head attention with a packed weight tensor
carrying a heads dim (attention.cc:212-216) so the search can shard heads (TP).
Here projections are einsums with an explicit heads axis — shardable over a
mesh axis the same way — and the softmax(QK^T)V core runs in f32. A Pallas
flash-attention kernel and ring-attention (sequence-parallel) variant live in
flexflow_tpu/kernels/ and are selected via params.
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from ..core.op import Op, WeightSpec, register_op
from ..ffconst import CompMode, DataType, OpType
from ..runtime.initializers import DefaultInitializer, ZeroInitializer
from ..runtime.platform import pallas_interpret
from .common import emit_dtype, matmul_dtype
from .latent_attention import _wide_add


def _packed(x):
    """(b, l, h, d) -> (b, l, h*d), the shape the KV cache stores; an
    already packed projection passes through."""
    return x.reshape(x.shape[0], x.shape[1], -1)


# rows of one MXU pass (v5e: 128 x 128)
_MXU_ROWS = 128


def _contract_heads_together(c, heads):
    """Whether the decode step contracts ALL heads at once over the packed
    (B, M, h*d) cache (`_scores` / `_context`). Yes while the C queries of
    every head together are at most one MXU pass of rows: the whole
    h*d-wide cache row is then read once, lane-dense and in place, and the
    h-fold products a block-diagonal query adds hide behind that read (the
    decode iteration and speculative verify are HBM-bound; measured on the
    v5e at 48 x 2,048 x 16 x 64, PERF.md section 6 PR 26). Beyond that (a
    prefill chunk, on a batch-1 cache) the added products are paid for in
    MXU passes — a 512-query chunk takes 7x as long — and one head at a
    time on a reshaped view costs a relayout of a 10 MB cache instead."""
    return c * heads <= _MXU_ROWS


# Grouped heads (`kv_heads` n < heads h): query head j reads KV head
# j // (h/n). The two contractions of the core on the logical layouts, q
# (B, Q, h, d) against k / v (B, K, n, d): the group's queries are a second
# batch axis of their KV head, so no key or value is ever repeated.

def _grouped_scores(q, k):
    b, lq, heads, d = q.shape
    n = k.shape[2]
    if n == heads:
        return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                          preferred_element_type=jnp.float32)
    s = jnp.einsum("bqngd,bknd->bngqk", q.reshape(b, lq, n, heads // n, d),
                   k, preferred_element_type=jnp.float32)
    return s.reshape(b, heads, lq, -1)


def _grouped_context(probs, v):
    b, heads, lq, lk = probs.shape
    n = v.shape[2]
    if n == heads:
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    o = jnp.einsum("bngqk,bknd->bqngd",
                   probs.reshape(b, n, heads // n, lq, lk), v)
    return o.reshape(b, lq, heads, -1)


# The decode step's two contractions over the cache AS STORED: q
# (B, C, h, d), caches (B, M, n*d) for n KV heads, scores (B, h, C, M) in
# float32, context (B, C, h, d). `together`: head j's query sits in the
# lanes of ITS KV head, [(j // g)*d, (j // g + 1)*d) with g = h/n, of its
# own n*d-wide row and zeros elsewhere, so one contraction over the packed
# row is every head's QK^T (the added products are exact zeros); of the
# n*d context lanes a head's probabilities give, its KV head's d are kept.
# Otherwise the per-head einsums on a (B, M, n, d) view.

def _own_lanes(heads, kv_heads):
    if kv_heads == heads:
        return jnp.eye(heads, dtype=bool)[:, :, None]  # (j, j', 1)
    return (jnp.arange(heads)[:, None] // (heads // kv_heads)
            == jnp.arange(kv_heads)[None, :])[:, :, None]


def _scores(q, kc, together):
    b, c, heads, d = q.shape
    n = kc.shape[-1] // d
    if not together:
        return _grouped_scores(q, kc.reshape(b, -1, n, d))
    qb = jnp.where(_own_lanes(heads, n), q[:, :, :, None, :], 0)
    logits = jnp.einsum("bxe,bme->bxm", qb.reshape(b, c * heads, n * d),
                        kc, preferred_element_type=jnp.float32)
    return logits.reshape(b, c, heads, -1).transpose(0, 2, 1, 3)


def _context(probs, vc, together, kv_heads):
    b, heads, c, m = probs.shape
    if not together:
        return _grouped_context(probs, vc.reshape(b, m, kv_heads, -1))
    wide = jnp.einsum("bxm,bme->bxe",
                      probs.transpose(0, 2, 1, 3).reshape(b, c * heads, m),
                      vc).reshape(b, c, heads, kv_heads, -1)
    return jnp.sum(jnp.where(_own_lanes(heads, kv_heads), wide, 0), axis=3)


# the shortest prefix of a long cache that a chunk at an offset is attended
# in; the next ones double it. The scores of 512 queries over the 12,799 rows
# of `laguna_xs2_1chip`'s batch-1 holder are 1.26 GB of float32 a full layer,
# for prompts whose chunks end at row 3,800 in the mean (PERF.md section 6,
# PR 33)
PREFIX_ROWS = 2048


def _prefixes(rows: int) -> List[int]:
    """The static lengths a cache of `rows` rows is attended in by queries
    at a scalar offset: `PREFIX_ROWS`, its doubles under `rows`, and the
    whole; a cache of up to two of them whole alone."""
    if rows <= 2 * PREFIX_ROWS:
        return [rows]
    out, p = [], PREFIX_ROWS
    while p < rows:
        out.append(p)
        p *= 2
    return out + [rows]


# A WINDOW op's cache is a RING of R rows (`kv_ring_rows`): position p
# lives in row p mod R, so row j holds the largest position <= the newest
# one that is congruent to j. Every mask below is computed from that
# position, never from the row index: rows a young sequence has not
# written yet hold a negative position and a previous tenant's rows hold
# one the new sequence has since overwritten, so neither is ever read.

def _ring_positions(newest, rows: int):
    """(..., rows) position each ring row holds once `newest` (...,) is
    the last position written; negative where nothing has been written."""
    j = jnp.arange(rows)
    newest = jnp.asarray(newest)[..., None]
    return newest - (newest - j) % rows


def _ring_write(cache, new, pos, valid=None):
    """`cache` (B, R, e) with `new` (B, C, e) — the rows of positions
    pos .. pos+C-1 (a scalar), of which the leading `valid` (all, if None)
    are real — written at their ring places: the last min(valid, R) of
    them land, as one gather, whatever C is to R."""
    r, c = cache.shape[1], new.shape[1]
    last = pos + (c if valid is None else valid) - 1
    src = _ring_positions(last, r)           # what row j holds afterwards
    idx = jnp.clip(src - pos, 0, c - 1)
    return jnp.where((src >= pos)[None, :, None], new[:, idx], cache)


@register_op
class MultiHeadAttentionOp(Op):
    """State, where the op's shapes admit the decode kernel that reads the
    rows each slot has filled (`_counts_rows`): how far the decode core's
    read is from the rows the sequences hold, threaded by the continuous
    batcher from one decode iteration to the next (`serving_counters`),
    as `LatentAttentionOp` counts — `attn_steps` (decode and verify
    steps), `rows_filled` (cache rows at or before each slot's position,
    summed over slots and steps) and `rows_read` (the rows the core that
    ran fetched: whole blocks up to the position under the kernel, slots
    x max_len under the reference chain); the two row counts are wide
    (ops/latent_attention.py `wide_count`)."""

    op_type = OpType.MULTIHEAD_ATTENTION
    serving_counters = ("attn_steps", "rows_filled", "rows_read")

    def _dims(self):
        q, k, v = self.inputs[:3]
        p = self.params
        embed = p["embed_dim"]
        heads = p["num_heads"]
        kdim = p.get("kdim") or embed // heads
        vdim = p.get("vdim") or embed // heads
        return q, k, v, embed, heads, kdim, vdim

    def _kv_heads(self) -> int:
        """KV heads (`kv_heads`; the query heads where none are given):
        query head j reads KV head j // (heads / kv_heads)."""
        return self.params.get("kv_heads") or self.params["num_heads"]

    def _window(self):
        """`window` W (None: every earlier position): key s is visible to
        query t iff 0 <= t - s < W, the key itself counted."""
        w = self.params.get("window")
        return int(w) if w else None

    def output_shapes(self):
        q, k, v, embed, heads, kdim, vdim = self._dims()
        kv_heads = self._kv_heads()
        rope = self.params.get("rope_parameters")
        window, gated = self._window(), self.params.get("head_gate")
        if window and not self.params.get("causal"):
            raise ValueError("multihead_attention: a window looks back"
                             " from the query only; it needs causal=True")
        if heads % kv_heads:
            raise ValueError(
                f"multihead_attention: num_heads={heads} is no multiple of"
                f" kv_heads={kv_heads}")
        if rope is not None and kdim % 2:
            raise ValueError("multihead_attention: rotary positions need an"
                             f" even kdim, got {kdim}")
        if kv_heads != heads or rope is not None or window or gated:
            # the flash, ring and ulysses kernels take one K/V head a query
            # head, unrotated projections, a plain causal mask and no gate
            # (ROADMAP Reach M1)
            for opt in ("use_flash", "sequence_parallel"):
                if self.params.get(opt):
                    raise ValueError(
                        f"multihead_attention: {opt}=True takes neither"
                        " grouped KV heads, rotary positions, a window nor"
                        " a head gate; the einsum core serves them")
        if self.params.get("sequence_parallel") and self.params.get("dropout", 0.0) > 0:
            # the ring kernel has no attention-probability dropout; fail loudly
            # rather than silently train with different regularization
            raise ValueError(
                "sequence_parallel attention does not support attention-prob "
                "dropout; set dropout=0 or sequence_parallel=False"
            )
        if self.params.get("use_flash"):
            kdim = self.params.get("kdim")
            vdim = self.params.get("vdim")
            if self.params.get("dropout", 0.0) > 0:
                raise ValueError(
                    "use_flash=True attention has no attention-prob dropout; "
                    "set dropout=0 or drop the explicit use_flash"
                )
            if kdim != vdim:
                raise ValueError(
                    "use_flash=True requires kdim == vdim (one head_dim in "
                    "the kernel); got kdim={} vdim={}".format(kdim, vdim)
                )
        return [q.dims[:-1] + (embed,)], [q.dtype]

    def weight_specs(self) -> List[WeightSpec]:
        q, k, v, embed, heads, kdim, vdim = self._dims()
        user_init = self.params.get("kernel_initializer")
        kvh = self._kv_heads()

        def init(fan_in, fan_out):
            return user_init or DefaultInitializer(fan_in=fan_in, fan_out=fan_out)

        dt = q.dtype
        specs = [
            WeightSpec("wq", (q.dims[-1], heads, kdim), dt, init(q.dims[-1], heads * kdim)),
            WeightSpec("wk", (k.dims[-1], kvh, kdim), dt, init(k.dims[-1], kvh * kdim)),
            WeightSpec("wv", (v.dims[-1], kvh, vdim), dt, init(v.dims[-1], kvh * vdim)),
            WeightSpec("wo", (heads, vdim, embed), dt, init(heads * vdim, embed)),
        ]
        if self.params.get("head_gate"):
            specs.append(WeightSpec("wg", (q.dims[-1], heads), dt,
                                    init(q.dims[-1], heads)))
        if self.params.get("bias", True):
            specs += [
                WeightSpec("bq", (heads, kdim), dt, ZeroInitializer()),
                WeightSpec("bk", (kvh, kdim), dt, ZeroInitializer()),
                WeightSpec("bv", (kvh, vdim), dt, ZeroInitializer()),
                WeightSpec("bo", (embed,), dt, ZeroInitializer()),
            ]
        return specs

    def _counts_rows(self) -> bool:
        """Whether a serving cache of this op can meet the registry's rule
        for the `attention_decode` kernel (kernels/registry.py
        `filled_rows_decode`; the cache's type and length are the
        pool's): a causal op without a window whose K and V heads are
        whole 128-lane tiles of one width."""
        _, _, _, _, _, kdim, vdim = self._dims()
        from ..kernels.registry import DECODE_HEAD_LANES

        return bool(self.params.get("causal") and self._window() is None
                    and kdim == vdim and kdim % DECODE_HEAD_LANES == 0)

    def state_specs(self):
        if not self._counts_rows():
            return []
        z = ZeroInitializer()
        return [WeightSpec("attn_steps", (), DataType.DT_INT32, z),
                WeightSpec("rows_filled", (2,), DataType.DT_INT32, z),
                WeightSpec("rows_read", (2,), DataType.DT_INT32, z)]

    def kv_cache_arrays(self):
        """A token's keys (after their rotation, where the op rotates) and
        values, the KV heads packed into one row each."""
        _, _, _, _, _, kdim, vdim = self._dims()
        kvh = self._kv_heads()
        return {"k_cache": kvh * kdim, "v_cache": kvh * vdim}

    def kv_ring_rows(self):
        """A window op keeps a ring of its window's rows: a query reads
        the last `window` positions and nothing older is ever needed
        (write-then-attend: the row a new token overwrites is the one
        that just left the window)."""
        return self._window()

    def lower(self, ctx, inputs, weights):
        q_in, k_in, v_in = inputs[:3]
        p = self.params
        _, _, _, embed, heads, kdim, vdim = self._dims()
        # the heads this trace holds: all of them inside the jitted step,
        # heads/tp when the search's op-cost measurement hands the op its
        # tensor-parallel weight shard (search/simulator.py OpCostCache)
        heads = weights["wq"].shape[1]
        kv_heads = weights["wk"].shape[1]
        rope = p.get("rope_parameters")
        window = self._window()
        cdt = matmul_dtype(ctx.config, q_in.dtype)

        # iteration seq_length truncation (reference: FFIterationConfig
        # threading, config.h:162-167): compute on the first L positions
        # only — a static slice per distinct length, zero-padded back below.
        # Skipped under sequence parallelism: the ring kernel's shard_map
        # needs the full length to divide the 'seq' mesh axis.
        L = getattr(ctx, "iter_seq_length", None)
        seq_parallel_active = (
            p.get("sequence_parallel", False)
            and ctx.mesh is not None
            and "seq" in getattr(ctx.mesh, "axis_names", ())
        )
        if seq_parallel_active:
            L = None
        full_q_len = q_in.shape[1]
        if L is not None and L < full_q_len:
            import jax.lax as lax

            q_in = lax.slice_in_dim(q_in, 0, L, axis=1)
            k_in = lax.slice_in_dim(k_in, 0, min(L, k_in.shape[1]), axis=1)
            v_in = lax.slice_in_dim(v_in, 0, min(L, v_in.shape[1]), axis=1)

        scale = 1.0 / np.sqrt(kdim)
        causal = p.get("causal", False)
        rate = p.get("dropout", 0.0)
        dropout_active = rate > 0.0 and ctx.mode == CompMode.COMP_MODE_TRAINING

        # Path selection happens BEFORE the projections. The pure-flash path
        # uses the PACKED kernel (kernels/flash_attention.py
        # flash_attention_packed): projections stay (b, l, heads*head_dim) —
        # exactly the shape the projection matmuls emit — and heads are
        # iterated inside the kernel body. A custom call can't absorb a
        # layout change, so the [b,h,l,d] kernels cost real transposes
        # between projection and kernel (~5 ms/step, 13%, at the BERT bench
        # config in the r4 xprof trace); the packed path has none. Every
        # other consumer (ring / ulysses shard_map, the decode step's
        # queries, einsum core) keeps the logical [b, l, h, d]. The KV
        # cache itself is stored packed, (rows, max_len, heads*head_dim).
        flash_selected = (
            kv_heads == heads and rope is None and window is None
            and "wg" not in weights
            and self._use_flash(ctx) and not dropout_active and kdim == vdim
            and not seq_parallel_active
        )
        kc = (ctx.state.get((self.name, "k_cache"))
              if hasattr(ctx, "state") else None)
        decode_active = (kc is not None
                         and getattr(ctx, "decode_pos", None) is not None)
        fill_active = (kc is not None
                       and getattr(ctx, "fill_kv_cache", False))
        # packed is incompatible with tensor-parallel head sharding: the
        # (e, h, d) -> (e, h*d) weight reshape merges the 'model'-sharded
        # heads axis into lanes, which would force GSPMD to all-gather the
        # projections — TP meshes stay on the blhd kernels. KV-cache
        # prefill works packed (the cache stores exactly the (b, l, h*d)
        # projection); the decode step projects per head and packs the
        # new rows itself.
        tp = 1
        if ctx.mesh is not None:
            tp = dict(getattr(ctx.mesh, "shape", {})).get("model", 1)
        use_packed = flash_selected and not decode_active and tp == 1

        if use_packed:
            e_q, e_k, e_v = (t.shape[-1] for t in (q_in, k_in, v_in))
            q = q_in.astype(cdt) @ weights["wq"].reshape(
                e_q, heads * kdim).astype(cdt)
            k = k_in.astype(cdt) @ weights["wk"].reshape(
                e_k, heads * kdim).astype(cdt)
            v = v_in.astype(cdt) @ weights["wv"].reshape(
                e_v, heads * vdim).astype(cdt)
            if "bq" in weights:
                q = q + weights["bq"].reshape(-1).astype(cdt)
                k = k + weights["bk"].reshape(-1).astype(cdt)
                v = v + weights["bv"].reshape(-1).astype(cdt)
        else:
            # note: a fused q/k/v projection (one wide matmul + split) wins
            # on an isolated micro-benchmark (~17%) but measured ~6% SLOWER
            # end-to-end on v5e — the split's forced materialization breaks
            # XLA's projection+attention fusion — so the three einsums stay
            # separate
            q = jnp.einsum("ble,ehd->blhd", q_in.astype(cdt),
                           weights["wq"].astype(cdt))
            k = jnp.einsum("ble,ehd->blhd", k_in.astype(cdt),
                           weights["wk"].astype(cdt))
            v = jnp.einsum("ble,ehd->blhd", v_in.astype(cdt),
                           weights["wv"].astype(cdt))
            if "bq" in weights:
                q = q + weights["bq"].astype(cdt)
                k = k + weights["bk"].astype(cdt)
                v = v + weights["bv"].astype(cdt)
        if p.get("key_multiplier", 1.0) != 1.0:
            k = k * jnp.asarray(p["key_multiplier"], cdt)
        if rope is not None:
            # rotated BEFORE the cache write: a cached key carries its own
            # position, a query the position it is asked at
            q, k = self._rotate(ctx, q, k, decode_active)

        # KV-cache paths for autoregressive serving (serving/generate.py;
        # reference role: the incremental-decoding half of the Triton
        # prototype). fill_kv_cache: a full (prefill) pass also writes its
        # K/V into the session cache. decode_pos: q is one new token; attend
        # against the cache up to the traced position.
        # the per-head gate is of the op's INPUT at the query's position,
        # so every entry below applies the same one to its context
        gate = self._head_gate(q_in, weights, cdt)
        if decode_active:
            return [self._decode_step(ctx, q, k, v, weights, scale, gate)]
        if fill_active:
            # the cache stores the packed (b, l, h*d) projection as it is;
            # a ring keeps the tail of the real tokens at their ring places
            vc = ctx.state[(self.name, "v_cache")]
            if window is None:
                store = lambda cache, rows: jax.lax.dynamic_update_slice(
                    cache, rows, (0, 0, 0))
            else:
                store = lambda cache, rows: _ring_write(
                    cache, rows, 0, getattr(ctx, "valid_len", None))
            ctx.state_updates[(self.name, "k_cache")] = store(
                kc, _packed(k).astype(kc.dtype))
            ctx.state_updates[(self.name, "v_cache")] = store(
                vc, _packed(v).astype(vc.dtype))

        if seq_parallel_active:
            # sequence/context parallelism over the 'seq' mesh axis — two
            # designs (SURVEY §5): "ring" (default) rotates K/V blocks on
            # ICI neighbor links with an online softmax
            # (kernels/ring_attention.py); "ulysses" all_to_alls to
            # head-sharding, runs exact local attention on full sequences,
            # and all_to_alls back (kernels/ulysses_attention.py — needs
            # num_heads divisible by the axis size)
            mode = p.get("sequence_parallel_mode", "ring")
            if mode in ("ulysses", "all_to_all"):
                from ..kernels.ulysses_attention import ulysses_attention_sharded

                # the local core is an ordinary dense attention, so the
                # same measured auto-policy picks flash vs einsum
                local_flash = (self._use_flash(ctx) and not dropout_active
                               and kdim == vdim)
                ctxv = ulysses_attention_sharded(
                    q, k, v, ctx.mesh, axis_name="seq", causal=causal,
                    scale=scale, use_flash=local_flash,
                    interpret=local_flash and pallas_interpret(),
                )
            elif mode == "ring":
                from ..kernels.ring_attention import ring_attention_sharded

                ctxv = ring_attention_sharded(
                    q, k, v, ctx.mesh, axis_name="seq", causal=causal,
                    scale=scale,
                )
            else:
                raise ValueError(
                    f"unknown sequence_parallel_mode {mode!r}: "
                    "expected 'ring' or 'ulysses'")
        elif use_packed:
            # hot path: Pallas flash attention in the packed (b, l, e)
            # layout — VMEM-tiled online softmax, no L x L score matrix in
            # HBM, no layout transposes (kernels/flash_attention.py)
            from ..kernels.flash_attention import flash_attention_packed

            ctxv = self._on_mesh(ctx, functools.partial(
                flash_attention_packed, num_heads=heads, scale=scale,
                causal=causal,
                interpret=pallas_interpret(),
            ), heads_dim=None)(q, k, v)
        elif flash_selected:
            # flash on a TP head-sharded mesh: head-separated [b,l,h,d]
            # projections (shardable on the heads axis) with the
            # transpose-based kernel wrapper
            from ..kernels.flash_attention import flash_attention

            ctxv = self._on_mesh(ctx, functools.partial(
                flash_attention, scale=scale, causal=causal,
                interpret=pallas_interpret(),
            ), heads_dim=2)(q, k, v)
        else:
            drop_key = ctx.next_rng() if dropout_active else None

            def attn_core(q, k, v, drop_key):
                logits = _grouped_scores(q, k) * scale
                if causal:
                    lq, lk = logits.shape[-2], logits.shape[-1]
                    mask = jnp.tril(jnp.ones((lq, lk), dtype=bool), lk - lq)
                    if window is not None:   # a band: 0 <= t - s < window
                        mask &= ~jnp.tril(jnp.ones((lq, lk), dtype=bool),
                                          lk - lq - window)
                    logits = jnp.where(mask, logits, -1e30)
                probs = jax.nn.softmax(logits, axis=-1)
                if drop_key is not None:
                    keep = jax.random.bernoulli(drop_key, 1.0 - rate,
                                                probs.shape)
                    probs = jnp.where(keep, probs / (1.0 - rate), 0.0)
                # scores/softmax stay f32 (stability); the context matmul
                # emits the compute dtype — the MXU accumulates f32
                # internally either way, and a bf16 output halves the HBM
                # write
                return _grouped_context(probs.astype(cdt), v)

            if ctx.mode == CompMode.COMP_MODE_TRAINING:
                # rematerialize in backward: recomputing logits+softmax
                # (~1/3 extra attention-core FLOPs) beats saving the f32
                # L x L probs to HBM — the same trade the flash kernel
                # makes structurally
                attn_core = jax.checkpoint(
                    attn_core,
                    policy=jax.checkpoint_policies.nothing_saveable)
            ctxv = attn_core(q, k, v, drop_key)

        if gate is not None:
            ctxv = self._gated(ctxv, gate)
        odt = emit_dtype(ctx.config, self.outputs[0].dtype)
        if use_packed:
            out = (ctxv.astype(cdt) @ weights["wo"].reshape(
                heads * vdim, embed).astype(cdt)).astype(odt)
        else:
            out = jnp.einsum(
                "bqhd,hde->bqe",
                ctxv.astype(cdt),
                weights["wo"].astype(cdt),
            ).astype(odt)
        if "bo" in weights:
            out = out + weights["bo"].astype(odt)
        if out.shape[1] < full_q_len:  # truncated: pad back to declared shape
            out = jnp.pad(out, [(0, 0), (0, full_q_len - out.shape[1]), (0, 0)])
        return [out]

    def _on_mesh(self, ctx, kernel, heads_dim):
        """`kernel(q, k, v)` as it must be called on a mesh. A Mosaic
        kernel has no GSPMD partitioning rule ("cannot be automatically
        partitioned"), so inside the jitted step it runs under shard_map:
        every device on its own batch shard and — on the [b, l, h, d]
        layout, heads_dim=2 — its own heads, with the axes the op's
        tensors already carry. Where GSPMD partitions nothing
        (LoweringContext.gspmd_partitioned) the kernel is called as it
        is."""
        if not ctx.gspmd_partitioned():
            return kernel
        from jax.sharding import PartitionSpec as P

        out_shape = self.outputs[0].parallel_shape
        spec = [out_shape.partition_spec()[0] if out_shape else None,
                None, None]
        if heads_dim is not None:
            wq = next(w for w in self.weights
                      if w._weight_spec.name == "wq").parallel_shape
            spec.insert(heads_dim, wq.partition_spec()[1] if wq else None)
        spec = P(*spec)
        return jax.shard_map(kernel, mesh=ctx.mesh, in_specs=(spec,) * 3,
                             out_specs=spec, check_vma=False)

    def _head_gate(self, x, weights, cdt):
        """(B, L, h) float32 sigmoid of x W_g, one logit a head (`head_gate`;
        None where the op has none)."""
        if "wg" not in weights:
            return None
        return jax.nn.sigmoid(jnp.einsum(
            "ble,eh->blh", x.astype(cdt), weights["wg"].astype(cdt),
            preferred_element_type=jnp.float32))

    @staticmethod
    def _gated(ctxv, gate):
        """Each head's context (B, L, h, d) times its gate, in float32."""
        return (ctxv.astype(jnp.float32) * gate[..., None]).astype(ctxv.dtype)

    def _decode_step(self, ctx, q, k, v, weights, scale, gate=None):
        """One incremental-decoding step: q/k/v are projections of the new
        token(s) (B, C, h, d); the K/V caches are STORED PACKED,
        (B, M, h*d) — lane-dense on the chip at every width whose h*d is a
        multiple of 128, where a (…, h, 64) cache is relaid out (and
        padded to 128 lanes) on every read and write. The new rows are
        written at decode_pos as (B, C, h*d) and the caches are attended
        with a causal <= position mask, in place.

        decode_pos may be a traced SCALAR (every row at the same position —
        the lockstep GenerativeSession path) or a traced (B,) VECTOR of
        per-row positions (continuous batching, serving/sched/continuous.py:
        each slot decodes its own sequence, so slot i writes its K/V at
        pos[i] and masks to its own length). The vector form is the
        continuous batcher's per-iteration hot loop, and a kernel
        family (`attention_decode`): where kernels/registry.py
        `filled_rows_decode` admits what the call holds — a TPU, heads of
        whole 128-lane tiles, a cache of 2-byte values, a length whole
        blocks divide — the QK^T -> masked softmax -> V chain runs as ONE
        fused kernel that reads the rows each slot has FILLED
        (kernels/pallas/decode.py, grouped KV heads included) instead of
        contracting over every allocated row and materializing the
        (B, h, 1, M) logits/probs in HBM; the einsum chain below is its
        reference/parity oracle, and what 64-lane heads, a float32 cache
        and a step jitted over a mesh keep. An op whose shapes can meet
        that rule counts its decode steps, the rows filled and the rows
        the core that ran read (`_counts_rows`, `serving_counters`).

        The scalar form doubles as the CHUNK-OFFSET PREFILL entry: with
        C > 1 query tokens at offset `pos`, the chunk's K/V rows are
        written at cache positions [pos, pos+C) and query j attends rows
        <= pos+j — causal over the already-filled prefix plus the chunk
        itself. That is what lets the continuous batcher split a long
        prompt into fixed-size chunks interleaved with decode iterations
        (serving/sched/continuous.py) instead of stalling every in-flight
        decode behind one monolithic prefill.

        The vector form also takes C > 1 queries per slot — SPECULATIVE
        decoding's verify step: slot i's C candidate tokens are written
        at rows [pos[i], pos[i]+C) of ITS cache and query j attends rows
        <= pos[i]+j. Rejected candidates are rolled back by the batcher
        moving its write-back pointer, never by touching the cache —
        the stale rows are masked out and rewritten before any later
        query can attend them.

        Every C > 1 entry (both forms) is the `attention_decode_mq`
        kernel family, which waits behind `KERNELS.override`: forced,
        the chunk runs as ONE fused multi-query kernel over the whole
        cache (kernels/pallas/decode.py; one K/V head a query head)
        instead of materializing the (B, h, C, M) logits/probs in HBM;
        the einsum chain below is the reference/parity oracle for both
        families.

        The reference chain's CONTRACTION follows what it can see in its
        operands (`_contract_heads_together`). One query a row (the decode
        iteration, the lockstep session) and speculative verify contract
        ALL heads at once on the cache as stored, the queries spread
        block-diagonally over the h*d lanes: no (…, h, d) layout of a
        whole cache is ever asked for. A prefill chunk (hundreds of
        queries on a batch-1 cache) and heads GSPMD shards over a mesh
        axis contract head by head on a reshaped view.

        A WINDOW op's caches are rings (`kv_ring_rows`, the helpers above
        the class): the decode iteration writes row pos mod R and masks by
        the position each row holds (`_ring_decode`); a chunk at an offset
        attends the ring as it stands AND its own rows under the band
        mask, then leaves the ring holding the chunk's tail
        (`_ring_chunk`)."""
        pos = ctx.decode_pos
        kc = ctx.state[(self.name, "k_cache")]
        vc = ctx.state[(self.name, "v_cache")]
        vector = getattr(pos, "ndim", 0) == 1
        c = q.shape[1]
        k_rows = _packed(k).astype(kc.dtype)  # (B, C, h*d)
        v_rows = _packed(v).astype(vc.dtype)
        if self._window() is not None:
            ring = self._ring_decode if vector else self._ring_chunk
            ctxv = ring(ctx, q, k_rows, v_rows, kc, vc, scale)
            return self._decode_project(ctxv, q.dtype, weights, gate)
        if vector:
            rows = jnp.arange(kc.shape[0])
            if c == 1:
                kc = kc.at[rows, pos].set(k_rows[:, 0])
                vc = vc.at[rows, pos].set(v_rows[:, 0])
            else:
                # slot i's C candidate rows land at [pos[i], pos[i]+C);
                # rows past max_len (speculation at the cache edge) are
                # DROPPED by the scatter — those queries' outputs are
                # never accepted, so the dropped writes are unreachable
                cols = pos[:, None] + jnp.arange(c)[None, :]  # (B, C)
                kc = kc.at[rows[:, None], cols].set(k_rows)
                vc = vc.at[rows[:, None], cols].set(v_rows)
        else:
            kc = jax.lax.dynamic_update_slice(kc, k_rows, (0, pos, 0))
            vc = jax.lax.dynamic_update_slice(vc, v_rows, (0, pos, 0))
        ctx.state_updates[(self.name, "k_cache")] = kc
        ctx.state_updates[(self.name, "v_cache")] = vc

        from ..kernels.pallas import decode
        from ..kernels.pallas.latent_decode import rows_read
        from ..kernels.registry import KERNELS

        max_len = kc.shape[1]
        one_query = vector and c == 1
        # GSPMD cannot partition a Mosaic kernel (see _on_mesh): a decode
        # step jitted over a mesh keeps the reference chain below. One
        # query a slot asks the registry with what the call holds — the
        # head's width, the cache's value size and length; the multi-query
        # kernel takes one K/V head a query head
        if ctx.gspmd_partitioned():
            fused = False
        elif one_query:
            fused = kc.shape == vc.shape and bool(KERNELS.select(
                "attention_decode",
                decode=(q.shape[3], kc.dtype.itemsize, max_len)))
        else:
            fused = k.shape[2] == q.shape[2] and bool(
                KERNELS.select("attention_decode_mq"))
        if vector and (self.name, "rows_read") in ctx.state:
            held = lambda var: ctx.state[(self.name, var)]
            last = jnp.minimum(pos + c - 1, max_len - 1)
            read = (rows_read(last, max_len) if fused and one_query
                    else kc.shape[0] * max_len)
            ctx.state_updates[(self.name, "attn_steps")] = (
                held("attn_steps") + 1)
            ctx.state_updates[(self.name, "rows_filled")] = _wide_add(
                held("rows_filled"), jnp.sum(last + 1))
            ctx.state_updates[(self.name, "rows_read")] = _wide_add(
                held("rows_read"), read)
        if fused:
            if one_query:
                ctxv = decode.fused_decode_attention(
                    q, kc, vc, pos, scale=scale,
                    interpret=pallas_interpret())
            else:
                posv = pos if vector else jnp.full(
                    (kc.shape[0],), pos, jnp.int32)
                ctxv = decode.fused_multiquery_decode_attention(
                    q, kc, vc, posv, scale=scale,
                    interpret=pallas_interpret())
            return self._decode_project(ctxv, q.dtype, weights, gate)

        if vector:
            # (B, C, M): query j of slot i attends rows <= pos[i]+j
            # (C == 1 degenerates to the plain <= pos decode mask)
            qpos = pos[:, None] + jnp.arange(c)[None, :]
            mask = (jnp.arange(kc.shape[1])[None, None, :]
                    <= qpos[:, :, None])[:, None, :, :]  # (B, 1, C, M)
        else:
            ctxv = self._prefix_core(ctx, q, kc, vc, pos, scale)
            return self._decode_project(ctxv, q.dtype, weights, gate)
        ctxv = self._masked_core(ctx, q, kc, vc, mask, scale)
        return self._decode_project(ctxv, q.dtype, weights, gate)

    def _prefix_core(self, ctx, q, kc, vc, pos, scale):
        """C queries at the scalar offset `pos` (a prefill chunk on a
        batch-1 holder, the lockstep session's step): query j attends rows
        <= pos + j, so no row past pos + C is visible, and the two
        contractions run over the shortest static prefix of the caches
        that holds them (`_prefixes`; a short cache whole, as ever)."""
        c = q.shape[1]

        def over(rows):
            def core(q, kc, vc, pos):
                qpos = pos + jnp.arange(c)  # (C,) absolute positions
                mask = (jnp.arange(rows)[None, :]
                        <= qpos[:, None])[None, None, :, :]  # (1, 1, C, M)
                return self._masked_core(ctx, q, kc[:, :rows], vc[:, :rows],
                                         mask, scale)
            return core

        prefixes = _prefixes(kc.shape[1])
        if len(prefixes) == 1:
            return over(prefixes[0])(q, kc, vc, pos)
        which = sum(jnp.asarray(pos + c > p, jnp.int32) for p in prefixes[:-1])
        return jax.lax.switch(which, [over(p) for p in prefixes],
                              q, kc, vc, pos)

    def _masked_core(self, ctx, q, keys, values, mask, scale):
        """softmax(q keys^T * scale under `mask`) values on the caches as
        stored: the reference chain's two contractions."""
        kv_heads = keys.shape[-1] // q.shape[-1]
        # contracted as one row, a head-sharded last dimension would have
        # GSPMD gather the cache: tensor-parallel heads contract head by head
        together = (not self._heads_sharded(ctx)
                    and _contract_heads_together(q.shape[1], q.shape[2]))
        logits = _scores(q, keys.astype(q.dtype), together) * scale
        logits = jnp.where(mask, logits, -1e30)  # (B, h, C, M)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        return _context(probs.astype(q.dtype), values.astype(q.dtype),
                        together, kv_heads)

    def _ring_decode(self, ctx, q, k_rows, v_rows, kc, vc, scale):
        """The decode iteration on a ring: slot i writes its one row at
        pos[i] mod R, then attends the rows whose position lies inside its
        window."""
        pos = ctx.decode_pos
        if q.shape[1] != 1:
            raise NotImplementedError(
                f"multihead_attention {self.name}: several queries a slot"
                " (speculative verify) on a window's ring: a rejected"
                " draft would have overwritten rows the window still"
                " needs")
        r = kc.shape[1]
        rows = jnp.arange(kc.shape[0])
        kc = kc.at[rows, pos % r].set(k_rows[:, 0])
        vc = vc.at[rows, pos % r].set(v_rows[:, 0])
        ctx.state_updates[(self.name, "k_cache")] = kc
        ctx.state_updates[(self.name, "v_cache")] = vc
        held = _ring_positions(pos, r)                        # (B, R)
        mask = (held >= 0) & (pos[:, None] - held < self._window())
        return self._masked_core(ctx, q, kc, vc, mask[:, None, None, :],
                                 scale)

    def _ring_chunk(self, ctx, q, k_rows, v_rows, kc, vc, scale):
        """C queries at offset `pos` (a scalar: a prefill chunk on a
        batch-1 holder, the lockstep session's step): they attend the ring
        as the earlier positions left it and the chunk's own rows, both
        under the band mask; then the chunk's real rows take their ring
        places."""
        pos, window = ctx.decode_pos, self._window()
        c, r = q.shape[1], kc.shape[1]
        qpos = pos + jnp.arange(c)
        held = _ring_positions(pos - 1, r)                    # (R,)
        behind = qpos[:, None] - jnp.concatenate([held, qpos])[None, :]
        mask = (behind >= 0) & (behind < window) & jnp.concatenate(
            [held >= 0, jnp.ones((c,), bool)])[None, :]       # (C, R + C)
        ctxv = self._masked_core(
            ctx, q, jnp.concatenate([kc, k_rows], axis=1),
            jnp.concatenate([vc, v_rows], axis=1), mask[None, None], scale)
        valid = getattr(ctx, "valid_len", None)
        ctx.state_updates[(self.name, "k_cache")] = _ring_write(
            kc, k_rows, pos, valid)
        ctx.state_updates[(self.name, "v_cache")] = _ring_write(
            vc, v_rows, pos, valid)
        return ctxv

    def _rotate(self, ctx, q, k, decode_active):
        """q (B, L, h, d) and k (B, L, n, d) rotated to their positions
        (ops/rope.py, half-split pairs; the leading `rotary_dim` values of
        a head where the group has a `partial_rotary_factor`): 0..L-1 for
        a whole sequence, `decode_pos` + 0..L-1 in the decode step's three
        forms."""
        from . import rope as rope_mod

        steps = jnp.arange(q.shape[1])
        pos = ctx.decode_pos if decode_active else None
        if pos is None:
            qpos = steps[None, :]
        elif getattr(pos, "ndim", 0) == 1:
            qpos = pos[:, None] + steps[None, :]        # (B, C)
        else:
            qpos = (pos + steps)[None, :]
        rope = self.params["rope_parameters"]
        cos, sin = rope_mod.cos_sin(
            qpos, rope_mod.rotary_dim(q.shape[-1], rope), rope)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
        return (rope_mod.rotate_half_split(q, cos, sin),
                rope_mod.rotate_half_split(k, cos, sin))

    def _heads_sharded(self, ctx) -> bool:
        """True where GSPMD partitions this op's heads over a mesh axis
        (the spec `_on_mesh` hands its kernels); a mesh that partitions
        nothing, or the batch alone, leaves every head on every device."""
        if not ctx.gspmd_partitioned():
            return False
        wq = next(w for w in self.weights
                  if w._weight_spec.name == "wq").parallel_shape
        return wq is not None and wq.partition_spec()[1] is not None

    def _decode_project(self, ctxv, cdt, weights, gate=None):
        """Output projection shared by the fused and reference decode
        paths, after the per-head gate where the op has one."""
        if gate is not None:
            ctxv = self._gated(ctxv, gate)
        out = jnp.einsum("bqhd,hde->bqe", ctxv.astype(cdt),
                         weights["wo"].astype(cdt))
        out = out.astype(self.outputs[0].dtype.jnp_dtype)
        if "bo" in weights:
            out = out + weights["bo"]
        return out

    def _use_flash(self, ctx) -> bool:
        """Flash or the einsum core: `KERNELS.select` (kernels/registry.py)
        given this op's explicit `use_flash` and its per-chip score shape
        (the batch dim shards over the mesh's data axis)."""
        from ..kernels.registry import KERNELS

        q, k = self.inputs[0], self.inputs[1]
        dp = 1
        if ctx is not None and ctx.mesh is not None:
            dp = dict(getattr(ctx.mesh, "shape", {})).get("data", 1)
        return bool(KERNELS.select(
            "attention", param=self.params.get("use_flash"),
            scores=(q.dims[0], self.params["num_heads"], q.dims[1],
                    k.dims[1], dp)))

    def flops(self) -> float:
        q, k, v, embed, heads, kdim, vdim = self._dims()
        b, lq = q.dims[0], q.dims[1]
        lk = k.dims[1]
        kvh = self._kv_heads()
        proj = 2.0 * b * (
            heads * lq * q.dims[-1] * kdim
            + kvh * lk * k.dims[-1] * kdim
            + kvh * lk * v.dims[-1] * vdim
            + heads * lq * vdim * embed
        )
        core = 2.0 * b * heads * lq * lk * (kdim + vdim)
        return proj + core
