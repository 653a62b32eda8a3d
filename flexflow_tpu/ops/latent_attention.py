"""Multi-head LATENT attention (MLA; DeepSeek-V2, arXiv:2405.04434 section
2.1): queries and keys/values go through low-rank projections, the rotary
part of the key is ONE vector shared by all heads, and what a token leaves
in the cache is its normalised kv latent beside that rotated key —
`kv_lora_rank + qk_rope_head_dim` values instead of a K and a V of
`heads * head_dim` each.

    c_q = RMSNorm(x W_qa);  q = c_q W_qb -> heads x [q_nope | q_rope]
    [c_kv | k_r] = x W_kva;  c_kv <- RMSNorm(c_kv);  k_r, q_rope <- RoPE
    [k_nope_h | v_h] = c_kv W_kvb[h]
    score_h(t, s) = (q_nope_h . k_nope_h(s) + q_rope_h . k_r(s)) scale a(t)

Two paths compute that, chosen from what the step sees:

  expanded  a prefill chunk, a one-shot prefill, training: k_nope and v of
            every row are materialised through W_kvb (hundreds of queries
            share the expansion) and the core is plain attention. At a
            scalar offset (a chunk on a batch-1 holder, the lockstep
            session's step) "every row" is the shortest static prefix of
            the holder that holds the queries' last row (`prefill_ladder`).
  absorbed  decode and speculative verify (a (B,) vector of positions): the
            query is carried into the latent space, q~_h = q_nope_h
            W_kvb[K,h]^T, scores and context are taken on the latent rows
            AS STORED — all heads share them, so the heads of one slot are
            the rows of one matrix product — and W_kvb[V,h] is applied to
            the context. No per-head key or value of a cached row exists.
            With one query a slot, off a GSPMD mesh and where the kernel
            registry says so (family `latent_decode`: a TPU), the core is
            kernels/pallas/latent_decode.py: one pass over the rows each
            slot has FILLED; everywhere else two contractions over all the
            rows the pool allocated, under a mask.

The cache is two arrays a layer: `c_kv` (rows, max_len, kv_lora_rank), the
latent after its norm, and `k_rope` (rows, max_len, 128), the shared key
after its rotation in lanes [0, qk_rope_head_dim) and zeros above. Both are
multiples of the chip's 128 lanes ON PURPOSE: the chip's compiler hands a
(rows, max_len, 320) or (rows, max_len, 64) array to the step rows-minor
and copies it whole before the scatter and again for the donated output
(`decode_all` compiled for a described v5e: 12 cache-sized copies, 1.3 GiB
of temporaries; PERF.md section 6, PR 27); a lane-dense array is scattered
into and contracted on where it lies. The price is 64 stored lanes the
algorithm does not need: 768 B a token a layer are stored, 640 are read.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..core.op import Op, WeightSpec, register_op
from ..ffconst import DataType, OpType
from ..runtime.initializers import (ConstantInitializer, DefaultInitializer,
                                    ZeroInitializer)
from ..runtime.platform import pallas_interpret
from . import rope as rope_mod
from .common import emit_dtype, matmul_dtype


def _rms(x, gamma, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                           + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


LANES = 128


def _lane_pad(x, width):
    """x (..., w) with zeros appended up to `width` lanes."""
    extra = width - x.shape[-1]
    return x if extra == 0 else jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, extra)])


def _mm(spec, a, b, cdt):
    return jnp.einsum(spec, a.astype(cdt), b.astype(cdt),
                      preferred_element_type=jnp.float32).astype(cdt)


# A row counter is (2,) int32, [count >> 20, count & (2**20 - 1)]: 128 slots
# of ~1,000 filled rows add 10**5 a decode iteration, and a plain int32 would
# wrap within ten minutes of serving.
_WIDE_BITS = 20


def _wide_add(counter, by):
    low = counter[1] + by
    return jnp.stack([counter[0] + (low >> _WIDE_BITS),
                      low & ((1 << _WIDE_BITS) - 1)])


def wide_count(counter) -> int:
    """The host's reading of a row counter of `serving_counters`."""
    return (int(counter[0]) << _WIDE_BITS) + int(counter[1])


# the least base of `prefill_ladder`: a chunk of C queries starts its ladder
# at C, a step of few queries (the lockstep session's one) here and not at
# 1, 2, 4, ... rows. `mistral_small4_ep4`'s chunks of 512 see 743 rows of
# their 4,607-row batch-1 holder in the mean (PERF.md section 6)
PREFILL_PREFIX_FLOOR = 512


def prefill_ladder(holder_rows: int, c: int) -> List[int]:
    """The static prefixes of a holder of `holder_rows` rows that C queries
    at a scalar offset are attended over: base max(C, PREFILL_PREFIX_FLOOR),
    its doubles under the holder, and the whole (ops/attention.py
    `_prefixes`); a holder of up to two bases is attended whole."""
    from .attention import _prefixes

    return _prefixes(holder_rows, max(c, PREFILL_PREFIX_FLOOR))


def _prefix_index(ladder: List[int], end):
    """Which entry of `ladder` is the first to hold rows [0, end): a host
    int for a host `end`, a traced one for a traced `end`."""
    return sum(end > p for p in ladder[:-1])


@register_op
class LatentAttentionOp(Op):
    """The op of the module's docstring. Its state says how far the decode
    core's read is from the rows the sequences hold, threaded by the
    continuous batcher from one decode iteration to the next
    (`serving_counters`): `attn_steps` (decode and verify steps),
    `rows_filled` (cache rows at or before each slot's position, summed
    over slots and steps: what the algorithm needs) and `rows_read` (the
    rows the core that ran fetched: whole blocks up to the position under
    the kernel, slots x max_len under the reference). The two row counts
    are wide (`wide_count`)."""

    op_type = OpType.LATENT_ATTENTION
    serving_counters = ("attn_steps", "rows_filled", "rows_read")

    def _dims(self):
        p = self.params
        return (self.inputs[0].dims[-1], p["num_heads"], p["q_lora_rank"],
                p["kv_lora_rank"], p["qk_nope_head_dim"],
                p["qk_rope_head_dim"], p["v_head_dim"])

    def output_shapes(self):
        x = self.inputs[0]
        if self.params["qk_rope_head_dim"] % 2:
            raise ValueError("latent_attention: qk_rope_head_dim must be even")
        return [x.dims], [x.dtype]

    def weight_specs(self) -> List[WeightSpec]:
        e, heads, qr, kvr, nope, rope, vd = self._dims()
        dt = self.inputs[0].dtype
        init = lambda i, o: (self.params.get("kernel_initializer")
                             or DefaultInitializer(fan_in=i, fan_out=o))
        one = ConstantInitializer(1.0)
        return [
            WeightSpec("wq_a", (e, qr), dt, init(e, qr)),
            WeightSpec("q_norm", (qr,), dt, one),
            WeightSpec("wq_b", (qr, heads, nope + rope), dt,
                       init(qr, heads * (nope + rope))),
            WeightSpec("wkv_a", (e, kvr + rope), dt, init(e, kvr + rope)),
            WeightSpec("kv_norm", (kvr,), dt, one),
            WeightSpec("wkv_b", (kvr, heads, nope + vd), dt,
                       init(kvr, heads * (nope + vd))),
            WeightSpec("wo", (heads, vd, e), dt, init(heads * vd, e)),
        ]

    def state_specs(self):
        z = ZeroInitializer()
        return [WeightSpec("attn_steps", (), DataType.DT_INT32, z),
                WeightSpec("rows_filled", (2,), DataType.DT_INT32, z),
                WeightSpec("rows_read", (2,), DataType.DT_INT32, z)]

    def kv_cache_arrays(self):
        """What a token leaves in the cache: its normalised kv latent, and
        the rotated shared key padded to whole 128-lane tiles."""
        _, _, _, kvr, _, rope, _ = self._dims()
        return {"c_kv": kvr, "k_rope": -(-rope // LANES) * LANES}

    def lower(self, ctx, inputs, weights):
        x = inputs[0]                                   # (B, L, E)
        p = self.params
        _, heads, _, kvr, nope, rope, vd = self._dims()
        rp = p.get("rope_parameters")
        eps = p.get("eps", 1e-6)
        cdt = matmul_dtype(ctx.config, x.dtype)
        b, length = x.shape[0], x.shape[1]

        cache = (ctx.state.get((self.name, "c_kv")),
                 ctx.state.get((self.name, "k_rope")))
        if cache[0] is None:
            cache = None
        pos = getattr(ctx, "decode_pos", None) if cache is not None else None
        vector = pos is not None and getattr(pos, "ndim", 0) == 1
        steps = jnp.arange(length)
        if pos is None:
            qpos = jnp.broadcast_to(steps[None, :], (b, length))
        elif vector:
            qpos = pos[:, None] + steps[None, :]        # (B, C)
        else:
            qpos = jnp.broadcast_to((pos + steps)[None, :], (b, length))
        cos, sin = rope_mod.cos_sin(qpos, rope, rp)     # (B, L, rope/2)

        with jax.named_scope("mla:q_proj"):
            c_q = _rms(_mm("ble,er->blr", x, weights["wq_a"], cdt),
                       weights["q_norm"], eps)
            q = _mm("blr,rhd->blhd", c_q, weights["wq_b"], cdt)
            q_nope = q[..., :nope]
            q_rope = rope_mod.rotate_interleaved(
                q[..., nope:], cos[:, :, None, :], sin[:, :, None, :])
        names = ("c_kv", "k_rope")
        rope_lanes = self.kv_cache_arrays()["k_rope"]
        with jax.named_scope("mla:kv_latent"):
            kv = _mm("ble,er->blr", x, weights["wkv_a"], cdt)
            # what a token stores: (B, L, kvr) and (B, L, rope_lanes)
            parts = (_rms(kv[..., :kvr], weights["kv_norm"], eps),
                     _lane_pad(rope_mod.rotate_interleaved(
                         kv[..., kvr:], cos, sin), rope_lanes))
        q_rope = _lane_pad(q_rope, rope_lanes)   # zeros meet zeros

        scale = rope_mod.attention_scale(nope + rope, rp)
        qscale = rope_mod.position_scale(qpos, rp)      # (B, L) or None
        qscale = scale if qscale is None else scale * qscale[:, None, :, None]

        if vector:
            rows = jnp.arange(cache[0].shape[0])
            new = []
            for c, part in zip(cache, parts):
                part = part.astype(c.dtype)
                if length == 1:
                    new.append(c.at[rows, pos].set(part[:, 0]))
                else:   # verify: rows past max_len are dropped by the scatter
                    new.append(c.at[rows[:, None], qpos].set(part))
            for n, c in zip(names, new):
                ctx.state_updates[(self.name, n)] = c
            from ..kernels.pallas import latent_decode
            from ..kernels.registry import KERNELS

            max_len = new[0].shape[1]
            # GSPMD cannot partition a Mosaic kernel: a decode step jitted
            # over a mesh keeps the reference contractions
            fused = bool(length == 1 and not ctx.gspmd_partitioned()
                         and latent_decode.block_rows(max_len) is not None
                         and KERNELS.select("latent_decode"))
            o = self._absorbed(q_nope, q_rope, new[0], new[1], qpos, qscale,
                               weights, cdt, fused)
            if (self.name, "rows_read") in ctx.state:
                held = lambda var: ctx.state[(self.name, var)]
                last = jnp.minimum(qpos[:, -1], max_len - 1)
                read = (latent_decode.rows_read(last, max_len) if fused
                        else b * max_len)
                ctx.state_updates[(self.name, "attn_steps")] = (
                    held("attn_steps") + 1)
                ctx.state_updates[(self.name, "rows_filled")] = _wide_add(
                    held("rows_filled"), jnp.sum(last + 1))
                ctx.state_updates[(self.name, "rows_read")] = _wide_add(
                    held("rows_read"), read)
        elif pos is not None:     # a chunk at offset `pos` of a batch-1 cache
            keys = [jax.lax.dynamic_update_slice(
                c, part.astype(c.dtype), (0, pos, 0))
                for c, part in zip(cache, parts)]
            for n, c in zip(names, keys):
                ctx.state_updates[(self.name, n)] = c
            o = self._prefix_expanded(q_nope, q_rope, keys, pos, qpos,
                                      qscale, weights, cdt)
        else:
            if cache is not None and getattr(ctx, "fill_kv_cache", False):
                for n, c, part in zip(names, cache, parts):
                    ctx.state_updates[(self.name, n)] = (
                        jax.lax.dynamic_update_slice(
                            c, part.astype(c.dtype), (0, 0, 0)))
            o = self._expanded(q_nope, q_rope, parts[0].astype(cdt),
                               parts[1].astype(cdt), qpos, qscale, weights,
                               cdt)
        with jax.named_scope("mla:out"):
            out = _mm("bqhv,hve->bqe", o, weights["wo"], cdt)
        return [out.astype(emit_dtype(ctx.config, self.outputs[0].dtype))]

    def _split_kvb(self, weights):
        nope = self.params["qk_nope_head_dim"]
        return weights["wkv_b"][..., :nope], weights["wkv_b"][..., nope:]

    def prefill_prefix_rows(self, holder_rows: int, pos: int, c: int) -> int:
        """Rows of a holder of `holder_rows` that C queries at the scalar
        offset `pos` are attended over: the branch `lower` takes, read on
        the host (the batcher counts it, `ff_mla_prefill_chunks_total`)."""
        ladder = prefill_ladder(holder_rows, c)
        return ladder[_prefix_index(ladder, pos + c)]

    def _prefix_expanded(self, q_nope, q_rope, keys, pos, qpos, qscale,
                         weights, cdt):
        """C queries at the scalar offset `pos` over the holder `keys`
        (c_kv, k_rope) as written: query j attends rows <= pos + j, so no
        row past pos + C - 1 is visible, and the expanded path runs over
        the shortest static prefix that holds them (`prefill_ladder`),
        chosen as ops/attention.py `_prefix_core` chooses."""
        c = q_nope.shape[1]

        def over(rows):
            def core(q_nope, q_rope, c_kv, k_rope):
                return self._expanded(
                    q_nope, q_rope, c_kv[:, :rows].astype(cdt),
                    k_rope[:, :rows].astype(cdt), qpos, qscale, weights, cdt)
            return core

        ladder = prefill_ladder(keys[0].shape[1], c)
        if len(ladder) == 1:
            return over(ladder[0])(q_nope, q_rope, *keys)
        which = jnp.asarray(_prefix_index(ladder, pos + c), jnp.int32)
        return jax.lax.switch(which, [over(r) for r in ladder], q_nope,
                              q_rope, *keys)

    def _expanded(self, q_nope, q_rope, c_kv, k_rope, qpos, qscale, weights,
                  cdt):
        """c_kv (B, M, kvr), k_rope (B, M, rope): the rows every query may
        attend, row m at position m; query (b, j) attends rows
        <= qpos[b, j]."""
        wk, wv = self._split_kvb(weights)
        with jax.named_scope("mla:kv_expand"):
            k_nope = _mm("bmc,chn->bmhn", c_kv, wk, cdt)
            v = _mm("bmc,chv->bmhv", c_kv, wv, cdt)
        with jax.named_scope("mla:scores"):
            s = (jnp.einsum("bqhn,bmhn->bhqm", q_nope, k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bqhr,bmr->bhqm", q_rope, k_rope,
                              preferred_element_type=jnp.float32))
            s = s * qscale
            keep = (jnp.arange(c_kv.shape[1])[None, None, :]
                    <= qpos[:, :, None])[:, None]       # (B, 1, Q, M)
            probs = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        with jax.named_scope("mla:context"):
            return _mm("bhqm,bmhv->bqhv", probs, v, cdt)

    def _absorbed(self, q_nope, q_rope, c_kv, k_rope, qpos, qscale, weights,
                  cdt, fused):
        """c_kv (B, M, kvr), k_rope (B, M, rope) as stored. All heads share
        the latent rows, so the C queries of all heads of a slot are the
        rows of ONE product over the slot's rows: q~ against c_kv plus
        q_rope against k_rope for the scores, the probabilities against
        c_kv for the context. `fused` (C = 1): the kernel makes that one
        pass over the rows each slot has filled, under the scope the
        reference's scores run under."""
        b, c, heads, _ = q_nope.shape
        wk, wv = self._split_kvb(weights)
        flat = lambda t: t.reshape(b, c * heads, t.shape[-1])
        with jax.named_scope("mla:absorb"):
            q_lat = _mm("bqhn,chn->bqhc", q_nope, wk, cdt)
        if fused:
            from ..kernels.pallas.latent_decode import latent_decode_attention

            with jax.named_scope("mla:scores"):
                ctx_lat = latent_decode_attention(
                    q_lat[:, 0], q_rope[:, 0].astype(cdt), c_kv, k_rope,
                    qpos[:, 0], jnp.reshape(qscale, (-1,)),
                    interpret=pallas_interpret())[:, None]
            with jax.named_scope("mla:context"):
                return _mm("bqhc,chv->bqhv", ctx_lat, wv, cdt)
        c_kv, k_rope = c_kv.astype(cdt), k_rope.astype(cdt)
        with jax.named_scope("mla:scores"):
            s = (jnp.einsum("bxc,bmc->bxm", flat(q_lat), c_kv,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bxr,bmr->bxm", flat(q_rope.astype(cdt)),
                              k_rope, preferred_element_type=jnp.float32))
            s = s.reshape(b, c, heads, -1).transpose(0, 2, 1, 3) * qscale
            keep = (jnp.arange(c_kv.shape[1])[None, None, :]
                    <= qpos[:, :, None])[:, None]       # (B, 1, C, M)
            probs = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        with jax.named_scope("mla:context"):
            ctx_lat = _mm(
                "bxm,bmc->bxc",
                probs.transpose(0, 2, 1, 3).reshape(b, c * heads, -1), c_kv,
                cdt).reshape(b, c, heads, -1)
            return _mm("bqhc,chv->bqhv", ctx_lat, wv, cdt)

    def flops(self) -> float:
        """Forward operations of the expanded path over the declared
        (batch, length): 2 per weight and token, and the causal core as the
        full L x L product the other attention op counts too."""
        e, heads, qr, kvr, nope, rope, vd = self._dims()
        b, length = self.inputs[0].dims[0], self.inputs[0].dims[1]
        per_token = (e * qr + qr * heads * (nope + rope) + e * (kvr + rope)
                     + kvr * heads * (nope + vd) + heads * vd * e)
        core = 2.0 * b * heads * length * length * (nope + rope + vd)
        return 2.0 * b * length * per_token + core
