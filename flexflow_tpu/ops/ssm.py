"""Mamba-2 state-space mixer (Dao & Gu, "Transformers are SSMs",
arXiv:2405.21060): a gated, selective linear recurrence over `n_heads` heads
of `d_ssm / n_heads` channels, each carrying a (head_dim x d_state) state.

    [z | xBC | dt] = h W_in, each of [z | x | B | C | dt] times its multiplier
    xBC = silu(causal depthwise conv1d(xBC, kernel d_conv) + bias)
    dt = softplus(dt + dt_bias);  A = -exp(A_log);  head n is of group n // (H/G)
    H_t[n] = exp(dt_t[n] A[n]) H_{t-1}[n] + dt_t[n] x_t[n] (x) B_t[group]
    y_t[n] = H_t[n] C_t[group] + D[n] x_t[n]
    out = (RMSNorm over each group of (y * silu(z))) * g  W_out

What a SEQUENCE keeps, whatever its length (`sequence_state_arrays`):
`ssm_state` (H, head_dim, d_state), STORED in `state_dtype` (float32 unless
the model says otherwise; the recurrence carries a stored rounding through
every later token, and a decode step reads and writes all of it for every
slot: half the type is half the step's largest traffic) and always
COMPUTED in float32, and `conv_tail`, the last d_conv - 1 rows of xBC
before the convolution, in the cache type.

Three entries, chosen from what the step sees, as the attention ops have:

  whole sequence   no `decode_pos`: from a zero state over all L tokens
                   (training, plain inference, a one-shot prefill, which
                   also stores the outgoing state where `fill_kv_cache`).
  chunk at offset  a scalar `decode_pos` and L > 1: from the INCOMING state
                   and tail in `ctx.state`, which the caller zeroed before a
                   sequence's first chunk; stores the outgoing ones.
  one token        L = 1 with a `decode_pos`: the recurrence stepped once
                   for every row (every slot of the continuous batcher,
                   idle ones too: rows never meet).

The two multi-token entries are one chunked scan (the SSD form): inside a
block of `chunk_size` tokens the quadratic form on the MXU, across blocks
the carried state. `ctx.valid_len` (a traced count of the leading REAL
tokens of the dispatch; None = all) keeps padding out of the state: a
padded token's dt is 0, so it neither decays nor feeds the state, and the
tail is taken where the real tokens end. Products take operands in the
compute type with float32 accumulation; exp, softplus, the decays, the
carried state and the gate norm's statistics are float32.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from ..core.op import Op, WeightSpec, register_op
from ..ffconst import DataType, OpType
from ..runtime.initializers import (ConstantInitializer, DefaultInitializer,
                                    Initializer, ZeroInitializer)
from .common import emit_dtype, matmul_dtype
from .latent_attention import _wide_add


class _LogUniformInitializer(Initializer):
    """exp(uniform(log lo, log hi)) mapped through `then`: the family's
    ranges for A (1..16, stored as its log) and dt (1e-3..1e-1, stored as
    the inverse softplus)."""

    def __init__(self, lo: float, hi: float, then):
        self.lo, self.hi, self.then = float(lo), float(hi), then

    def __call__(self, key, shape, dtype):
        u = jax.random.uniform(key, shape, jnp.float32, np.log(self.lo),
                               np.log(self.hi))
        return self.then(jnp.exp(u)).astype(dtype)


def _mm(spec, a, b, cdt):
    return jnp.einsum(spec, a.astype(cdt), b.astype(cdt),
                      preferred_element_type=jnp.float32)


@register_op
class Mamba2MixerOp(Op):
    """The op of the module's docstring. Its counters say what the decode
    step's state traffic was, threaded by the continuous batcher from one
    decode iteration to the next (`serving_counters`): `ssm_steps` (one-token
    steps) and `state_rows_stepped` (rows whose state a step read and wrote,
    idle slots' too; wide, ops/latent_attention.py `wide_count`)."""

    op_type = OpType.SSM
    serving_counters = ("ssm_steps", "state_rows_stepped")

    def _dims(self):
        p = self.params
        d_ssm, heads = p["d_ssm"], p["n_heads"]
        groups, n = p["n_groups"], p["d_state"]
        conv_dim = d_ssm + 2 * groups * n
        return (self.inputs[0].dims[-1], d_ssm, heads, d_ssm // heads, groups,
                n, p["d_conv"], conv_dim)

    def output_shapes(self):
        x = self.inputs[0]
        p = self.params
        if p["d_ssm"] % p["n_heads"] or p["n_heads"] % p["n_groups"]:
            raise ValueError(
                f"ssm_mixer: d_ssm={p['d_ssm']} must divide into n_heads="
                f"{p['n_heads']}, and those into n_groups={p['n_groups']}")
        if p["d_conv"] < 2 or p["chunk_size"] < 1:
            raise ValueError("ssm_mixer: need d_conv >= 2 and chunk_size >= 1")
        mult = p.get("slice_multipliers")
        if mult is not None and len(mult) != 5:
            raise ValueError("ssm_mixer: slice_multipliers are five, of"
                             " [z | x | B | C | dt]")
        return [x.dims], [x.dtype]

    def weight_specs(self) -> List[WeightSpec]:
        e, d_ssm, heads, _, groups, n, d_conv, conv_dim = self._dims()
        dt = self.inputs[0].dtype
        init = lambda i, o: (self.params.get("kernel_initializer")
                             or DefaultInitializer(fan_in=i, fan_out=o))
        width = d_ssm + conv_dim + heads
        specs = [
            WeightSpec("w_in", (e, width), dt, init(e, width)),
            WeightSpec("conv_w", (d_conv, conv_dim), dt, init(d_conv, 1)),
            WeightSpec("dt_bias", (heads,), dt, _LogUniformInitializer(
                1e-3, 1e-1, lambda v: v + jnp.log(-jnp.expm1(-v)))),
            WeightSpec("A_log", (heads,), dt, _LogUniformInitializer(
                1.0, 16.0, jnp.log)),
            WeightSpec("D", (heads,), dt, ConstantInitializer(1.0)),
            WeightSpec("norm", (d_ssm,), dt, ConstantInitializer(1.0)),
            WeightSpec("w_out", (d_ssm, e), dt, init(d_ssm, e)),
        ]
        if self.params.get("conv_bias", True):
            specs.insert(2, WeightSpec("conv_b", (conv_dim,), dt,
                                       ZeroInitializer()))
        return specs

    def state_specs(self):
        z = ZeroInitializer()
        return [WeightSpec("ssm_steps", (), DataType.DT_INT32, z),
                WeightSpec("state_rows_stepped", (2,), DataType.DT_INT32, z)]

    def sequence_state_arrays(self):
        _, _, heads, hd, _, n, d_conv, conv_dim = self._dims()
        return {"ssm_state": ((heads, hd, n), self.params.get(
                    "state_dtype") or DataType.DT_FLOAT),
                "conv_tail": ((d_conv - 1, conv_dim), None)}

    # -- lowering ------------------------------------------------------------
    def lower(self, ctx, inputs, weights):
        x = inputs[0]                                   # (B, L, E)
        _, d_ssm, heads, hd, groups, n, d_conv, conv_dim = self._dims()
        cdt = matmul_dtype(ctx.config, x.dtype)
        b, length = x.shape[0], x.shape[1]
        f32 = jnp.float32

        state = ctx.state.get((self.name, "ssm_state"))
        tail = ctx.state.get((self.name, "conv_tail"))
        pos = getattr(ctx, "decode_pos", None) if state is not None else None
        if pos is not None and getattr(pos, "ndim", 0) == 1 and length > 1:
            raise NotImplementedError(
                f"ssm_mixer {self.name!r}: several tokens a slot at per-slot"
                " positions (speculative verify) would need the state"
                " rolled back on a rejected draft")
        carried = pos is not None        # the state comes in and goes out
        stored = carried or (state is not None
                             and getattr(ctx, "fill_kv_cache", False))

        with jax.named_scope("ssm:in_proj"):
            proj = _mm("ble,ew->blw", x, weights["w_in"], cdt)
            mult = self.params.get("slice_multipliers")
            if mult is not None:
                sizes = (d_ssm, d_ssm, groups * n, groups * n, heads)
                proj = proj * jnp.asarray(np.repeat(
                    np.asarray(mult, np.float32), sizes))
            z = proj[..., :d_ssm]
            xbc = proj[..., d_ssm:d_ssm + conv_dim].astype(cdt)
            dt_raw = proj[..., d_ssm + conv_dim:]

        if carried:
            prev = tail.astype(cdt)
        else:
            prev = jnp.zeros((b, d_conv - 1, conv_dim), cdt)
        valid = getattr(ctx, "valid_len", None) if length > 1 else None
        with jax.named_scope("ssm:conv"):
            window = jnp.concatenate([prev, xbc], axis=1)   # (B, L+k-1, C)
            w = weights["conv_w"].astype(f32)
            acc = sum(window[:, k:k + length].astype(f32) * w[k]
                      for k in range(d_conv))
            if "conv_b" in weights:
                acc = acc + weights["conv_b"].astype(f32)
            xbc = jax.nn.silu(acc).astype(cdt)
            if stored:
                # the rows of `window` that end where the real tokens do
                end = length if valid is None else valid
                new_tail = jax.lax.dynamic_slice_in_dim(
                    window, end, d_conv - 1, axis=1)
        xs = xbc[..., :d_ssm].reshape(b, length, heads, hd)
        bm = xbc[..., d_ssm:d_ssm + groups * n].reshape(b, length, groups, n)
        cm = xbc[..., d_ssm + groups * n:].reshape(b, length, groups, n)
        dt = jax.nn.softplus(dt_raw + weights["dt_bias"].astype(f32))
        a = -jnp.exp(weights["A_log"].astype(f32))          # (H,)
        if valid is not None:
            dt = jnp.where((jnp.arange(length) < valid)[None, :, None],
                           dt, 0.0)
        # the state's read, its step and its store under ONE scope: the
        # compiler fuses them, and the fusion's time is read by that name
        h0 = lambda: state.astype(f32) if carried else jnp.zeros(
            (b, heads, hd, n), f32)
        as_stored = lambda h: h.astype(state.dtype) if stored else h
        if carried and length == 1:
            with jax.named_scope("ssm:state_update"):
                y, h_last = _step(xs[:, 0], bm[:, 0], cm[:, 0], dt[:, 0], a,
                                  h0())
                y, h_last = y[:, None], as_stored(h_last)
        else:
            with jax.named_scope("ssm:scan"):
                y, h_last = _chunked_scan(
                    xs, bm, cm, dt, a, h0(), int(self.params["chunk_size"]),
                    cdt)
                h_last = as_stored(h_last)
        y = y + weights["D"].astype(f32)[:, None] * xs.astype(f32)

        if stored:
            ctx.state_updates[(self.name, "ssm_state")] = h_last
            ctx.state_updates[(self.name, "conv_tail")] = new_tail.astype(
                tail.dtype)
        if carried and length == 1 and (
                self.name, "state_rows_stepped") in ctx.state:
            held = lambda var: ctx.state[(self.name, var)]
            ctx.state_updates[(self.name, "ssm_steps")] = held("ssm_steps") + 1
            ctx.state_updates[(self.name, "state_rows_stepped")] = _wide_add(
                held("state_rows_stepped"), b)

        with jax.named_scope("ssm:gate_norm"):
            y = y.reshape(b, length, d_ssm) * jax.nn.silu(z)
            yg = y.reshape(b, length, groups, d_ssm // groups)
            yg = yg * jax.lax.rsqrt(
                jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
                + self.params.get("eps", 1e-5))
            y = (yg.reshape(b, length, d_ssm)
                 * weights["norm"].astype(f32)).astype(cdt)
        with jax.named_scope("ssm:out_proj"):
            out = _mm("bld,de->ble", y, weights["w_out"], cdt)
        return [out.astype(emit_dtype(ctx.config, self.outputs[0].dtype))]

    def flops(self) -> float:
        """Forward operations over the declared (batch, length): 2 per
        weight and token, and the recurrence's state update and read-out
        (2 x 2 x head_dim x d_state a head and token)."""
        e, d_ssm, heads, hd, groups, n, _, conv_dim = self._dims()
        b, length = self.inputs[0].dims[0], self.inputs[0].dims[1]
        per_token = (e * (d_ssm + conv_dim + heads) + d_ssm * e
                     + 2 * heads * hd * n)
        return 2.0 * b * length * per_token


def _step(x, bm, cm, dt, a, h):
    """One token of the recurrence for every row: x (B, H, P), bm / cm
    (B, G, N), dt (B, H), a (H,), h (B, H, P, N) float32. Returns (y
    (B, H, P) float32 without the D term, the new h). One pass over h."""
    b, heads, hd, n = h.shape
    groups = bm.shape[1]
    hg = h.reshape(b, groups, heads // groups, hd, n)
    decay = jnp.exp(dt * a).reshape(b, groups, heads // groups, 1, 1)
    dx = (dt[..., None] * x.astype(jnp.float32)).reshape(
        b, groups, heads // groups, hd, 1)
    hg = hg * decay + dx * bm.astype(jnp.float32)[:, :, None, None, :]
    y = jnp.sum(hg * cm.astype(jnp.float32)[:, :, None, None, :], axis=-1)
    return y.reshape(b, heads, hd), hg.reshape(b, heads, hd, n)


def _chunked_scan(x, bm, cm, dt, a, h0, block, cdt):
    """The recurrence over L tokens in blocks of `block`: x (B, L, H, P),
    bm / cm (B, L, G, N) in the compute type, dt (B, L, H) float32 (0 at a
    padded token), a (H,), h0 (B, H, P, N) float32. Returns (y (B, L, H, P)
    float32 without the D term, the state after the last token).

    Inside a block, y_t = sum_{s<=t} exp(cum_t - cum_s) dt_s (C_t . B_s) x_s
    with cum the running sum of dt A: two products on the MXU. Across
    blocks the state is carried: H' = exp(cum_end) H + sum_s exp(cum_end -
    cum_s) dt_s x_s (x) B_s, and a token reads the state its block came in
    with, decayed to it."""
    b, length, heads, hd = x.shape
    groups, n = bm.shape[2], bm.shape[3]
    per = heads // groups
    f32 = jnp.float32
    pad = -length % block
    if pad:
        widen = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (
            t.ndim - 2))
        x, bm, cm, dt = widen(x), widen(bm), widen(cm), widen(dt)
    nb = (length + pad) // block
    blocks = lambda t: t.reshape((b, nb, block) + t.shape[2:])
    x, bm, cm, dt = blocks(x), blocks(bm), blocks(cm), blocks(dt)
    cum = jnp.cumsum(dt * a, axis=2)                    # (B, nb, Q, H), <= 0
    # inside a block
    cb = jnp.einsum("bctgn,bcsgn->bcgts", cm, bm,
                    preferred_element_type=f32)         # C_t . B_s
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B, nb, t, s, H)
    causal = jnp.tril(jnp.ones((block, block), bool))[None, None, :, :, None]
    mix = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0) \
        * dt[:, :, None, :, :]
    mix = mix.reshape(b, nb, block, block, groups, per) \
        * cb.transpose(0, 1, 3, 4, 2)[..., None]        # (B, nb, t, s, G, J)
    y = jnp.einsum("bctsgj,bcsgjp->bctgjp", mix.astype(cdt),
                   x.reshape(b, nb, block, groups, per, hd),
                   preferred_element_type=f32)
    # what a block adds to the state, and the carry across blocks
    to_end = jnp.exp(cum[:, :, -1:, :] - cum) * dt      # (B, nb, Q, H)
    xw = (x.astype(f32) * to_end[..., None]).astype(cdt)
    add = jnp.einsum("bcsgjp,bcsgn->bcgjpn",
                     xw.reshape(b, nb, block, groups, per, hd), bm,
                     preferred_element_type=f32)
    block_decay = jnp.exp(cum[:, :, -1, :]).reshape(b, nb, groups, per)

    def carry(h, args):
        add_c, decay_c = args
        return h * decay_c[..., None, None] + add_c, h

    h_last, h_in = jax.lax.scan(
        carry, h0.reshape(b, groups, per, hd, n),
        (jnp.moveaxis(add, 1, 0), jnp.moveaxis(block_decay, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)                     # (B, nb, G, J, P, N)
    y = y + jnp.einsum("bctgn,bcgjpn->bctgjp", cm, h_in.astype(cdt),
                       preferred_element_type=f32) \
        * jnp.exp(cum).reshape(b, nb, block, groups, per)[..., None]
    y = y.reshape(b, nb * block, heads, hd)[:, :length]
    return y, h_last.reshape(b, heads, hd, n)
