"""Plain float32 `jax.numpy` building blocks of the references: no kernel,
no cache, no batching tricks, nothing imported from flexflow_tpu.

`prec` chooses how a matrix product is computed:
  "float32"   f32 operands, `highest` precision — THE reference
  "bfloat16"  operands and every stored activation rounded to bf16, f32
              accumulation
  "fp8"       operands rounded to e4m3 with one scale per tensor
              (straight-through in the backward pass), bf16 activations
  "bf16_kv"   float32 throughout, but the keys and values are rounded to
              bf16 where a cache would hold them (a served model's second
              control: the cache alone one precision down)
A training reference also takes "bf16_weights": float32 arithmetic, but the
weights are KEPT in bf16 between optimizer steps (no float32 master copy).
Which of these is a configuration's control is its `control_precision`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


_F32 = ("float32", "bf16_kv")


def act_dtype(prec: str):
    return jnp.float32 if prec in _F32 else jnp.bfloat16


def _fp8(x):
    """Round to float8_e4m3fn under a per-tensor scale; gradient passes
    straight through."""
    xf = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12)
    s = 448.0 / amax
    q = (xf * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return xf + jax.lax.stop_gradient(q - xf)


def mm(a, b, spec: str, prec: str):
    """einsum `spec` of activations a with weights (or activations) b."""
    if prec in _F32:
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    if prec == "fp8":
        a, b = _fp8(a), _fp8(b)
    out = jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.astype(jnp.bfloat16)


def layer_norm(x, p, prec: str, eps: float):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) / jnp.sqrt(var + eps)
    y = y * p["gamma"].astype(jnp.float32) + p["beta"].astype(jnp.float32)
    return y.astype(act_dtype(prec))


def gelu_tanh(x):
    xf = x.astype(jnp.float32)
    y = 0.5 * xf * (1.0 + jnp.tanh(
        0.7978845608028654 * (xf + 0.044715 * xf * xf * xf)))
    return y.astype(x.dtype)


def attention(x, p, prec: str, causal: bool):
    """x (B, L, E) -> (B, L, E): multi-head self-attention with biases,
    scale 1/sqrt(head_dim), optional causal mask; softmax in f32."""
    dt = act_dtype(prec)
    q = mm(x, p["wq"], "ble,ehd->blhd", prec) + p["bq"].astype(dt)
    k = mm(x, p["wk"], "ble,ehd->blhd", prec) + p["bk"].astype(dt)
    v = mm(x, p["wv"], "ble,ehd->blhd", prec) + p["bv"].astype(dt)
    if prec == "bf16_kv":   # reduce_precision: a cast there and back is
        # dropped by the TPU compiler as excess precision
        k, v = (jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)
                for t in (k, v))
    s = mm(q, k, "bqhd,bkhd->bhqk", prec).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.float32(q.shape[-1]))
    if causal:
        L = x.shape[1]
        keep = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
        s = jnp.where(keep[None, None], s, -1e30)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s)
    probs = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(dt)
    ctx = mm(probs, v, "bhqk,bkhd->bqhd", prec)
    return mm(ctx, p["wo"], "bqhd,hde->bqe", prec) + p["bo"].astype(dt)


def post_ln_layer(x, params, name: str, prec: str, causal: bool, eps: float):
    """x + attention -> LN -> + FFN(GELU) -> LN (post-LN, as BERT)."""
    dt = act_dtype(prec)
    a = attention(x, params[f"{name}_attn"], prec, causal)
    x = layer_norm(x + a, params[f"{name}_ln1"], prec, eps)
    f1, f2 = params[f"{name}_ff1"], params[f"{name}_ff2"]
    h = gelu_tanh(mm(x, f1["kernel"], "ble,ef->blf", prec)
                  + f1["bias"].astype(dt))
    h = mm(h, f2["kernel"], "blf,fe->ble", prec) + f2["bias"].astype(dt)
    return layer_norm(x + h, params[f"{name}_ln2"], prec, eps)
