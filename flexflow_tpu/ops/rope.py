"""Rotary position embedding: the default and the YaRN frequency table, and
the rotation of interleaved or half-split pairs at given positions.

`rope_parameters` is the published group of a model's `config.json`
(`rope_theta`, and for `rope_type: "yarn"`: `factor`,
`original_max_position_embeddings`, `beta_fast`, `beta_slow`, `mscale`,
`mscale_all_dim`, or `attention_factor` given outright; Ministral's
`llama_4_scaling_beta`; `partial_rotary_factor`); with no `rope_type`
(or "default") the table is plain theta^(-2j/dim) and nothing is scaled.
`partial_rotary_factor` f < 1 rotates the LEADING f * dim values of a head
(`rotary_dim`; the tables are those of a head that wide) and passes the
rest through.
Pairs are (x[2j], x[2j+1]) where a model says `rope_interleave`
(`rotate_interleaved`) and (x[j], x[j + dim/2]) otherwise
(`rotate_half_split`). YaRN's conventions are the
DeepSeek-V2/V3 ones: the frequencies of the dimensions that turn more than
`beta_fast` times inside the original context are kept, those that turn
fewer than `beta_slow` times are divided by `factor`, a linear ramp in
between; the cos/sin tables carry `yarn_mscale(factor, mscale) /
yarn_mscale(factor, mscale_all_dim)`, and the softmax scale carries
`yarn_mscale(factor, mscale_all_dim)` squared (`attention_scale`).
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np


def yarn_mscale(factor: float, mscale: float) -> float:
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(turns: float, dim: int, theta: float, ctx: int) -> float:
    """The (fractional) pair index whose frequency makes `turns` full turns
    inside `ctx` positions."""
    return dim * math.log(ctx / (turns * 2.0 * math.pi)) / (
        2.0 * math.log(theta))


def inv_freq(dim: int, rope: Optional[Dict] = None) -> np.ndarray:
    """(dim/2,) float64 inverse frequencies, pair j turning at
    theta^(-2j/dim), blended towards theta-scaled-by-`factor` under YaRN."""
    rope = rope or {}
    theta = float(rope.get("rope_theta", 10000.0))
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", rope.get("type")) != "yarn":
        return base
    factor = float(rope["factor"])
    ctx = int(rope["original_max_position_embeddings"])
    lo = math.floor(_correction_dim(float(rope.get("beta_fast", 32)), dim,
                                    theta, ctx))
    hi = math.ceil(_correction_dim(float(rope.get("beta_slow", 1)), dim,
                                   theta, ctx))
    lo, hi = max(lo, 0), min(hi, dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - lo)
                   / max(hi - lo, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp            # 1: extrapolate (frequency kept)
    return base / factor * (1.0 - keep) + base * keep


def rotary_dim(dim: int, rope: Optional[Dict] = None) -> int:
    """How many leading values of a `dim`-wide head are rotated:
    `partial_rotary_factor` of them (all, where the group has none)."""
    return int(dim * float((rope or {}).get("partial_rotary_factor", 1.0)))


def table_scale(rope: Optional[Dict]) -> float:
    """What YaRN multiplies cos and sin by: `attention_factor` where the
    group gives it outright, else the ratio of its two mscales."""
    rope = rope or {}
    if rope.get("rope_type", rope.get("type")) != "yarn":
        return 1.0
    if rope.get("attention_factor") is not None:
        return float(rope["attention_factor"])
    factor = float(rope["factor"])
    return (yarn_mscale(factor, float(rope.get("mscale", 1.0)))
            / yarn_mscale(factor, float(rope.get("mscale_all_dim", 0.0))))


def attention_scale(head_dim: int, rope: Optional[Dict]) -> float:
    """head_dim^-0.5 times the square of YaRN's all-dimension mscale."""
    scale = float(head_dim) ** -0.5
    rope = rope or {}
    if (rope.get("rope_type", rope.get("type")) == "yarn"
            and rope.get("mscale_all_dim")):
        m = yarn_mscale(float(rope["factor"]), float(rope["mscale_all_dim"]))
        scale *= m * m
    return scale


def position_scale(positions, rope: Optional[Dict]):
    """Ministral's query scaling a(t) = 1 + beta * ln(1 + floor(t / ctx)):
    exactly 1 below the original context. None where the model has none."""
    rope = rope or {}
    beta = rope.get("llama_4_scaling_beta")
    if not beta:
        return None
    ctx = int(rope["original_max_position_embeddings"])
    return 1.0 + float(beta) * jnp.log1p(
        jnp.floor_divide(positions, ctx).astype(jnp.float32))


def cos_sin(positions, dim: int, rope: Optional[Dict] = None):
    """positions (...,) int -> cos, sin (..., dim/2) float32, for the `dim`
    values that are rotated (`rotary_dim` of a head)."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv_freq(dim, rope), jnp.float32)
    s = table_scale(rope)
    return jnp.cos(ang) * s, jnp.sin(ang) * s


def rotate_interleaved(x, cos, sin):
    """x (..., dim) whose pairs are (x[2j], x[2j+1]) (`rope_interleave`),
    rotated by the angles of cos / sin (..., dim/2) — broadcast against x's
    leading dims by the caller. Computed in float32, returned in x's type.
    The rotated pairs stay interleaved: a score is a sum over pairs, so
    query and key only have to agree."""
    xf = x.astype(jnp.float32)
    pairs = xf.reshape(xf.shape[:-1] + (xf.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(xf.shape).astype(x.dtype)


def rotate_half_split(x, cos, sin):
    """x (..., dim) whose pairs are (x[j], x[j + dim/2]) (the default
    layout of the published rotary models), rotated by the angles of cos /
    sin (..., dim/2) — broadcast against x's leading dims by the caller.
    Computed in float32, returned in x's type; the halves stay where they
    were. Tables narrower than dim/2 (`partial_rotary_factor`) rotate the
    leading 2 * their width values, pairs (x[j], x[j + width]), and the
    rest of x passes through."""
    xf = x.astype(jnp.float32)
    half = cos.shape[-1]
    a, b = xf[..., :half], xf[..., half:2 * half]
    parts = [a * cos - b * sin, a * sin + b * cos]
    if 2 * half < xf.shape[-1]:
        parts.append(xf[..., 2 * half:])
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)
