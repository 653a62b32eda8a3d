"""Fused scalar reduction as a Pallas TPU kernel.

In the spirit of arXiv:1811.09736 (single-pass tensor-core-era
reduction): the loss/metrics reductions (`jnp.mean` of a crossentropy
row, accuracy means, MSE) each cost a full HBM read per reduction when
XLA schedules them as separate fusions at the step epilogue.
`fused_reduce` streams the flattened array once through VMEM in
lane-shaped blocks, accumulating into a persistent f32 output block
across the sequential grid — one pass, one scalar out.

 - `fused_reduce(x, kind="sum"|"mean"|"max")`: scalar f32 reduction.
   sum/mean carry a custom VJP (broadcast of the cotangent — the
   mathematically exact gradient, no kernel needed); max is
   forward-only (its consumers — metrics — never differentiate).

`fused_reduce` is what runtime/losses.py and runtime/metrics.py route
through the KernelRegistry's `reduction` family (reference impl = plain
jnp).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128


def _reduce_kernel(x_ref, o_ref, *, kind):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.full_like(
            o_ref, -jnp.inf if kind == "max" else 0.0)

    # whole-block (1, 1) stores: the TPU lowering cannot store a scalar
    # to VMEM
    if kind == "max":
        o_ref[...] = jnp.maximum(o_ref[...], jnp.max(x, keepdims=True))
    else:
        o_ref[...] += jnp.sum(x, keepdims=True)


def _reduce_sum_or_max(x, kind, block_rows, interpret):
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    if n == 0:
        return jnp.float32(-jnp.inf if kind == "max" else 0.0)
    lanes = min(_LANES, n)
    pad_id = jnp.float32(-jnp.inf if kind == "max" else 0.0)
    cols = -(-n // lanes) * lanes
    flat = jnp.pad(flat, (0, cols - n), constant_values=pad_id)
    x2 = flat.reshape(-1, lanes)
    r = x2.shape[0]
    block_r = max(1, min(block_rows, r))
    rpad = -(-r // block_r) * block_r
    if rpad != r:
        x2 = jnp.pad(x2, ((0, rpad - r), (0, 0)), constant_values=pad_id)
    out = pl.pallas_call(
        functools.partial(_reduce_kernel, kind=kind),
        grid=(rpad // block_r,),
        in_specs=[pl.BlockSpec((block_r, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret,
    )(x2)
    return out[0, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _fused_reduce(x, kind, block_rows, interpret):
    s = _reduce_sum_or_max(x, "max" if kind == "max" else "sum",
                           block_rows, interpret)
    if kind == "mean":
        s = s / max(1, x.size)
    return s


def _fused_reduce_fwd(x, kind, block_rows, interpret):
    # residual: a zero-size prototype carrying x's shape+dtype (raw
    # shape/dtype objects are not valid JAX residual types)
    return _fused_reduce(x, kind, block_rows, interpret), (
        jnp.zeros((0,) + x.shape, x.dtype),)


def _fused_reduce_bwd(kind, block_rows, interpret, res, g):
    (proto,) = res
    if kind == "max":
        raise TypeError("fused_reduce(kind='max') is forward-only; use the "
                        "reference reduction for differentiable maxima")
    shape = proto.shape[1:]
    n = 1
    for d in shape:
        n *= d
    scale = g / max(1, n) if kind == "mean" else g
    return (jnp.full(shape, scale, dtype=jnp.float32).astype(proto.dtype),)


_fused_reduce.defvjp(_fused_reduce_fwd, _fused_reduce_bwd)


def fused_reduce(x, kind: str = "sum", *, block_rows: int = 256,
                 interpret: bool = False):
    """Single-pass scalar reduction of x (any shape) -> f32 scalar."""
    if kind not in ("sum", "mean", "max"):
        raise ValueError(f"kind must be sum, mean or max, got {kind!r}")
    return _fused_reduce(x, kind, int(block_rows), bool(interpret))
