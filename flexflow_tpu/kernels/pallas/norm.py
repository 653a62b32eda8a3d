"""Fused LayerNorm / RMSNorm / Softmax as Pallas TPU kernels (fwd + bwd).

Role in the tier (docs/kernels.md): the unfused jnp lowerings in
ops/norm.py walk the activation through HBM several times (mean, var,
normalize, affine — resp. exp, sum, divide); each of these kernels makes
ONE pass with the whole normalized row resident in VMEM, statistics and
accumulation in f32, I/O in the stored dtype (bf16 under mixed
precision). The backward passes are hand-derived single-pass kernels of
the standard normalization gradients, with the cross-row dgamma/dbeta
reductions accumulated in f32 output blocks across the sequential grid
(the same persistent-block trick the flash kernels use for their online
softmax state).

All kernels normalize over the TRAILING axis with every leading dim
flattened into rows; the wrappers restore shapes. `interpret=True` runs
the identical kernels in the Pallas interpreter so the CPU parity suite
(tests/test_pallas_kernels.py) covers fwd AND bwd bit-for-tolerance
against the jnp reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rows(x):
    """Flatten (..., N) -> (R, N)."""
    n = x.shape[-1]
    return x.reshape(-1, n)


def _pad_rows(x2, block_r):
    r = x2.shape[0]
    rem = r % block_r
    if rem == 0:
        return x2
    return jnp.pad(x2, ((0, block_r - rem), (0, 0)))


def _row_mask(i, block_r, n_rows):
    """(block_r, 1) f32 mask of real (unpadded) rows in block i."""
    pos = i * block_r + jax.lax.broadcasted_iota(jnp.int32, (block_r, 1), 0)
    return (pos < n_rows).astype(jnp.float32)


def _grid_block(n_rows, block_r):
    block_r = max(1, min(block_r, n_rows))
    n_pad = -(-n_rows // block_r) * block_r
    return block_r, n_pad // block_r


# ---------------------------------------------------------------------------
# LayerNorm
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps,
                   affine):
    x = x_ref[...].astype(jnp.float32)                    # (br, N)
    mean = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean) * rstd
    if affine:
        y = y * g_ref[...].astype(jnp.float32) + b_ref[...].astype(
            jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _ln_bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, dy_ref, dx_ref, dg_ref,
                   db_ref, *, affine, block_r, n_rows):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    mean = mean_ref[...]
    rstd = rstd_ref[...]
    xhat = (x - mean) * rstd
    gdot = dy * g_ref[...].astype(jnp.float32) if affine else dy
    m1 = jnp.mean(gdot, axis=1, keepdims=True)
    m2 = jnp.mean(gdot * xhat, axis=1, keepdims=True)
    dx_ref[...] = ((gdot - m1 - xhat * m2) * rstd).astype(dx_ref.dtype)
    if affine:
        mask = _row_mask(i, block_r, n_rows)

        @pl.when(i == 0)
        def _init():
            dg_ref[...] = jnp.zeros_like(dg_ref)
            db_ref[...] = jnp.zeros_like(db_ref)

        dg_ref[...] += jnp.sum(dy * xhat * mask, axis=0, keepdims=True)
        db_ref[...] += jnp.sum(dy * mask, axis=0, keepdims=True)


def _ln_fwd(x, gamma, beta, eps, block_rows, interpret, affine):
    x2 = _rows(x)
    r, n = x2.shape
    block_r, n_blocks = _grid_block(r, block_rows)
    xp = _pad_rows(x2, block_r)
    row_spec = pl.BlockSpec((block_r, n), lambda i: (i, 0))
    stat_spec = pl.BlockSpec((block_r, 1), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    ins = [xp]
    in_specs = [row_spec]
    if affine:
        ins += [gamma.reshape(1, n), beta.reshape(1, n)]
        in_specs += [vec_spec, vec_spec]
    else:
        # placeholder operands keep one kernel signature for both modes
        ins += [jnp.zeros((1, n), x.dtype)] * 2
        in_specs += [vec_spec, vec_spec]
    y, mean, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps, affine=affine),
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=[row_spec, stat_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x.dtype),
            jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32),
            jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32),
        ],
        interpret=interpret,
    )(*ins)
    return y[:r].reshape(x.shape), mean[:r], rstd[:r]


def _ln_bwd(x, gamma, mean, rstd, dy, block_rows, interpret, affine):
    x2 = _rows(x)
    dy2 = _rows(dy)
    r, n = x2.shape
    block_r, n_blocks = _grid_block(r, block_rows)
    xp, dyp = _pad_rows(x2, block_r), _pad_rows(dy2, block_r)
    meanp, rstdp = _pad_rows(mean, block_r), _pad_rows(rstd, block_r)
    row_spec = pl.BlockSpec((block_r, n), lambda i: (i, 0))
    stat_spec = pl.BlockSpec((block_r, 1), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    g_in = (gamma.reshape(1, n) if affine
            else jnp.zeros((1, n), x.dtype))
    dx, dg, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, affine=affine, block_r=block_r,
                          n_rows=r),
        grid=(n_blocks,),
        in_specs=[row_spec, vec_spec, stat_spec, stat_spec, row_spec],
        out_specs=[row_spec, vec_spec, vec_spec],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=interpret,
    )(xp, g_in, meanp, rstdp, dyp)
    dx = dx[:r].reshape(x.shape)
    if not affine:
        return dx, None, None
    return dx, dg[0].astype(gamma.dtype), db[0].astype(gamma.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_layernorm_affine(x, gamma, beta, eps, block_rows, interpret):
    y, _, _ = _ln_fwd(x, gamma, beta, eps, block_rows, interpret, True)
    return y


def _fused_ln_affine_fwd(x, gamma, beta, eps, block_rows, interpret):
    y, mean, rstd = _ln_fwd(x, gamma, beta, eps, block_rows, interpret, True)
    return y, (x, gamma, mean, rstd)


def _fused_ln_affine_bwd(eps, block_rows, interpret, res, g):
    x, gamma, mean, rstd = res
    dx, dg, db = _ln_bwd(x, gamma, mean, rstd, g, block_rows, interpret,
                         True)
    return dx, dg, db


_fused_layernorm_affine.defvjp(_fused_ln_affine_fwd, _fused_ln_affine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _fused_layernorm_plain(x, eps, block_rows, interpret):
    y, _, _ = _ln_fwd(x, None, None, eps, block_rows, interpret, False)
    return y


def _fused_ln_plain_fwd(x, eps, block_rows, interpret):
    y, mean, rstd = _ln_fwd(x, None, None, eps, block_rows, interpret, False)
    return y, (x, mean, rstd)


def _fused_ln_plain_bwd(eps, block_rows, interpret, res, g):
    x, mean, rstd = res
    dx, _, _ = _ln_bwd(x, None, mean, rstd, g, block_rows, interpret, False)
    return (dx,)


_fused_layernorm_plain.defvjp(_fused_ln_plain_fwd, _fused_ln_plain_bwd)


def fused_layernorm(x, gamma=None, beta=None, *, eps: float = 1e-5,
                    block_rows: int = 128, interpret: bool = False):
    """LayerNorm over the trailing axis in one fused pass (f32 stats,
    I/O in x.dtype). gamma/beta shape (N,) or None for no affine."""
    if (gamma is None) != (beta is None):
        raise ValueError("gamma and beta must be given together")
    if gamma is None:
        return _fused_layernorm_plain(x, float(eps), int(block_rows),
                                      bool(interpret))
    return _fused_layernorm_affine(x, gamma.reshape(-1), beta.reshape(-1),
                                   float(eps), int(block_rows),
                                   bool(interpret))


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def _rms_fwd_kernel(x_ref, g_ref, y_ref, rstd_ref, *, eps, affine):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    y = x * rstd
    if affine:
        y = y * g_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    rstd_ref[...] = rstd


def _rms_bwd_kernel(x_ref, g_ref, rstd_ref, dy_ref, dx_ref, dg_ref, *,
                    affine, block_r, n_rows):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    rstd = rstd_ref[...]
    xhat = x * rstd
    gdot = dy * g_ref[...].astype(jnp.float32) if affine else dy
    m2 = jnp.mean(gdot * xhat, axis=1, keepdims=True)
    dx_ref[...] = ((gdot - xhat * m2) * rstd).astype(dx_ref.dtype)
    if affine:
        mask = _row_mask(i, block_r, n_rows)

        @pl.when(i == 0)
        def _init():
            dg_ref[...] = jnp.zeros_like(dg_ref)

        dg_ref[...] += jnp.sum(dy * xhat * mask, axis=0, keepdims=True)


def _rms_fwd(x, gamma, eps, block_rows, interpret, affine):
    x2 = _rows(x)
    r, n = x2.shape
    block_r, n_blocks = _grid_block(r, block_rows)
    xp = _pad_rows(x2, block_r)
    row_spec = pl.BlockSpec((block_r, n), lambda i: (i, 0))
    stat_spec = pl.BlockSpec((block_r, 1), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    g_in = gamma.reshape(1, n) if affine else jnp.zeros((1, n), x.dtype)
    y, rstd = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps, affine=affine),
        grid=(n_blocks,),
        in_specs=[row_spec, vec_spec],
        out_specs=[row_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x.dtype),
            jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp, g_in)
    return y[:r].reshape(x.shape), rstd[:r]


def _rms_bwd(x, gamma, rstd, dy, block_rows, interpret, affine):
    x2, dy2 = _rows(x), _rows(dy)
    r, n = x2.shape
    block_r, n_blocks = _grid_block(r, block_rows)
    xp, dyp, rstdp = (_pad_rows(x2, block_r), _pad_rows(dy2, block_r),
                      _pad_rows(rstd, block_r))
    row_spec = pl.BlockSpec((block_r, n), lambda i: (i, 0))
    stat_spec = pl.BlockSpec((block_r, 1), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    g_in = gamma.reshape(1, n) if affine else jnp.zeros((1, n), x.dtype)
    dx, dg = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, affine=affine, block_r=block_r,
                          n_rows=r),
        grid=(n_blocks,),
        in_specs=[row_spec, vec_spec, stat_spec, row_spec],
        out_specs=[row_spec, vec_spec],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=interpret,
    )(xp, g_in, rstdp, dyp)
    dx = dx[:r].reshape(x.shape)
    return dx, (dg[0].astype(gamma.dtype) if affine else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused_rmsnorm_affine(x, gamma, eps, block_rows, interpret):
    y, _ = _rms_fwd(x, gamma, eps, block_rows, interpret, True)
    return y


def _fused_rms_affine_fwd(x, gamma, eps, block_rows, interpret):
    y, rstd = _rms_fwd(x, gamma, eps, block_rows, interpret, True)
    return y, (x, gamma, rstd)


def _fused_rms_affine_bwd(eps, block_rows, interpret, res, g):
    x, gamma, rstd = res
    dx, dg = _rms_bwd(x, gamma, rstd, g, block_rows, interpret, True)
    return dx, dg


_fused_rmsnorm_affine.defvjp(_fused_rms_affine_fwd, _fused_rms_affine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _fused_rmsnorm_plain(x, eps, block_rows, interpret):
    y, _ = _rms_fwd(x, None, eps, block_rows, interpret, False)
    return y


def _fused_rms_plain_fwd(x, eps, block_rows, interpret):
    y, rstd = _rms_fwd(x, None, eps, block_rows, interpret, False)
    return y, (x, rstd)


def _fused_rms_plain_bwd(eps, block_rows, interpret, res, g):
    x, rstd = res
    dx, _ = _rms_bwd(x, None, rstd, g, block_rows, interpret, False)
    return (dx,)


_fused_rmsnorm_plain.defvjp(_fused_rms_plain_fwd, _fused_rms_plain_bwd)


def fused_rmsnorm(x, gamma=None, *, eps: float = 1e-6,
                  block_rows: int = 128, interpret: bool = False):
    """RMSNorm over the trailing axis in one fused pass. Default eps
    matches RMSNormOp's 1e-6 (LayerNorm keeps the framework's 1e-5)."""
    if gamma is None:
        return _fused_rmsnorm_plain(x, float(eps), int(block_rows),
                                    bool(interpret))
    return _fused_rmsnorm_affine(x, gamma.reshape(-1), float(eps),
                                 int(block_rows), bool(interpret))


# ---------------------------------------------------------------------------
# Softmax
# ---------------------------------------------------------------------------

def _softmax_fwd_kernel(x_ref, y_ref):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=1, keepdims=True)
    e = jnp.exp(x - m)
    y_ref[...] = (e / jnp.sum(e, axis=1, keepdims=True)).astype(y_ref.dtype)


def _softmax_bwd_kernel(y_ref, dy_ref, dx_ref):
    y = y_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    s = jnp.sum(y * dy, axis=1, keepdims=True)
    dx_ref[...] = (y * (dy - s)).astype(dx_ref.dtype)


# Scoped-VMEM budget the softmax row blocks are sized against: well under
# Mosaic's 16 MiB default on v5e. A (rows, N) block costs about ten f32
# copies of itself — double-buffered operands and result plus the f32
# temporaries of the body.
_SOFTMAX_VMEM_BUDGET = 12 * 2 ** 20
_SOFTMAX_BLOCK_COPIES = 10


def softmax_block_rows(n_cols: int, block_rows: int = 128) -> int:
    """Rows per softmax block for rows of n_cols: block_rows where that
    fits the VMEM budget, else the largest multiple of 8 that does. 0
    means even 8 rows do not fit — the row is too wide for the
    whole-row-resident kernel and the selector (ops/norm.py, CostModel)
    keeps the reference lowering."""
    fit = _SOFTMAX_VMEM_BUDGET // (_SOFTMAX_BLOCK_COPIES * 4 * max(n_cols, 1))
    return min(block_rows, fit // 8 * 8)


def _softmax_call(kernel, outs_like, block_rows, interpret, *arrays):
    x2s = [_rows(a) for a in arrays]
    r, n = x2s[0].shape
    block_rows = softmax_block_rows(n, block_rows)
    if block_rows == 0:
        raise ValueError(
            f"fused_softmax: rows of {n} do not fit VMEM; use the reference"
            " softmax (softmax_block_rows is the selector's gate)")
    block_r, n_blocks = _grid_block(r, block_rows)
    row_spec = pl.BlockSpec((block_r, n), lambda i: (i, 0))
    padded = [_pad_rows(a, block_r) for a in x2s]
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[row_spec] * len(padded),
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct(padded[0].shape, outs_like.dtype),
        interpret=interpret,
    )(*padded)
    return out[:r].reshape(outs_like.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _fused_softmax(x, block_rows, interpret):
    return _softmax_call(_softmax_fwd_kernel, x, block_rows, interpret, x)


def _fused_softmax_fwd(x, block_rows, interpret):
    y = _softmax_call(_softmax_fwd_kernel, x, block_rows, interpret, x)
    return y, (y,)


def _fused_softmax_bwd(block_rows, interpret, res, g):
    (y,) = res
    dx = _softmax_call(_softmax_bwd_kernel, y, block_rows, interpret, y, g)
    return (dx,)


_fused_softmax.defvjp(_fused_softmax_fwd, _fused_softmax_bwd)


def fused_softmax(x, *, block_rows: int = 128, interpret: bool = False):
    """softmax over the trailing axis in one fused pass (f32 exp/sum,
    output in x.dtype)."""
    return _fused_softmax(x, int(block_rows), bool(interpret))
