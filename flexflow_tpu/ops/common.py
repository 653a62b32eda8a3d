"""Shared helpers for op lowerings."""
from __future__ import annotations

import jax.numpy as jnp

from ..ffconst import ActiMode


def apply_activation(x, activation: ActiMode):
    import jax

    if activation is None or activation == ActiMode.AC_MODE_NONE:
        return x
    if activation == ActiMode.AC_MODE_RELU:
        return jax.nn.relu(x)
    if activation == ActiMode.AC_MODE_SIGMOID:
        return jax.nn.sigmoid(x)
    if activation == ActiMode.AC_MODE_TANH:
        return jnp.tanh(x)
    if activation == ActiMode.AC_MODE_GELU:
        return jax.nn.gelu(x)
    if activation == ActiMode.AC_MODE_SILU:
        return jax.nn.silu(x)
    raise ValueError(f"unknown activation {activation}")


def matmul_dtype(config, dtype):
    """bfloat16 accumulate-f32 matmuls on the MXU when allowed."""
    import jax.numpy as jnp

    if config is not None and config.allow_mixed_precision and dtype == jnp.float32:
        return jnp.bfloat16
    return dtype


def emit_dtype(config, declared_dtype):
    """dtype an op's output is stored in at the PCG boundary. Under mixed
    precision, f32 activations are stored bf16 — halving the HBM traffic for
    both the forward values and their backward cotangents — while parameters
    stay f32 (the optimizer's master copy) and reductions (softmax/layernorm
    statistics, loss) still compute in f32. The executor applies this cast
    centrally to every op output (runtime/executor.py), so individual
    lowerings never need to. With allow_mixed_precision off this is the
    declared dtype: the exact-parity align tests are unaffected."""
    jdt = declared_dtype.jnp_dtype if hasattr(declared_dtype, "jnp_dtype") else declared_dtype
    return matmul_dtype(config, jdt)
