"""Loss functions (reference: src/loss_functions/loss_functions.cc:1-214).

The reference's loss "backward" kernels seed the logit gradients scaled by
1/batch; here each loss is a scalar-valued function and jax.grad produces the
identical seeding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ffconst import LossType


def sparse_categorical_crossentropy(logits, labels):
    """labels: int class ids, shape logits.shape[:-1] or (..., 1)."""
    if labels.ndim == logits.ndim:
        labels = labels[..., 0]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.mean(ll)


def categorical_crossentropy(probs_or_logits, labels, from_logits: bool = False):
    x = probs_or_logits.astype(jnp.float32)
    if from_logits:
        logp = jax.nn.log_softmax(x, axis=-1)
    else:
        logp = jnp.log(jnp.clip(x, 1e-12, 1.0))
    return -jnp.mean(jnp.sum(labels.astype(jnp.float32) * logp, axis=-1))


def mean_squared_error(pred, target, reduce: str = "avg"):
    se = jnp.square(pred.astype(jnp.float32) - target.astype(jnp.float32))
    per_sample = jnp.sum(se.reshape(se.shape[0], -1), axis=-1)
    return jnp.mean(per_sample) if reduce == "avg" else jnp.sum(per_sample)


def identity_loss(pred, target=None):
    return jnp.mean(pred.astype(jnp.float32))


def loss_fn_for(loss_type: LossType):
    if loss_type == LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY:
        return sparse_categorical_crossentropy
    if loss_type == LossType.LOSS_CATEGORICAL_CROSSENTROPY:
        return categorical_crossentropy
    if loss_type == LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE:
        return lambda p, t: mean_squared_error(p, t, "avg")
    if loss_type == LossType.LOSS_MEAN_SQUARED_ERROR_SUM_REDUCE:
        return lambda p, t: mean_squared_error(p, t, "sum")
    if loss_type == LossType.LOSS_IDENTITY:
        return identity_loss
    raise ValueError(f"unknown loss {loss_type}")


class Loss:
    """API-compat wrapper (reference: loss_functions.h:27-90)."""

    def __init__(self, loss_type: LossType, repl_labels: bool = False):
        if isinstance(loss_type, str):
            loss_type = {
                "categorical_crossentropy": LossType.LOSS_CATEGORICAL_CROSSENTROPY,
                "sparse_categorical_crossentropy": LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                "mean_squared_error": LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE,
                "identity": LossType.LOSS_IDENTITY,
            }[loss_type]
        self.loss_type = loss_type
        self.repl_labels = repl_labels
        self.fn = loss_fn_for(loss_type)

    def __call__(self, pred, target):
        return self.fn(pred, target)
