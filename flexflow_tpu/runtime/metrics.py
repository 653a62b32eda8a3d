"""Metrics (reference: src/metrics_functions/metrics_functions.cc:1-249).

Metrics are computed on device inside the jitted step and accumulated into a
host-side PerfMetrics — the reference's future-chained `update_metrics_task`
(model.h:763) collapses to returning a small dict from the step function.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp

from ..ffconst import LossType, MetricsType


@dataclasses.dataclass
class PerfMetrics:
    """Accumulated training metrics (reference: PerfMetrics struct)."""

    train_all: int = 0
    train_correct: int = 0
    cce_loss: float = 0.0
    sparse_cce_loss: float = 0.0
    mse_loss: float = 0.0
    rmse_loss: float = 0.0
    mae_loss: float = 0.0
    loss_sum: float = 0.0
    start_time: float = 0.0

    def update(self, batch: int, vals: Dict[str, float]) -> None:
        self.train_all += batch
        if "accuracy" in vals:
            self.train_correct += int(round(vals["accuracy"] * batch))
        self.cce_loss += vals.get("cce", 0.0) * batch
        self.sparse_cce_loss += vals.get("sparse_cce", 0.0) * batch
        self.mse_loss += vals.get("mse", 0.0) * batch
        self.rmse_loss += vals.get("rmse", 0.0) * batch
        self.mae_loss += vals.get("mae", 0.0) * batch
        self.loss_sum += vals.get("loss", 0.0) * batch

    @property
    def accuracy(self) -> float:
        return self.train_correct / max(1, self.train_all)

    def summary(self) -> Dict[str, float]:
        n = max(1, self.train_all)
        return {
            "samples": self.train_all,
            "accuracy": self.accuracy,
            "loss": self.loss_sum / n,
            "cce": self.cce_loss / n,
            "sparse_cce": self.sparse_cce_loss / n,
            "mse": self.mse_loss / n,
            "rmse": self.rmse_loss / n,
            "mae": self.mae_loss / n,
        }


class Metrics:
    """Computes the selected metric set from (pred, label) on device."""

    def __init__(self, loss_type: LossType, metrics: Sequence[MetricsType]):
        self.loss_type = loss_type
        self.metrics = list(metrics)

    def compute(self, pred, label) -> Dict[str, jnp.ndarray]:
        out: Dict[str, jnp.ndarray] = {}
        sparse_label = (
            label[..., 0] if (label.ndim == pred.ndim and label.shape[-1] == 1
                              and pred.shape[-1] != 1 and not jnp.issubdtype(label.dtype, jnp.floating))
            else label
        )
        for m in self.metrics:
            if m == MetricsType.METRICS_ACCURACY:
                if jnp.issubdtype(sparse_label.dtype, jnp.floating) and sparse_label.ndim == pred.ndim:
                    tgt = jnp.argmax(sparse_label, axis=-1)
                else:
                    tgt = sparse_label
                out["accuracy"] = jnp.mean(
                    (jnp.argmax(pred, axis=-1) == tgt.astype(jnp.int32)).astype(jnp.float32)
                )
            elif m == MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY:
                from .losses import sparse_categorical_crossentropy

                out["sparse_cce"] = sparse_categorical_crossentropy(pred, label)
            elif m == MetricsType.METRICS_CATEGORICAL_CROSSENTROPY:
                from .losses import categorical_crossentropy

                out["cce"] = categorical_crossentropy(pred, label)
            elif m == MetricsType.METRICS_MEAN_SQUARED_ERROR:
                # f32 BEFORE the reduction: a bf16-accumulated mean drifts
                out["mse"] = jnp.mean(jnp.square(
                    pred - label.astype(pred.dtype)).astype(jnp.float32))
            elif m == MetricsType.METRICS_ROOT_MEAN_SQUARED_ERROR:
                out["rmse"] = jnp.sqrt(jnp.mean(jnp.square(
                    pred - label.astype(pred.dtype)).astype(jnp.float32)))
            elif m == MetricsType.METRICS_MEAN_ABSOLUTE_ERROR:
                out["mae"] = jnp.mean(jnp.abs(
                    pred - label.astype(pred.dtype)).astype(jnp.float32))
        return out
