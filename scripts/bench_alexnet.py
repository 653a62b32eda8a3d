"""Benchmark: AlexNet / CIFAR-10 training throughput on the local TPU chip.

The second north-star metric (BASELINE.md config 1): the reference trains
AlexNet on CIFAR-10 resized to 229x229 at batch 64 per GPU
(bootcamp_demo/ff_alexnet_cifar10.py, tests/cpp_gpu_tests.sh:34), SGD lr
0.01, sparse categorical crossentropy. This script reproduces that config
single-chip with synthetic pixels (throughput, not accuracy — the >=90%
accuracy gate lives in tests/test_accuracy_gate.py) and prints ONE JSON
line with samples/sec/chip, the device it ran on, MFU vs that chip's bf16
peak (search/machine_model.py chip table), and an analytically-anchored vs_baseline (A100 @ 45% MFU of 312 TFLOP/s bf16 —
an ASSUMED anchor; the reference publishes no AlexNet number).

Timing follows bench.py's idiom: K optimizer steps per jitted dispatch
(lax.scan), one-deep dispatch pipeline, median per-window rate, every
window closed by jax.block_until_ready. TPU only, one process (the
ALEXBENCH_* knobs shrink the shapes for a quick run on the chip).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

BATCH = int(os.environ.get("ALEXBENCH_BATCH", 64))
IMG = int(os.environ.get("ALEXBENCH_IMG", 229))
CLASSES = 10
ITERS = int(os.environ.get("ALEXBENCH_ITERS", 120))
K = int(os.environ.get("ALEXBENCH_STEPS_PER_EXEC", 20))

A100_BF16_PEAK = 312e12
A100_MFU = 0.45
TARGET_RATIO = 1.0 / 1.2  # BASELINE.md: within 1.2x of A100 -> 1.0 == met


def _build():
    import flexflow_tpu as ff
    from flexflow_tpu.models.alexnet import build_alexnet

    config = ff.FFConfig()
    config.num_devices = 1
    config.batch_size = BATCH
    model = ff.FFModel(config)
    x = model.create_tensor([BATCH, 3, IMG, IMG], ff.DataType.DT_FLOAT)
    build_alexnet(model, x, num_classes=CLASSES)
    model.compile(
        optimizer=ff.SGDOptimizer(model, lr=0.01),
        loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
    )
    return model


def train_flops_per_sample(model) -> float:
    """3x forward FLOPs (fwd + ~2x in bwd), summed from the graph's own
    per-op estimates (conv/linear flops(); elementwise counted as 0 — the
    same convention the simulator and the BERT bench anchor use)."""
    fwd = sum(op.flops() for op in model.ops) / BATCH
    return 3.0 * fwd


def _run(model, iters: int) -> float:
    """samples/sec over `iters` steps via K-step dispatches; median of
    per-window rates (bench.py rationale: a single all-up rate folds
    host hiccups into the device number)."""
    import jax

    rng = np.random.RandomState(0)
    x = rng.randn(BATCH, 3, IMG, IMG).astype(np.float32)
    y = rng.randint(0, CLASSES, size=(BATCH, 1)).astype(np.int32)

    mstep = model._get_multi_step()
    name = model.input_ops[0].name
    inputs_k = {name: model.executor.shard_batch(np.stack([x] * K),
                                                 batch_axis=1)}
    label_k = model.executor.shard_batch(np.stack([y] * K), batch_axis=1)
    rng_k = jax.random.split(model._next_rng(), K)
    params, opt_state, state = model.params, model.opt_state, model.state
    # warmup / compile
    params, opt_state, state, mvals = mstep(
        params, opt_state, state, inputs_k, label_k, rng_k)
    jax.block_until_ready(mvals)
    rates = []
    prev = None
    t_last = time.perf_counter()
    for _ in range(max(1, iters // K)):
        params, opt_state, state, mvals = mstep(
            params, opt_state, state, inputs_k, label_k, rng_k)
        if prev is not None:
            jax.block_until_ready(prev)  # completes window i-1
            t = time.perf_counter()
            rates.append(K * BATCH / (t - t_last))
            t_last = t
        prev = mvals
    jax.block_until_ready(prev)
    t = time.perf_counter()
    rates.append(K * BATCH / (t - t_last))
    print(f"bench_alexnet: window rates {[round(r, 1) for r in rates]}",
          file=sys.stderr)
    model.params, model.opt_state, model.state = params, opt_state, state
    return float(np.median(rates))


def main():
    import jax

    from flexflow_tpu.runtime.platform import (enable_compile_cache,
                                               require_tpu)
    from flexflow_tpu.search.machine_model import chip_for_device

    dev = require_tpu("bench_alexnet.py")[0]
    chip = chip_for_device(dev)  # raises on a kind not in the table
    enable_compile_cache()

    model = _build()
    flops = train_flops_per_sample(model)
    sps = _run(model, ITERS)
    a100_est = A100_BF16_PEAK * A100_MFU / flops
    print(json.dumps({
        "metric": "alexnet_cifar10_train_throughput",
        "value": round(sps, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": round(sps / (a100_est * TARGET_RATIO), 3),
        "a100_anchor_samples_per_sec": round(a100_est, 1),
        "anchor_note": "assumed A100@45%MFU analytic anchor (BASELINE.md "
                       "publishes no AlexNet number)",
        "mfu": round(sps * flops / (chip.peak_bf16_tflops * 1e12), 4),
        "peak_bf16_tflops": chip.peak_bf16_tflops,
        "train_flops_per_sample": round(flops / 1e9, 3),
        "train_flops_unit": "GFLOP",
        "batch": BATCH,
        "img": IMG,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }))


if __name__ == "__main__":
    main()
