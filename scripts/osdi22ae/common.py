"""Shared runner for the OSDI'22-style artifact benchmarks (reference:
scripts/osdi22ae/*.sh — each runs a model twice, Unity search vs
--only-data-parallel, and compares throughput).

On hardware with one chip the multi-device strategies execute on a virtual
device mesh (host-platform device count), which still validates the searched
strategy end-to-end; throughput ratios on a real v5e slice are the headline
numbers.
"""
from __future__ import annotations

import os
import sys
import time
from typing import Optional

import numpy as np

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

ON_CPU = os.environ.get("JAX_PLATFORMS") == "cpu"
if ON_CPU:
    # an oversubscribed host (8 virtual devices sharing one CI core)
    # serializes device threads; XLA's CPU collective rendezvous ABORTS the
    # process when a device is >40 s late to an all-reduce. Raise the
    # rendezvous timeouts before any backend exists — correctness runs
    # prefer slow over dead.
    flags = os.environ.get("XLA_FLAGS", "")
    for f in ("--xla_cpu_collective_call_warn_stuck_timeout_seconds=300",
              "--xla_cpu_collective_call_terminate_timeout_seconds=1200"):
        if f.split("=")[0] not in flags:
            flags = f"{flags} {f}".strip()
    os.environ["XLA_FLAGS"] = flags


def knob(env: str, default: int, cpu_default: int) -> int:
    """Model-size knob: the env var wins; otherwise the hardware default, or
    a CI-scale default on the CPU mesh. An oversubscribed host (8 virtual
    devices on a 1-core CI box) serializes device threads, and XLA's CPU
    collective rendezvous aborts the process when a device takes >40 s to
    reach an all-reduce — at reference-scale dims that's guaranteed. The
    CPU run validates the searched strategies end-to-end; throughput
    numbers only mean anything on real hardware anyway."""
    if env in os.environ:
        return int(os.environ[env])
    return cpu_default if ON_CPU else default


def run_once(build_fn, make_data, batch_size: int, num_devices: int,
             search_budget: int, only_data_parallel: bool,
             iters: Optional[int] = None):
    """build_fn(model) -> None builds the net; make_data(n) -> (inputs, label)."""
    import flexflow_tpu as ff

    if iters is None:
        # a 1-core CI host runs the 8-virtual-device mesh serially: keep the
        # CPU validation pass short (env overrides for real measurements)
        iters = int(os.environ.get("BENCH_STEPS", 2 if ON_CPU else 8))

    config = ff.FFConfig.from_command_line()
    config.batch_size = batch_size
    config.num_devices = num_devices
    config.search_budget = search_budget
    config.only_data_parallel = only_data_parallel

    model = ff.FFModel(config)
    build_fn(model, config)
    model.compile(
        optimizer=ff.SGDOptimizer(model, lr=0.01),
        loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
    )
    inputs, label = make_data(batch_size)
    model.set_iteration_batch(inputs, label)
    # warmup (compile)
    model.forward(); model.zero_gradients(); model.backward(); model.update()
    t0 = time.time()
    for _ in range(iters):
        model.forward(); model.zero_gradients(); model.backward(); model.update()
    model.get_perf_metrics()  # forces completion
    dt = time.time() - t0
    return iters * batch_size / dt


def compare(name: str, build_fn, make_data, batch_size: int = 64,
            num_devices: int = None, budget: int = 20):
    n_dev = num_devices or int(os.environ.get("BENCH_DEVICES", 8))
    dp = run_once(build_fn, make_data, batch_size, n_dev, 0, True)
    unity = run_once(build_fn, make_data, batch_size, n_dev, budget, False)
    print(f"[{name}] data-parallel: {dp:.1f} samples/s | "
          f"unity(budget={budget}): {unity:.1f} samples/s | "
          f"ratio {unity / dp:.2f}x")
    return dp, unity
