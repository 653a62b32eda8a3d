"""Validate the event-driven graph simulator against measured step time.

Done-criterion (ROADMAP S8): simulated vs measured step time within
~25% on (a) the BENCH BERT config and (b) an Inception-style branchy graph,
on the real chip. The simulator predicts fwd+bwd time (it does not model the
optimizer's elementwise update, which the reference also simulates as
separate update tasks priced by grad-sync comm only — simulator.cc:815+), so
the measured comparator here is the grad step (forward+backward), with the
full train step reported alongside for context.

The BERT model/config is IMPORTED from bench.py (same BENCH_* env knobs,
same builder) so the simulator is validated against exactly the benched
model. Every timed window ends in jax.block_until_ready. Runs on the TPU
(one process, like bench.py); there is no CPU switch.

Usage: python scripts/validate_simulator.py [--skip-inception]
Prints one JSON line per model plus a summary.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench import BATCH, SEQ, VOCAB, _build_model  # noqa: E402

ITERS = int(os.environ.get("BENCH_ITERS", 10))


def build_bert():
    model = _build_model(use_flash=None)  # the auto attention policy
    rng = np.random.RandomState(0)
    x = rng.randint(0, VOCAB, size=(BATCH, SEQ)).astype(np.int32)
    y = np.random.RandomState(1).randint(
        0, 2, size=(BATCH, SEQ, 1)).astype(np.int32)
    return model, x, y


def build_inception(batch=8, num_classes=10):
    import flexflow_tpu as ff
    from flexflow_tpu.models.inception import build_inception_v3

    config = ff.FFConfig()
    config.num_devices = 1
    config.batch_size = batch
    model = ff.FFModel(config)
    x = model.create_tensor([batch, 3, 299, 299], ff.DataType.DT_FLOAT)
    build_inception_v3(model, x, num_classes=num_classes)
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.01),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[])
    xs = np.random.RandomState(0).randn(batch, 3, 299, 299).astype(np.float32)
    ys = np.random.RandomState(1).randint(
        0, num_classes, size=(batch, 1)).astype(np.int32)
    return model, xs, ys


def measure_steps(model, x, y):
    """(grad_step_ms, full_step_ms) on the current backend."""
    import jax
    import jax.numpy as jnp

    inputs = {model.input_ops[0].name: model.executor.shard_batch(x)}
    label = jnp.asarray(y)
    key = model._next_rng()

    gstep = model._grad_step
    for _ in range(5):  # warmup: compile + stabilize (first windows run hot)
        g = gstep(model.params, model.state, inputs, label, key)
        jax.block_until_ready(g)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        g = gstep(model.params, model.state, inputs, label, key)
    jax.block_until_ready(g)
    grad_ms = (time.perf_counter() - t0) / ITERS * 1e3

    step = model._train_step
    params, opt_state, state = model.params, model.opt_state, model.state
    for _ in range(5):
        params, opt_state, state, mv = step(params, opt_state, state, inputs,
                                            label, key)
    jax.block_until_ready(mv)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        params, opt_state, state, mv = step(params, opt_state, state, inputs,
                                            label, key)
    jax.block_until_ready(mv)
    full_ms = (time.perf_counter() - t0) / ITERS * 1e3
    model.params, model.opt_state, model.state = params, opt_state, state
    return grad_ms, full_ms


def simulate(model):
    """Predicted single-chip fwd+bwd ms with measured per-op costs."""
    from flexflow_tpu.core.graph import Graph
    from flexflow_tpu.search.machine_model import TpuPodModel
    from flexflow_tpu.search.simulator import OpCostCache, OpStrategy, Simulator

    cache = OpCostCache(model.config)
    sim = Simulator(TpuPodModel(1), model.config, measured=cache)
    graph = Graph(model.ops)
    strategies = {op.guid: OpStrategy(1, 1) for op in model.ops}
    us = sim.simulate(graph, strategies)
    return us / 1e3, sim.analytic_fallbacks


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-inception", action="store_true")
    args = ap.parse_args()

    import jax

    from flexflow_tpu.runtime.platform import (enable_compile_cache,
                                               require_tpu)

    dev = require_tpu("validate_simulator.py")[0]
    # same cache as bench.py: the BERT step here is the benched program
    enable_compile_cache()
    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "device_count": len(jax.devices())}
    builders = [("bert", build_bert)]
    if not args.skip_inception:
        builders.append(("inception", build_inception))

    for name, build in builders:
        model, x, y = build()
        grad_ms, full_ms = measure_steps(model, x, y)
        sim_ms, fallbacks = simulate(model)
        ratio = sim_ms / grad_ms if grad_ms else float("nan")
        out[name] = {
            "simulated_fwd_bwd_ms": round(sim_ms, 2),
            "measured_fwd_bwd_ms": round(grad_ms, 2),
            "measured_full_step_ms": round(full_ms, 2),
            "sim_over_measured": round(ratio, 3),
            "within_25pct": bool(0.75 <= ratio <= 1.25),
            "analytic_fallbacks": fallbacks,
        }
        print(json.dumps({name: out[name]}), flush=True)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
