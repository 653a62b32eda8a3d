"""Benchmark driver: BERT-style training throughput on the local TPU chip.

Config mirrors the reference's OSDI'22 BERT benchmark (scripts/osdi22ae/bert.sh,
examples/cpp/Transformer/transformer.cc:80-84: 12 layers, hidden 1024, seq 512,
16 heads) at a per-chip batch size. One process, one chip. Prints ONE JSON
line:
  {"metric": ..., "value": N, "unit": "samples/sec/chip", "vs_baseline": N,
   "platform": "tpu", "device_kind": "...", "device_count": 1, ...}

It runs on a TPU or not at all: there is no CPU switch, and a device kind
missing from the chip table (search/machine_model.py DEVICE_KIND_CHIP) is an
error, not a default peak.

vs_baseline anchors to BASELINE.md's north star: v5e within 1.2x of A100 —
the A100 per-GPU throughput for this config is estimated analytically from
its bf16 peak (312 TFLOP/s) at 45% MFU over the model's 6*P*tokens
train-step FLOPs; vs_baseline >= 1.0 means within-1.2x is met. (The anchor
and the einsum/flash probe below are ROADMAP S1's to replace.)

Timing: every timed window ends in jax.block_until_ready on that window's
outputs, so a rate is device work, not enqueue time.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# env overrides shrink the shapes for a quick run on the chip
BATCH = int(os.environ.get("BENCH_BATCH", 8))
SEQ = int(os.environ.get("BENCH_SEQ", 512))
HIDDEN = int(os.environ.get("BENCH_HIDDEN", 1024))
LAYERS = int(os.environ.get("BENCH_LAYERS", 12))
HEADS = int(os.environ.get("BENCH_HEADS", 16))
VOCAB = int(os.environ.get("BENCH_VOCAB", 30522))

A100_BF16_PEAK = 312e12
A100_MFU = 0.45
TARGET_RATIO = 1.0 / 1.2  # within 1.2x of A100 -> parity at vs_baseline == 1.0


def train_step_flops() -> float:
    """6 * matmul_params * tokens (fwd 2PT + bwd 4PT) + attention
    score/context FLOPs, per sample. The vocab embedding is a gather (not a
    matmul) on any hardware, so it is excluded — the same exclusion applies
    to the A100 anchor, keeping the comparison fair."""
    ffn = 2 * HIDDEN * 4 * HIDDEN
    attn_proj = 4 * HIDDEN * HIDDEN
    params = LAYERS * (ffn + attn_proj)
    matmul = 6.0 * params * SEQ
    attn_core = LAYERS * 6.0 * 2.0 * SEQ * SEQ * HIDDEN
    return matmul + attn_core


def _build_model(use_flash):
    import flexflow_tpu as ff
    from flexflow_tpu.models import TransformerConfig, build_bert_encoder

    config = ff.FFConfig()
    config.num_devices = 1
    config.batch_size = BATCH

    model = ff.FFModel(config)
    tokens = model.create_tensor([BATCH, SEQ], ff.DataType.DT_INT32)
    cfg = TransformerConfig(hidden_size=HIDDEN, embedding_size=HIDDEN,
                            num_heads=HEADS, num_layers=LAYERS,
                            sequence_length=SEQ, vocab_size=VOCAB)
    build_bert_encoder(model, tokens, cfg, use_flash=use_flash)
    # bf16 Adam moments: the TPU-native configuration for this benchmark —
    # halves the m/v share of the optimizer's HBM traffic (5.1 GB/step at
    # f32 moments, ROADMAP S3); both >=90% real-digits accuracy gates pass
    # with it (tests/test_accuracy_gate.py re-run under bf16 moments).
    # BENCH_MOMENTS=float32 restores reference-parity Adam semantics.
    import jax.numpy as jnp

    moments_env = os.environ.get("BENCH_MOMENTS", "bfloat16")
    moments_map = {"float32": None, "fp32": None, "f32": None,
                   "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16}
    if moments_env not in moments_map:
        raise ValueError(
            f"BENCH_MOMENTS={moments_env!r}: use float32 or bfloat16")
    moments = moments_map[moments_env]
    model.compile(
        optimizer=ff.AdamOptimizer(model, alpha=1e-4,
                                   moments_dtype=moments),
        loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
    )
    return model


def _run(model, iters, sync_every):
    """Returns samples/sec over `iters` timed steps (after warmup).

    Timed per sync-window with the MEDIAN window rate reported: the median
    keeps a host hiccup (GC, housekeeping) in one window out of the device
    number without cherry-picking the best window.

    Steps are dispatched through fit(steps_per_execution)'s multi-step fn:
    one jitted lax.scan of K optimizer steps per dispatch, with window i+1
    dispatched before window i is waited on, so host dispatch overlaps
    device execution (the same execution shape a user gets from
    fit(steps_per_execution=K); what K buys on the current set-up is not
    measured — ROADMAP S4). BENCH_STEPS_PER_EXEC=1 restores per-step
    dispatch."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    x = rng.randint(0, VOCAB, size=(BATCH, SEQ)).astype(np.int32)
    y = rng.randint(0, 2, size=(BATCH, SEQ, 1)).astype(np.int32)

    K = int(os.environ.get("BENCH_STEPS_PER_EXEC", 40))
    if K > 1:
        mstep = model._get_multi_step()
        name = model.input_ops[0].name
        inputs_k = {name: model.executor.shard_batch(
            np.stack([x] * K), batch_axis=1)}
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
        print(f"bench: window rates {[round(r, 1) for r in rates]}",
              file=sys.stderr)
        model.params, model.opt_state, model.state = params, opt_state, state
        return float(np.median(rates))

    step = model._train_step
    inputs = {model.input_ops[0].name: model.executor.shard_batch(x)}
    label = jnp.asarray(y)

    # warmup / compile; the rng key is hoisted out of the timed loop
    key = model._next_rng()
    params, opt_state, state = model.params, model.opt_state, model.state
    for _ in range(3):
        params, opt_state, state, mvals = step(
            params, opt_state, state, inputs, label, key
        )
    jax.block_until_ready(mvals)

    rates = []
    t0 = time.perf_counter()
    done = 0
    for i in range(iters):
        params, opt_state, state, mvals = step(
            params, opt_state, state, inputs, label, key
        )
        if (i + 1) % sync_every == 0:
            jax.block_until_ready(mvals)
            t1 = time.perf_counter()
            rates.append((i + 1 - done) * BATCH / (t1 - t0))
            t0, done = t1, i + 1
    if done < iters:
        jax.block_until_ready(mvals)
        rates.append((iters - done) * BATCH / (time.perf_counter() - t0))
    # params were donated: drop the stale references so the model object
    # doesn't pin deleted buffers
    model.params, model.opt_state, model.state = params, opt_state, state
    return float(np.median(rates))


def main():
    from flexflow_tpu.runtime.platform import (enable_compile_cache,
                                               require_tpu)
    from flexflow_tpu.search.machine_model import chip_for_device

    devices = require_tpu("bench.py")
    chip = chip_for_device(devices[0])  # raises on a kind not in the table
    enable_compile_cache()

    iters = int(os.environ.get("BENCH_ITERS", 240))
    sync_every = int(os.environ.get("BENCH_SYNC_EVERY", 10))

    # measured attention-path selection: the einsum-vs-flash crossover moved
    # between rounds as other code changed, so probe both with short runs and
    # keep the winner (reference analog: the simulator MEASURES kernels
    # rather than trusting a model, simulator.cc:489). The probe runs one
    # BENCH_STEPS_PER_EXEC window, compiling the SAME K-step scan the final
    # measurement uses — the winner's executable is reused.
    # BENCH_ATTENTION_PATH=einsum|flash skips the other probe.
    probe_iters = int(os.environ.get("BENCH_PROBE_ITERS", sync_every))
    pinned = os.environ.get("BENCH_ATTENTION_PATH", "")
    candidates = (("einsum", False), ("flash", True))
    if pinned:
        if pinned not in ("einsum", "flash"):
            raise ValueError(
                f"BENCH_ATTENTION_PATH={pinned!r}: must be 'einsum' or 'flash'")
        candidates = tuple(c for c in candidates if c[0] == pinned)
    paths = {}
    results = {}
    for name, use_flash in candidates:
        model = _build_model(use_flash)
        paths[name] = _run(model, probe_iters, sync_every=probe_iters)
        results[name] = model
    best = max(paths, key=paths.get)
    print(f"bench: attention probe {paths}, using {best}", file=sys.stderr)
    model = results.pop(best)
    results.clear()  # free the losing model's params/opt state in HBM
    samples_per_sec = _run(model, iters, sync_every)

    a100_est = A100_BF16_PEAK * A100_MFU / train_step_flops()
    vs_baseline = samples_per_sec / (a100_est * TARGET_RATIO)
    print(
        json.dumps(
            {
                "metric": "bert_base_train_throughput",
                "value": round(samples_per_sec, 2),
                "unit": "samples/sec/chip",
                "vs_baseline": round(vs_baseline, 3),
                "platform": devices[0].platform,
                "device_kind": devices[0].device_kind,
                "device_count": len(devices),
                "a100_anchor_samples_per_sec": round(a100_est, 1),
                "anchor_note": "assumed A100@45%MFU analytic anchor "
                               "(BASELINE.md publishes no reference number)",
                "mfu": round(samples_per_sec * train_step_flops()
                             / (chip.peak_bf16_tflops * 1e12), 3),
                "peak_bf16_tflops": chip.peak_bf16_tflops,
                "attention_path": best,
                "attention_probe_samples_per_sec": {
                    k: round(v, 2) for k, v in paths.items()},
            }
        )
    )


if __name__ == "__main__":
    main()
