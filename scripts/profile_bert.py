"""Ablation profile of the BENCH BERT step on the local chip.

Answers "where does the non-MXU time go" with measured ablations rather
than guesses:

  full          the exact bench.py step (einsum attention auto-policy)
  full-flash    same step, Pallas flash attention forced on
  fwd           forward pass only (inference mode jit)
  grad          forward+backward (no optimizer update)
  noattn        full step with num_heads-proj-only attention removed is not
                expressible; instead `seq128` shrinks the attention core
                (seq 128 keeps matmul params identical, attn FLOPs /16)

Each ablation prints samples/sec and derived ms/step; the final JSON block
is what a PERF.md "Where the time goes" entry cites. One process holds the
chip; every timed window ends in jax.block_until_ready.

Usage: python scripts/profile_bert.py [--trace /tmp/xprof]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

BATCH = int(os.environ.get("BENCH_BATCH", 8))
SEQ = int(os.environ.get("BENCH_SEQ", 512))
HIDDEN = int(os.environ.get("BENCH_HIDDEN", 1024))
LAYERS = int(os.environ.get("BENCH_LAYERS", 12))
HEADS = int(os.environ.get("BENCH_HEADS", 16))
VOCAB = int(os.environ.get("BENCH_VOCAB", 30522))
ITERS = int(os.environ.get("BENCH_ITERS", 20))


def build(seq=SEQ, use_flash=None, batch=BATCH):
    # pin the attention path the same way bench.py does, so the traced /
    # ablated step is the same program the bench measures
    if use_flash is None:
        pinned = os.environ.get("BENCH_ATTENTION_PATH", "")
        if pinned:
            if pinned not in ("einsum", "flash"):
                raise ValueError(
                    f"BENCH_ATTENTION_PATH={pinned!r}: must be 'einsum' or "
                    "'flash'")
            use_flash = pinned == "flash"
    import flexflow_tpu as ff
    from flexflow_tpu.models import TransformerConfig

    config = ff.FFConfig()
    config.num_devices = 1
    config.batch_size = batch
    model = ff.FFModel(config)
    tokens = model.create_tensor([batch, seq], ff.DataType.DT_INT32)
    cfg = TransformerConfig(hidden_size=HIDDEN, embedding_size=HIDDEN,
                            num_heads=HEADS, num_layers=LAYERS,
                            sequence_length=seq, vocab_size=VOCAB)
    t = model.embedding(tokens, cfg.vocab_size, cfg.hidden_size,
                        ff.AggrMode.AGGR_MODE_NONE, name="tok_emb")
    from flexflow_tpu.ffconst import ActiMode
    for i in range(cfg.num_layers):
        attn = model.multihead_attention(
            t, t, t, cfg.hidden_size, cfg.num_heads, use_flash=use_flash,
            name=f"layer{i}_attn")
        t = model.layer_norm(model.add(t, attn), [-1], name=f"layer{i}_ln1")
        h = model.dense(t, cfg.hidden_size * 4, ActiMode.AC_MODE_GELU,
                        name=f"layer{i}_ff1")
        h = model.dense(h, cfg.hidden_size, name=f"layer{i}_ff2")
        t = model.layer_norm(model.add(t, h), [-1], name=f"layer{i}_ln2")
    t = model.dense(t, 2, name="cls")
    out = model.softmax(t)
    # same Adam-moments dtype policy as bench.py so the breakdown decomposes
    # the same step the bench measures (BENCH_MOMENTS=float32 for reference
    # semantics)
    import jax.numpy as jnp
    moments = {"float32": None, "fp32": None, "f32": None}.get(
        os.environ.get("BENCH_MOMENTS", "bfloat16"), jnp.bfloat16)
    model.compile(optimizer=ff.AdamOptimizer(model, alpha=1e-4,
                                             moments_dtype=moments),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[])
    return model, out


def timeit(fn, sync, iters=ITERS):
    fn()
    sync()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    sync()
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default="")
    ap.add_argument("--variants", default="full,full-flash,grad,fwd,seq128")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    results = {}
    variants = args.variants.split(",")

    rng = np.random.RandomState(0)

    def data(batch=BATCH, seq=SEQ):
        x = rng.randint(0, VOCAB, size=(batch, seq)).astype(np.int32)
        y = rng.randint(0, 2, size=(batch, seq, 1)).astype(np.int32)
        return x, jnp.asarray(y)

    def run_full(use_flash=None, seq=SEQ, tag="full", batch=BATCH):
        model, _ = build(seq=seq, use_flash=use_flash, batch=batch)
        x, label = data(batch=batch, seq=seq)
        inputs = {model.input_ops[0].name: model.executor.shard_batch(x)}
        key = model._next_rng()
        holder = [model.params, model.opt_state, model.state, None]

        def step():
            holder[0], holder[1], holder[2], holder[3] = model._train_step(
                holder[0], holder[1], holder[2], inputs, label, key)

        def sync():
            jax.block_until_ready(holder[3])

        dt = timeit(step, sync)
        results[tag] = {"ms": round(dt * 1e3, 2),
                        "samples_per_sec": round(batch / dt, 1)}
        print(tag, results[tag], flush=True)
        # the jitted step donates its (params, opt_state, state) arguments —
        # re-point the model at the live output buffers so later variants
        # (trace/grad/fwd) don't touch donated arrays
        model.params, model.opt_state, model.state = holder[:3]
        return model, inputs, label, key

    if "full" in variants:
        model, inputs, label, key = run_full(tag="full")
        if args.trace:
            with jax.profiler.trace(args.trace):
                p, o, s = model.params, model.opt_state, model.state
                for _ in range(3):
                    p, o, s, mv = model._train_step(p, o, s, inputs, label, key)
                jax.block_until_ready(mv)
            model.params, model.opt_state, model.state = p, o, s  # donated
            print("trace written to", args.trace, flush=True)

        if "grad" in variants:
            gstep = model._grad_step  # built at compile()
            holder = [None]

            def gfn():
                holder[0] = gstep(model.params, model.state, inputs, label, key)

            def gsync():
                jax.block_until_ready(holder[0])

            dt = timeit(gfn, gsync)
            results["grad"] = {"ms": round(dt * 1e3, 2)}
            print("grad", results["grad"], flush=True)

        if "fwd" in variants:
            holder = [None]

            fstep = model.executor.build_forward(model.final_tensor)

            def ffn():
                holder[0] = fstep(model.params, model.state, inputs, key)

            def fsync():
                jax.block_until_ready(holder[0])

            dt = timeit(ffn, fsync)
            results["fwd"] = {"ms": round(dt * 1e3, 2)}
            print("fwd", results["fwd"], flush=True)

    if "full-flash" in variants:
        run_full(use_flash=True, tag="full-flash")
    if "seq128" in variants:
        run_full(seq=128, tag="seq128")
    if "batch32" in variants:
        run_full(tag="batch32", batch=32)

    # derived breakdown
    if "full" in results and "grad" in results and "fwd" in results:
        full, grad, fwd = (results[k]["ms"] for k in ("full", "grad", "fwd"))
        results["derived"] = {
            "optimizer+metrics_ms": round(full - grad, 2),
            "backward_ms": round(grad - fwd, 2),
            "forward_ms": round(fwd, 2),
        }
    print(json.dumps(results))


if __name__ == "__main__":
    main()
