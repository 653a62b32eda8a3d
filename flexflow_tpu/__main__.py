"""Command-line driver: `python -m flexflow_tpu [--model NAME] [flags...]`.

reference parity: the C++ example drivers (src/runtime/cpp_driver.cc +
examples/cpp/*/) and the `flexflow_python` interpreter — one entry point
that takes the standard FFConfig flags, builds a named model from the zoo on
synthetic data, and trains it under the chosen strategy. Run a user script
instead with `python -m flexflow_tpu script.py [flags...]` (the script sees
the remaining argv, like flexflow_python).
"""
from __future__ import annotations

import runpy
import sys
import time

import numpy as np


def _synthetic(model_name, config):
    """Build (model, inputs, label) for a zoo model on synthetic data."""
    import flexflow_tpu as ff
    from flexflow_tpu import models as zoo

    b = config.batch_size
    rng = np.random.RandomState(0)
    m = ff.FFModel(config)

    if model_name in ("alexnet", "resnet50", "inception", "resnext50",
                      "cifar10_cnn", "mnist_cnn"):
        size = {"alexnet": 229, "resnet50": 224, "inception": 299,
                "resnext50": 224, "cifar10_cnn": 32, "mnist_cnn": 28}[model_name]
        chans = 1 if model_name == "mnist_cnn" else 3
        build = {"alexnet": zoo.build_alexnet, "resnet50": zoo.build_resnet50,
                 "inception": zoo.build_inception_v3,
                 "resnext50": zoo.build_resnext50,
                 "cifar10_cnn": zoo.build_cifar10_cnn,
                 "mnist_cnn": zoo.build_mnist_cnn}[model_name]
        inp = m.create_tensor([b, chans, size, size])
        build(m, inp)
        x = rng.randn(b * 4, chans, size, size).astype(np.float32)
        y = rng.randint(0, 10, size=(b * 4, 1)).astype(np.int32)
        return m, [x], y
    if model_name == "mnist_mlp":
        inp = m.create_tensor([b, 784])
        zoo.build_mnist_mlp(m, inp)
        x = rng.randn(b * 4, 784).astype(np.float32)
        y = rng.randint(0, 10, size=(b * 4, 1)).astype(np.int32)
        return m, [x], y
    if model_name == "bert":
        import os

        # FF_BERT_* env knobs shrink the OSDI'22 config so the CPU CI
        # (and the kernels job's profile run) can afford it; unset =
        # the real bert_base (bench.py's BENCH_* knobs, same idea)
        cfg = zoo.TransformerConfig(
            hidden_size=int(os.environ.get("FF_BERT_HIDDEN", 1024)),
            embedding_size=int(os.environ.get("FF_BERT_HIDDEN", 1024)),
            num_heads=int(os.environ.get("FF_BERT_HEADS", 16)),
            num_layers=int(os.environ.get("FF_BERT_LAYERS", 12)),
            sequence_length=int(os.environ.get("FF_BERT_SEQ", 512)),
            vocab_size=int(os.environ.get("FF_BERT_VOCAB", 30522)),
        )
        tokens = m.create_tensor([b, cfg.sequence_length],
                                 ff.DataType.DT_INT32)
        zoo.build_bert_encoder(m, tokens, cfg)
        x = rng.randint(0, cfg.vocab_size,
                        size=(b * 2, cfg.sequence_length)).astype(np.int32)
        y = rng.randint(0, 2, size=(b * 2, cfg.sequence_length, 1)).astype(np.int32)
        return m, [x], y
    if model_name == "mlp_unify":
        in1 = m.create_tensor([b, 4096])
        in2 = m.create_tensor([b, 4096])
        zoo.build_mlp_unify(m, in1, in2)
        xs = [rng.randn(b * 4, 4096).astype(np.float32) for _ in range(2)]
        y = rng.randint(0, 10, size=(b * 4, 1)).astype(np.int32)
        return m, xs, y
    if model_name == "moe":
        import os

        # FF_MOE_* env knobs mirror the FF_BERT_* pattern: the defaults
        # are the multipod dryrun's switch-transformer shape, shrinkable
        # for CPU CI profiling runs
        cfg = zoo.MoeTransformerConfig(
            hidden_size=int(os.environ.get("FF_MOE_HIDDEN", 512)),
            num_heads=int(os.environ.get("FF_MOE_HEADS", 8)),
            num_layers=int(os.environ.get("FF_MOE_LAYERS", 2)),
            num_experts=int(os.environ.get("FF_MOE_EXPERTS", 8)),
            top_k=int(os.environ.get("FF_MOE_TOPK", 2)),
            vocab_size=int(os.environ.get("FF_MOE_VOCAB", 1024)),
        )
        seq = int(os.environ.get("FF_MOE_SEQ", 64))
        tokens = m.create_tensor([b, seq], ff.DataType.DT_INT32)
        zoo.build_moe_transformer(m, tokens, cfg)
        x = rng.randint(0, cfg.vocab_size,
                        size=(b * 2, seq)).astype(np.int32)
        y = rng.randint(0, 2, size=(b * 2, seq, 1)).astype(np.int32)
        return m, [x], y
    raise SystemExit(
        f"unknown --model {model_name!r}; choices: alexnet resnet50 inception "
        f"resnext50 cifar10_cnn mnist_cnn mnist_mlp bert mlp_unify moe, or "
        f"pass a script path")


def main(argv=None):
    # a JAX_PLATFORMS=cpu run gets the tests' 8 virtual devices, BEFORE
    # any backend touch; a TPU run sees its chips as they are
    from .runtime.platform import cpu_mesh_from_env

    cpu_mesh_from_env()
    argv = list(sys.argv[1:] if argv is None else argv)
    # elastic drill: scripted kill-and-recover scenario on CPU host-device
    # emulation (docs/elastic.md)
    if argv and argv[0] == "elastic-drill":
        from .elastic.drill import run_drill

        raise SystemExit(run_drill(argv[1:]))
    # plan sanitizer: static diagnostic report over a zoo model's PCG plus
    # an exported strategy JSON (docs/analysis.md)
    if argv and argv[0] == "analyze":
        from .analysis.cli import run_analyze

        raise SystemExit(run_analyze(argv[1:]))
    # observability capture: train a zoo model with tracing on; emit the
    # Perfetto trace, simulator-calibration report, and metrics dump
    # (docs/observability.md)
    if argv and argv[0] == "profile":
        from .obs.cli import run_profile

        raise SystemExit(run_profile(argv[1:]))
    # post-mortem timeline: merge a tracer export, an EventLog dump, and
    # a flight-recorder bundle into ONE Perfetto trace on a shared clock
    # (docs/observability.md "Request tracing & post-mortem timelines")
    if argv and argv[0] == "timeline":
        from .obs.timeline import run_timeline

        raise SystemExit(run_timeline(argv[1:]))
    # collective microbench: sweep the explicit reduction-strategy
    # lowerings x message sizes on the live mesh; emits the calibration
    # rows the per-tier link-constant refit consumes (docs/machine.md
    # "Lowering", docs/observability.md)
    if argv and argv[0] == "collective-bench":
        from .obs.collective_bench import run_collective_bench

        raise SystemExit(run_collective_bench(argv[1:]))
    # serving load test: continuous batching vs the lockstep generation
    # path on a mixed-length workload (docs/serving.md)
    if argv and argv[0] == "serve-bench":
        from .serving.sched.bench import run_bench

        raise SystemExit(run_bench(argv[1:]))
    # script mode: first non-flag arg ending in .py
    script = next((a for a in argv if a.endswith(".py")), None)
    if script is not None:
        sys.argv = [script] + [a for a in argv if a != script]
        runpy.run_path(script, run_name="__main__")
        return

    model_name = "mnist_mlp"
    if "--model" in argv:
        i = argv.index("--model")
        model_name = argv[i + 1]
        del argv[i:i + 2]
    c_spec = None
    if "--from-c-spec" in argv:  # train a model exported by the C API
        i = argv.index("--from-c-spec")
        if i + 1 >= len(argv):
            raise SystemExit("missing value for --from-c-spec")
        c_spec = argv[i + 1]
        del argv[i:i + 2]

    import flexflow_tpu as ff

    config = ff.FFConfig()
    rest = config.parse_args(argv)
    if rest:
        print(f"warning: unrecognized flags {rest}", file=sys.stderr)

    if c_spec is not None:
        from .ffconst import OpType
        from .native.c_model import model_from_spec

        # explicit CLI batch size wins over the spec's
        cli_batch = (config.batch_size
                     if "-b" in argv or "--batch-size" in argv else None)
        model = model_from_spec(c_spec, config=config, batch_size=cli_batch)
        model_name = c_spec
        rng = np.random.RandomState(0)
        b = model.config.batch_size
        # valid synthetic id range: the smallest embedding vocabulary
        vocab = min((op.params["num_entries"] for op in model.ops
                     if op.op_type == OpType.EMBEDDING), default=100)
        xs = []
        for op in model.input_ops:
            dims = (b * 4,) + op.outputs[0].dims[1:]
            if op.outputs[0].dtype.value.startswith("int"):
                xs.append(rng.randint(0, vocab, size=dims).astype(np.int32))
            else:
                xs.append(rng.randn(*dims).astype(np.float32))
        out_dim = model.ops[-1].outputs[0].dims[-1]
        y = rng.randint(0, out_dim, size=(b * 4, 1)).astype(np.int32)
    else:
        model, xs, y = _synthetic(model_name, config)
    model.compile(
        optimizer=ff.SGDOptimizer(model, lr=config.learning_rate),
        loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[ff.MetricsType.METRICS_ACCURACY],
    )
    n = y.shape[0]
    t0 = time.time()
    hist = model.fit(xs, y, batch_size=config.batch_size,
                     epochs=config.epochs,
                     steps_per_execution=config.steps_per_execution)
    dt = time.time() - t0
    thru = n * config.epochs / max(dt, 1e-9)
    print(f"[{model_name}] {config.epochs} epoch(s) in {dt:.2f}s "
          f"({thru:.1f} samples/s), final metrics: "
          + ", ".join(f"{k}={v:.4f}" for k, v in hist[-1].items()
                      if isinstance(v, float)))


if __name__ == "__main__":
    main()
