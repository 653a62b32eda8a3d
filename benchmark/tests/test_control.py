"""The controls, at a size a test run can hold: the plain reference computed
one precision below the configuration's, put in the program's place, has to
read clearly worse than the program does, and `Record.correct` at the cell's
own limits (the configuration's `checks`) has to come out false for it — the
comparison can fail. (The readings at the cells' own sizes, on the chip, are
in PERF.md section 2; `readings.py` makes them, and prints the same
`correct`.)"""
import numpy as np

from benchmark import check, harness, traffic as traffic_mod
from benchmark.runners import serve_continuous as sc
from benchmark.runners import train_fit
from benchmark.tests.tiny import tiny_context


def test_training_control_reads_worse_than_the_program():
    ctx = tiny_context("bert_train_1chip")
    cfg = {**ctx.config, "deployment": {**ctx.config["deployment"],
                                        **ctx.sizes}}
    builder = harness.module_of("configs", cfg["builder"])
    K, bs, seq = 2, 4, 32
    model = builder.build_program(cfg, ctx.traffic, 1, ctx.seed)
    x, y = traffic_mod.make_train_rows(ctx.traffic, ctx.seed, 97, K * bs, seq, 2)
    model.fit([x], y, batch_size=bs, epochs=1, steps_per_execution=K)
    prog = train_fit.program_readings(model, builder, cfg, ctx.seed)
    xs, ys = x.reshape(K, bs, seq), y[:, :, 0].reshape(K, bs, seq)
    ref = train_fit.reference_readings(cfg, builder, ctx.seed, xs, ys, K)
    ctl = train_fit.reference_readings(cfg, builder, ctx.seed, xs, ys, K,
                                       prec=cfg["control_precision"])
    assert cfg["control_precision"] == "bf16_weights"
    limits = cfg["checks"]
    prog["moment_rel_diffs"] = check.rel_diffs(prog.pop("moment_host"),
                                               ref["moments"])
    ctl["moment_rel_diffs"] = check.rel_diffs(ctl["moments"], ref["moments"])
    lower, _ = check.train_checks(prog, ref, limits)
    upper, _ = check.train_checks(ctl, ref, limits)
    print("lower", lower, "upper", upper)
    # weights kept in bf16 lose the whole update to rounding
    assert upper["update_norm_gap"]["value"] >= max(
        1.0, 3.0 * lower["update_norm_gap"]["value"]), (lower, upper)
    assert harness.Record({}, 1, 0, lower, 0).correct, lower
    assert not harness.Record({}, 1, 0, upper, 0).correct, upper


def test_serving_control_reads_worse_than_the_program():
    ctx = tiny_context("lm_decode_sat")
    # a vocabulary wide enough for near-ties: that is where a lower
    # precision puts another token first
    cfg = {**ctx.config, "vocab_size": 4096,
           "deployment": {**ctx.config["deployment"], **ctx.sizes}}
    builder = harness.module_of("configs", cfg["builder"])
    model, batcher = builder.build_program(cfg, ctx.traffic, 1, ctx.seed)
    reqs = traffic_mod.make_requests(ctx.traffic, ctx.seed, 4096, 32, 0)
    with batcher:
        handles = [batcher.submit(r.prompt, r.max_new_tokens) for r in reqs]
        for h in handles:
            h.result(timeout=120.0)
    served = sc.reference_gaps(cfg, builder, ctx.seed, handles)
    assert cfg["control_precision"] == "bfloat16"
    lower, _ = check.serve_checks(served, cfg["checks"])
    assert harness.Record({}, 1, 0, lower, 0).correct, lower
    lo = lower["served_logit_gap_max"]["value"]
    assert lo <= 1e-4            # float32 on the CPU serves the best token
    # the control at the same positions of the same prompts and tokens; at
    # this width greedy text from random weights has wide margins, so it is
    # also read on seeded random text, where near-ties are common
    control = sc.reference_gaps(cfg, builder, ctx.seed, handles,
                                control=cfg["control_precision"])
    rng = np.random.default_rng(ctx.seed)

    class Text:
        def __init__(self):
            self.prompt = rng.integers(0, 4096, 24, dtype=np.int32)
            self.tokens = list(rng.integers(0, 4096, 40, dtype=np.int32))

    texts = [Text() for _ in range(8)]
    control += sc.reference_gaps(cfg, builder, ctx.seed, texts,
                                 control=cfg["control_precision"])
    upper, _ = check.serve_checks(control, cfg["checks"])
    hi = upper["served_logit_gap_max"]["value"]
    assert hi > 1e-5 and hi >= 3.0 * max(lo, 1e-6), (lo, hi)
    assert not harness.Record({}, 1, 0, upper, 0).correct, upper
    # the second control, the cache alone in bf16, moves the logits by far
    # less (at this width 1e-4 beside 6e-3): its readings are the chip's
    kv = sc.reference_gaps(cfg, builder, ctx.seed, texts, control="bf16_kv")
    assert max(float(np.max(g)) for g in kv) <= hi
