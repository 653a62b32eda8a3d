"""Rehearsal 1 of `ms4_decode_sat` at a tiny size on the CPU (the real
runner, builder, reference, traffic file and metric files), the hand counts
of its shape functions, and its controls."""
import json

import numpy as np
import pytest

from benchmark import harness
from benchmark.metrics import mla_moe_shapes as shapes
from benchmark.runners import serve_continuous_bf16 as runner
from benchmark.tests.tiny_ms4 import tiny_ms4_context


def test_cell_runs_and_is_correct_and_drops_nothing():
    ctx = tiny_ms4_context(trace=True)
    rec = harness.module_of("runners", ctx.config["runner"]).run(ctx)
    line = harness.result_line(ctx, rec)
    json.dumps(line)
    assert list(line)[-1] == "checks" and rec.correct, rec.checks
    assert rec.counters["window_compiles"] == 0
    # the dropless layer's dropped counter reads 0 by construction
    assert rec.counters["moe_dropped_assignments"] == 0
    # 4 slots x top-2 over 8 experts, 4 of them held: ~1 token an expert
    assert 0.5 < rec.counters["moe_local_tokens_per_expert"] < 1.5
    assert {"moe_local_tokens_per_expert", "decode_iter_ms_p50",
            "itl_p50_ms_sat", "setup_compile_s"} <= set(line["metrics"])
    # a CPU has no device trace: no time, roofline or MFU under its name
    assert not any(k.endswith("_roofline") or "mfu" in k or "ms_per_iter" in k
                   for k in line["metrics"] if k.startswith(("moe", "mla", "ms4")))
    assert rec.notes["tokens_compared"] > 20
    assert {"moe_local_assignments", "moe_experts_hit", "decode_tokens",
            "decode_attended_rows"} <= set(rec.work)


def test_shape_functions_by_hand():
    cfg = harness.load_config("mistral_small4_ep4")
    # 4096*1024 + 1024*32*128 + 4096*320 + 256*32*192 + 32*128*4096
    assert shapes.attention_params(cfg) == (
        4_194_304 + 4_194_304 + 1_310_720 + 1_572_864 + 16_777_216)
    assert shapes.expert_params(cfg) == 3 * 4096 * 2048 == 25_165_824
    assert shapes.dense_params_per_token(cfg) == 6 * (
        28_049_408 + 25_165_824 + 4096 * 128) + 4096 * 32768
    # 100 assignments on 7 experts hit: 2 ops a weight; 3 bf16 matrices a hit
    flops, nbytes = shapes.moe_experts(cfg, {"moe_local_assignments": 100,
                                             "moe_experts_hit": 7})
    assert flops == 100 * 2 * 25_165_824 and nbytes == 7 * 25_165_824 * 2
    # one step, one token that attends 1,000 rows: 640 B and 36,864 ops a
    # row a layer; W_kvb (256 x 32 x 192) once a step and through it twice
    # (query in, context out)
    flops, nbytes = shapes.mla_decode(cfg, {"decode_attended_rows": 1000,
                                            "decode_tokens": 1,
                                            "decode_steps": 1})
    kvb = 256 * 32 * 192
    assert nbytes == 6 * (1000 * 640 + kvb * 2)
    assert flops == 6 * (1000 * 36_864 + 2 * kvb)
    work = {"prompt_tokens": 0, "decode_tokens": 1, "moe_local_assignments": 6,
            "decode_attended_rows": 0, "prefill_attended_rows": 0}
    assert shapes.serve_forward_flops(cfg, work) == 2 * (
        shapes.dense_params_per_token(cfg) + 6 * 25_165_824)
    # a prompt token: the uniform router's share, 4 x 32/128 = 1 a layer
    work.update(prompt_tokens=1, decode_tokens=0, moe_local_assignments=0,
                prefill_attended_rows=10)
    assert shapes.serve_forward_flops(cfg, work) == 2 * (
        shapes.dense_params_per_token(cfg) + 6 * 25_165_824) \
        + 6 * 2 * 32 * 10 * 256


def test_numbers_and_the_fragile_rule():
    gaps = [np.array([0.0, 0.5, 0.0, 0.2])]
    margins = [np.array([0.3, 0.001, 0.3, 0.3])]
    n = runner.gap_numbers(gaps, margins, {"router_margin_min": 0.01}, 0)
    assert n["served_logit_gap_max"] == 0.5
    assert n["served_off_best_share"] == 0.5
    assert n["router_fragile_share"] == 0.25
    assert n["served_off_best_share_firm"] == pytest.approx(1 / 3)
    assert n["served_logit_gap_mean_firm"] == pytest.approx(0.2 / 3)
    checks, notes = runner.serve_checks(n, {"served_logit_gap_max": 1.0,
                                            "unanswered": 0})
    assert set(checks) == {"served_logit_gap_max", "unanswered"}
    assert "served_off_best_share" in notes
    # nothing compared is never "ok"
    assert runner.gap_numbers([], [], {}, 0)["served_logit_gap_max"] == np.inf


def test_controls_read_worse_than_the_configurations_precision():
    """The reference computed one precision below the configuration's (fp8
    operands; the latent cache alone in fp8), put in the program's place on
    seeded random text, reads clearly worse than the reference in the
    configuration's own precision (bfloat16) does, on the numbers the cell
    limits; the float32 reference in its own place reads 0 and is
    `correct`. (The limits themselves are set at the cell's size, where the
    logits spread 8x wider: PERF.md section 2 has both controls' readings
    there, `correct` false on every seed.)"""
    ctx = tiny_ms4_context(vocab_size=4096)
    cfg = {**ctx.config, "deployment": {**ctx.config["deployment"],
                                        **ctx.sizes}}
    builder = harness.module_of("configs", cfg["builder"])
    ref = harness.module_of("reference", cfg["reference"])
    rng = np.random.default_rng(ctx.seed)
    prompts = [rng.integers(0, 4096, 24, dtype=np.int32) for _ in range(8)]
    served = [rng.integers(0, 4096, 40, dtype=np.int32) for _ in range(8)]
    controls = [cfg["control_precision"], *cfg["more_controls"]]
    assert controls == ["fp8", "fp8_kv"]
    got = ref.served_gaps(lambda g: builder.make_group(cfg, ctx.seed, g), cfg,
                          prompts, served, pad_to=ref.pad_length(64, 64),
                          controls=["float32", "bfloat16", *controls])
    numbers = lambda name: runner.gap_numbers(got[name], got["margin"],
                                              {"router_margin_min": 0.0})
    exact, _ = runner.serve_checks(numbers("float32"), cfg["checks"])
    assert exact["served_logit_gap_max"]["value"] == 0.0
    assert harness.Record({}, 1, 0, exact, 0).correct, exact
    own, fp8, fp8_kv = (numbers(n) for n in ("bfloat16", *controls))
    assert fp8["served_logit_gap_mean"] >= 3.0 * own["served_logit_gap_mean"]
    assert fp8["served_off_best_share"] >= 1.5 * own["served_off_best_share"]
    # the cache alone in fp8, float32 elsewhere: at two layers and 64 rows
    # it moves the logits by far less (its readings are the chip's), but
    # the comparison sees it
    assert 0.0 < fp8_kv["served_logit_gap_max"] <= fp8["served_logit_gap_max"]
