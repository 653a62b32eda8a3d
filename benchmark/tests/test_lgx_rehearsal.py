"""Rehearsal 1 of `lgx_decode_sat` at a tiny size on the CPU (the real
runner, builder, reference, traffic file and metric files), the hand counts
of its shape functions, and its control and faults."""
import json

import numpy as np

from benchmark import harness
from benchmark.metrics import swa_moe_shapes as shapes
from benchmark.runners import serve_continuous_bf16 as runner
from benchmark.tests.tiny_lgx import tiny_lgx_context


def test_cell_runs_and_is_correct_and_reports_its_ring():
    ctx = tiny_lgx_context(trace=True)
    rec = harness.module_of("runners", ctx.config["runner"]).run(ctx)
    line = harness.result_line(ctx, rec)
    json.dumps(line)
    assert list(line)[-1] == "checks" and rec.correct, rec.checks
    assert rec.counters["window_compiles"] == 0
    assert rec.counters["moe_dropped_assignments"] == 0
    # 4 slots x top-4 over 16 experts, all held: ~1 token an expert a step
    assert 0.5 < rec.counters["moe_local_tokens_per_expert"] < 1.5
    assert {"moe_local_tokens_per_expert", "decode_iter_ms_p50",
            "itl_p50_ms_sat", "setup_compile_s",
            "kv_ring_bytes_per_slot"} <= set(line["metrics"])
    # 6 window layers x 8 rows x (K + V) x 32 bf16 values
    assert line["metrics"]["kv_ring_bytes_per_slot"]["value"] == 6 * 8 * 128
    # a CPU has no device trace: no time, roofline or MFU under its name
    assert not any(k.endswith("_roofline") or "mfu" in k or "ms_per_iter" in k
                   for k in line["metrics"]
                   if k.startswith(("moe", "swa", "full", "lgx")))
    assert rec.notes["tokens_compared"] > 20


def test_shape_functions_by_hand():
    cfg = harness.load_config("laguna_xs2_1chip")
    ls = shapes.layers(cfg)
    assert [(l["kind"][0], l["heads"], l["mlp"]) for l in ls] == [
        ("f", 48, "dense"), ("s", 64, "sparse"), ("s", 64, "sparse"),
        ("s", 64, "sparse"), ("f", 48, "sparse")]
    # W_q + W_o 2 x 2048 x 48 x 128, W_k + W_v 2 x 2048 x 1024, W_g 2048 x 48
    assert shapes.attention_params(cfg, 48) == 25_165_824 + 4_194_304 + 98_304
    assert shapes.attention_params(cfg, 64) == 33_554_432 + 4_194_304 + 131_072
    assert shapes.expert_params(cfg) == 3 * 2048 * 512 == 3_145_728
    assert shapes.mlp_params_per_token(cfg, ls[0]) == 3 * 2048 * 8192
    assert shapes.mlp_params_per_token(cfg, ls[1]) == 2048 * 256 + 3_145_728
    # one decoded token at position 4,999 (5,000 rows filled): the 2 full
    # layers read 5,000 rows of K and V, 2 x 1,024 bf16 values, and do
    # 4 x 48 x 128 operations a row; the 3 window layers 512 rows at 64
    work = {"decode_attended_rows": 5000.0, "decode_window_rows": 512.0}
    assert shapes.full_decode(cfg, work) == (5000 * 2 * 4 * 48 * 128,
                                             5000 * 2 * 4096)
    assert shapes.swa_decode(cfg, work) == (512 * 3 * 4 * 64 * 128,
                                            512 * 3 * 4096)
    flops, nbytes = shapes.moe_experts(cfg, {"moe_local_assignments": 100,
                                             "moe_experts_hit": 7})
    assert flops == 100 * 2 * 3_145_728 and nbytes == 7 * 3_145_728 * 2
    # the window layers' rows follow from the runner's counts where every
    # prompt is at least a window long, and not otherwise
    tr = {"prompt": {"min": 1024}}
    got = shapes.window_rows(cfg, tr, {"decode_tokens": 10, "prompt_tokens":
                                       3000, "prefill_requests": 2})
    assert got == {"decode_window_rows": 5120.0,
                   "prefill_window_rows": 2 * 512 * 513 / 2 + (3000 - 1024) * 512}
    assert shapes.window_rows(cfg, {"prompt": {"min": 64}}, {}) == {}
    # one decoded token: every layer's weights, 4 x 8 routed experts (the
    # counters), the head; nothing attended
    dense = (2 * shapes.attention_params(cfg, 48)
             + 3 * shapes.attention_params(cfg, 64) + 3 * 2048 * 8192
             + 4 * (2048 * 256 + 3_145_728))
    zero = {"prompt_tokens": 0, "decode_tokens": 0, "prefill_requests": 0,
            "moe_local_assignments": 0, "decode_attended_rows": 0,
            "prefill_attended_rows": 0, "decode_window_rows": 0,
            "prefill_window_rows": 0}
    assert shapes.serve_forward_flops(
        cfg, {**zero, "decode_tokens": 1, "moe_local_assignments": 32}) == 2 * (
            dense + 32 * 3_145_728 + 2048 * 100_352)
    # a request of 1,000 prompt tokens: the head and the last layer's MLP
    # (router, shared, 8 experts) once, the rest a token
    last = 2048 * 256 + 3_145_728 + 8 * 3_145_728
    assert shapes.serve_forward_flops(
        cfg, {**zero, "prompt_tokens": 1000, "prefill_requests": 1}) == 2 * (
            1000 * (dense + 32 * 3_145_728 - last) + last + 2048 * 100_352)


def test_control_and_faults_read_worse_than_the_configurations_precision():
    """The reference one precision below the configuration's (fp8 operands)
    and the two serving faults (window layers attending every row; a
    reused slot's stale ring rows unmasked), each put in the program's
    place on seeded random text, read worse than the reference in the
    configuration's own precision (bfloat16); the float32 reference in its
    own place reads 0 and is `correct`. (The limits themselves are set at
    the cell's size: PERF.md section 2.)"""
    ctx = tiny_lgx_context(vocab_size=4096)
    cfg = {**ctx.config, "deployment": {**ctx.config["deployment"],
                                        **ctx.sizes}}
    builder = harness.module_of("configs", cfg["builder"])
    ref = harness.module_of("reference", cfg["reference"])
    rng = np.random.default_rng(ctx.seed)
    # short prompts: the stale rows of a reused ring show at young positions
    prompts = [rng.integers(0, 4096, 3, dtype=np.int32) for _ in range(8)]
    served = [rng.integers(0, 4096, 40, dtype=np.int32) for _ in range(8)]
    names = [cfg["control_precision"], *cfg["more_controls"]]
    assert names == ["fp8", "fault_no_window", "fault_stale_ring"]
    got = ref.served_gaps(lambda g: builder.make_group(cfg, ctx.seed, g), cfg,
                          prompts, served, pad_to=ref.pad_length(64, 64),
                          controls=["float32", "bfloat16", *names])
    numbers = lambda name: runner.gap_numbers(got[name], got["margin"],
                                              {"router_margin_min": 0.0})
    exact, _ = runner.serve_checks(numbers("float32"), cfg["checks"])
    assert exact["served_logit_gap_max"]["value"] == 0.0
    assert harness.Record({}, 1, 0, exact, 0).correct, exact
    own = numbers("bfloat16")
    for name in names:
        worse = numbers(name)
        assert worse["served_logit_gap_mean"] >= \
            2.0 * own["served_logit_gap_mean"], name
