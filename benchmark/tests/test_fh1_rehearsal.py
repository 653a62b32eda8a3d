"""Rehearsal 1 of `fh1_decode_sat` at a tiny size on the CPU (the real
runner, builder, reference, traffic file and metric files), the hand counts
of its shape functions, and its controls and faults."""
import json

import numpy as np
import pytest

from benchmark import harness
from benchmark.metrics import hybrid_ssm_shapes as shapes
from benchmark.runners import serve_continuous_ssm as runner
from benchmark.tests.tiny_fh1 import tiny_fh1_context


def test_cell_runs_and_is_correct_and_counts_its_state_traffic():
    ctx = tiny_fh1_context(trace=True)
    rec = harness.module_of("runners", ctx.config["runner"]).run(ctx)
    line = harness.result_line(ctx, rec)
    json.dumps(line)
    assert list(line)[-1] == "checks" and rec.correct, rec.checks
    assert rec.counters["window_compiles"] == 0
    # every decode iteration stepped all 4 rows of the pool in both layers,
    # and hundreds of admissions each reset a slot
    assert rec.counters["ssm_state_rows_stepped"] == \
        4 * rec.counters["ssm_layer_steps"] > 0
    assert rec.counters["ssm_state_resets"] > 50
    assert rec.work["state_rows_per_layer_step"] == 4.0
    assert {"decode_iter_ms_p50", "itl_p50_ms_sat", "setup_compile_s",
            "window_compiles"} <= set(line["metrics"])
    # a CPU has no device trace: no time, roofline or MFU under its name
    assert not any(k.startswith(("ssm_", "gqa_", "fh1_", "decode_attn_"))
                   for k in line["metrics"])
    assert rec.notes["tokens_compared"] > 20
    assert {"decode_tokens", "decode_attended_rows", "prompt_tokens",
            "prefill_attended_rows"} <= set(rec.work)


def test_shape_functions_by_hand():
    cfg = harness.load_config("falcon_h1_34b_1chip")
    # q 5120x2560, k and v 5120x512 each, o 2560x5120
    assert shapes.attention_params(cfg) == 13_107_200 + 2 * 2_621_440 \
        + 13_107_200 == 31_457_280
    # in 5120 x (4096 + 5120 + 32), out 4096 x 5120
    assert shapes.mixer_params(cfg) == 47_349_760 + 20_971_520
    assert shapes.mlp_params(cfg) == 3 * 5120 * 21504 == 330_301_440
    assert shapes.params_per_token(cfg) == 5 * 430_080_000 + 5120 * 261120
    # the state is stored in bf16
    assert shapes.state_bytes(cfg) == 32 * 128 * 256 * 2 == 2_097_152
    # one decode step of 96 rows: 5 layers x 96 states read and written
    # once; 4 operations a state value (decay-and-add, read-out)
    flops, nbytes = shapes.ssm_state(cfg, {"state_rows_per_layer_step": 96,
                                           "decode_steps": 1})
    assert nbytes == 5 * 96 * 2 * 2_097_152 == 2_013_265_920
    assert flops == 5 * 96 * 4 * 1_048_576
    # one chunk of 512 tokens = 4 blocks of 128; per block: 2 groups x
    # 2*128*128*256 (C.B^T) + 32 heads x 2*128*128*128 (its product with x)
    # + 32 heads x 4*128*128*256 (state added, state read)
    flops, nbytes = shapes.ssm_scan(cfg, {"prefill_chunks": 1,
                                          "prefill_chunk_tokens": 512})
    assert flops == 5 * 4 * (2 * 8_388_608 + 32 * 4_194_304
                             + 32 * 16_777_216)
    # x|B|C 5120 bf16, dt 32 f32 and y 4096 f32 a token; the state in and out
    assert nbytes == 5 * (512 * (5120 * 2 + 32 * 4 + 4096 * 4)
                          + 2 * 2_097_152)
    # one token that attends 1,000 rows: K and V of 4 x 128 bf16 values a
    # row a layer; 4 x 20 x 128 operations a row a layer
    flops, nbytes = shapes.gqa_decode(cfg, {"decode_attended_rows": 1000})
    assert nbytes == 5 * 1000 * 2 * 512 * 2 and flops == 5 * 1000 * 10_240
    work = {"prompt_tokens": 0, "decode_tokens": 1, "decode_attended_rows": 0,
            "prefill_attended_rows": 0}
    assert shapes.serve_forward_flops(cfg, work) == 2 * (
        shapes.params_per_token(cfg)) + 5 * 4 * 128 * 256 * 32
    work.update(prompt_tokens=1, decode_tokens=0, prefill_attended_rows=10)
    assert shapes.serve_forward_flops(cfg, work) == 2 * (
        shapes.params_per_token(cfg)) + 5 * 4 * 128 * 256 * 32 \
        + 5 * 10 * 10_240


def test_control_and_faults_are_not_correct_at_the_cells_limits():
    """The reference computed one precision below the configuration's (fp8
    operands) and with each of two serving faults (a sequence that starts
    from a previous tenant's state; the mixer's branch left out), put in
    the program's place on seeded random text, each judged by the cell's
    own limits. The float32 reference in its own place reads 0 and the
    reference in the configuration's own precision (bfloat16) is `correct`;
    fp8 and both faults are `correct` false. A stale state decays, so the
    unreset slot shows on an answer's first tens of positions: prompts of 8
    and answers of 24 tokens here (mean gap 0.007-0.015 on three seeds for
    a limit of 0.002); at 24 + 40 tokens this tiny mixer's reading falls to
    0.0018-0.0028, on the limit. (At the cell's size, where the state is
    256 wide: 0.0076-0.090 on every seed, PERF.md section 2. The state
    ALONE in bf16, which is what the configuration states for it, moves no
    pick at this size and is read for information.)"""
    ctx = tiny_fh1_context(vocab_size=4096)
    cfg = {**ctx.config, "deployment": {**ctx.config["deployment"],
                                        **ctx.sizes}}
    builder = harness.module_of("configs", cfg["builder"])
    ref = harness.module_of("reference", cfg["reference"])
    rng = np.random.default_rng(ctx.seed)
    prompts = [rng.integers(0, 4096, 8, dtype=np.int32) for _ in range(16)]
    served = [rng.integers(0, 4096, 24, dtype=np.int32) for _ in range(16)]
    controls = [cfg["control_precision"], *cfg["more_controls"],
                *cfg["also_read"]]
    assert controls == ["fp8", "bf16_state"]
    assert cfg["faults"] == ["fault_no_reset", "fault_no_mixer"]
    names = ["float32", "bfloat16", *controls, *cfg["faults"]]
    got = ref.served_gaps(lambda g: builder.make_group(cfg, ctx.seed, g), cfg,
                          prompts, served, pad_to=ref.pad_length(32, 32),
                          controls=names)
    n = {name: runner.gap_numbers(got[name]) for name in names}
    correct = lambda name: harness.Record({}, 1, 0, runner.serve_checks(
        n[name], cfg["checks"])[0], 0).correct
    assert n["float32"]["served_logit_gap_max"] == 0.0 and correct("float32")
    assert correct("bfloat16") and correct("bf16_state")
    for name in ("fp8", "fault_no_reset", "fault_no_mixer"):
        assert not correct(name), (name, n[name])
    limit = cfg["checks"]["served_logit_gap_mean"]
    assert n["fp8"]["served_logit_gap_mean"] > 3.0 * limit
    assert n["fault_no_reset"]["served_logit_gap_mean"] > 3.0 * limit
    assert n["fault_no_mixer"]["served_off_best_share"] > 0.5


def test_gap_numbers():
    n = runner.gap_numbers([np.array([0.0, 0.5, 0.0, 0.3])], 0)
    assert n["served_logit_gap_max"] == 0.5
    assert n["served_off_best_share"] == 0.5
    assert n["served_logit_gap_mean"] == pytest.approx(0.2)
    # nothing compared is never "ok"
    assert runner.gap_numbers([], 0)["served_logit_gap_max"] == np.inf
