"""Rehearsal 1 — every cell's runner end to end at a tiny size on the CPU,
and the open loop through an example cell — and the broken-path tests:
the same run with the timed path broken underneath has to come out with
`correct` false, once for each fault the cell can have."""
import json

import numpy as np
import pytest

from benchmark import harness
from benchmark.tests.tiny import tiny_context


def _run(ctx):
    runner = harness.module_of("runners", ctx.config["runner"])
    rec = runner.run(ctx)
    line = harness.result_line(ctx, rec)
    json.dumps(line)
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    return rec, line


def test_train_cell_runs_and_is_correct():
    ctx = tiny_context("bert_train_1chip")
    rec, line = _run(ctx)
    assert rec.correct, rec.checks
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert rec.counters["window_compiles"] == 0
    assert line["device"]["count"] == ctx.chips
    assert rec.notes["leaves"] >= rec.notes["leaves_compared"] > 0


@pytest.mark.parametrize("workload", ["lm_decode_sat", "example_open_cell"])
def test_serve_cell_runs_and_is_correct(workload):
    ctx = tiny_context(workload, trace=(workload == "example_open_cell"))
    rec, line = _run(ctx)
    assert rec.correct, rec.checks
    assert rec.counters["window_compiles"] == 0
    assert rec.notes["tokens_compared"] > 20
    if workload == "lm_decode_sat":
        assert set(line["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
        assert line["metrics"]["serve_out_tokens_per_s"]["value"] > 0
    else:   # traced: per-layer names, and nothing a CPU cannot read
        assert {"queue_wait_p90_ms", "loadgen_late_p99_ms",
                "prefill_chunk_ms_p50", "decode_iter_ms_p50_open",
                "setup_compile_s"} <= set(line["metrics"])
        assert rec.end_to_end["ttft_p90_ms"] > 0 < rec.end_to_end["itl_p95_ms"]
        assert not any(k.endswith("_roofline") or "mfu" in k
                       for k in line["metrics"])


def _break_multi_step(monkeypatch, how: str):
    from flexflow_tpu.model import FFModel

    real = FFModel._get_multi_step

    def broken(self):
        step = real(self)

        def wrapper(params, opt_state, state, inputs_k, label_k, rng_k):
            import jax
            import jax.numpy as jnp

            if how == "unchanged":
                keep = jax.tree.map(jnp.copy, (params, opt_state, state))
                _p, _o, _s, mvals = step(params, opt_state, state, inputs_k,
                                         label_k, rng_k)
                return (*keep, mvals)
            # half of the batch left out, the mean taken over the rest
            def part(a):
                n = a.shape[1] // 2
                return jnp.concatenate([a[:, :n]] * 2, axis=1)
            return step(params, opt_state, state,
                        {k: part(v) for k, v in inputs_k.items()},
                        part(label_k), rng_k)
        return wrapper

    monkeypatch.setattr(FFModel, "_get_multi_step", broken)


@pytest.mark.parametrize("how", ["unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(monkeypatch, how):
    _break_multi_step(monkeypatch, how)
    rec, _ = _run(tiny_context("bert_train_1chip"))
    assert not rec.correct, rec.checks
    if how == "unchanged":
        assert rec.checks["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_altered_token_is_not_correct(monkeypatch):
    from flexflow_tpu.serving.sched.continuous import GenRequest

    real = GenRequest._emit

    def altered(self, tok):
        if len(self.tokens) == 2:          # the third token of every answer
            tok = (int(tok) + 1) % 97
        return real(self, tok)

    monkeypatch.setattr(GenRequest, "_emit", altered)
    rec, _ = _run(tiny_context("lm_decode_sat"))
    assert not rec.correct, rec.checks
    assert rec.notes["tokens_off_best"] > 0


def test_unanswered_request_is_not_correct():
    from benchmark import check

    checks, _ = check.serve_checks([np.zeros(5)], {"served_logit_gap_max": 1.0,
                                                   "unanswered": 0}, 1)
    assert checks["unanswered"]["value"] > checks["unanswered"]["limit"]
    rec = harness.Record({}, 1, 1, checks, 0)
    assert not rec.correct
    assert not harness.Record({}, 0, 0, {}, 0).correct   # nothing compared
