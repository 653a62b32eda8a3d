"""The trace reduction, on a small trace recorded on a v5e chip
(data/probe_v5e.xplane.pb: five runs of a jitted step with a matmul under
`linear:ff1`, an attention-like block under
`multihead_attention:layer0_attn`, and a cache copy) and on hand-made
intervals."""
import os

import pytest

from benchmark import xplane

TRACE = os.path.join(os.path.dirname(__file__), "data", "probe_v5e.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return xplane.summarize(xplane.read_xspace(TRACE))


def test_planes_and_programs(summary):
    assert summary.chips == 1
    assert summary.module_runs("jit_step") == 5
    assert len(summary.program_ids("jit_step")) == 1


def test_busy_and_idle(summary):
    # five runs of ~188 us in a ~51 ms window with sleeps between: mostly idle
    assert 0.0008 < summary.busy_s < 0.0011
    assert 0.04 < summary.window_s < 0.06
    assert summary.busy_s < summary.window_s
    gaps = xplane.idle_gaps_by_host(summary)
    assert gaps and sum(s for _n, s in gaps) == pytest.approx(
        summary.window_s - summary.busy_s, rel=0.02)


def test_time_by_scope_and_category(summary):
    by_scope = summary.by_key(lambda o: xplane.scope_key(o.scope))
    assert by_scope["linear:ffN"] == pytest.approx(5 * 90.85e-6, rel=0.02)
    assert by_scope["multihead_attention:layerN_attn"] > 5 * 50e-6
    by_cat = summary.by_key(lambda o: o.category)
    assert by_cat["convolution fusion"] == pytest.approx(
        5 * (90.85e-6 + 44.24e-6), rel=0.02)
    # copies: chosen by category / no counted operation, not by name
    copy_s = summary.per_chip(xplane.is_copy)
    assert copy_s == pytest.approx(by_cat["copy-done"] + by_cat["copy-start"]
                                   + by_cat["async-start"]
                                   + by_cat["async-done"]
                                   + by_cat["dynamic-update-slice"]
                                   + sum(o.dur_ps for o in summary.ops[0]
                                         if o.category == "loop fusion"
                                         and o.flops == 0.0) / 1e12,
                                   rel=1e-6)
    assert not any(xplane.is_copy(o) for o in summary.ops[0]
                   if o.category == "convolution fusion")


def test_top_ops_name_the_scopes(summary):
    top = dict(xplane.top_device_ops(summary, 4))
    assert "linear:ffN" in top and "multihead_attention:layerN_attn" in top


def test_scope_key_folds_layers_and_reads_backward_ops():
    assert xplane.scope_key(
        "jit(multi_step)/while/body/transpose(jvp(linear:layer11_ff1))/dot_general:"
    ) == "linear:layerN_ffN"
    assert xplane.scope_key("jit(step)/add:") == ""


def test_hlo_opcode():
    assert xplane.hlo_opcode(
        "%fusion.7 = f32[2048,4096]{1,0} fusion(f32[1024,4096] %a), kind=kOutput"
    ) == "fusion"
    assert xplane.hlo_opcode(
        "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={}"
    ) == "all-reduce"
    assert xplane.hlo_result_name("%copy-done.1 = f32[4] copy-done(%c)") == \
        "copy-done.1"


def test_interval_arithmetic():
    assert xplane.union_ps([(0, 10), (5, 20), (30, 40)]) == 30
    assert xplane.subtract_ps([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert xplane.gaps_ps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]
