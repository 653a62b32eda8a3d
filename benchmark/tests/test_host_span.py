"""The reader of the program's spans (`metrics/host_span.py`) on a hand-built
trace: a scheduler thread with three passes of nested spans, a second thread,
three device ops with idle time around them. Every expected number below is
worked out by hand from the intervals, in milliseconds."""
import pytest

from benchmark import harness, xplane
from benchmark.metrics import host_span

MS = 10**9   # picoseconds


def ev(name, start, end, **stats):
    return xplane.Event(name, start * MS, (end - start) * MS, stats)


def op(start, end):
    return xplane.DeviceOp("fusion.1", "fusion", "loop fusion", "",
                           start * MS, (end - start) * MS)


SCHEDULER = [
    ev("serve.iter", 0, 100, decode_slots=2),
    ev("serve.schedule", 1, 11),
    ev("serve.prefix_install", 3, 7),          # nested in serve.schedule
    ev("serve.prefill", 11, 31),
    ev("serve.decode_stage", 32, 36),
    ev("serve.decode", 36, 90),
    ev("serve.decode_dispatch", 36, 40),
    ev("serve.decode_fetch", 40, 90),
    ev("serve.emit", 90, 98),
    ev("serve.iter", 100, 160, decode_slots=0),  # admitted, decoded nothing
    ev("serve.schedule", 101, 103),
    ev("serve.iter", 160, 250, decode_slots=3),
    ev("serve.decode_stage", 161, 165),
    ev("serve.decode", 165, 240),
    ev("serve.decode_dispatch", 165, 170),
    ev("serve.decode_fetch", 170, 240),
    ev("serve.emit", 240, 249),
    ev("TransferToDevice", 33, 34),             # the runtime's, not a span
]
CLIENT = [ev("serve.admit", 20, 22), ev("PjitFunction(f)", 0, 300)]


def trace(lo=0, hi=270, host=None):
    ops = [op(5, 30), op(45, 88), op(172, 238)]
    runs = [ev("jit_decode_all(7)", 45, 88), ev("jit_decode_all(7)", 172, 238),
            ev("jit_prefill_last_chunk(9)", 5, 30)]
    host = [("scheduler", SCHEDULER), ("client", CLIENT)] \
        if host is None else host
    return xplane.TraceSummary(1, (hi - lo) / 1e3, 0.134, {0: ops}, {0: runs},
                               host, lo * MS, hi * MS)


def read(spec, tr):
    rec = harness.Record({}, 0, 0, {}, 0, trace=tr)
    return host_span.read(spec, None, rec)


def test_p50_of_a_span_by_an_arg():
    spec = {"stat": "p50_ms", "span": "serve.iter"}
    assert read(spec, trace()) == pytest.approx(90.0)       # 100, 60, 90
    spec["where_positive"] = "decode_slots"
    assert read(spec, trace()) == pytest.approx(95.0)       # 100, 90
    # only a span that lies wholly in the window has its own duration
    assert read(spec, trace(hi=245)) == pytest.approx(100.0)


def test_covered_time_counts_a_nested_span_once():
    spec = {"stat": "ms_per", "program": "decode_all",
            "spans": ["serve.schedule", "serve.prefix_install",
                      "serve.prefill", "serve.prefix_insert"]}
    # [1, 11) with [3, 7) inside, [11, 31), [101, 103); two runs
    assert read(spec, trace()) == pytest.approx((10 + 20 + 2) / 2)
    assert read(spec, trace(lo=5)) == pytest.approx((6 + 20 + 2) / 2)


def test_self_time_is_a_span_less_its_children():
    spec = {"stat": "self_ms_per", "program": "decode_all",
            "self_of": ["serve.iter"]}
    # 100 - (10 + 20 + 4 + 54 + 8); 60 - 2; 90 - (4 + 75 + 9)
    assert read(spec, trace()) == pytest.approx((4 + 58 + 2) / 2)
    both = {"stat": "ms_per", "program": "decode_all", "self_of":
            ["serve.iter"], "spans": ["serve.decode_stage", "serve.emit"]}
    assert read(both, trace()) == pytest.approx((4 + 4 + 8 + 9 + 64) / 2)


def test_idle_time_under_no_leaf_span():
    # gaps [0, 5) under serve.schedule's self time, [30, 45) under
    # serve.decode_dispatch, [88, 172) under the second serve.iter's self
    # time, [238, 270) past every span: 5 + 84 + 32 of 136 have no name
    spec = {"stat": "idle_unnamed_share"}
    assert read(spec, trace()) == pytest.approx(100.0 * 121 / 136)
    # the other thread's leaf names a gap too: [20, 22) covers no middle,
    # a span over [120, 140) covers the long gap's
    named = [("scheduler", SCHEDULER),
             ("client", CLIENT + [ev("serve.admit", 120, 140)])]
    assert read(spec, trace(host=named)) == pytest.approx(100.0 * 37 / 136)


def test_nothing_to_read_is_none():
    bare = trace(host=[("client", [ev("PjitFunction(f)", 0, 300)])])
    for spec in ({"stat": "p50_ms", "span": "serve.iter"},
                 {"stat": "idle_unnamed_share"},
                 {"stat": "ms_per", "program": "decode_all",
                  "spans": ["serve.emit"]}):
        assert read(spec, bare) is None
        assert host_span.read(spec, None,
                              harness.Record({}, 0, 0, {}, 0)) is None
    assert read({"stat": "p50_ms", "span": "serve.resize"}, trace()) is None
    assert read({"stat": "ms_per", "program": "decode_all",
                 "spans": ["fit.load"]}, trace()) is None
    assert read({"stat": "ms_per", "program": "multi_step",
                 "spans": ["serve.emit"]}, trace()) is None
    with pytest.raises(ValueError):
        read({"stat": "p99_ms", "span": "serve.iter"}, trace())


def test_the_new_metrics_are_found_by_name():
    manifest = harness.load_manifest()
    mine = {m["name"] for m in manifest["per_layer"]}
    for name in ("sched_iter_ms_p50", "sched_admit_ms_per_iter",
                 "sched_host_ms_per_iter", "idle_unnamed_share_sat",
                 "fit_host_ms_per_dispatch"):
        assert name in mine
        spec = harness.load_metric(name)
        assert harness.reader_of(spec) is host_span.read
        assert spec["stat"] in host_span.STATS
