"""Unified observability layer (flexflow_tpu/obs): metrics registry,
span tracer, step stats, and simulator calibration."""
import json
import time

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu import obs
from flexflow_tpu.obs import (MetricsRegistry, StepStats, Tracer,
                              parse_exposition, validate_exposition)


# ---------------------------------------------------------------------------
# MetricsRegistry + exposition format
# ---------------------------------------------------------------------------
def test_registry_counter_gauge_histogram_render():
    reg = MetricsRegistry()
    c = reg.counter("ff_x_total", "things", labels=("kind",))
    c.inc(kind="a")
    c.inc(2, kind="b")
    g = reg.gauge("ff_g", "a gauge")
    g.set(2.5)
    h = reg.histogram("ff_h_ms", "latencies", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(50.0)
    text = reg.render()
    fams = validate_exposition(text)
    assert fams["ff_x_total"]["type"] == "counter"
    assert fams["ff_g"]["type"] == "gauge"
    assert fams["ff_h_ms"]["type"] == "histogram"
    samples = {(n, tuple(sorted(lbl.items()))): v
               for n, lbl, v in fams["ff_x_total"]["samples"]}
    assert samples[("ff_x_total", (("kind", "a"),))] == 1
    assert samples[("ff_x_total", (("kind", "b"),))] == 2
    # histogram: cumulative buckets + sum + count
    hs = {(n, lbl.get("le")): v for n, lbl, v in fams["ff_h_ms"]["samples"]}
    assert hs[("ff_h_ms_bucket", "1")] == 1
    assert hs[("ff_h_ms_bucket", "10")] == 2
    assert hs[("ff_h_ms_bucket", "+Inf")] == 3
    assert hs[("ff_h_ms_count", None)] == 3
    assert hs[("ff_h_ms_sum", None)] == pytest.approx(55.5)


def test_registry_label_escaping_round_trips():
    reg = MetricsRegistry()
    nasty = 'he said "hi"\\path\nnewline'
    reg.counter("ff_esc_total", "escapes", labels=("v",)).inc(v=nasty)
    fams = parse_exposition(reg.render())
    (_, labels, value), = fams["ff_esc_total"]["samples"]
    assert labels["v"] == nasty
    assert value == 1


def test_registry_kind_mismatch_rejected_and_reset_keeps_handles():
    reg = MetricsRegistry()
    c = reg.counter("ff_one_total", "one")
    with pytest.raises(ValueError):
        reg.gauge("ff_one_total", "one")
    c.inc(3)
    reg.reset_all()
    assert c.value() == 0
    c.inc()  # the cached handle still feeds the same (reset) family
    assert reg.counter("ff_one_total", "one").value() == 1


def test_histogram_bucket_mismatch_rejected():
    reg = MetricsRegistry()
    h = reg.histogram("ff_hb_ms", "h", buckets=(1.0, 10.0))
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("ff_hb_ms", "h", buckets=(100.0, 1000.0))
    # fetching without explicit buckets never conflicts
    assert reg.histogram("ff_hb_ms", "h") is h


def test_histogram_quantile_interpolates_and_clamps():
    reg = MetricsRegistry()
    h = reg.histogram("ff_q_ms", "q", buckets=(10.0, 100.0, 1000.0))
    assert h.quantile(0.99) == 0.0  # nothing observed
    for v in (5.0, 5.0, 50.0, 50.0):
        h.observe(v)
    # p50 lands on the 10ms bucket boundary (2 of 4 samples <= 10)
    assert h.quantile(0.5) == pytest.approx(10.0)
    # p75 interpolates inside (10, 100]
    assert 10.0 < h.quantile(0.75) <= 100.0
    # +Inf bucket clamps to the last finite boundary
    h.observe(10_000.0)
    assert h.quantile(1.0) == pytest.approx(1000.0)
    with pytest.raises(ValueError, match=r"\[0, 1\]"):
        h.quantile(1.5)
    # labeled histograms quantile per labelset
    lab = reg.histogram("ff_ql_ms", "q", labels=("cache",),
                        buckets=(10.0, 100.0))
    lab.observe(5.0, cache="hit")
    assert lab.quantile(0.9, cache="hit") <= 10.0
    assert lab.quantile(0.9, cache="miss") == 0.0


def test_histogram_windowed_quantile_since_snapshot():
    """quantile(since=snapshot) covers only observations AFTER the
    snapshot — the windowed read the fleet autoscaler's TTFT SLO signal
    uses (the buckets themselves never decay)."""
    reg = MetricsRegistry()
    h = reg.histogram("ff_w_ms", "w", buckets=(10.0, 100.0, 1000.0))
    # an unseen labelset snapshots as all-zero
    base0 = h.snapshot()
    assert set(base0) == {0.0}
    h.observe(900.0)          # historic slow burst
    snap = h.snapshot()
    assert h.quantile(0.99) > 100.0              # lifetime sees it
    assert h.quantile(0.99, since=snap) == 0.0   # window is empty
    h.observe(5.0)
    h.observe(5.0)
    assert h.quantile(0.99, since=snap) <= 10.0  # window: fast only
    assert h.quantile(0.99, since=base0) > 100.0  # pre-burst baseline
    assert h.quantile(0.99) > 100.0              # lifetime unchanged


def test_render_merged_stamps_replica_label_and_rejects_collisions():
    from flexflow_tpu.obs import render_merged

    a, b = MetricsRegistry(), MetricsRegistry()
    for reg, n in ((a, 3), (b, 5)):
        reg.counter("ff_m_total", "c", labels=("outcome",)).inc(
            n, outcome="ok")
        reg.histogram("ff_m_ms", "h", buckets=(1.0, 10.0)).observe(n)
        reg.gauge("ff_only_a" if reg is a else "ff_only_b", "g").set(n)
    text = render_merged({"r0": a, "r1": b})
    fams = validate_exposition(text)
    # ONE TYPE header per family, every sample stamped
    assert text.count("# TYPE ff_m_total counter") == 1
    samples = fams["ff_m_total"]["samples"]
    assert {(s[1]["replica"], s[1]["outcome"], s[2]) for s in samples} \
        == {("r0", "ok", 3.0), ("r1", "ok", 5.0)}
    assert all("replica" in s[1] for s in fams["ff_m_ms"]["samples"])
    # families present in only one registry still render, stamped
    assert 'ff_only_a{replica="r0"} 3' in text
    # kind collision -> loud error, never a silent sum
    c = MetricsRegistry()
    c.gauge("ff_m_total", "now a gauge", labels=("outcome",))
    with pytest.raises(ValueError, match="collision"):
        render_merged({"r0": a, "r2": c})
    # histogram bucket mismatch is a collision too
    d = MetricsRegistry()
    d.histogram("ff_m_ms", "h", buckets=(500.0,)).observe(1)
    with pytest.raises(ValueError, match="collision"):
        render_merged({"r0": a, "r3": d})
    # a family already carrying the merge label is ambiguous
    e = MetricsRegistry()
    e.counter("ff_r_total", "c", labels=("replica",)).inc(replica="x")
    with pytest.raises(ValueError, match="ambiguous"):
        render_merged({"r0": e})


def test_render_labeled_mixes_bare_and_stamped_members():
    # the fleet /metrics fan-in shape: an UNSTAMPED member (the server /
    # default registry) sharing a family name with replica-stamped
    # members must render under ONE TYPE header, bare samples first-class
    # alongside the labeled ones.
    from flexflow_tpu.obs import render_labeled

    base, r0, r1 = (MetricsRegistry() for _ in range(3))
    for reg, v in ((base, 1), (r0, 2), (r1, 3)):
        reg.gauge("ff_pages", "g", labels=("pool",)).set(v, pool="p")
    text = render_labeled([((), base),
                           ((("replica", "r0"),), r0),
                           ((("replica", "r1"), ("fleet", "f")), r1)])
    fams = validate_exposition(text)
    assert text.count("# TYPE ff_pages gauge") == 1
    got = {(s[1].get("replica"), s[1].get("fleet"), s[2])
           for s in fams["ff_pages"]["samples"]}
    assert got == {(None, None, 1.0), ("r0", None, 2.0), ("r1", "f", 3.0)}
    with pytest.raises(ValueError, match="invalid merge label"):
        render_labeled([((("bad-name!", "x"),), base)])


def test_validate_exposition_rejects_garbage():
    with pytest.raises(ValueError):
        validate_exposition("ff_bad{unterminated 1\n")
    with pytest.raises(ValueError):
        validate_exposition("# TYPE ff_x sometype\n")
    with pytest.raises(ValueError):
        validate_exposition("not a metric line at all!\n")


def test_preexisting_counter_shims_are_registry_backed():
    from flexflow_tpu.elastic.watchdog import (reset_watchdog_counters,
                                               watchdog_counters)
    from flexflow_tpu.runtime.durability import (checkpoint_counters,
                                                 reset_checkpoint_counters)

    obs.REGISTRY.counter("ff_checkpoint_saved_total", "").inc(2)
    obs.REGISTRY.counter("ff_watchdog_skips_total", "").inc()
    assert checkpoint_counters() == {"saved": 2}
    assert watchdog_counters() == {"skips": 1}
    reset_checkpoint_counters()
    assert checkpoint_counters() == {}
    assert watchdog_counters() == {"skips": 1}  # untouched by the other reset
    reset_watchdog_counters()
    assert watchdog_counters() == {}


# ---------------------------------------------------------------------------
# Tracer + Chrome trace export
# ---------------------------------------------------------------------------
def test_chrome_trace_events_are_spec_compliant(tmp_path):
    t = Tracer(enabled=True)
    with t.span("outer", phase="demo"):
        time.sleep(0.002)
        with t.span("inner"):
            time.sleep(0.001)
        with t.span("inner"):
            pass
    t.instant("marker", note=1)
    path = t.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        data = json.load(f)  # valid JSON
    events = [e for e in data["traceEvents"] if e.get("ph") == "X"]
    assert len(events) == 3
    for e in events:
        for field in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert field in e, (field, e)
        assert e["dur"] >= 0
    # nested spans properly contained in their parent
    outer = next(e for e in events if e["name"] == "outer")
    for inner in (e for e in events if e["name"] == "inner"):
        assert outer["ts"] <= inner["ts"] + 1e-3
        assert (inner["ts"] + inner["dur"]
                <= outer["ts"] + outer["dur"] + 1e-3)
    # the profile CLI's validator agrees
    from flexflow_tpu.obs.cli import validate_trace

    assert validate_trace(path) == ["inner", "marker", "outer"]


def test_span_records_exception_and_args():
    t = Tracer(enabled=True)
    with pytest.raises(RuntimeError):
        with t.span("boom", step=3):
            raise RuntimeError("x")
    (ev,) = t.events("boom")
    assert ev["args"]["error"] == "RuntimeError"
    assert ev["args"]["step"] == 3


def test_disabled_tracing_is_effectively_free():
    """ISSUE acceptance: with the ring disabled a span is a profiler
    annotation that nobody records and nothing more; the enabled path is
    bounded. Min-of-repeats de-noises a loaded CI host; the bounds are
    deliberately loose — the property pinned is the ORDER of the overhead,
    not the constant."""
    t = Tracer(enabled=False)

    def per_call_us(call, n=5_000, repeats=5):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(n):
                call()
            best = min(best, (time.perf_counter() - t0) / n * 1e6)
        return best

    def one_span():
        with t.span("hot", request=7):
            pass

    disabled_us = per_call_us(one_span)
    assert disabled_us < 20.0, f"disabled span cost {disabled_us:.2f}us"
    assert t.events() == []  # and truly recorded nothing
    # the executor's dispatch wrapper rides the process tracer (disabled
    # by the autouse reset): the same order of cost per dispatch
    dispatch_us = per_call_us(obs.traced_dispatch(lambda: None, "hot"))
    assert dispatch_us < 20.0, f"disabled dispatch cost {dispatch_us:.2f}us"
    assert obs.get_tracer().events() == []
    t.enable()
    enabled_us = per_call_us(one_span)
    assert enabled_us < 250.0, f"enabled span cost {enabled_us:.2f}us"


def _host_events(trace_dir, name):
    """The profiler trace's host events called `name`, with their args."""
    import glob
    import os

    import jax

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    return [(e, dict(e.stats)) for plane in profile.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name == name]


@pytest.mark.parametrize("ring", [False, True])
def test_span_is_a_profiler_annotation_on_its_own_thread(tmp_path, ring):
    """One clock: under a profiler session a span opened on a worker
    thread is in the trace's host plane with its name and scalar args —
    whether or not the ring records it too — and a list stays in the ring."""
    import threading

    from flexflow_tpu.runtime.profiling import trace

    t = Tracer(enabled=ring)

    def work():
        with t.span("x", request=7, requests=[7, 8]) as sp:
            time.sleep(0.002)
            with t.step("x.step", 3):
                pass
            sp.set(emitted=2)

    with trace(str(tmp_path)):
        th = threading.Thread(target=work)
        th.start()
        th.join(timeout=60)
        assert not th.is_alive()
    ((ev, args),) = _host_events(str(tmp_path), "x")
    assert args == {"request": 7, "emitted": 2}
    assert ev.duration_ns >= 2e6
    ((_, step_args),) = _host_events(str(tmp_path), "x.step")
    assert step_args == {"step_num": 3, "_r": 1}   # a profiler step marker
    if ring:
        (rec,) = t.events("x")
        assert rec["args"] == {"request": 7, "requests": [7, 8],
                               "emitted": 2}
    else:
        assert t.events() == []


def test_tracer_ring_bounds_memory():
    t = Tracer(enabled=True, max_events=10)
    for i in range(50):
        with t.span(f"s{i}"):
            pass
    evs = t.events()
    assert len(evs) == 10
    assert evs[0]["name"] == "s40"  # oldest dropped


# ---------------------------------------------------------------------------
# Request-scoped tracing: handoff/resume stitching, ring drops, exemplars
# ---------------------------------------------------------------------------
def test_handoff_resume_stitches_spans_across_threads(tmp_path):
    """The serving submit path in miniature: a client thread opens a span
    and captures a Handoff; a scheduler thread resumes it. Both spans
    must share one trace_id, the resumed span must parent under the
    submitting span, and the flow-arrow pair must bind the two tracks."""
    import threading

    from flexflow_tpu.obs.tracing import root_context, use_context

    t = Tracer(enabled=True)
    t.set_thread_name("client")
    ctx = root_context()
    with use_context(ctx):
        with t.span("submit"):
            token = t.handoff("crossing")

    def worker():
        t.set_thread_name("sched")
        with t.resume(token), t.span("prefill", request=1):
            pass

    th = threading.Thread(target=worker)
    th.start()
    th.join(10.0)
    assert not th.is_alive()

    spans = {e["name"]: e for e in t.events() if e["ph"] == "X"}
    assert spans["prefill"]["args"]["trace_id"] == ctx.trace_id
    assert spans["submit"]["args"]["trace_id"] == ctx.trace_id
    assert spans["prefill"]["args"]["parent_id"] \
        == spans["submit"]["args"]["span_id"]
    assert spans["submit"]["tid"] != spans["prefill"]["tid"]
    # flow arrow: start on the client track, finish on the scheduler's,
    # sharing one id under the "handoff" category
    s, f = [e for e in t.events() if e["ph"] in ("s", "f")]
    assert (s["ph"], f["ph"]) == ("s", "f")
    assert s["id"] == f["id"] and s["cat"] == f["cat"] == "handoff"
    assert f["bp"] == "e"
    assert s["tid"] == spans["submit"]["tid"]
    assert f["tid"] == spans["prefill"]["tid"]
    # a second resume re-enters the context but must not re-emit the
    # flow finish (the arrow is one edge, not one per resume)
    with t.resume(token):
        pass
    assert len([e for e in t.events() if e["ph"] == "f"]) == 1

    # the export names both tracks and still validates
    path = t.export_chrome_trace(str(tmp_path / "t.json"))
    from flexflow_tpu.obs.cli import validate_trace

    assert validate_trace(path) == ["prefill", "submit"]
    with open(path) as fh:
        data = json.load(fh)
    names = {e["args"]["name"] for e in data["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    assert {"client", "sched"} <= names


def test_handoff_is_noop_when_disabled_or_contextless():
    t = Tracer(enabled=False)
    assert t.handoff() is None
    with t.resume(None):  # a None token must be a no-op scope
        pass
    t.enable()
    assert t.handoff() is None  # no current context -> nothing to carry
    assert t.events() == []


def test_instant_args_are_jsonable(tmp_path):
    """Regression: numpy scalars/arrays passed to instant() must not
    break json.dump at export time."""
    t = Tracer(enabled=True)
    t.instant("marker", arr=np.arange(3), val=np.float64(1.5),
              n=np.int64(7))
    path = t.export_chrome_trace(str(tmp_path / "t.json"))
    with open(path) as fh:
        data = json.load(fh)  # would raise before the fix
    (ev,) = [e for e in data["traceEvents"] if e.get("ph") == "i"]
    assert ev["args"]["val"] == 1.5


def test_ring_overflow_counts_drops_and_stamps_export():
    t = Tracer(enabled=True, max_events=10)
    for i in range(50):
        with t.span(f"s{i}"):
            pass
    assert t.dropped_events == 40
    data = t.to_chrome_trace()
    meta = next(e for e in data["traceEvents"]
                if e.get("ph") == "M" and e["name"] == "trace_metadata")
    assert meta["args"]["dropped_events"] == 40
    assert meta["args"]["epoch_wall_s"] > 0
    # mirrored onto the registry so dashboards see the truncation
    from flexflow_tpu.obs import get_registry

    assert get_registry().counter(
        "ff_trace_events_dropped_total", "").value() == 40
    t.clear()
    assert t.dropped_events == 0


def test_histogram_exemplar_round_trip():
    reg = MetricsRegistry()
    h = reg.histogram("ff_e_ms", "latencies", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0, exemplar="abc123")
    text = reg.render()
    assert '# {trace_id="abc123"} 5' in text
    validate_exposition(text)  # exemplars must not break validation
    fams = parse_exposition(text)
    # samples stay plain 3-tuples; exemplars ride in their own list
    assert all(len(s) == 3 for s in fams["ff_e_ms"]["samples"])
    (name, labels, exlabels, value), = fams["ff_e_ms"]["exemplars"]
    assert name == "ff_e_ms_bucket" and labels["le"] == "10"
    assert exlabels == {"trace_id": "abc123"}
    assert value == pytest.approx(5.0)


def test_traceparent_request_scope_parsing():
    """The HTTP door: a well-formed inbound traceparent CONTINUES the
    caller's trace; garbage or absence mints local state only when
    tracing is on; the request id is always present."""
    from flexflow_tpu.obs.tracing import get_tracer
    from flexflow_tpu.serving.server import (_format_traceparent,
                                             _request_scope)

    caller_trace, caller_span = "ab" * 16, "cd" * 8
    ctx, rid = _request_scope({
        "traceparent": f"00-{caller_trace}-{caller_span}-01",
        "X-Request-Id": "req-42"})
    assert rid == "req-42"
    assert ctx.trace_id == caller_trace and ctx.parent_id == caller_span
    assert _format_traceparent(ctx) \
        == f"00-{caller_trace}-{ctx.span_id}-01"
    # malformed header + tracing disabled -> no context, minted id
    assert not get_tracer().enabled  # conftest reset guarantees this
    ctx2, rid2 = _request_scope({"traceparent": "00-nope-bad-ff"})
    assert ctx2 is None and len(rid2) == 16
    # tracing enabled -> a fresh local root even without a header
    get_tracer().enable()
    try:
        ctx3, _ = _request_scope({})
        assert ctx3 is not None and ctx3.parent_id is None
    finally:
        get_tracer().disable()


def test_flight_recorder_rings_triggers_and_debounces(tmp_path):
    from flexflow_tpu.elastic.events import EventLog
    from flexflow_tpu.obs.flightrecorder import FlightRecorder

    reg = MetricsRegistry()
    reg.counter("ff_fr_total", "recorded things").inc()
    tracer = Tracer(enabled=True)
    with tracer.span("before_death"):
        pass
    elog = EventLog()
    rec = FlightRecorder(dump_dir=str(tmp_path / "fr"), capacity=8,
                         tracer=tracer, registries={"unit": reg},
                         max_dumps=2, debounce_s=3600.0).attach(elog)
    try:
        elog.record("fleet.suspect", replica="r0")   # health stream
        elog.record("retry", attempt=1)              # plain event
        rec.snapshot_metrics()                       # metrics stream
        assert not rec.dumps  # nothing triggered yet
        elog.record("fleet.dead", replica="r0")      # TRIGGER
        elog.record("fleet.failover", replica="r0")  # debounced away
        assert len(rec.dumps) == 1
        bundle = rec.dumps[0]
        with open(bundle + "/recorder.json") as fh:
            dump = json.load(fh)
        assert dump["meta"]["trigger"] == "fleet.dead"
        assert {"health", "events", "metrics"} \
            <= set(dump["meta"]["streams"])
        kinds = [e.get("kind") for e in dump["entries"]]
        assert "fleet.suspect" in kinds and "retry" in kinds
        # the bundle carries the trace and a fresh exposition render
        with open(bundle + "/trace.json") as fh:
            trace = json.load(fh)
        assert any(e.get("name") == "before_death"
                   for e in trace["traceEvents"])
        with open(bundle + "/metrics_unit.txt") as fh:
            assert "ff_fr_total" in fh.read()
        # manual dumps bypass the debounce, max_dumps caps the disk
        assert rec.dump(trigger="manual") is not None
        assert rec.dump(trigger="manual") is None  # cap reached
        # ring stays bounded
        for i in range(20):
            elog.record("retry", attempt=i)
        assert len(rec.entries()) == 8
    finally:
        rec.detach()


# ---------------------------------------------------------------------------
# StepStats
# ---------------------------------------------------------------------------
def test_stepstats_rates_and_summary():
    reg = MetricsRegistry()
    s = StepStats(flops_per_step=1e9, peak_tflops=10.0, registry=reg)
    s.start()
    time.sleep(0.005)
    rec = s.record_step(32, loss=1.5)
    assert rec["wall_ms"] >= 5.0 * 0.5  # timer resolution slack
    assert rec["samples_per_s"] > 0
    assert rec["tflops"] == pytest.approx(
        1e9 / (rec["step_ms"] / 1e3) / 1e12)
    assert rec["mfu"] == pytest.approx(rec["tflops"] / 10.0)
    time.sleep(0.001)
    s.record_step(32, loss=1.0, steps=4)  # a K-step chunk
    summ = s.summary()
    assert summ["steps"] == 5 and summ["recorded"] == 2
    assert summ["last_loss"] == 1.0
    assert summ["p95_step_ms"] >= summ["p50_step_ms"]
    assert reg.counter("ff_train_steps_total", "").value() == 5
    assert reg.histogram("ff_step_wall_ms", "").count() == 2


def test_stepstats_zero_dt_guard():
    s = StepStats(flops_per_step=1e9, peak_tflops=1.0,
                  registry=MetricsRegistry())
    s._mark = time.perf_counter() + 60.0  # force a non-positive interval
    rec = s.record_step(8, loss=0.1)
    assert rec["wall_ms"] == 0.0
    assert rec["samples_per_s"] == 0.0 and rec["mfu"] == 0.0


def test_stepstats_ring_capacity():
    s = StepStats(capacity=4, registry=MetricsRegistry())
    s.start()
    for _ in range(10):
        s.record_step(1)
    assert len(s) == 4 and s.total_steps == 10


# ---------------------------------------------------------------------------
# fit() integration: step stats recorded, history schema unchanged
# ---------------------------------------------------------------------------
def _small_model(batch=8, **cfg_kw):
    config = ff.FFConfig()
    config.batch_size = batch
    for k, v in cfg_kw.items():
        setattr(config, k, v)
    m = ff.FFModel(config)
    t = m.create_tensor([batch, 16])
    t = m.dense(t, 32, ff.ActiMode.AC_MODE_RELU)
    t = m.dense(t, 4)
    m.softmax(t)
    m.compile(
        optimizer=ff.SGDOptimizer(m, lr=0.05),
        loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[ff.MetricsType.METRICS_ACCURACY],
    )
    return m


def _data(n=32):
    rng = np.random.RandomState(0)
    x = rng.randn(n, 16).astype(np.float32)
    y = rng.randint(0, 4, size=(n, 1)).astype(np.int32)
    return x, y


def test_fit_records_step_stats_and_keeps_history_schema():
    m = _small_model()
    x, y = _data()
    hist = m.fit(x, y, epochs=2)
    assert m.step_stats is not None
    assert m.step_stats.total_steps == 8  # 4 steps/epoch x 2
    assert len(m.step_stats) == 8
    for r in m.step_stats.records():
        assert r["samples"] == 8 and "loss" in r
    # history schema unchanged by the obs layer
    assert set(hist[-1]) == {"samples", "accuracy", "loss", "cce",
                             "sparse_cce", "mse", "rmse", "mae", "epoch",
                             "throughput"}
    assert obs.REGISTRY.counter("ff_train_steps_total", "").value() == 8


def _inside(child, parent):
    return (child["tid"] == parent["tid"] and parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


def test_fit_chunked_path_records_per_dispatch():
    """Per K-step dispatch: one StepStats record that keeps the K single
    losses beside their mean, and one `fit.chunk` span holding the four
    phases of the dispatch; the fetch after the loop is a `fit.absorb`
    of its own."""
    tr = obs.enable_tracing()
    m = _small_model()
    x, y = _data(32)
    tr.clear()
    m.fit(x, y, epochs=1, steps_per_execution=2)
    # 2 chunks of K=2: two records carrying 2 steps each
    assert m.step_stats.total_steps == 4
    recs = m.step_stats.records()
    assert [r["steps"] for r in recs] == [2.0, 2.0]
    for r in recs:
        assert len(r["losses"]) == 2
        assert np.mean(r["losses"]) == pytest.approx(r["loss"], rel=1e-6)
    assert recs[0]["losses"][0] != recs[0]["losses"][1]
    chunks = tr.events("fit.chunk")
    assert [c["args"]["chunk"] for c in chunks] == [0, 1]
    assert [c["args"]["step_num"] for c in chunks] == [0, 2]
    for c in chunks:
        assert c["args"]["steps"] == 2 and c["args"]["samples"] == 16
        for phase in ("fit.load", "fit.stage", "executor.multi_step"):
            (ev,) = [e for e in tr.events(phase) if _inside(e, c)]
            assert ev["dur"] > 0
    absorbs = tr.events("fit.absorb")
    assert len(absorbs) == 2           # one behind: the second chunk holds
    assert not any(_inside(a, chunks[0]) for a in absorbs)   # the first's
    assert _inside(absorbs[0], chunks[1])
    assert absorbs[1]["ts"] >= chunks[1]["ts"] + chunks[1]["dur"]


def test_train_steps_name_what_runs_outside_the_graph():
    """The device names of the loss, the metrics and the optimizer update
    are in the lowered multi-step program, beside the graph ops' own."""
    import jax

    m = _small_model()
    x, y = _data(16)
    step = m._get_multi_step().__wrapped__
    text = step.lower(
        m.params, m.opt_state, m.state,
        {m.input_ops[0].name: x.reshape(2, 8, 16)}, y.reshape(2, 8, 1),
        jax.random.split(jax.random.PRNGKey(0), 2)).as_text(debug_info=True)
    for scope in ("loss:sparse_categorical_crossentropy", "metrics:compute",
                  "optimizer:update", "linear:"):
        assert scope in text, scope


def test_fit_with_tracing_emits_dispatch_spans():
    tr = obs.enable_tracing()
    tr.clear()
    try:
        m = _small_model()
        x, y = _data()
        m.fit(x, y, epochs=1)
        names = tr.span_names()
        assert "compile" in names
        assert "executor.train_step" in names
        assert len(tr.events("executor.train_step")) == 4
    finally:
        obs.disable_tracing()


# ---------------------------------------------------------------------------
# search: predicted step cost recorded for calibration
# ---------------------------------------------------------------------------
def test_search_result_carries_predicted_step_us():
    m = _small_model(batch=8, search_budget=4, num_devices=8,
                     measure_op_costs=False)
    sr = m.search_result
    assert sr is not None
    assert sr.predicted_step_us == pytest.approx(sr.cost_us)
    assert sr.predicted_step_us > 0


def test_calibration_report_shape_and_json():
    m = _small_model()
    x, y = _data()
    m.fit(x, y, epochs=1)
    rep = obs.calibrate(m, warmup=0, repeats=1)
    assert rep.predicted_step_us and rep.predicted_step_us > 0
    assert rep.measured_step_us and rep.measured_step_us > 0
    ops = {o.op: o for o in rep.ops}
    assert {"linear_0", "linear_1", "softmax_0"} <= set(ops)
    good = [o for o in rep.ops if o.error is None]
    assert good and all(o.predicted_us > 0 for o in good)
    data = json.loads(rep.to_json())
    assert data["measured_steps"] == 4
    assert data["step_ratio"] == pytest.approx(rep.step_ratio)
    assert "calibration" in rep.format()


# ---------------------------------------------------------------------------
# serving: /metrics via the shared renderer, /healthz
# ---------------------------------------------------------------------------
def test_server_metrics_render_validates_and_keeps_names():
    from flexflow_tpu.analysis import record_report
    from flexflow_tpu.analysis.diagnostics import (DiagnosticReport,
                                                   make_diag)
    from flexflow_tpu.elastic.events import EventLog
    from flexflow_tpu.runtime.durability import _bump
    from flexflow_tpu.serving.server import InferenceServer

    server = InferenceServer()
    try:
        server.record_load_failure("broken", RuntimeError("nope"))
        _bump("saved")
        obs.REGISTRY.counter("ff_watchdog_skips_total", "").inc()
        record_report(DiagnosticReport(
            [make_diag("FFTA050", "synthetic")], passes_run=("t",)))
        ev = EventLog()
        ev.record("retry", step=1)
        server.attach_elastic_events(ev)
        text = server.prometheus_text()
        fams = validate_exposition(text)  # every line parses
        # all pre-existing metric names survive the registry migration
        for name in ("ff_inference_requests_total",
                     "ff_inference_failures_total",
                     "ff_inference_avg_latency_ms",
                     "ff_model_load_failures_total",
                     "ff_plan_diagnostics_total",
                     "ff_checkpoint_saved_total",
                     "ff_watchdog_skips_total",
                     "ff_elastic_events_total"):
            assert name in fams, name
        assert 'ff_model_load_failures_total{model="broken"} 1' in text
        assert "ff_checkpoint_saved_total 1" in text.replace("\r", "")
        (_, diag_lbl, _), = fams["ff_plan_diagnostics_total"]["samples"]
        assert diag_lbl["code"] == "FFTA050"
        (_, ev_lbl, ev_n), = fams["ff_elastic_events_total"]["samples"]
        assert ev_lbl == {"kind": "retry"} and ev_n == 1
    finally:
        server.shutdown()


def test_reregistered_model_metrics_start_from_zero():
    from flexflow_tpu.serving.server import InferenceServer, ModelMetrics

    server = InferenceServer()
    try:
        m1 = ModelMetrics(server.registry, "m")
        server._metrics["m"] = m1
        m1.record(50.0, ok=True)
        m1.record(10.0, ok=True)
        assert m1.stats()["requests"] == 2
        server.unregister("m")
        # the old incarnation's series no longer render
        assert 'model="m"' not in server.prometheus_text()
        # a fresh registration under the same name starts from zero —
        # no mixing of the old histogram sums with a reset max_ms
        m2 = ModelMetrics(server.registry, "m")
        s = m2.stats()
        assert s == {"requests": 0, "failures": 0, "avg_latency_ms": 0.0,
                     "max_latency_ms": 0.0}
        # and the idle model renders zero-valued series immediately
        # (dashboards join on series existence)
        assert 'ff_inference_requests_total{model="m"} 0' \
            in server.prometheus_text()
    finally:
        server.shutdown()


def test_generate_metrics_survive_repeat_requests():
    """_metrics_for must not rebuild (and thereby zero) live series on a
    repeat request — the eager-setdefault trap."""
    from flexflow_tpu.serving.server import InferenceServer

    server = InferenceServer()
    try:
        m = server._metrics_for("g")
        m.record(1.0, ok=True)
        assert server._metrics_for("g") is m
        server._metrics_for("g").record(2.0, ok=True)
        assert server.stats("g")["requests"] == 2
    finally:
        server.shutdown()


def test_two_servers_do_not_share_per_model_series():
    from flexflow_tpu.serving.server import InferenceServer

    a, b = InferenceServer(), InferenceServer()
    try:
        a.record_load_failure("m", RuntimeError("x"))
        assert 'ff_model_load_failures_total{model="m"} 1' \
            in a.prometheus_text()
        assert 'ff_model_load_failures_total{model="m"}' \
            not in b.prometheus_text()
    finally:
        a.shutdown()
        b.shutdown()


def test_healthz_endpoint():
    import urllib.request

    from flexflow_tpu.serving.server import InferenceServer

    server = InferenceServer()
    httpd = server.serve_http(port=0)
    try:
        port = httpd.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz") as r:
            body = json.loads(r.read())
        assert r.status == 200
        assert body["status"] == "ok"
        assert body["models"] == []
        assert body["uptime_s"] >= 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as r:
            validate_exposition(r.read().decode())
    finally:
        httpd.shutdown()
        server.shutdown()


# ---------------------------------------------------------------------------
# satellites: print_event_log tail=0, IterationTimer shim
# ---------------------------------------------------------------------------
def test_print_event_log_tail_zero_shows_counts_only():
    from flexflow_tpu.elastic.events import EventLog
    from flexflow_tpu.runtime.profiling import print_event_log

    ev = EventLog()
    ev.record("retry", step=1)
    ev.record("retry", step=2)
    out = []
    print_event_log(ev, sink=out.append, tail=0)
    assert out == [ev.summary()]
    out2 = []
    print_event_log(ev, sink=out2.append, tail=1)
    assert len(out2) == 2  # one event line + the summary
    out3 = []
    print_event_log(EventLog(), sink=out3.append, tail=0)
    assert out3 == ["elastic: no events"]


def test_iteration_timer_zero_dt_and_prints():
    from flexflow_tpu.runtime.profiling import IterationTimer

    lines = []
    t = IterationTimer(4, print_freq=2, sink=lines.append)
    for _ in range(5):  # consecutive ticks can land in one clock quantum
        t.tick()
    assert t._count == 4
    assert len(lines) == 2 and all("samples/s" in ln for ln in lines)


# ---------------------------------------------------------------------------
# elastic: recovery spans appear in the trace
# ---------------------------------------------------------------------------
def test_recovery_spans_in_trace(tmp_path):
    from flexflow_tpu.elastic import (ElasticCoordinator, EventLog,
                                      FaultPlan, RetryPolicy)
    from flexflow_tpu.obs.cli import validate_trace

    tr = obs.enable_tracing()
    tr.clear()
    try:
        def builder(cfg):
            m = ff.FFModel(cfg)
            t = m.create_tensor([cfg.batch_size, 16])
            t = m.dense(t, 32, ff.ActiMode.AC_MODE_RELU)
            t = m.dense(t, 4)
            m.softmax(t)
            m.compile(
                optimizer=ff.SGDOptimizer(m, lr=0.05),
                loss_type=(
                    ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY),
                metrics=[])
            return m

        config = ff.FFConfig()
        config.batch_size = 8
        config.device_ids = [0, 1, 2, 3]
        plan = FaultPlan().add_chip_loss(at_step=3, chips=[3])
        coord = ElasticCoordinator(
            builder, config, fault_plan=plan,
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            events=EventLog(),
            retry_policy=RetryPolicy(max_retries=2, base_delay_s=0.01),
            # pin the disk restore path: this test asserts the
            # checkpoint.restore span; live-recovery spans are covered
            # by test_resharding.py
            live_resharding=False)
        x, y = _data(32)
        coord.fit(x, y, steps=6)
        names = tr.span_names()
        for required in ("elastic.recover", "elastic.replan",
                         "elastic.restore", "checkpoint.save",
                         "checkpoint.restore", "elastic.detect",
                         "elastic.resume", "compile",
                         "executor.train_step"):
            assert required in names, (required, names)
        # recover contains replan + restore
        rec = tr.events("elastic.recover")[0]
        for child in ("elastic.replan", "elastic.restore"):
            ev = tr.events(child)[0]
            assert rec["ts"] <= ev["ts"]
            assert ev["ts"] + ev["dur"] <= rec["ts"] + rec["dur"] + 1e-3
        path = tr.export_chrome_trace(str(tmp_path / "trace.json"))
        validate_trace(path)
    finally:
        obs.disable_tracing()


def test_conftest_fixture_resets_counters():
    """Paired with the autouse fixture: state bumped in OTHER tests must
    not be visible here (each test starts from zero)."""
    from flexflow_tpu.analysis import diagnostic_counters
    from flexflow_tpu.runtime.durability import checkpoint_counters

    assert checkpoint_counters() == {}
    assert diagnostic_counters() == {}
    assert obs.get_tracer().events() == []


# ---------------------------------------------------------------------------
# cold start: one-shot phases, first calls, compile stages by program
# (docs/observability.md "Cold start")
# ---------------------------------------------------------------------------
def _startup(family, **labels):
    fam = obs.REGISTRY.get(family)
    return fam.value(**labels) if fam is not None else 0.0


def test_phase_records_to_the_registry_ring_off_and_to_the_ring_when_on():
    tr = Tracer()                   # ring off, no profiler anywhere
    with tr.phase("toy.phase", n=1) as ph:
        ph.set(found=2)
        time.sleep(0.01)
    first = _startup("ff_startup_seconds", phase="toy.phase")
    assert 0.01 <= first < 1.0
    assert _startup("ff_startup_phase_runs_total", phase="toy.phase") == 1
    assert _startup("ff_startup_phase_at_seconds", phase="toy.phase") \
        <= time.perf_counter() - first
    assert tr.events() == []
    tr.enable()
    with tr.phase("toy.phase", n=2) as ph:
        ph.set(found=3)
    (ev,) = tr.events("toy.phase")
    assert ev["args"] == {"n": 2, "found": 3} and ev["ph"] == "X"
    # a gauge that ADDS, beside the count of runs to divide by
    assert _startup("ff_startup_seconds", phase="toy.phase") >= first
    assert _startup("ff_startup_phase_runs_total", phase="toy.phase") == 2


def test_compile_phase_holds_its_four_children_each_run_once():
    _small_model(batch=8, search_budget=4, num_devices=8,
                 measure_op_costs=False)
    children = ("search", "compile.analysis", "compile.init_params",
                "compile.build_steps")
    for phase in ("compile",) + children:
        assert _startup("ff_startup_phase_runs_total", phase=phase) == 1, \
            phase
        assert _startup("ff_startup_seconds", phase=phase) > 0, phase
    assert _startup("ff_startup_seconds", phase="compile") >= sum(
        _startup("ff_startup_seconds", phase=c) for c in children)
    start = {p: _startup("ff_startup_phase_at_seconds", phase=p)
             for p in ("compile",) + children}
    assert all(start[c] >= start["compile"] for c in children)


def test_first_call_records_once_forwards_and_leaves_the_bare_function():
    from flexflow_tpu.obs.tracing import first_call

    calls = []

    def program(a, b=0):
        calls.append((a, b))
        time.sleep(0.005)
        return a + b

    class Owner:
        pass

    owner = Owner()
    owner.fn = held = first_call(program, "toy_program", owner, "fn")
    assert held.__name__ == "program"       # everything else is fn's own
    import weakref

    weakref.ref(held)       # jax.eval_shape keys a cache by the function
    t_before = time.perf_counter()
    assert owner.fn(1, b=2) == 3
    assert owner.fn is program              # no wrapper left behind
    secs = _startup("ff_first_dispatch_seconds", program="toy_program")
    assert 0.005 <= secs < 1.0
    assert t_before <= _startup("ff_first_dispatch_at_seconds",
                                program="toy_program") \
        <= time.perf_counter() - secs
    assert owner.fn(4) == 4 and held(5) == 5    # a kept handle still calls
    assert _startup("ff_first_dispatch_seconds",
                    program="toy_program") == secs   # recorded ONCE
    assert calls == [(1, 2), (4, 0), (5, 0)]
    # whoever swapped the attribute before the first call keeps their own
    owner.fn = held2 = first_call(program, "toy_program", owner, "fn")
    owner.fn = lambda *a: held2(*a)
    swapped = owner.fn
    assert owner.fn(7) == 7 and owner.fn is swapped


def test_traced_dispatch_times_the_first_call_and_adds_no_frame_after():
    """The executor's programs: the first dispatch lands in
    `ff_first_dispatch_seconds` under the program's own name, timed in
    the span wrapper's own frame — neither the first `fit()` dispatch (a
    frame more slowed the trace of a deep train step on the chip) nor any
    later one pays a frame for it."""
    import sys

    depths = []

    def multi_step(x):
        f, n = sys._getframe(), 0
        while f is not None:
            f, n = f.f_back, n + 1
        depths.append(n)
        return x

    step = obs.traced_dispatch(multi_step, "executor.multi_step")
    here = multi_step(0) or depths.pop()     # this frame + the function's
    for i in range(3):
        step(i)
    assert depths[0] == depths[1] == depths[2] == here + 1  # + the wrapper
    assert _startup("ff_first_dispatch_seconds", program="multi_step") > 0
    assert step.__wrapped__ is multi_step

    m = _small_model(num_devices=1)     # over a mesh the SECOND dispatch
    x, y = _data()                      # compiles again (PERF.md section 7)
    m.fit(x, y, epochs=1)
    assert _startup("ff_first_dispatch_seconds", program="train_step") > 0
    # compile stages by program, from jax.monitoring
    for stage in ("trace", "lower", "backend"):
        assert _startup("ff_compile_seconds_total", program="train_step",
                        stage=stage) > 0, stage
    assert _startup("ff_compiles_total", program="train_step") == 1


def test_cold_start_families_pass_validate_exposition():
    m = _small_model()
    x, y = _data()
    m.fit(x, y, epochs=1)
    fams = validate_exposition(obs.REGISTRY.render())
    for name, kind in (("ff_startup_seconds", "gauge"),
                       ("ff_startup_phase_at_seconds", "gauge"),
                       ("ff_startup_phase_runs_total", "counter"),
                       ("ff_first_dispatch_seconds", "gauge"),
                       ("ff_first_dispatch_at_seconds", "gauge"),
                       ("ff_compile_seconds_total", "counter"),
                       ("ff_compiles_total", "counter")):
        assert fams[name]["type"] == kind and fams[name]["samples"], name
    phases = {lbl["phase"] for _, lbl, _ in
              fams["ff_startup_seconds"]["samples"]}
    assert {"compile", "compile.init_params", "compile.build_steps"} \
        <= phases
