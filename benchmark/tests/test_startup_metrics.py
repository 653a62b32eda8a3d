"""`metrics/startup.py` on a hand-filled registry and hand-set laps: sums,
extras, a residual that is never negative, and None where the program keeps
no such family (a parent commit)."""
import pytest

from benchmark import harness
from benchmark.metrics import startup
from flexflow_tpu.obs.registry import REGISTRY

FAMILIES = ("ff_startup_seconds", "ff_startup_phase_at_seconds",
            "ff_first_dispatch_seconds", "ff_first_dispatch_at_seconds",
            "ff_compile_seconds_total")
NEW = ("setup_import_s", "setup_platform_init_s", "setup_model_compile_s",
       "setup_init_params_s", "setup_serve_build_s", "setup_first_dispatch_s",
       "setup_trace_lower_s", "setup_unnamed_s")


class Setup:
    """A `SetupClock` as the runner leaves it: t0 and the laps in order."""

    def __init__(self, t0, **laps):
        self.t0, self.phases = t0, dict(laps)


class Ctx:
    def __init__(self, setup):
        self.setup = setup


@pytest.fixture
def registry(monkeypatch):
    """The families as the program registers them, emptied before and
    after; `absent` hides them all, as on a tree that has none."""
    def fill(phases=(), first=(), stages=()):
        sec = REGISTRY.gauge("ff_startup_seconds", "", labels=("phase",))
        at = REGISTRY.gauge("ff_startup_phase_at_seconds", "",
                            labels=("phase",))
        for name, start, seconds in phases:
            sec.inc(seconds, phase=name)
            at.set(start, phase=name)
        fsec = REGISTRY.gauge("ff_first_dispatch_seconds", "",
                              labels=("program",))
        fat = REGISTRY.gauge("ff_first_dispatch_at_seconds", "",
                             labels=("program",))
        for name, start, seconds in first:
            fsec.set(seconds, program=name)
            fat.set(start, program=name)
        comp = REGISTRY.counter("ff_compile_seconds_total", "",
                                labels=("program", "stage"))
        for program, stage, seconds in stages:
            comp.inc(seconds, program=program, stage=stage)

    def absent():
        real = REGISTRY.get
        monkeypatch.setattr(
            REGISTRY, "get",
            lambda name: None if name in FAMILIES else real(name))

    for name in FAMILIES:
        REGISTRY.reset_all(name)
    fill.absent = absent
    yield fill
    for name in FAMILIES:
        REGISTRY.reset_all(name)


def _read(name, ctx=None):
    spec = harness.load_metric(name)
    assert spec["reducer"] == "startup"
    return harness.reader_of(spec)(spec, ctx, None)


# process start at 100.0: imports 12 s, build 9 s, data 1 s, warm-up 20 s,
# fill 5 s
SETUP = dict(imports=12.0, build_compile_init=9.0, data=1.0, warmup=20.0,
             fill=5.0, to_window=0.5)
PHASES = [("startup.import", 100.5, 6.0), ("startup.platform", 107.0, 4.5),
          ("compile", 113.0, 3.0), ("search", 113.1, 0.25),
          ("compile.analysis", 113.4, 0.5),
          ("compile.init_params", 114.0, 1.5),
          ("compile.build_steps", 115.6, 0.125),
          ("serve.build", 119.0, 1.75), ("serve.build.kv_alloc", 119.5, 1.0),
          ("serve.build.programs", 119.1, 0.25)]
FIRST = [("decode_all", 125.0, 8.0), ("prefill_chunk", 123.0, 1.5),
         ("prefill_last_chunk", 134.0, 6.0), ("install_prefix", 143.0, 0.5)]
STAGES = [("decode_all", "trace", 1.0), ("decode_all", "lower", 0.5),
          ("decode_all", "backend", 6.0), ("prefill_chunk", "trace", 0.25),
          ("served_gaps", "trace", 9.0), ("_where", "trace", 0.125),
          ("", "cache_load", 2.0)]


def test_phases_with_their_children(registry):
    registry(PHASES)
    assert _read("setup_import_s") == (6.0, {})
    assert _read("setup_platform_init_s") == (4.5, {})
    assert _read("setup_init_params_s") == (1.5, {})
    assert _read("setup_model_compile_s") == (3.0, {
        "search": 0.25, "compile.analysis": 0.5, "compile.init_params": 1.5,
        "compile.build_steps": 0.125})
    assert _read("setup_serve_build_s") == (1.75, {
        "serve.build.kv_alloc": 1.0, "serve.build.programs": 0.25})


def test_a_phase_summed_over_its_runs_and_a_child_that_never_ran(registry):
    registry([("compile", 1.0, 2.0), ("compile", 5.0, 3.0),
              ("compile.init_params", 1.5, 1.0)])
    assert _read("setup_model_compile_s") == (5.0,
                                              {"compile.init_params": 1.0})
    assert _read("setup_serve_build_s") is None      # no batcher was built


def test_first_dispatch_and_the_stages_no_cache_saves(registry):
    registry(first=FIRST, stages=STAGES)
    value, extra = _read("setup_first_dispatch_s")
    assert value == 16.0
    assert extra["by_program"]["prefill_last_chunk"] == 6.0
    value, extra = _read("setup_trace_lower_s")
    # the reference's `served_gaps` and jax's own `_where` are left out
    assert value == 1.75
    assert extra["by_program"] == {
        "decode_all": {"trace": 1.0, "lower": 0.5, "backend": 6.0},
        "prefill_chunk": {"trace": 0.25}}
    assert extra["cache_load_s"] == 2.0


def test_unnamed_is_each_lap_less_what_starts_inside_it(registry):
    registry(PHASES, FIRST, STAGES)
    value, extra = _read("setup_unnamed_s", Ctx(Setup(100.0, **SETUP)))
    laps = extra["laps"]
    assert set(laps) == {"imports", "build_compile_init", "warmup"}
    assert laps["imports"] == {
        "lap_s": 12.0, "named_s": 10.5, "unnamed_s": 1.5,
        "named": {"startup.import": 6.0, "startup.platform": 4.5}}
    # children are not subtracted a second time
    assert laps["build_compile_init"]["named"] == {"compile": 3.0,
                                                   "serve.build": 1.75}
    assert laps["build_compile_init"]["unnamed_s"] == 4.25
    assert laps["warmup"]["named"] == {
        "first_dispatch:prefill_chunk": 1.5, "first_dispatch:decode_all": 8.0,
        "first_dispatch:prefill_last_chunk": 6.0}
    assert laps["warmup"]["unnamed_s"] == 4.5
    # a program the warm-up did not drive fell into `fill`: listed, and left
    # out of every lap
    assert extra["first_calls_after_warmup"] == {"install_prefix": 0.5}
    assert value == 1.5 + 4.25 + 4.5
    for row in laps.values():
        assert row["unnamed_s"] >= 0
        assert row["named_s"] + row["unnamed_s"] == row["lap_s"]


def test_unnamed_in_a_training_run_and_a_phase_inside_another(registry):
    # the mesh is the first to ask for the devices: inside `compile`
    registry([("startup.import", 50.0, 2.0), ("compile", 53.0, 4.0),
              ("startup.platform", 53.5, 3.0)],
             [("multi_step", 58.5, 10.0)])
    setup = Setup(49.0, imports=3.5, build_compile_init=5.0, data=0.5,
                  first_dispatch=14.0, to_window=0.0)
    value, extra = _read("setup_unnamed_s", Ctx(setup))
    assert extra["laps"]["imports"]["named"] == {"startup.import": 2.0}
    assert extra["laps"]["build_compile_init"]["named"] == {"compile": 4.0}
    assert extra["laps"]["first_dispatch"]["unnamed_s"] == 4.0
    assert value == 1.5 + 1.0 + 4.0
    assert extra["first_calls_after_warmup"] == {}


def test_a_phase_before_the_clock_started_is_in_no_lap(registry):
    # a rehearsal makes its clock long after the package was imported
    registry([("startup.import", 10.0, 2.0), ("compile", 101.0, 1.0)])
    setup = Setup(100.0, imports=0.0, build_compile_init=3.0, warmup=1.0)
    value, extra = _read("setup_unnamed_s", Ctx(setup))
    assert extra["laps"]["imports"] == {"lap_s": 0.0, "named_s": 0.0,
                                        "unnamed_s": 0.0, "named": {}}
    assert value == 2.0 + 1.0


def test_every_reader_reads_none_where_the_program_keeps_no_family(registry):
    registry.absent()
    ctx = Ctx(Setup(100.0, **SETUP))
    assert [_read(name, ctx) for name in NEW] == [None] * len(NEW)


def test_an_empty_record_is_zero_not_missing(registry):
    """The families are there and hold nothing (no program ran yet)."""
    registry()
    assert _read("setup_first_dispatch_s") == (0.0, {"by_program": {}})
    assert _read("setup_trace_lower_s")[0] == 0.0
    assert _read("setup_import_s") is None
    value, extra = _read("setup_unnamed_s", Ctx(Setup(0.0, imports=2.0,
                                                      warmup=3.0)))
    assert value == 5.0 and extra["laps"]["warmup"]["named"] == {}


def test_the_manifest_lists_the_eight(registry):
    manifest = harness.load_manifest()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"][-8:]] == list(NEW)
    for name in NEW:
        m = by_name[name]
        assert (m["layer"], m["moves"], m["unit"], m["better"],
                m["source"]) == ("entry points", "setup_s", "s", "lower",
                                 "program_counter")
    served = [c["name"] for c in manifest["workloads"]
              if c["name"] != "bert_train_1chip"]
    assert by_name["setup_serve_build_s"]["workloads"] == served
    assert all("workloads" not in by_name[n] for n in NEW
               if n != "setup_serve_build_s")
    for cell in manifest["workloads"]:
        mine = {m["name"] for m in harness.cell_metrics(
            manifest, cell["name"], "per_layer")}
        want = set(NEW) - ({"setup_serve_build_s"}
                           if cell["name"] == "bert_train_1chip" else set())
        assert want == mine & set(NEW), cell["name"]
