"""Cold start end to end (docs/observability.md "Cold start"): a toy model
compiled and served through `ContinuousBatcher` leaves its phases, its
programs' first calls and their compile stages in the default registry, the
programs' attributes hold the bare jitted functions again, warm passes fire
no compile event — and the benchmark's reader (`benchmark/metrics/startup.py`)
turns that record into the eight `setup_*_s` metrics."""
import math
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.metrics import startup as reader
from flexflow_tpu import obs
from flexflow_tpu.obs.tracing import _FirstCall
from flexflow_tpu.serving.sched import ContinuousBatcher
from tests.conftest import module_xla_cache
from tests.test_generate import _build_lm

# module-scoped XLA compilation cache — see conftest.module_xla_cache
_xla_cache = pytest.fixture(scope="module", autouse=True)(module_xla_cache)

PROGRAMS = ("_prefill_fn", "_decode_fn", "_chunk_fn", "_last_chunk_fn",
            "_install_fn", "_insert_fn", "_import_fn")
NEW_METRICS = ("setup_import_s", "setup_platform_init_s",
               "setup_model_compile_s", "setup_init_params_s",
               "setup_serve_build_s", "setup_first_dispatch_s",
               "setup_trace_lower_s", "setup_unnamed_s")


def _value(family, **labels):
    fam = obs.REGISTRY.get(family)
    return fam.value(**labels) if fam is not None else 0.0


def _programs(family="ff_first_dispatch_seconds"):
    fam = obs.REGISTRY.get(family)
    return {k[0]: v for k, v in fam.items()} if fam is not None else {}


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 50, size=(n,)).astype(
        np.int32)


class _CompileEvents:
    """Every compile event jax.monitoring fires while listening: what the
    program's own listeners (obs/startup.py) are called with."""

    def __init__(self):
        import jax.monitoring as mon

        self.seen = []
        self.listening = False
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if self.listening and event.startswith("/jax/core/compile/"):
            self.seen.append((event, kw))


@pytest.fixture(scope="module")
def compile_events():
    return _CompileEvents()


@pytest.mark.parametrize("chunk", [0, 4], ids=["one_shot", "chunked"])
def test_batcher_first_calls_are_recorded_and_leave_bare_functions(chunk):
    lm = _build_lm(2, 12)
    with ContinuousBatcher(lm, max_len=12, num_slots=2, page_size=4,
                           max_queue=8, prefill_chunk_tokens=chunk) as cb:
        assert all(isinstance(getattr(cb, a), _FirstCall) for a in PROGRAMS)
        # what the compile tools do before any call still works
        assert cb._decode_fn.__name__ == "decode_all"
        assert callable(cb._decode_fn.lower)
        cb.submit(_prompt(6), 4).result(timeout=300)
    ran = {"decode_all"} | ({"prefill_one"} if chunk == 0 else
                            {"prefill_chunk", "prefill_last_chunk",
                             "insert_pages"})
    first = _programs()
    assert ran <= set(first) <= ran | {"install_prefix"}
    assert all(0 < first[p] < 120 for p in ran)
    at = _programs("ff_first_dispatch_at_seconds")
    assert all(0 < at[p] <= time.perf_counter() - first[p] for p in ran)
    called = {a for a in PROGRAMS if not isinstance(getattr(cb, a),
                                                    _FirstCall)}
    assert "_decode_fn" in called and "_import_fn" not in called
    for attr in called:     # the bare jitted function: jax's own type
        assert type(getattr(cb, attr)).__module__.startswith("jax"), attr
    for program in ran:
        assert _value("ff_compiles_total", program=program) == 1, program
        for stage in ("trace", "lower", "backend"):
            assert _value("ff_compile_seconds_total", program=program,
                          stage=stage) > 0, (program, stage)
    for phase in ("serve.build", "serve.build.kv_alloc",
                  "serve.build.programs"):
        assert _value("ff_startup_phase_runs_total", phase=phase) == 1
    assert _value("ff_startup_seconds", phase="serve.build") >= \
        _value("ff_startup_seconds", phase="serve.build.kv_alloc") + \
        _value("ff_startup_seconds", phase="serve.build.programs") > 0


def test_a_recompile_is_named_and_warm_passes_fire_no_event(compile_events):
    import jax.numpy as jnp

    lm = _build_lm(2, 12)
    with ContinuousBatcher(lm, max_len=12, num_slots=2, page_size=4,
                           max_queue=8, prefill_chunk_tokens=0) as cb:
        cb.submit(_prompt(6), 4).result(timeout=300)        # warm-up
        assert _value("ff_compiles_total", program="prefill_one") == 1
        compile_events.listening = True
        try:
            for seed in (1, 2, 3):      # further warm prefills and decodes
                cb.submit(_prompt(3 + seed, seed), 5).result(timeout=300)
        finally:
            compile_events.listening = False
        assert compile_events.seen == []
        assert _value("ff_compiles_total", program="prefill_one") == 1
        assert _value("ff_compiles_total", program="decode_all") == 1
    # a second prompt SHAPE of the same program: which one recompiled
    tok, _caches = cb._prefill_fn(
        lm.params, lm.state, cb._caches, jnp.zeros((1, 8), jnp.int32), 0, 4,
        jnp.zeros((2,), jnp.uint32))
    int(tok)
    assert _value("ff_compiles_total", program="prefill_one") == 2
    assert _value("ff_compiles_total", program="decode_all") == 1


def test_the_reader_accounts_for_a_toy_set_up():
    """The program's record through the benchmark's reader: every metric
    finite and >= 0, and per lap named + unnamed = lap."""
    setup = harness.SetupClock(time.perf_counter())
    lm = _build_lm(2, 12)
    cb = ContinuousBatcher(lm, max_len=12, num_slots=2, page_size=4,
                           max_queue=8)
    setup.lap("build_compile_init")
    time.sleep(0.02)
    setup.lap("data")
    with cb:
        cb.submit(_prompt(6), 3).result(timeout=300)
        setup.lap("warmup")
        # a program the warm-up did not drive: its first call is in `fill`
        cb.submit(_prompt(6), 3).result(timeout=300)   # a prefix hit
        setup.lap("fill")
    setup.window_opens()

    class Ctx:
        pass

    ctx = Ctx()
    ctx.setup = setup
    got = {}
    for name in NEW_METRICS:
        spec = harness.load_metric(name)
        assert harness.reader_of(spec) is reader.read
        out = reader.read(spec, ctx, None)
        if out is not None:
            got[name] = out
    # the package was imported and the backend made before this test (the
    # suite's fixture zeroes the registry between tests)
    assert set(NEW_METRICS) - set(got) == {"setup_import_s",
                                           "setup_platform_init_s"}
    for name, (value, extra) in got.items():
        assert math.isfinite(value) and value >= 0, name
    value, extra = got["setup_model_compile_s"]
    assert value >= sum(extra.values()) > 0
    assert {"compile.analysis", "compile.init_params",
            "compile.build_steps"} == set(extra)    # no search ran
    assert got["setup_init_params_s"][0] == extra["compile.init_params"]
    value, extra = got["setup_serve_build_s"]
    assert value >= sum(extra.values()) and len(extra) == 2
    value, extra = got["setup_first_dispatch_s"]
    by_program = extra["by_program"]
    assert value == pytest.approx(sum(by_program.values()))
    assert {"decode_all", "prefill_chunk", "prefill_last_chunk",
            "insert_pages", "install_prefix"} == set(by_program)
    value, extra = got["setup_trace_lower_s"]
    assert set(extra["by_program"]) == set(by_program)
    assert value == pytest.approx(sum(
        s["trace"] + s["lower"] for s in extra["by_program"].values()))
    assert 0 < value < got["setup_first_dispatch_s"][0]
    value, extra = got["setup_unnamed_s"]
    laps = extra["laps"]
    assert set(laps) == {"imports", "build_compile_init", "warmup"}
    for lap, row in laps.items():
        assert row["unnamed_s"] >= 0, (lap, row)
        assert row["named_s"] + row["unnamed_s"] == pytest.approx(
            row["lap_s"])
        assert row["lap_s"] == pytest.approx(setup.phases[lap])
        assert row["named_s"] == pytest.approx(sum(row["named"].values()))
    assert set(laps["build_compile_init"]["named"]) == {"compile",
                                                        "serve.build"}
    assert set(laps["warmup"]["named"]) == {
        f"first_dispatch:{p}" for p in by_program if p != "install_prefix"}
    assert set(extra["first_calls_after_warmup"]) == {"install_prefix"}
    assert value == pytest.approx(sum(r["unnamed_s"] for r in laps.values()))
