"""Cold start on the record (docs/observability.md "Cold start").

What a process does ONCE — import the package, create the TPU client,
`FFModel.compile()`, build a `ContinuousBatcher`, call each jitted program
for the first time — happens before any profiler is open and before a
runner enables the tracer's ring, so a span alone would go nowhere. The
sinks here are families of the default registry, which outlive every
window and are read after the run:

  ff_startup_seconds{phase}            wall seconds `Tracer.phase()` summed
  ff_startup_phase_runs_total{phase}   how many times that phase ran
  ff_startup_phase_at_seconds{phase}   start of its latest run, on
                                       `time.perf_counter()`
  ff_first_dispatch_seconds{program}   host seconds of a program's first
                                       call (`tracing.first_call`), and
  ff_first_dispatch_at_seconds{program}  its start on `time.perf_counter()`
  ff_compile_seconds_total{program,stage}  jax.monitoring's compile stages
  ff_compiles_total{program}           backend compiles (a cache load counts)
  ff_compile_cache_total{result}       the persistent cache's hits / misses

`program` is the jitted function's own name (`decode_all`, `multi_step`):
what jax.monitoring passes as `fun_name`, less its `jit(...)` wrapping.
"""
from __future__ import annotations

from typing import List, Tuple

from .registry import REGISTRY

# jax.monitoring duration event -> stage label
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_CACHE_RESULTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


def record_phase(name: str, t0: float, seconds: float) -> None:
    """The sink of `Tracer.phase()` (`t0`: its start on
    `time.perf_counter()`); `flexflow_tpu/__init__.py` calls it directly
    for the import it cannot wrap."""
    REGISTRY.gauge(
        "ff_startup_seconds",
        "Wall seconds of a one-shot start-up phase, summed over its runs",
        labels=("phase",)).inc(seconds, phase=name)
    REGISTRY.gauge(
        "ff_startup_phase_at_seconds",
        "time.perf_counter() at the start of a phase's latest run",
        labels=("phase",)).set(t0, phase=name)
    REGISTRY.counter(
        "ff_startup_phase_runs_total",
        "Runs of a one-shot start-up phase", labels=("phase",)
    ).inc(phase=name)


def record_first_dispatch(program: str, t0: float, seconds: float) -> None:
    REGISTRY.gauge(
        "ff_first_dispatch_seconds",
        "Host seconds of a jitted program's first call, dispatch to return"
        " (trace, lowering, compile or cache load, transfer, enqueue)",
        labels=("program",)).set(seconds, program=program)
    REGISTRY.gauge(
        "ff_first_dispatch_at_seconds",
        "time.perf_counter() at the start of a program's first call",
        labels=("program",)).set(t0, program=program)


def _program(fun_name) -> str:
    """`jit(decode_all)` -> `decode_all`: the trace stage names the function,
    the later stages its module."""
    name = str(fun_name or "")
    if name.endswith(")") and name.startswith(("jit(", "pmap(")):
        return name[name.index("(") + 1:-1]
    return name


# seconds of the latest trace of each function name, kept until that name is
# LOWERED: a program's trace passes through every jitted library function it
# calls (`_where`, `_einsum`: thousands of events in one train step), and
# only what goes on to be compiled is a program
_traced: dict = {}


def _on_duration(event: str, seconds: float, fun_name="", **_) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        return
    program = _program(fun_name)
    if stage == "trace":
        _traced[program] = seconds
        return
    family = REGISTRY.counter(
        "ff_compile_seconds_total",
        "Seconds jax spent compiling, by program and stage (trace, lower,"
        " backend: a persistent-cache load counts; cache_load: the load"
        " alone, which jax does not name a program for)",
        labels=("program", "stage"))
    if stage == "lower" and program in _traced:
        family.inc(_traced.pop(program), program=program, stage="trace")
    family.inc(seconds, program=program, stage=stage)
    if stage == "backend":
        REGISTRY.counter(
            "ff_compiles_total",
            "Backend compiles (a persistent-cache load counts as one)",
            labels=("program",)).inc(program=program)


def _on_event(event: str, **_) -> None:
    result = _CACHE_RESULTS.get(event)
    if result is not None:
        REGISTRY.counter(
            "ff_compile_cache_total",
            "Persistent compilation cache lookups by result",
            labels=("result",)).inc(result=result)


_watching = False


def watch_compiles() -> None:
    """Register the jax.monitoring listeners, once a process: called by the
    first `FFModel.compile()` or `ContinuousBatcher`, never at import. The
    listeners run at compiles only; a warm dispatch fires no event."""
    global _watching
    if _watching:
        return
    _watching = True
    import jax.monitoring as mon

    mon.register_event_duration_secs_listener(_on_duration)
    mon.register_event_listener(_on_event)


def startup_table() -> List[Tuple[str, float]]:
    """(name, seconds) rows of what the process has recorded so far: the
    phases, then each program's first call — `chip_smoke.py` prints it."""
    rows: List[Tuple[str, float]] = []
    for family, prefix in (("ff_startup_seconds", ""),
                           ("ff_first_dispatch_seconds", "first_dispatch:")):
        fam = REGISTRY.get(family)
        for (label,), v in (fam.items() if fam is not None else ()):
            rows.append((prefix + label, v))
    return rows
