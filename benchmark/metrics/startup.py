"""Reader of the program's own record of its cold start (PR 35).

The program keeps, in its process-wide registry, what it does once a process
or once a model (`flexflow_tpu/obs/startup.py`): `ff_startup_seconds{phase}`
with each phase's start `ff_startup_phase_at_seconds{phase}`, every jitted
program's first call `ff_first_dispatch_seconds{program}` with its start
`ff_first_dispatch_at_seconds{program}`, and jax.monitoring's compile stages
by program `ff_compile_seconds_total{program, stage}`. Starts are on
`time.perf_counter()`, the clock `harness.SetupClock` laps on. A metric's
JSON names this module as its `reducer` and a `kind`:

  phase           seconds of the phase `phase`; beside it (extra keys) the
                  phases `children` names
  first_dispatch  sum over programs of their first call's host seconds
                  (dispatch to return, not the device's run); beside it the
                  seconds by program
  trace_lower     sum of the `trace` and `lower` stages over the programs
                  `ff_first_dispatch_seconds` names (the plain reference's
                  own jits run in this process too and are left out): the
                  part of a first call no compile cache saves; beside it by
                  program, with `backend` and `cache_load`
  unnamed         the laps of the run's `SetupClock` in which the PROGRAM is
                  being set up (`laps`), less what the program names inside
                  them: the top-level phases `phases` and the first calls,
                  each counted in the lap its start falls into and skipped
                  where it lies inside another. Beside it each lap with its
                  named and unnamed part, and `first_calls_after_warmup`:
                  the first calls that fell into a later lap (a program the
                  warm-up did not drive)

A program that keeps no such family (a parent commit) reads None, and the
metric is left out of the line.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple


def _family(name: str) -> Optional[Dict[Tuple[str, ...], float]]:
    from flexflow_tpu.obs.registry import get_registry

    family = get_registry().get(name)
    return dict(family.items()) if family is not None else None


def _by_label(name: str) -> Optional[Dict[str, float]]:
    """{label value: sample} of a family with one label."""
    family = _family(name)
    return None if family is None else {k[0]: v for k, v in family.items()}


def _phase(spec: Dict):
    seconds = _by_label("ff_startup_seconds")
    if seconds is None or spec["phase"] not in seconds:
        return None
    return seconds[spec["phase"]], {
        c: seconds[c] for c in spec.get("children", []) if c in seconds}


def _first_dispatch(spec: Dict):
    first = _by_label("ff_first_dispatch_seconds")
    if first is None:
        return None
    return sum(first.values()), {"by_program": first}


def _trace_lower(spec: Dict):
    first = _by_label("ff_first_dispatch_seconds")
    stages = _family("ff_compile_seconds_total")
    if first is None or stages is None:
        return None
    by_program: Dict[str, Dict[str, float]] = {}
    for (program, stage), v in stages.items():
        if program in first:
            by_program.setdefault(program, {})[stage] = v
    total = sum(s.get("trace", 0.0) + s.get("lower", 0.0)
                for s in by_program.values())
    return total, {"by_program": by_program,
                   "cache_load_s": stages.get(("", "cache_load"), 0.0)}


def lap_intervals(setup) -> List[Tuple[str, float, float]]:
    """(name, start, end) of a `SetupClock`'s laps on `time.perf_counter()`:
    they follow one another from its `t0` in the order they were taken."""
    out, t = [], setup.t0
    for name, seconds in setup.phases.items():
        out.append((name, t, t + seconds))
        t += seconds
    return out


def _unnamed(spec: Dict, ctx):
    seconds = _by_label("ff_startup_seconds")
    starts = _by_label("ff_startup_phase_at_seconds")
    first = _by_label("ff_first_dispatch_seconds")
    first_at = _by_label("ff_first_dispatch_at_seconds")
    if None in (seconds, starts, first, first_at):
        return None
    items = [(starts[p], seconds[p], p) for p in spec["phases"]
             if p in seconds and p in starts]
    items += [(first_at[p], first[p], f"first_dispatch:{p}")
              for p in first if p in first_at]
    items.sort()
    intervals = lap_intervals(ctx.setup)
    laps = {name: {"lap_s": end - start, "named_s": 0.0, "named": {}}
            for name, start, end in intervals if name in spec["laps"]}
    later: Dict[str, float] = {}
    covered_until = float("-inf")
    for start, secs, name in items:
        if start < covered_until:
            continue            # inside a phase that is already counted
        covered_until = start + secs
        lap = next((n for n, lo, hi in intervals if lo <= start < hi), None)
        if lap in laps:
            laps[lap]["named_s"] += secs
            laps[lap]["named"][name] = secs
        elif name.startswith("first_dispatch:"):
            later[name.split(":", 1)[1]] = secs
    if not laps:
        return None
    for lap in laps.values():
        lap["unnamed_s"] = lap["lap_s"] - lap["named_s"]
    return (sum(lap["unnamed_s"] for lap in laps.values()),
            {"laps": laps, "first_calls_after_warmup": later})


def read(spec: Dict, ctx, rec):
    kind = spec["kind"]
    if kind == "phase":
        return _phase(spec)
    if kind == "first_dispatch":
        return _first_dispatch(spec)
    if kind == "trace_lower":
        return _trace_lower(spec)
    if kind == "unnamed":
        return _unnamed(spec, ctx)
    raise ValueError(f"startup: unknown kind {kind!r}")
