"""Reader of the program's own spans in a profiler trace.

Every `Tracer.span()` of the program is also a profiler annotation
(`flexflow_tpu/obs/tracing.py`), so a captured trace holds the program's
spans in its host plane, on the thread that did the work and on the device
trace's clock. This reader reduces them, clipped to the trace's
`[lo_ps, hi_ps)`, by the metric file's `"stat"`:

  p50_ms              median duration of the `"span"` spans that lie wholly
                      inside the window; `"where_positive"` names an arg
                      that has to be above 0 (`decode_slots`)
  ms_per              time the `"spans"` cover (a span nested in another
                      counts once) plus the self time of the `"self_of"`
                      spans, per run of `"program"`
  self_ms_per         `ms_per` of `"self_of"` alone: a span's duration less
                      what the program's other spans nested inside it cover
  idle_unnamed_share  share of the first chip's idle time whose gap's middle
                      lies under no LEAF span of the program (one with no
                      other program span inside it): idle time the program
                      has no name for, %

A program span is a host event named `serve.*`, `fit.*` or `executor.*`. A
program that opens no such annotation (a parent commit) reads None, and the
metric is left out of the line.
"""
from __future__ import annotations

import bisect
import statistics
from typing import Dict, List, Sequence, Tuple

from .. import xplane

PROGRAM_SPANS = ("serve.", "fit.", "executor.")
STATS = ("p50_ms", "ms_per", "self_ms_per", "idle_unnamed_share")

Interval = Tuple[int, int]


def program_threads(trace) -> List[List[xplane.Event]]:
    """Per host thread that has any, the program's spans overlapping the
    window, by start."""
    out = []
    for _name, events in trace.host:
        mine = [e for e in events if e.name.startswith(PROGRAM_SPANS)
                and e.end_ps > trace.lo_ps and e.start_ps < trace.hi_ps]
        if mine:
            out.append(sorted(mine, key=lambda e: (e.start_ps, -e.dur_ps)))
    return out


def _clip(events, trace) -> List[Interval]:
    return [(max(e.start_ps, trace.lo_ps), min(e.end_ps, trace.hi_ps))
            for e in events]


def covered_ps(threads, names: Sequence[str], trace) -> int:
    """Time the named spans cover, each thread for itself."""
    return sum(xplane.union_ps(_clip([e for e in t if e.name in names],
                                     trace)) for t in threads)


def self_ps(threads, names: Sequence[str], trace) -> int:
    """The named spans' time less what the program's other spans nested
    inside them cover (the named spans do not nest in one another)."""
    total = 0
    for t in threads:
        starts = [e.start_ps for e in t]
        for i, span in enumerate(t):
            if span.name not in names:
                continue
            # by (start, longest first): what starts before `span` ends and
            # comes after it is nested in it
            j = bisect.bisect_left(starts, span.end_ps)
            inside = [e for e in t[i + 1:j] if e.name not in names]
            total += xplane.subtract_ps(_clip([span], trace),
                                        _clip(inside, trace))
    return total


def leaf_intervals(threads) -> List[Interval]:
    """The program's spans that hold no other program span, all threads
    together, merged and by start."""
    leaves: List[Interval] = []
    for t in threads:
        for i, e in enumerate(t):
            # sorted by (start, longest first): a child comes right after
            nxt = t[i + 1] if i + 1 < len(t) else None
            if nxt is None or nxt.start_ps >= e.end_ps:
                leaves.append((e.start_ps, e.end_ps))
    merged: List[Interval] = []
    for s, e in sorted(leaves):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def idle_unnamed_share(trace, threads) -> float:
    ops = trace.ops[min(trace.ops)]
    gaps = xplane.gaps_ps(((o.start_ps, o.end_ps) for o in ops),
                          trace.lo_ps, trace.hi_ps)
    leaves = leaf_intervals(threads)
    starts = [s for s, _e in leaves]
    idle = unnamed = 0
    for g0, g1 in gaps:
        idle += g1 - g0
        mid = (g0 + g1) // 2
        j = bisect.bisect_right(starts, mid) - 1
        if j < 0 or mid >= leaves[j][1]:
            unnamed += g1 - g0
    return 100.0 * unnamed / idle if idle else 0.0


def read(spec: Dict, ctx, rec):
    stat = spec["stat"]
    if stat not in STATS:
        raise ValueError(f"unknown stat {stat!r}")
    trace = rec.trace
    if trace is None or not trace.ops:
        return None
    threads = program_threads(trace)
    if not threads:
        return None
    if stat == "idle_unnamed_share":
        return idle_unnamed_share(trace, threads)
    if stat == "p50_ms":
        key = spec.get("where_positive")
        durs = [e.dur_ps / 1e9 for t in threads for e in t
                if e.name == spec["span"]
                and e.start_ps >= trace.lo_ps and e.end_ps <= trace.hi_ps
                and (key is None or float(e.stats.get(key, 0) or 0) > 0)]
        return statistics.median(durs) if durs else None
    whole = () if stat == "self_ms_per" else tuple(spec.get("spans", ()))
    own = tuple(spec.get("self_of", ()))
    named = frozenset(whole + own)
    if not any(e.name in named for t in threads for e in t):
        return None
    runs = trace.module_runs(spec["program"])
    if not runs:
        return None
    ps = covered_ps(threads, whole, trace) + self_ps(threads, own, trace)
    return ps / 1e9 / runs
