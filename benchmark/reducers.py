"""The generic readers a per-layer metric's JSON names (`"reducer"`), each
`(spec, ctx, record) -> value | (value, extra keys) | None`. None means
"nothing to read here": the metric is left out of the line — a share of a
roofline or of a peak is never reported as 0.

  series_stat      a statistic of a host-clock series the runner recorded
                   (spans, token gaps, waits, lateness, step times)
  counter          a number the runner counted
  device_time      device time of selected ops per unit of work, ms
  roofline         least time by the chip's peaks / device time of the
                   selected ops, %, with the side that bounds it
  mfu              operations the window needed / (window x chips' peak), %
  memory_share     peak bytes of the fullest chip / its capacity, %

A reader of another kind is a module `metrics/<reducer>.py` with a `read` of
the same signature (`harness.reader_of`).

Selecting device ops (`"select"`), always inside the compiled programs whose
name holds `"program"`:
  {"kind": "copy"}                 ops that move data and compute nothing
  {"kind": "scope", "part": s}     ops under a named scope holding `s`
  {"kind": "kernel", "part": s}    custom calls under such a scope
Units of work (`"per"`): "program_runs" (executions of the program in the
trace) or "work:<key>" (a count the runner recorded for the traced window).
"""
from __future__ import annotations

import statistics
from typing import Dict, Optional

from . import peaks as peaks_mod
from . import shapes, xplane


def _stat(values, stat: str) -> Optional[float]:
    vals = sorted(float(v) for v in values)
    if not vals:
        return None
    if stat == "p50":
        return statistics.median(vals)
    if stat == "mean":
        return sum(vals) / len(vals)
    if stat == "max":
        return vals[-1]
    if stat.startswith("p"):
        q = float(stat[1:]) / 100.0
        # nearest-rank: the smallest value with at least q of the sample
        # at or below it
        return vals[min(len(vals) - 1, max(0, int(-(-q * len(vals) // 1)) - 1))]
    raise ValueError(f"unknown stat {stat!r}")


def percentile(values, q: float) -> float:
    """Nearest-rank percentile, q in [0, 100]; the end-to-end tails use it
    too."""
    return _stat(values, f"p{q:g}")


def series_stat(spec: Dict, ctx, rec):
    vals = rec.series.get(spec["series"])
    if not vals:
        return None
    return _stat(vals, spec["stat"])


def counter(spec: Dict, ctx, rec):
    v = rec.counters.get(spec["counter"])
    return None if v is None else float(v)


def _selector(spec: Dict, trace):
    ids = trace.program_ids(spec["program"]) if spec.get("program") else None
    sel = spec["select"]
    kind = sel["kind"]

    def in_program(o):
        return ids is None or o.program_id in ids

    if kind == "copy":
        return lambda o: in_program(o) and xplane.is_copy(o)
    if kind == "scope":
        return lambda o: in_program(o) and sel["part"] in o.scope
    if kind == "kernel":
        return lambda o: (in_program(o) and sel["part"] in o.scope
                          and o.opcode == "custom-call")
    raise ValueError(f"unknown select kind {kind!r}")


def _units(spec: Dict, trace, rec) -> Optional[float]:
    per = spec.get("per", "program_runs")
    if per == "program_runs":
        return float(trace.module_runs(spec["program"]))
    if per.startswith("work:"):
        v = rec.work.get(per[5:])
        return None if v is None else float(v)
    raise ValueError(f"unknown per {per!r}")


def device_time(spec: Dict, ctx, rec):
    if rec.trace is None or not rec.trace.ops:
        return None
    units = _units(spec, rec.trace, rec)
    if not units:
        return None
    secs = rec.trace.per_chip(_selector(spec, rec.trace))
    return secs / units * 1e3


def roofline(spec: Dict, ctx, rec):
    if rec.trace is None or not rec.trace.ops:
        return None
    secs = rec.trace.per_chip(_selector(spec, rec.trace))
    if secs <= 0.0:
        return None
    need = spec.get("needs_work", [])
    if any(k not in rec.work for k in need):
        return None
    flops, nbytes = shapes.SHAPE_FNS[spec["shape_fn"]](ctx.config, rec.work)
    if spec.get("scale_by_work"):   # the shape function counts one unit
        n = float(rec.work[spec["scale_by_work"]])
        flops, nbytes = flops * n, nbytes * n
    pk = peaks_mod.peaks_for(ctx.devices[0].device_kind)
    least, side = peaks_mod.least_time_s(flops, nbytes, pk)
    return 100.0 * least / secs, {"bound_by": side}


def mfu(spec: Dict, ctx, rec):
    if rec.trace is None or rec.trace.window_s <= 0:
        return None
    need = spec.get("needs_work", [])
    if any(k not in rec.work for k in need):
        return None
    flops = shapes.SHAPE_FNS[spec["shape_fn"]](ctx.config, rec.work)
    pk = peaks_mod.peaks_for(ctx.devices[0].device_kind)
    # work is counted per chip (train) or on the one chip (serve)
    return (100.0 * flops / (rec.trace.window_s * pk.bf16_flops_per_s),
            {"bound_by": "mxu"})


def memory_share(spec: Dict, ctx, rec):
    if rec.memory_peak_bytes <= 0:
        return None
    pk = peaks_mod.peaks_for(ctx.devices[0].device_kind)
    return 100.0 * rec.memory_peak_bytes / pk.hbm_bytes


REDUCERS = {
    "series_stat": series_stat, "counter": counter,
    "device_time": device_time, "roofline": roofline, "mfu": mfu,
    "memory_share": memory_share,
}
