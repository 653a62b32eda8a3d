"""What every cell's run shares: the manifest, the clocks, the trace capture,
the per-layer readers and the result line. Nothing here knows a cell by
name: a cell is its entry in BENCHMARK.json, `configs/<config>.json`,
`traffic/<traffic>.json` and the `metrics/<name>.json` of its per-layer
metrics.
"""
from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# -- manifest ----------------------------------------------------------------
def load_manifest(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str, root: str = HERE) -> Dict:
    with open(os.path.join(root, "configs", f"{name}.json")) as f:
        return json.load(f)


def load_metric(name: str, root: str = HERE) -> Dict:
    with open(os.path.join(root, "metrics", f"{name}.json")) as f:
        return json.load(f)


def apply_matmul_precision(config: Dict) -> None:
    """A configuration that states how float32 operands are multiplied
    (`matmul_precision`: "highest" = true float32) is run so: JAX's own
    default for every matrix product that names no precision, set before
    anything is traced. Unset, the TPU multiplies float32 operands in one
    bf16 pass (PERF.md section 2)."""
    prec = config.get("matmul_precision")
    if prec:
        import jax

        jax.config.update("jax_default_matmul_precision", str(prec))


def find_cell(manifest: Dict, workload: str) -> Dict:
    for cell in manifest["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"benchmark: no workload {workload!r} in BENCHMARK.json;"
                     f" it has {[c['name'] for c in manifest['workloads']]}")


def cell_metrics(manifest: Dict, cell: str, kind: str) -> List[Dict]:
    """The `end_to_end` or `per_layer` entries this cell reports: those that
    list it under `workloads`, and those with no such key whose end-to-end
    metric (itself, or the one it `moves`) the cell reports."""
    e2e_here = {m["name"] for m in manifest["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]}
    if kind == "end_to_end":
        return [m for m in manifest["end_to_end"] if m["name"] in e2e_here]
    return [m for m in manifest["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e_here)]


def module_of(package: str, name: str):
    """`benchmark.<package>.<name>`: a builder, runner or reference found by
    the name a JSON file gives."""
    if not name.replace("_", "").isalnum():
        raise ValueError(f"benchmark: bad module name {name!r}")
    return importlib.import_module(f"benchmark.{package}.{name}")


def open_compile_cache() -> str:
    """The program's own rule for where the cache lives
    ($JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache), with every
    program written to it, whatever it took to compile, and nothing evicted:
    under a size limit one entry that has lost its access-time file makes
    every later write fail (seen on the chip machine, PERF.md section 6),
    and every run would compile again."""
    import jax
    from flexflow_tpu.runtime.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return cache_dir


# -- clocks ------------------------------------------------------------------
class CompileClock:
    """Backend compiles (a persistent-cache load counts as one, with its
    seconds) and the cache's hits and misses, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.count = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds += secs
            self.count += 1

    def _on_event(self, event, **_):
        if event.endswith("compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.misses += 1

    def snapshot(self):
        return {"seconds": self.seconds, "count": self.count,
                "hits": self.hits, "misses": self.misses}


class SetupClock:
    """Set-up split into named phases; `setup_s` is process start to window
    open, so nothing falls between the phases unseen."""

    def __init__(self, t_process_start: float):
        self.t0 = t_process_start
        self.phases: Dict[str, float] = {}
        self._mark = time.perf_counter()
        self.phases["imports"] = self._mark - t_process_start

    def lap(self, name: str) -> float:
        now = time.perf_counter()
        self.phases[name] = self.phases.get(name, 0.0) + now - self._mark
        self._mark = now
        return self.phases[name]

    def window_opens(self) -> float:
        self.lap("to_window")
        return time.perf_counter() - self.t0


def log(kind: str, **fields) -> None:
    """One of the earlier lines of standard output."""
    print(json.dumps({"bench": kind, **fields}), flush=True)


# -- the run's context and record ----------------------------------------------
@dataclass
class RunContext:
    manifest: Dict
    cell: Dict
    config: Dict
    traffic: Dict
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    setup: SetupClock
    compiles: CompileClock
    trace_seconds: float = 6.0
    sizes: Dict[str, Any] = field(default_factory=dict)  # tests shrink here
    roots: tuple = (HERE,)       # where metrics/<name>.json is looked for

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])


@dataclass
class Record:
    """What a runner hands back for the result line."""
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: Dict[str, Dict[str, float]]      # name -> {"value", "limit"}
    memory_peak_bytes: int
    series: Dict[str, List[float]] = field(default_factory=dict)
    counters: Dict[str, float] = field(default_factory=dict)
    work: Dict[str, float] = field(default_factory=dict)   # traced window
    trace: Any = None                        # xplane.TraceSummary
    notes: Dict[str, Any] = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            _finite(c["value"]) and c["value"] <= c["limit"]
            for c in self.checks.values())


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and x == x and abs(x) != float("inf")


# -- trace capture -------------------------------------------------------------
class TraceCapture:
    """`with TraceCapture() as t:` profiles what runs inside; afterwards
    `t.summary()` reads the device planes and the host lines, and the
    files are deleted (a trace is tens of MB and the host keeps every block
    ever written). Python-level tracing is off: it would slow the very host
    loop the trace is there to watch."""

    def __init__(self):
        self.dir: Optional[str] = None
        self.t0 = self.t1 = 0.0

    def __enter__(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        import jax

        self.t1 = time.monotonic()
        jax.profiler.stop_trace()
        return False

    def summary(self):
        from . import xplane

        try:
            path = xplane.find_xplane(self.dir)
            nbytes = os.path.getsize(path)
            planes = xplane.read_xspace(
                path, want_line=lambda plane, line: (
                    plane.startswith("/device:TPU:")
                    and line in ("XLA Ops", "XLA Modules"))
                or plane.startswith("/host:CPU"))
            log("trace", xplane_bytes=nbytes, host_window_s=self.t1 - self.t0)
            return xplane.summarize(planes)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# -- per-layer metrics -----------------------------------------------------------
def reader_of(spec: Dict):
    """The reader a metric's file names: one of `reducers.REDUCERS`, or
    `benchmark/metrics/<reducer>.py` with a `read(spec, ctx, record)` of its
    own, which a later PR can add as a file."""
    from . import reducers

    fn = reducers.REDUCERS.get(spec["reducer"])
    return fn if fn is not None else module_of("metrics", spec["reducer"]).read


def per_layer_values(ctx: RunContext, rec: Record) -> Dict:
    """Each per-layer metric of the cell through its own reader
    (`metrics/<name>.json` names a reducer and its arguments). A reader that
    finds nothing returns None and the metric is left out of the line."""
    out = {}
    for m in cell_metrics(ctx.manifest, ctx.cell["name"], "per_layer"):
        spec = None
        for root in ctx.roots:
            if os.path.exists(os.path.join(root, "metrics",
                                           f"{m['name']}.json")):
                spec = load_metric(m["name"], root)
                break
        if spec is None:
            raise FileNotFoundError(f"metric {m['name']}: no"
                                    f" metrics/{m['name']}.json")
        got = reader_of(spec)(spec, ctx, rec)
        if got is None:
            continue
        value, extra = got if isinstance(got, tuple) else (got, {})
        out[m["name"]] = {"value": float(value), "unit": m["unit"], **extra}
    return out


# -- the result line -------------------------------------------------------------
def device_block(ctx: RunContext, rec: Record) -> Dict:
    d = ctx.devices[0]
    dev = {"platform": d.platform, "kind": d.device_kind,
           "count": ctx.chips, "memory_peak_bytes": int(rec.memory_peak_bytes)}
    if ctx.trace and rec.trace is not None:
        dev["busy_s"] = rec.trace.busy_s
        dev["window_s"] = rec.trace.window_s
    return dev


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def result_line(ctx: RunContext, rec: Record) -> Dict:
    from . import xplane

    if ctx.trace:
        metrics = per_layer_values(ctx, rec)
    else:
        metrics = {}
        for m in cell_metrics(ctx.manifest, ctx.cell["name"], "end_to_end"):
            if m["name"] not in rec.end_to_end:
                raise KeyError(f"cell {ctx.cell['name']} did not measure"
                               f" {m['name']}")
            metrics[m["name"]] = {"value": float(rec.end_to_end[m["name"]]),
                                  "unit": m["unit"]}
    line = {"correct": rec.correct, "attempted": int(rec.attempted),
            "failed": int(rec.failed), "metrics": metrics,
            "device": device_block(ctx, rec)}
    if ctx.trace and rec.trace is not None:
        line["breakdown"] = {
            "device_ops": xplane.top_device_ops(rec.trace),
            "idle_gaps": xplane.idle_gaps_by_host(rec.trace)}
    line["workload"] = ctx.cell["name"]
    line["seed"] = ctx.seed
    line["checks"] = rec.checks     # last: each number beside its limit
    return line


def emit(ctx: RunContext, rec: Record) -> int:
    """Print the result line last on stdout and the compared numbers last
    on stderr."""
    line = result_line(ctx, rec)
    sys.stdout.flush()
    for name, c in rec.checks.items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r}"
              f" {'ok' if _finite(c['value']) and c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr)
    print(f"correct: {rec.correct}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
