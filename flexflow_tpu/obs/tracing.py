"""Span tracer: nestable wall-clock spans with Chrome-trace-event export,
plus request-scoped distributed tracing (docs/observability.md "Request
tracing & post-mortem timelines").

The runtime is instrumented with `with tracer.span("name"):` blocks at
every phase boundary (search enumerate/prune/simulate, compile, executor
step dispatch, the fit() dispatch loop, checkpoint save/restore, the
elastic recovery pipeline, the serving scheduler's iteration). Every
span is written to two places:

 - THE PROFILER'S TRACE, always: the span opens a
   `jax.profiler.TraceAnnotation` of its name carrying its scalar args,
   so any profile (`runtime/profiling.trace()`, a benchmark's capture)
   shows the program's spans on the thread that did the work and on the
   device trace's clock. Outside a profiler session an annotation is a
   TraceMe that checks "is anyone recording" and does nothing more
   (under a microsecond; `tests/test_obs.py` bounds it).
 - THE RING, only while `enabled`: two monotonic clock reads plus one
   dict append under a lock; the buffer is a ring (`max_events`) so a
   long training run cannot grow memory without bound. Ring overflow is
   COUNTED (`dropped_events`, mirrored onto
   `ff_trace_events_dropped_total` and stamped into the exported trace
   metadata) so a truncated timeline is never mistaken for a complete
   one.

Request-scoped tracing: a `TraceContext` (trace_id / span_id /
parent_id) rides a contextvar. While a context is current, every span
becomes a CHILD of it — the span allocates its own span_id, records
trace_id/span_id/parent_id in its args, and re-parents the contextvar
for its duration, so nested spans chain correctly even across library
layers that know nothing about requests. Thread crossings are EXPLICIT:
the sending side captures `tracer.handoff(name)` (which emits a Chrome
flow-start "s" event so Perfetto draws the arrow) and the receiving
thread runs its work under `with tracer.resume(handoff):` (flow-finish
"f" on first resume, context restored on every resume). Both return
no-ops when the ring is disabled or no context is current, so the
serving hot path pays nothing by default. Request contexts live in the
ring only: the profiler's copy of a span names its request by the
`request` arg.

Export is the Chrome trace-event JSON format (complete "X" events with
`name`/`ph`/`ts`/`dur`/`pid`/`tid`, flow "s"/"f" events for handoffs),
loadable in Perfetto / chrome://tracing. `ts` is microseconds from
tracer start; the wall-clock epoch captured at the same instant is
exported as trace metadata so other streams (EventLog, metric
snapshots) can be aligned onto the same axis by the `timeline` CLI.
Spans on one thread nest by construction, so parent events always
contain their children.
"""
from __future__ import annotations

import contextvars
import functools
import itertools
import json
import numbers
import os
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, List, Optional

from .startup import record_first_dispatch, record_phase


class _NullSpan:
    """A shared, stateless no-op context manager (`resume()` of nothing)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):  # matches _Span.set; still a no-op
        return self


_NULL_SPAN = _NullSpan()


# -- the profiler's copy of a span -------------------------------------------
_Annotation = None   # bound on first use: this module imports without jax


def _bind_annotation():
    from jax.profiler import TraceAnnotation

    class _Annotation(TraceAnnotation):
        """A span as the profiler sees it; `set()` matches `_Span.set`."""

        __slots__ = ()

        def set(self, **args):
            self.set_metadata(**_scalars(args))
            return self

    return _Annotation


def _scalars(args: Dict[str, Any]) -> Dict[str, Any]:
    """The args an annotation can carry: TraceMe encodes them as `k=v`
    pairs separated by commas after the event's name, so lists and dicts
    (`requests=[...]`) stay in the ring."""
    return {k: v for k, v in args.items()
            if isinstance(v, (int, float, str))
            or isinstance(v, numbers.Number)}


def _annotation(name: str, args: Dict[str, Any]):
    global _Annotation
    if _Annotation is None:
        _Annotation = _bind_annotation()
    return _Annotation(name, **_scalars(args))


# -- request context -------------------------------------------------------
class TraceContext:
    """One request's position in its trace: which trace it belongs to
    (`trace_id`), the id of the span currently open for it (`span_id`),
    and that span's parent (`parent_id`, None at the root). Immutable —
    spans and handoffs derive CHILD contexts instead of mutating."""

    __slots__ = ("trace_id", "span_id", "parent_id")

    def __init__(self, trace_id: str, span_id: str,
                 parent_id: Optional[str] = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id

    def child(self) -> "TraceContext":
        return TraceContext(self.trace_id, _new_span_id(), self.span_id)

    def __repr__(self):
        return (f"TraceContext(trace_id={self.trace_id!r},"
                f" span_id={self.span_id!r}, parent_id={self.parent_id!r})")


_CTX: "contextvars.ContextVar[Optional[TraceContext]]" = \
    contextvars.ContextVar("ff_trace_context", default=None)


def new_trace_id() -> str:
    return uuid.uuid4().hex


def _new_span_id() -> str:
    return uuid.uuid4().hex[:16]


def current_context() -> Optional[TraceContext]:
    """The TraceContext current on this thread/task, or None."""
    return _CTX.get()


def current_trace_id() -> Optional[str]:
    ctx = _CTX.get()
    return ctx.trace_id if ctx is not None else None


class _CtxScope:
    """`with use_context(ctx):` — install a TraceContext on the current
    thread, restore the previous one on exit."""

    __slots__ = ("_ctx", "_token")

    def __init__(self, ctx: Optional[TraceContext]):
        self._ctx = ctx

    def __enter__(self):
        self._token = _CTX.set(self._ctx)
        return self._ctx

    def __exit__(self, *exc):
        _CTX.reset(self._token)
        return False


def use_context(ctx: Optional[TraceContext]) -> _CtxScope:
    """Run a block under `ctx` (None clears the context — e.g. scheduler
    work not attributable to any request)."""
    return _CtxScope(ctx)


def root_context(trace_id: Optional[str] = None,
                 parent_id: Optional[str] = None) -> TraceContext:
    """A fresh root context: new trace unless `trace_id` is given (the
    server passes the id from an incoming `traceparent` header, with the
    caller's span as `parent_id`)."""
    return TraceContext(trace_id or new_trace_id(), _new_span_id(),
                        parent_id)


class Handoff:
    """An explicit thread-crossing token: the captured TraceContext plus
    the Chrome flow id binding the sending span to the receiving one.
    Created by `Tracer.handoff()`, consumed by `Tracer.resume()` —
    resumable any number of times (the flow-finish event is emitted once)."""

    __slots__ = ("ctx", "flow_id", "name", "_consumed")

    def __init__(self, ctx: TraceContext, flow_id: int, name: str):
        self.ctx = ctx
        self.flow_id = flow_id
        self.name = name
        self._consumed = False

    @property
    def trace_id(self) -> str:
        return self.ctx.trace_id


class _Resume:
    """`with tracer.resume(handoff):` — restore the handed-off context on
    the receiving thread; first resume emits the flow-finish event."""

    __slots__ = ("_tracer", "_handoff", "_token")

    def __init__(self, tracer: "Tracer", handoff: Handoff):
        self._tracer = tracer
        self._handoff = handoff

    def __enter__(self):
        h = self._handoff
        self._token = _CTX.set(h.ctx)
        if not h._consumed:
            h._consumed = True
            self._tracer._emit_flow("f", h)
        return h.ctx

    def __exit__(self, *exc):
        _CTX.reset(self._token)
        return False


class _Span:
    """A span while the ring is enabled: the ring's record plus the
    profiler's annotation, opened and closed together."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_ctx", "_token",
                 "_annotation")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any],
                 parent: Optional[TraceContext], annotation):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._ctx = parent.child() if parent is not None else None
        self._annotation = annotation

    def set(self, **args) -> "_Span":
        """Attach/override args mid-span (e.g. a result count discovered
        while the span is open)."""
        self.args.update(args)
        self._annotation.set(**args)
        return self

    def __enter__(self):
        self._annotation.__enter__()
        self._token = _CTX.set(self._ctx) if self._ctx is not None else None
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        self._annotation.__exit__(exc_type, exc, tb)
        if self._token is not None:
            _CTX.reset(self._token)
        if exc_type is not None:
            self.args.setdefault("error", exc_type.__name__)
        ctx = self._ctx
        if ctx is not None:
            self.args["trace_id"] = ctx.trace_id
            self.args["span_id"] = ctx.span_id
            if ctx.parent_id is not None:
                self.args["parent_id"] = ctx.parent_id
        self._tracer._emit(self.name, self._t0, t1, self.args)
        return False


class _Phase:
    """A span of a one-shot phase (`Tracer.phase`): the span it wraps, plus
    its wall duration added to `ff_startup_seconds{phase}` on exit — a sink
    that is readable when neither the ring nor a profiler was on."""

    __slots__ = ("_span", "_name", "_t0")

    def __init__(self, span, name: str):
        self._span = span
        self._name = name

    def set(self, **args) -> "_Phase":
        self._span.set(**args)
        return self

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._span.__exit__(exc_type, exc, tb)
        record_phase(self._name, self._t0,
                     time.perf_counter() - self._t0)
        return False


class Tracer:
    """A span buffer. One process-wide instance (`get_tracer()`) backs the
    whole runtime; independent Tracers exist for tests."""

    def __init__(self, enabled: bool = False, max_events: int = 200_000):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=max_events)
        # wall <-> perf_counter epoch pair, captured back-to-back: `ts`
        # microseconds are relative to _epoch_ns, and _epoch_wall_s is
        # the SAME instant on the wall clock — the alignment anchor the
        # timeline CLI uses to merge wall-clocked streams (EventLog,
        # metric snapshots) onto the trace axis
        self._epoch_wall_s = time.time()
        self._epoch_ns = time.perf_counter_ns()
        # per-thread-LIFETIME track ids. Keyed through threading.local —
        # NOT threading.get_ident(), which the interpreter recycles the
        # moment a thread dies: a respawned replica's scheduler would
        # inherit the dead one's ident, fold both incarnations onto one
        # track, and rename the victim's spans after the fact.
        self._tid_local = threading.local()
        self._next_tid = itertools.count(1)
        self._thread_names: Dict[int, str] = {}
        self._dropped = 0
        self._flow_ids = itertools.count(1)

    # -- recording --------------------------------------------------------
    def span(self, name: str, **args):
        """Context manager timing a block: always a profiler annotation
        (free outside a profiler session), and a ring record while
        enabled. Arguments that cost something to build belong in
        `.set()` under `if tracer.enabled`."""
        annotation = _annotation(name, args)
        if not self.enabled:
            return annotation
        return _Span(self, name, args, _CTX.get(), annotation)

    def step(self, name: str, step_num: int, **args):
        """A span that is also a step marker of the profiler
        (`jax.profiler.StepTraceAnnotation`: `_r=1` beside `step_num`),
        so its step view groups device work by the program's steps."""
        return self.span(name, step_num=step_num, _r=1, **args)

    def phase(self, name: str, **args):
        """A span of a ONE-SHOT phase (the package's import, the TPU
        client's start, `FFModel.compile()`, a batcher's construction): a
        `span()` whose wall duration is also added to
        `ff_startup_seconds{phase=name}` of the default registry and
        counted in `ff_startup_phase_runs_total`, so set-up can be read
        after a run whose ring was off and whose profiler opened later.
        For code that runs once a process or once a model: nothing on a
        per-pass or per-dispatch path may call it."""
        return _Phase(self.span(name, **args), name)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker (Chrome "i" event) — e.g. the moment a
        topology loss is detected, before recovery spans open."""
        if not self.enabled:
            return
        ctx = _CTX.get()
        if ctx is not None:
            args.setdefault("trace_id", ctx.trace_id)
        now = time.perf_counter_ns()
        self._append({
            "name": name, "ph": "i", "s": "t",
            "ts": (now - self._epoch_ns) / 1e3,
            "pid": os.getpid(), "tid": self._tid(),
            "args": {k: _jsonable(v) for k, v in args.items()},
        })

    # -- request context / thread handoff ---------------------------------
    def handoff(self, name: str = "handoff") -> Optional[Handoff]:
        """Capture the current TraceContext for an explicit thread
        crossing, emitting the Chrome flow-start ("s") event so Perfetto
        draws the arrow from here to the receiving thread's resume().
        Returns None (a no-op token) when disabled or there is no
        current context."""
        if not self.enabled:
            return None
        ctx = _CTX.get()
        if ctx is None:
            return None
        h = Handoff(ctx, next(self._flow_ids), name)
        self._emit_flow("s", h)
        return h

    def resume(self, handoff: Optional[Handoff]):
        """Run a block on the receiving thread under the handed-off
        context (no-op for a None token)."""
        if handoff is None or not self.enabled:
            return _NULL_SPAN
        return _Resume(self, handoff)

    def _emit_flow(self, ph: str, h: Handoff) -> None:
        now = time.perf_counter_ns()
        ev = {
            "name": h.name, "ph": ph, "cat": "handoff",
            "id": h.flow_id,
            "ts": (now - self._epoch_ns) / 1e3,
            "pid": os.getpid(), "tid": self._tid(),
            "args": {"trace_id": h.ctx.trace_id},
        }
        if ph == "f":
            ev["bp"] = "e"  # bind the arrow to the enclosing slice
        self._append(ev)

    def set_thread_name(self, name: str) -> None:
        """Label the CURRENT thread's track in the exported trace (Chrome
        `thread_name` metadata) — e.g. a replica's scheduler thread, so
        the merged timeline shows one track per replica. Cheap and valid
        before `enable()`."""
        self._thread_names[self._tid()] = str(name)

    def _tid(self) -> int:
        # Chrome trace tids render best small and stable per thread;
        # threading.local dies with its thread, so a tid is never reused
        tid = getattr(self._tid_local, "tid", None)
        if tid is None:
            tid = self._tid_local.tid = next(self._next_tid)
        return tid

    def _emit(self, name: str, t0_ns: int, t1_ns: int,
              args: Dict[str, Any]) -> None:
        self._append({
            "name": name, "ph": "X",
            "ts": (t0_ns - self._epoch_ns) / 1e3,
            "dur": (t1_ns - t0_ns) / 1e3,
            "pid": os.getpid(), "tid": self._tid(),
            "args": {k: _jsonable(v) for k, v in args.items()},
        })

    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1
            self._events.append(ev)

    # -- control ----------------------------------------------------------
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    # -- export -----------------------------------------------------------
    @property
    def dropped_events(self) -> int:
        """Ring-buffer overflow count since the last clear() — also
        mirrored onto `ff_trace_events_dropped_total` at export."""
        with self._lock:
            return self._dropped

    def events(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        with self._lock:
            evs = list(self._events)
        if name is not None:
            evs = [e for e in evs if e["name"] == name]
        return evs

    def span_names(self) -> List[str]:
        return sorted({e["name"] for e in self.events()})

    def to_chrome_trace(self) -> Dict[str, Any]:
        """The Chrome trace-event container Perfetto loads. Prepends
        process/thread names plus a `trace_metadata` record carrying the
        wall<->perf_counter epoch pair and the ring-drop count."""
        dropped = self.dropped_events
        self._sync_dropped_metric(dropped)
        pid = os.getpid()
        meta = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": "flexflow_tpu"},
        }, {
            "name": "trace_metadata", "ph": "M", "pid": pid, "tid": 0,
            "args": {"epoch_wall_s": self._epoch_wall_s,
                     "dropped_events": dropped},
        }]
        for tid, tname in sorted(self._thread_names.items()):
            meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                         "tid": tid, "args": {"name": tname}})
        return {"traceEvents": meta + self.events(),
                "displayTimeUnit": "ms"}

    def _sync_dropped_metric(self, dropped: int) -> None:
        if dropped <= 0:
            return
        from .registry import REGISTRY

        REGISTRY.counter(
            "ff_trace_events_dropped_total",
            "Trace events dropped by the tracer's ring buffer"
            " (a nonzero value means exported timelines are truncated)"
        ).set_total(dropped)

    def export_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    return str(v)


# -- the process-wide tracer ----------------------------------------------
_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def enable_tracing() -> Tracer:
    _TRACER.enable()
    return _TRACER


def disable_tracing() -> None:
    _TRACER.disable()


def span(name: str, **args):
    """Module-level convenience over the process tracer. Hot loops should
    hoist `tr = get_tracer()` and call `tr.span` directly."""
    return _TRACER.span(name, **args)


class _FirstCall:
    """What `first_call` leaves on `owner.attr` until the first call has
    put `fn` back. Everything but the call itself (`lower`, `__name__`)
    is `fn`'s own. The first call is timed INLINE: one frame between the
    caller and `fn`, and none after."""

    # weak-referenceable: jax.eval_shape and friends key caches by function
    __slots__ = ("_fn", "_program", "_owner", "_attr", "_called",
                 "__weakref__")

    def __init__(self, fn, program, owner, attr):
        self._fn, self._program = fn, program
        self._owner, self._attr = owner, attr
        self._called = False

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __call__(self, *a, **k):
        if self._called:    # a caller that kept this object: plain calls
            return self._fn(*a, **k)
        self._called = True
        # a caller that swapped the attribute meanwhile keeps its own
        if getattr(self._owner, self._attr, None) is self:
            setattr(self._owner, self._attr, self._fn)
        t0 = time.perf_counter()
        try:
            with _TRACER.span("first_dispatch", program=self._program):
                return self._fn(*a, **k)
        finally:
            record_first_dispatch(self._program, t0,
                                  time.perf_counter() - t0)


def first_call(fn, program: str, owner, attr: str):
    """`owner.attr = first_call(jitted, "decode_all", owner, "attr")`: the
    FIRST call of `fn` is timed on the host clock, dispatch to return
    (trace, lowering, cache lookup, compile or load, argument transfer,
    enqueue — not the device's run, which nothing blocks on), under a
    `first_dispatch` span into `ff_first_dispatch_seconds{program}`, and
    puts the bare `fn` back on `owner.attr`: the second and every later
    call go straight to it — no wrapper is left on the dispatch path."""
    return _FirstCall(fn, program, owner, attr)


def phased(name: str):
    """Decorator: every call of the function is `Tracer.phase(name)` — for
    a constructor, which is one-shot by nature (`ContinuousBatcher`)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*a, **k):
            with _TRACER.phase(name):
                return fn(*a, **k)
        return wrapper
    return deco


def traced_dispatch(fn, name: str):
    """Wrap a jitted step function so each host-side dispatch becomes a
    span. The wall time is the DISPATCH (host call until the result's
    futures are returned), not device completion — jax dispatch is async;
    the per-step wall clock lives in StepStats. The first dispatch is also
    the program's first call: its seconds go to
    `ff_first_dispatch_seconds` under the jitted function's own name. It
    is timed HERE, in this frame: a frame more between the span and `fn`
    made the trace of a 24-layer train step with Pallas kernels 3 s
    longer on the chip (PERF.md section 6, PR 35)."""
    tr = _TRACER
    program = getattr(fn, "__name__", name)
    first = True

    def wrapper(*a, **k):
        nonlocal first
        if first:
            first = False
            t0 = time.perf_counter()
            try:
                with tr.span(name):
                    return fn(*a, **k)
            finally:
                record_first_dispatch(program, t0, time.perf_counter() - t0)
        with tr.span(name):
            return fn(*a, **k)

    wrapper.__wrapped__ = fn
    wrapper.__name__ = program
    return wrapper
