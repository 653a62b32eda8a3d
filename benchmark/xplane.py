"""Reading a profiler trace (`*.xplane.pb`) with nothing but the standard
library, and reducing it to what the per-layer metrics read.

jax's own `ProfileData` shows an event's name, start and duration but not
the statistics the TPU profiler hangs on the event's METADATA — the HLO
category and the `jax.named_scope` path (`tf_op`) — and those are what say
"this op is a copy" or "this op belongs to the attention layer" without
leaning on today's op names. So this file decodes the protobuf wire format
itself; the field numbers are xplane.proto's (tsl/profiler/protobuf).

    XSpace   1 planes
    XPlane   1 id  2 name  3 lines  4 event_metadata<map>  5 stat_metadata<map>  6 stats
    XLine    1 id  2 name  3 timestamp_ns  4 events  9 duration_ps  10 display_id  11 display_name
    XEvent   1 metadata_id  2 offset_ps  3 duration_ps  4 stats  5 num_occurrences
    XStat    1 metadata_id  2 double  3 uint64  4 int64  5 str  6 bytes  7 ref
    XEventMetadata  1 id  2 name  3 metadata  4 display_name  5 stats  6 child_id
    XStatMetadata   1 id  2 name  3 description
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import struct
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


# -- wire format -------------------------------------------------------------
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = 0
    out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """Yield (field number, wire type, value) over one message's bytes;
    length-delimited values come back as memoryview slices."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val = buf[i:i + 8]
            i += 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wt == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"xplane: wire type {wt} is not in xplane.proto")
        yield num, wt, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


@dataclass
class Event:
    name: str            # the metadata's name (for a device op: its HLO text)
    start_ps: int        # from the start of the trace
    dur_ps: int
    stats: Dict[str, object]   # the event's own and its metadata's

    @property
    def end_ps(self) -> int:
        return self.start_ps + self.dur_ps


@dataclass
class Line:
    name: str
    events: List[Event] = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: List[Line] = field(default_factory=list)
    stats: Dict[str, object] = field(default_factory=dict)


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, object]:
    key, val = "", None
    for num, wt, v in _fields(buf):
        if num == 1:
            key = stat_names.get(v, str(v))
        elif num == 2:
            val = struct.unpack("<d", bytes(v))[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _signed(v)
        elif num in (5, 6):
            val = bytes(v).decode("utf-8", "replace")
        elif num == 7:
            val = stat_names.get(v, str(v))   # a reference to a string
    return key, val


def _map_entry(buf) -> Tuple[int, bytes]:
    k, v = 0, b""
    for num, _wt, val in _fields(buf):
        if num == 1:
            k = val
        elif num == 2:
            v = val
    return k, v


def _plane(buf, want_line) -> Plane:
    raw_lines, raw_emeta, raw_smeta, raw_stats = [], [], [], []
    name = ""
    for num, _wt, v in _fields(buf):
        if num == 2:
            name = bytes(v).decode()
        elif num == 3:
            raw_lines.append(v)
        elif num == 4:
            raw_emeta.append(v)
        elif num == 5:
            raw_smeta.append(v)
        elif num == 6:
            raw_stats.append(v)
    stat_names: Dict[int, str] = {}
    for entry in raw_smeta:
        k, v = _map_entry(entry)
        for num, _wt, val in _fields(v):
            if num == 2:
                stat_names[k] = bytes(val).decode()
    emeta: Dict[int, Tuple[str, Dict[str, object]]] = {}
    for entry in raw_emeta:
        k, v = _map_entry(entry)
        ename, estats = "", {}
        for num, _wt, val in _fields(v):
            if num == 2:
                ename = bytes(val).decode("utf-8", "replace")
            elif num == 5:
                sk, sv = _stat(val, stat_names)
                estats[sk] = sv
        emeta[k] = (ename, estats)
    plane = Plane(name, stats=dict(_stat(s, stat_names) for s in raw_stats))
    for lbuf in raw_lines:
        lname, t0_ns, raw_events = "", 0, []
        for num, _wt, v in _fields(lbuf):
            if num == 2:
                lname = bytes(v).decode()
            elif num == 3:
                t0_ns = _signed(v)
            elif num == 4:
                raw_events.append(v)
        line = Line(lname)
        plane.lines.append(line)
        if not want_line(name, lname):
            continue
        for ebuf in raw_events:
            mid = off = dur = 0
            own = {}
            for num, _wt, v in _fields(ebuf):
                if num == 1:
                    mid = v
                elif num == 2:
                    off = _signed(v)
                elif num == 3:
                    dur = _signed(v)
                elif num == 4:
                    sk, sv = _stat(v, stat_names)
                    own[sk] = sv
            ename, mstats = emeta.get(mid, (str(mid), {}))
            line.events.append(Event(ename, t0_ns * 1000 + off, dur,
                                     {**mstats, **own}))
    return plane


def read_xspace(path: str, want_line=lambda plane, line: True) -> List[Plane]:
    """Every plane of the trace. `want_line(plane name, line name)` keeps a
    line's events out of memory when nothing reads them."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(v, want_line) for num, _wt, v in _fields(buf) if num == 1]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


# -- reduction ---------------------------------------------------------------
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_LINE = "XLA Ops"
_MODULE_LINE = "XLA Modules"
_HLO_OPCODE = re.compile(r"\s([a-z][a-z0-9\-_]*)\(")

# HLO opcodes that move or re-lay data and do no arithmetic. The profiler's
# own category is used where the trace carries one; the opcode is the
# fallback, and both are properties of the compiled program, not names the
# repo chose.
COPY_OPCODES = frozenset((
    "copy", "copy-start", "copy-done", "transpose", "bitcast", "reshape",
    "dynamic-update-slice", "dynamic-slice", "slice", "concatenate", "pad",
    "broadcast", "gather", "scatter", "async-start", "async-done",
    "async-update", "slice-start", "slice-done", "tuple", "get-tuple-element"))
COPY_CATEGORIES = frozenset((
    "data formatting", "copy", "copy-start", "copy-done", "dynamic-update-slice",
    "dynamic-slice", "slice", "transpose", "pad", "concatenate", "broadcast",
    "gather", "scatter", "async-start", "async-done", "reshape", "bitcast"))
# ops that only wrap a body whose ops the trace lists as well
CONTAINER_OPCODES = frozenset(("while", "conditional", "call"))
# no arithmetic, but not a copy either: kernels the compiler cannot see into
OPAQUE_CATEGORIES = frozenset(("custom-call", "custom call", "infeed",
                               "outfeed", "send", "recv", "host"))
COLLECTIVE_OPCODES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast")


def hlo_opcode(hlo_text: str) -> str:
    """`%x = f32[..] fusion(...), kind=kLoop` -> `fusion`."""
    rhs = hlo_text.split(" = ", 1)[-1]
    m = _HLO_OPCODE.search(" " + rhs)
    return m.group(1) if m else hlo_text.split("(")[0].strip("% ")


def hlo_result_name(hlo_text: str) -> str:
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


@dataclass
class DeviceOp:
    name: str       # the HLO instruction's own name, e.g. fusion.7
    opcode: str
    category: str   # the profiler's hlo_category, "" where it gives none
    scope: str      # jax.named_scope path (tf_op), "" where unscoped
    start_ps: int
    dur_ps: int
    flops: float = -1.0     # the profiler's count for the op; -1 = not given
    program_id: str = ""    # which compiled program (XLA module) it is in

    @property
    def end_ps(self) -> int:
        return self.start_ps + self.dur_ps


def _device_ops(plane: Plane) -> List[DeviceOp]:
    ops = []
    for line in plane.lines:
        if line.name != _OP_LINE:
            continue
        for e in line.events:
            st = e.stats
            opcode = hlo_opcode(e.name)
            if opcode in CONTAINER_OPCODES:
                continue   # its body's ops are on the line too
            flops = st.get("flops")
            ops.append(DeviceOp(
                hlo_result_name(e.name), opcode,
                str(st.get("hlo_category", "") or ""),
                str(st.get("tf_op", "") or ""),
                e.start_ps, e.dur_ps,
                float(flops) if flops not in (None, "") else -1.0,
                str(st.get("program_id", "") or "")))
    ops.sort(key=lambda o: o.start_ps)
    return ops


def union_ps(intervals: Iterable[Tuple[int, int]]) -> int:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def subtract_ps(a: Sequence[Tuple[int, int]],
                b: Sequence[Tuple[int, int]]) -> int:
    """Length of (union of a) minus (union of b)."""
    both = union_ps(list(a) + list(b))
    return both - union_ps(b)


def gaps_ps(intervals: Iterable[Tuple[int, int]], lo: int,
            hi: int) -> List[Tuple[int, int]]:
    """The idle gaps inside [lo, hi) that the intervals leave."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def is_copy(op: DeviceOp) -> bool:
    """An op that moves or re-lays data and computes nothing: by the
    profiler's category, or a fusion for which it counts no operation."""
    if is_collective(op):
        return False
    cat = op.category.lower()
    if cat:
        if cat in COPY_CATEGORIES:
            return True
        return op.flops == 0.0 and cat not in OPAQUE_CATEGORIES \
            and op.opcode != "custom-call"
    return op.opcode in COPY_OPCODES


def is_collective(op: DeviceOp) -> bool:
    text = (op.category + " " + op.opcode + " " + op.name).lower()
    return any(c in text for c in COLLECTIVE_OPCODES)


_SCOPE_PART = re.compile(r"([a-z][a-z0-9_]*:[A-Za-z_][A-Za-z0-9_.]*)")


def scope_key(scope: str) -> str:
    """The framework op a device op belongs to: the first `kind:name` part of
    its scope path (the executor's `jax.named_scope`; a backward op carries
    it inside `transpose(jvp(...))`), with layer numbers folded
    (`layer3_attn` -> `layerN_attn`) so the twelve layers read as one row."""
    m = _SCOPE_PART.search(scope)
    return re.sub(r"\d+", "N", m.group(1)) if m else ""


@dataclass
class TraceSummary:
    """What one traced window reduces to. Times are seconds, per chip where
    `chips` > 1 (averaged over the device planes)."""
    chips: int
    window_s: float
    busy_s: float
    ops: Dict[int, List[DeviceOp]]          # by chip
    modules: Dict[int, List[Event]]         # by chip: program executions
    host: List[Tuple[str, List[Event]]]     # host lines (thread, events)
    lo_ps: int
    hi_ps: int

    def per_chip(self, pred) -> float:
        """Seconds, averaged over chips, of device ops for which pred holds."""
        if not self.ops:
            return 0.0
        tot = 0
        for ops in self.ops.values():
            tot += sum(o.dur_ps for o in ops if pred(o))
        return tot / len(self.ops) / 1e12

    def by_key(self, key) -> Dict[str, float]:
        out: Dict[str, float] = {}
        n = max(1, len(self.ops))
        for ops in self.ops.values():
            for o in ops:
                k = key(o)
                out[k] = out.get(k, 0.0) + o.dur_ps / 1e12 / n
        return out

    def module_runs(self, name_part: str) -> int:
        """Executions, on the first chip, of programs whose name holds
        `name_part`."""
        if not self.modules:
            return 0
        first = self.modules[min(self.modules)]
        return sum(1 for e in first if name_part in e.name)

    def program_ids(self, name_part: str) -> frozenset:
        """Ids of the compiled programs whose name holds `name_part`; a
        module event is named `jit_fn(<program id>)`."""
        ids = set()
        for evs in self.modules.values():
            for e in evs:
                if name_part in e.name and "(" in e.name:
                    ids.add(e.name.rsplit("(", 1)[1].rstrip(")"))
        return frozenset(ids)


def summarize(planes: List[Plane], lo_ps: Optional[int] = None,
              hi_ps: Optional[int] = None) -> TraceSummary:
    """Clip every device plane to [lo, hi) (default: the span of the device
    events) and reduce."""
    dev: Dict[int, List[DeviceOp]] = {}
    mods: Dict[int, List[Event]] = {}
    host: List[Tuple[str, List[Event]]] = []
    for p in planes:
        m = _DEVICE_PLANE.match(p.name)
        if m:
            chip = int(m.group(1))
            ops = _device_ops(p)
            if ops:
                dev[chip] = ops
            for line in p.lines:
                if line.name == _MODULE_LINE and line.events:
                    mods[chip] = sorted(line.events, key=lambda e: e.start_ps)
        elif p.name.startswith("/host:CPU"):
            for line in p.lines:
                if line.events:
                    host.append((line.name, line.events))
    all_ops = [o for ops in dev.values() for o in ops]
    if lo_ps is None:
        lo_ps = min((o.start_ps for o in all_ops), default=0)
    if hi_ps is None:
        hi_ps = max((o.end_ps for o in all_ops), default=0)
    busy = 0
    for chip, ops in dev.items():
        kept = [o for o in ops if o.end_ps > lo_ps and o.start_ps < hi_ps]
        dev[chip] = kept
        busy += union_ps((max(o.start_ps, lo_ps), min(o.end_ps, hi_ps))
                         for o in kept)
    for chip, evs in mods.items():
        mods[chip] = [e for e in evs if e.end_ps > lo_ps and e.start_ps < hi_ps]
    n = max(1, len(dev))
    return TraceSummary(len(dev), (hi_ps - lo_ps) / 1e12, busy / n / 1e12,
                        dev, mods, host, lo_ps, hi_ps)


def top_device_ops(s: TraceSummary, k: int = 10) -> List[List[object]]:
    def key(o: DeviceOp) -> str:
        sk = scope_key(o.scope)
        if sk:
            return sk
        cat = (o.category or o.opcode).replace(" ", "_")
        return "unscoped_" + cat
    rows = sorted(s.by_key(key).items(), key=lambda kv: -kv[1])[:k]
    return [[name, secs] for name, secs in rows]


def idle_gaps_by_host(s: TraceSummary, k: int = 10) -> List[List[object]]:
    """The device's idle time on the first chip, split by what the host was
    doing in each gap: the innermost host event (any thread) that covers the
    gap's middle, preferring the Python thread's annotations."""
    if not s.ops:
        return []
    ops = s.ops[min(s.ops)]
    gaps = gaps_ps(((o.start_ps, o.end_ps) for o in ops), s.lo_ps, s.hi_ps)
    # host events sorted by start, per thread
    lines = [(name, sorted(evs, key=lambda e: e.start_ps))
             for name, evs in s.host]
    starts = [[e.start_ps for e in evs] for _n, evs in lines]
    out: Dict[str, float] = {}
    for g0, g1 in gaps:
        mid = (g0 + g1) // 2
        best = None
        for (lname, evs), st in zip(lines, starts):
            j = bisect.bisect_right(st, mid) - 1
            # walk back a little: events nest, the innermost starts last
            for jj in range(j, max(-1, j - 8), -1):
                e = evs[jj]
                if e.start_ps <= mid < e.end_ps:
                    score = (not e.name.startswith("$"), -e.dur_ps)
                    if best is None or score > best[0]:
                        best = (score, e.name)
                    break
        name = "unattributed" if best is None else re.sub(
            r"[^A-Za-z0-9_.:\-]+", "_", best[1])[:60]
        out[name] = out.get(name, 0.0) + (g1 - g0) / 1e12
    rows = sorted(out.items(), key=lambda kv: -kv[1])[:k]
    return [[name, secs] for name, secs in rows]
