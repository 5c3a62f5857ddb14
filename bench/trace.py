"""Reduce a profiler trace to device busy time and time per named scope.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps two
lists, both on the trace's clock in nanoseconds:

* device ops: every event of the ``XLA Ops`` line of each TPU plane, with
  the name scope of the HLO op it ran (the ``jax.named_scope`` path).  The
  events carry only the op's HLO text, so the path is read from the HLO
  modules that the trace itself holds (the ``Hlo Proto`` stats of its
  ``/host:metadata`` plane): the instruction's ``op_name`` in the module
  whose ``XLA Modules`` event holds the op;
* host spans: every event of the host's threads (``TraceAnnotation`` spans
  and the program's own dispatch events).

``summarize`` turns them into a :class:`Summary`: busy seconds (the union of
device op intervals, averaged over the chips used), the traced window's
length, device seconds per scope pattern (the union of the intervals of the
ops in scope, so that a loop op and the body ops it holds count once), and a
breakdown of the device ops that took most time and of the longest idle
gaps with what the host was doing in them.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import pathlib
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_PROGRAM = re.compile(r"\((\d+)\)\s*$")  # "jit_f(12)": module of program 12


@dataclasses.dataclass
class Op:
    device: str
    start_ns: float
    dur_ns: float
    name: str
    scope: str


@dataclasses.dataclass
class Span:
    thread: str
    start_ns: float
    dur_ns: float
    name: str


@dataclasses.dataclass
class Trace:
    ops: list
    spans: list


# -- the HLO modules a trace carries, read from the protobuf wire format ----

def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, value) of each field of one protobuf message; a
    length-delimited value is a memoryview, a varint an int."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield field, v


def _first(b, field, default=b""):
    for f, v in _fields(b):
        if f == field:
            return v
    return default


def _op_names(hlo_proto) -> dict:
    """Instruction name -> ``OpMetadata.op_name`` over an ``HloProto``
    (hlo_module 1; computations 3; instructions 2; name 1, metadata 7;
    op_name 2)."""
    out = {}
    for f, comp in _fields(_first(hlo_proto, 1)):
        if f != 3:
            continue
        for g, ins in _fields(comp):
            if g != 2:
                continue
            name = meta = b""
            for h, v in _fields(ins):
                if h == 1:
                    name = v
                elif h == 7:
                    meta = v
            op_name = bytes(_first(meta, 2)).decode() if len(meta) else ""
            if op_name:
                out[bytes(name).decode()] = op_name
    return out


def hlo_scopes(path) -> dict:
    """Program id -> {instruction name -> op_name} for every HLO module in
    the ``/host:metadata`` plane of the xplane file at ``path`` (XSpace
    planes 1; XPlane name 2, event_metadata 4, stat_metadata 5;
    XEventMetadata id 1, name 2, stats 5; XStat metadata_id 1, bytes 6)."""
    data = memoryview(pathlib.Path(path).read_bytes())
    programs = {}
    for f, plane in _fields(data):
        if f != 1 or bytes(_first(plane, 2)) != b"/host:metadata":
            continue
        stat_names, metas = {}, []
        for g, v in _fields(plane):
            if g == 5:  # map entry: key 1, value 2 (XStatMetadata: id 1, name 2)
                m = _first(v, 2)
                stat_names[_first(m, 1, 0)] = bytes(_first(m, 2)).decode()
            elif g == 4:
                metas.append(_first(v, 2))
        for m in metas:
            mid, name, protos = 0, "", []
            for g, v in _fields(m):
                if g == 1:
                    mid = v
                elif g == 2:
                    name = bytes(v).decode()
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == "Hlo Proto" and 6 in stat:
                        protos.append(stat[6])
            match = _PROGRAM.search(name)
            pid = int(match.group(1)) if match else mid
            for proto in protos:
                programs.setdefault(pid, {}).update(_op_names(proto))
    return programs


def _instruction(name: str) -> str:
    """The HLO instruction an op event ran: its name is the instruction's
    HLO text, ``%<instruction> = ...``."""
    name = name.strip()
    return name[1:].split(" ", 1)[0] if name.startswith("%") else name


def _scope(name: str, start_ns: float, modules: list, programs: dict) -> str:
    """The op's ``jax.named_scope`` path: the ``op_name`` of its instruction
    in the HLO module of the ``XLA Modules`` event (start, end, program id)
    that holds it in time; "" where the trace does not know it."""
    i = bisect.bisect_right(modules, (start_ns, float("inf"), 0)) - 1
    if i < 0 or not modules[i][0] <= start_ns <= modules[i][1]:
        return ""
    return programs.get(modules[i][2], {}).get(_instruction(name), "")


def _line(plane, name):
    return next((line for line in plane.lines if line.name == name), None)


def load(trace_dir) -> Trace:
    """The device ops and host spans of the one trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} traces under {trace_dir}")
    data = ProfileData.from_file(str(files[0]))
    programs = hlo_scopes(files[0])
    ops, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            line = _line(plane, OPS_LINE)
            mods = _line(plane, MODULES_LINE)
            modules = sorted(
                (e.start_ns, e.start_ns + e.duration_ns,
                 int(m.group(1)) if (m := _PROGRAM.search(e.name)) else -1)
                for e in (mods.events if mods is not None else ()))
            for e in line.events if line is not None else ():
                ops.append(Op(plane.name, e.start_ns, e.duration_ns, e.name,
                              _scope(e.name, e.start_ns, modules, programs)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    spans.append(Span(line.name, e.start_ns, e.duration_ns,
                                      e.name))
    return Trace(ops, spans)


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Summary:
    busy_s: float  # device busy seconds, averaged over the chips used
    window_s: float  # traced window on the host's clock
    ops: list
    gaps: list  # (seconds, what the host was doing)

    def scope_s(self, pattern: str) -> float:
        """Device seconds in ops whose scope matches ``pattern``: the union
        of their intervals, so that a loop op and the body ops it holds
        count once, averaged over the chips used; 0 when none does."""
        rx = re.compile(pattern)
        by_dev = collections.defaultdict(list)
        for o in self.ops:
            if rx.search(o.scope):
                by_dev[o.device].append((o.start_ns, o.start_ns + o.dur_ns))
        n = len({o.device for o in self.ops}) or 1
        return sum(e - s for iv in by_dev.values()
                   for s, e in _union(iv)) * 1e-9 / n

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (grouped by scope, else by
        op name; ops that hold others, as a loop holds its body, left out)
        and the longest idle gaps by what the host was doing."""
        by = collections.Counter()
        n = len({o.device for o in self.ops}) or 1
        for o in _leaves(self.ops):
            by[_label(o)] += o.dur_ns * 1e-9 / n
        gaps = collections.Counter()
        for secs, what in self.gaps:
            gaps[what] += secs
        return {"device_ops": [[k, v] for k, v in by.most_common(top)],
                "idle_gaps": [[k, v] for k, v in gaps.most_common(top)]}


def _leaves(ops):
    """The ops that hold no other op of their device in time."""
    by_dev = collections.defaultdict(list)
    for o in ops:
        by_dev[o.device].append(o)
    out = []
    for dev_ops in by_dev.values():
        dev_ops.sort(key=lambda o: (o.start_ns, -o.dur_ns))
        holders, stack = set(), []
        for i, o in enumerate(dev_ops):
            while stack and (dev_ops[stack[-1]].start_ns
                             + dev_ops[stack[-1]].dur_ns) <= o.start_ns:
                stack.pop()
            if stack and (dev_ops[stack[-1]].start_ns
                          + dev_ops[stack[-1]].dur_ns) >= (o.start_ns
                                                           + o.dur_ns):
                holders.add(stack[-1])
            stack.append(i)
        out.extend(o for i, o in enumerate(dev_ops) if i not in holders)
    return out


def _label(op: Op) -> str:
    """An op's scope without the jit wrapper and the HLO op's own name, else
    the op's instruction name."""
    parts = [p for p in op.scope.split("/")
             if p and not p.startswith(("jit(", "pjit(", "jvp(", "while",
                                        "body", "cond", "checkpoint",
                                        "remat", "transpose("))]
    if len(parts) > 1:
        parts = parts[:-1]  # the last element names the primitive
    return "/".join(parts[:3]) or _instruction(op.name)


class _HostIndex:
    """Host spans sorted by start, to name what the host did at an instant:
    the shortest span covering it among the most recently started ones."""

    LOOK_BACK = 4096  # spans scanned back from the instant

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s.start_ns)
        self.starts = [s.start_ns for s in self.spans]

    def at(self, t) -> str:
        i = bisect.bisect_right(self.starts, t)
        best = None
        for s in self.spans[max(0, i - self.LOOK_BACK):i]:
            if s.start_ns + s.dur_ns >= t and (best is None
                                               or s.dur_ns < best.dur_ns):
                best = s
        return f"host: {best.name}" if best else "host: no span"


def summarize(trace: Trace, span_s=None, min_gap_s: float = 1e-4) -> Summary:
    """Busy time, window and idle gaps of ``trace``.  ``span_s`` is the
    traced window's (start, end) on the host clock in seconds; without it
    the window runs from the first to the last device op."""
    by_dev = collections.defaultdict(list)
    for o in trace.ops:
        by_dev[o.device].append((o.start_ns, o.start_ns + o.dur_ns))
    busy = 0.0
    gaps = []
    host = _HostIndex(trace.spans)
    for dev, iv in by_dev.items():
        merged = _union(iv)
        busy += sum(e - s for s, e in merged) * 1e-9
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            if (s1 - e0) * 1e-9 >= min_gap_s:
                gaps.append(((s1 - e0) * 1e-9, host.at((e0 + s1) / 2)))
    n = max(len(by_dev), 1)
    if span_s is not None and span_s[1] > span_s[0]:
        window = span_s[1] - span_s[0]
    else:
        lo = min((s for iv in by_dev.values() for s, _ in iv), default=0)
        hi = max((e for iv in by_dev.values() for _, e in iv), default=0)
        window = (hi - lo) * 1e-9
    return Summary(busy / n, window, trace.ops, gaps)
