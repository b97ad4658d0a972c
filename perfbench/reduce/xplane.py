"""From a profiler trace (``.xplane.pb``) to numbers: device busy time as
the union of operation intervals, per-name sums, self time, exposed
collective time, and idle gaps named by what the host was doing.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. The
reduction works on plain tuples so that the tests can check it by hand.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

#: line of a device plane that holds one event per executed HLO operation
OPS_LINE = "XLA Ops"
#: line of a device plane that holds one event per executed program
MODULES_LINE = "XLA Modules"
#: host spans the harness writes with ``jax.profiler.TraceAnnotation``
SPAN_PREFIX = "perfbench/"

#: operations that only hold other operations (their time is their body's)
CONTAINERS = ("while", "conditional", "call")

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute", re.I
)


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    text: str = ""  # name plus the string stats, for matching kernels

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """``devices``: plane name -> {line name -> [Event]}; ``host_spans``:
    the harness's own annotations, from every host thread."""
    devices: dict = field(default_factory=dict)
    host_spans: list = field(default_factory=list)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and "TPU" in plane.name.upper()
        lines = {}
        for line in plane.lines:
            events = []
            for ev in line.events:
                name = ev.name
                if is_device:
                    strs = [str(v) for _, v in ev.stats if isinstance(v, str)]
                    events.append(Event(op_name(name), ev.start_ns, ev.duration_ns,
                                        " ".join([name, *strs])))
                elif name.startswith(SPAN_PREFIX):
                    trace.host_spans.append(Event(name, ev.start_ns, ev.duration_ns))
            if is_device and events:
                lines[line.name] = events
        if is_device and OPS_LINE in lines:
            trace.devices[plane.name] = lines
    trace.host_spans.sort(key=lambda e: e.start_ns)
    return trace


def union_ns(intervals) -> float:
    """Total length covered by ``[(start, end), ...]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(events, t0_ns: float, t1_ns: float) -> list:
    """Events cut to the window."""
    out = []
    for e in events:
        s, t = max(e.start_ns, t0_ns), min(e.end_ns, t1_ns)
        if t > s:
            out.append(Event(e.name, s, t - s, e.text))
    return out


def self_times(events) -> list:
    """``[(event, self_ns)]``: an event's duration less what later-starting
    events cover of it (a ``while`` holds its body's operations; where two
    operations overlap in part, the overlap is booked to the later one), so
    that self times sum to the union of all intervals."""
    order = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    out, stack = [], []  # stack of [event, child_ns]
    for e in order:
        while stack and stack[-1][0].end_ns <= e.start_ns:
            ev, child = stack.pop()
            out.append((ev, max(ev.dur_ns - child, 0.0)))
        if stack:
            stack[-1][1] += min(e.end_ns, stack[-1][0].end_ns) - e.start_ns
        stack.append([e, 0.0])
    while stack:
        ev, child = stack.pop()
        out.append((ev, max(ev.dur_ns - child, 0.0)))
    return out


def exposed_ns(events, pattern=COLLECTIVE) -> float:
    """Time in events matching ``pattern`` during which no other event of
    the same list runs (pass ``flat`` events: a ``while`` covers its body)."""
    coll = [(e.start_ns, e.end_ns) for e in events if pattern.search(e.name)]
    other = merged((e.start_ns, e.end_ns) for e in events if not pattern.search(e.name))
    total = 0.0
    for s, e in merged(coll):
        covered = 0.0
        for os_, oe in other:
            lo, hi = max(s, os_), min(e, oe)
            if hi > lo:
                covered += hi - lo
        total += (e - s) - covered
    return total


def flat(events) -> list:
    """Events without the control-flow containers that only hold others."""
    return [e for e in events if base_name(e.name) not in CONTAINERS]


def op_name(name: str) -> str:
    """The TPU's trace names an operation by its whole HLO line
    (``%fusion.12 = bf16[...] fusion(...)``): keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def base_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``: XLA numbers its operations."""
    return re.sub(r"([.:]\d+|\.remat\d*|\.clone)+$", "", name)


def window_of(trace: Trace) -> tuple:
    """The traced window: from the first to the last device event."""
    starts, ends = [], []
    for lines in trace.devices.values():
        for e in lines[OPS_LINE]:
            starts.append(e.start_ns)
            ends.append(e.end_ns)
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def reduce_trace(trace: Trace, kernel_names=()) -> dict:
    """The summary every layer-metric reader works from."""
    t0, t1 = window_of(trace)
    per_device = {}
    for plane, lines in trace.devices.items():
        ops = clip(lines[OPS_LINE], t0, t1)
        busy = union_ns((e.start_ns, e.end_ns) for e in ops)
        st = self_times(ops)
        by_name: dict = {}
        for e, self_ns in st:
            key = base_name(e.name)
            for k in kernel_names:
                if k in e.name or (" = " not in e.text and k in e.text):
                    key = k
                    break
            by_name[key] = by_name.get(key, 0.0) + self_ns
        leaf = flat(ops)
        modules = {}
        for e in clip(lines.get(MODULES_LINE, []), t0, t1):
            modules.setdefault(re.sub(r"\(\d+\)$", "", e.name), []).append(e.dur_ns)
        per_device[plane] = {
            "busy_ns": busy,
            "self_by_name": by_name,
            "exposed_collective_ns": exposed_ns(leaf),
            "collective_ns": sum(e.dur_ns for e in leaf if COLLECTIVE.search(e.name)),
            "modules": modules,
            "gaps": idle_gaps(ops, t0, t1),
            "ops": ops,
        }
    return {"t0_ns": t0, "t1_ns": t1, "window_ns": t1 - t0, "devices": per_device,
            "host_spans": trace.host_spans}


def idle_gaps(ops, t0: float, t1: float) -> list:
    """``[(start, end)]`` in which no operation ran."""
    gaps, cur = [], t0
    for s, e in merged((e.start_ns, e.end_ns) for e in ops):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    return gaps


def name_gaps(gaps, host_spans) -> dict:
    """Idle nanoseconds by what the host was doing: each gap is split over
    the harness's spans that overlap it (innermost wins by being listed
    last); the rest is ``host/unspanned``."""
    out: dict = {}
    for gs, ge in gaps:
        covered = []
        for sp in host_spans:
            lo, hi = max(gs, sp.start_ns), min(ge, sp.end_ns)
            if hi > lo:
                out[sp.name] = out.get(sp.name, 0.0) + (hi - lo)
                covered.append((lo, hi))
        rest = (ge - gs) - union_ns(covered)
        if rest > 0:
            out["host/unspanned"] = out.get("host/unspanned", 0.0) + rest
    return out
