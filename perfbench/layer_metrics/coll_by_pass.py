"""Collectives: ``coll.exposed_pct`` split by the pass of the collective's
own scope stack — exposed time is time in a collective during which no
other operation runs on that device; per cent of the traced window, on the
device with the most exposed time, so that the passes sum to
``coll.exposed_pct``."""

import bisect

from perfbench.layer_metrics import _spans
from perfbench.reduce import xplane


def exposed_by_pass(dev: dict, tables: list) -> dict:
    """Exposed nanoseconds by pass. A collective's exposed time is what of
    it no other operation covers; where two collectives overlap, the
    overlap is booked to the one that started first."""
    leaf = xplane.flat(dev["ops"])
    busy = xplane.merged((e.start_ns, e.end_ns) for e in leaf
                         if not xplane.COLLECTIVE.search(e.name))
    starts = [s for s, _ in busy]
    out = dict.fromkeys(_spans.PASSES, 0.0)
    booked_to = float("-inf")  # collectives before this one cover up to here
    for e in sorted((e for e in leaf if xplane.COLLECTIVE.search(e.name)),
                    key=lambda e: e.start_ns):
        lo, hi = max(e.start_ns, booked_to), e.end_ns
        booked_to = max(booked_to, hi)
        if hi <= lo:
            continue
        covered = 0.0
        for s, t in busy[max(bisect.bisect_right(starts, lo) - 1, 0):]:
            if s >= hi:
                break
            covered += max(min(t, hi) - max(s, lo), 0.0)
        out[_spans.pass_of(_spans.name_stack(e, tables))] += (hi - lo) - covered
    return out


def read(name: str, lc: dict):
    trace = lc.get("trace")
    key = name.split(".")[1][:-len("_pct")] if name.count(".") == 1 else None
    if trace is None or key not in _spans.PASSES:
        return None
    tables = _spans.scope_tables(lc)
    dev = max(trace["devices"].values(), key=lambda d: d["exposed_collective_ns"])
    if not tables or not dev["collective_ns"]:
        return None  # no scope table, or no collective
    return 100.0 * exposed_by_pass(dev, tables)[key] / trace["window_ns"]
