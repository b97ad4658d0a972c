"""Collectives: time in all-gather / reduce-scatter / all-reduce during
which no other operation runs on that device, over the traced window, on
the worst device."""

from perfbench.layer_metrics import _util


def read(name: str, lc: dict):
    trace = lc.get("trace")
    if name == "coll.exposed_pct" and trace is not None:
        return _util.worst_device(
            trace, lambda d: 100.0 * d["exposed_collective_ns"] / trace["window_ns"])
    return None
