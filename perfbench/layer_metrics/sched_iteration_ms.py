"""Serving scheduler: wall time of one engine iteration — the median of the
flight recorder's ``wall_s`` over the iterations of the window. A first
token waits ``ceil(prompt / prefill_chunk)`` of these."""

from perfbench.layer_metrics import _util


def read(name: str, lc: dict):
    rec = lc.get("recorder")
    if rec is None or name != "sched.iteration_ms":
        return None
    return _util.median_or_none([e["wall_s"] * 1e3 for e in rec.flight])
