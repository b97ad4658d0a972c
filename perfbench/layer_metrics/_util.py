"""Helpers the readers share."""

from __future__ import annotations

from statistics import median


def worst_device(trace: dict, fn) -> float | None:
    """``fn(device summary)`` on every traced device, the largest."""
    vals = [fn(d) for d in trace["devices"].values()]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


def module_durations_ms(trace: dict, prefix: str) -> list:
    out = []
    for d in trace["devices"].values():
        for name, durs in d["modules"].items():
            if name.startswith(prefix):
                out += [x / 1e6 for x in durs]
    return out


def kernel_ns(dev: dict, names) -> float:
    return sum(dev["self_by_name"].get(n, 0.0) for n in names)


def median_or_none(values):
    return float(median(values)) if values else None
