"""Serving scheduler: the named parts of an engine iteration's phases.

Since PR 40 a flight entry carries, beside the phase ``intervals``, the
``parts`` the engine stamped inside ``prefill``, ``dispatch`` and ``harvest``
(``["<phase>/<part>", start_s, end_s]`` on the intervals' origin, each inside
one interval of its phase; ``accelerate_tpu/serving/flight.py:ITERATION_PARTS``).
What a phase spends under no part is its ``rest``.

* ``part.idle.<phase>.<part>_pct`` and ``part.idle.<phase>.rest_pct``: the
  idle gaps of the device trace laid over the parts as ``idle.py`` lays them
  over the phases - same device (the idlest), same session start, per cent of
  the traced window. ``rest`` is the phase's idle time (``_spans.idle_by_phase``)
  less its parts', so a phase's parts and rest add up to ``idle.<phase>_pct``.
* ``part.host_ms.<phase>.<part>``: milliseconds of host time an iteration
  spent in the part, the mean over every flight entry of the window (an
  iteration without the part counts 0): what more chunks an iteration
  multiply, and what overlap could hide, idle under it or not.
* ``part.fetch_lag_us.median``: over the ``device_wait`` intervals that lie in
  the traced window and at whose end the device is idle, the time from the
  end of the device's last operation (or the interval's start, if later) to
  the interval's end: the fetch and the host's wake-up. Intervals that end
  with the device busy (a prefill chunk queued behind the round) are left
  out; how many is said on standard error, and beside it each part's host
  milliseconds in the iterations the profiler recorded against the others
  (what the session costs the host code it watches).

``None`` without a trace, without stamped flights, and from a program whose
flight entries carry no ``parts``.
"""

from __future__ import annotations

import bisect
import sys

from perfbench.layer_metrics import _spans
from perfbench.layer_metrics._util import median_or_none


def idle_by_part(gaps: list, flights: list, start: int) -> dict:
    """Idle nanoseconds of ``gaps`` (in time order) by the part that ran
    then (``_spans.idle_by_phase`` over ``parts``)."""
    out: dict = {}
    ends = [ge for _, ge in gaps]
    for f in flights:
        base = f["t_start_unix_ns"] - start
        for name, a, b in f["parts"]:
            lo, hi = base + a * 1e9, base + b * 1e9
            for gs, ge in gaps[bisect.bisect_right(ends, lo):]:
                if gs >= hi:
                    break
                out[name] = out.get(name, 0.0) + min(ge, hi) - max(gs, lo)
    return out


def fetch_lags_ns(gaps: list, flights: list, start: int, t0_ns: float, t1_ns: float) -> tuple:
    """``(lags, busy)``: for every ``device_wait`` interval inside
    ``[t0_ns, t1_ns]`` that ends in a gap, the gap's part inside the interval
    up to its end; and the count of those that end with the device busy."""
    lags, busy = [], 0
    starts = [gs for gs, _ in gaps]
    for f in flights:
        base = f["t_start_unix_ns"] - start
        for phase, a, b in f["intervals"]:
            lo, hi = base + a * 1e9, base + b * 1e9
            if phase != "device_wait" or lo < t0_ns or hi > t1_ns:
                continue
            i = bisect.bisect_left(starts, hi) - 1  # the last gap that starts before the end
            if i >= 0 and gaps[i][1] >= hi:
                lags.append(hi - max(gaps[i][0], lo))
            else:
                busy += 1
    return lags, busy


def _reduced(lc: dict):
    """Everything the family reads, worked out once a run."""
    if "part_metrics" not in lc:
        lc["part_metrics"] = _reduce(lc)
    return lc["part_metrics"]


def _reduce(lc: dict):
    trace = lc.get("trace")
    flights = [f for f in _spans.stamped_flights(lc) if "parts" in f]
    if trace is None or not flights:
        return None
    start, _ = _spans.session_start_ns(flights, trace["host_spans"])
    if start is None:
        return None
    dev = min(trace["devices"].values(), key=lambda d: d["busy_ns"])
    idle = idle_by_part(dev["gaps"], flights, start)
    for phase, ns in _spans.idle_by_phase(dev["gaps"], flights, start).items():
        under = sum(v for k, v in idle.items() if k.startswith(phase + "/"))
        idle[phase + "/rest"] = max(ns - under, 0.0)
    t0, t1 = trace["t0_ns"], trace["t1_ns"]
    traced = [t0 <= f["t_start_unix_ns"] - start
              and f["t_start_unix_ns"] - start + f["wall_s"] * 1e9 <= t1 for f in flights]
    host = host_ms_by_part(flights)
    lags, busy = fetch_lags_ns(dev["gaps"], flights, start, t0, t1)
    print(f"perfbench: part.fetch_lag_us over {len(lags)} device_wait intervals; "
          f"{busy} more end with the device busy", file=sys.stderr)
    # what the profiler session costs the host: the same parts, in the
    # iterations it recorded and in those it did not
    inside = host_ms_by_part([f for f, t in zip(flights, traced) if t])
    outside = host_ms_by_part([f for f, t in zip(flights, traced) if not t])
    print(f"perfbench: part.host_ms in the {sum(traced)} traced iterations / the other "
          f"{len(flights) - sum(traced)}: "
          + ", ".join(f"{k} {inside.get(k, 0.0):.4f} / {outside.get(k, 0.0):.4f}"
                      for k in sorted(host)), file=sys.stderr)
    return {
        "idle_pct": {k: 100.0 * v / trace["window_ns"] for k, v in idle.items()},
        "host_ms": host,
        "fetch_lag_us": median_or_none([x / 1e3 for x in lags]),
    }


def host_ms_by_part(flights: list) -> dict:
    """Mean milliseconds an iteration of ``flights`` spent in each part."""
    out: dict = {}
    for f in flights:
        for name, a, b in f["parts"]:
            out[name] = out.get(name, 0.0) + 1e3 * (b - a) / len(flights)
    return out


def read(name: str, lc: dict):
    got = _reduced(lc)
    if got is None:
        return None
    _, kind, *rest = name.split(".")
    if kind == "fetch_lag_us":
        return got["fetch_lag_us"]
    phase, part = rest
    if kind == "idle":
        return got["idle_pct"].get(f"{phase}/{part[:-len('_pct')]}", 0.0)
    if kind == "host_ms":
        return got["host_ms"].get(f"{phase}/{part}", 0.0)
    return None
