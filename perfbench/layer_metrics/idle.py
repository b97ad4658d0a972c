"""Serving scheduler: the device's idle time by what the engine was doing —
the idle gaps of the device trace laid over the flight recorder's phase
intervals (``_spans``). ``idle.<phase>_pct`` for the five phases and
``outside_step``, per cent of the traced window on the idlest device; they
sum to ``device.idle_pct.chat``. ``idle.flight_overhang_us`` is the check
on the laying-over: how far the worst iteration sticks out of the
harness's own span of it (microseconds; one clock reads near 0)."""

from perfbench.layer_metrics import _spans


def read(name: str, lc: dict):
    trace = lc.get("trace")
    flights = _spans.stamped_flights(lc)
    if trace is None or not flights:
        return None
    start, pairs = _spans.session_start_ns(flights, trace["host_spans"])
    if start is None:
        return None
    if name == "idle.flight_overhang_us":
        return _spans.flight_overhang_ns(start, pairs) / 1e3
    key = name.split(".")[1][:-len("_pct")]
    dev = min(trace["devices"].values(), key=lambda d: d["busy_ns"])
    idle = _spans.idle_by_phase(dev["gaps"], flights, start)
    return 100.0 * idle[key] / trace["window_ns"] if key in idle else None
