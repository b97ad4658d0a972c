"""Per-iteration flight recorder: host/device time attribution for the
decode loop.

Every :meth:`InferenceEngine.step` iteration is decomposed into
**exclusive, telescoping phases** — consecutive ``perf_counter`` stamps,
so the phase durations sum to the measured iteration wall time *exactly*
(modulo float ulp; :meth:`FlightRecorder.record` asserts the invariant
rather than logging it):

``schedule``
    admission, eviction, radix lookups, deadline sweeps — pure host work.
``prefill``
    chunked prefill dispatch + its harvest for every prefilling slot.
``dispatch``
    building the decode operands and handing the (single) compiled decode
    executable to the runtime — host work again.
``device_wait``
    the blocking ``device_get`` in ``_harvest_inflight`` — the *residual*
    sync the host could not hide behind its own work, and (together with
    ``overlap_hidden_s``) the denominator of every "is the accelerator
    actually busy?" question.
``harvest``
    token emission, finish bookkeeping, telemetry — host work.

Three phases have **named parts** (:data:`ITERATION_PARTS`), stamped by the
same stamper on one clock read a boundary: a ``parts`` row ``[<phase>/<part>,
start_s, end_s]`` lies inside one interval of its phase, and what a phase
spends under no part is its ``rest``. ``schedule`` and ``device_wait`` have
none.

``host_fraction`` = 1 − (device_wait + overlap_hidden) / wall over the
recorded window: the ROADMAP item-5 measurement ("host-scheduling time
leaving the per-token critical path"). Under the double-buffered engine
host phases can run *while a decode round is in flight on device*; such
intervals are still attributed to their phase (the vocabulary stays
exclusive and telescoping) but are additionally accumulated into the
per-iteration ``overlap_hidden_s`` stat, because they are off the
critical path — the device was busy the whole time. ``device_wait`` is
then only the *residual* sync the host could not hide. With the
synchronous engine ``overlap_hidden_s`` is identically 0.0 and the
formula reduces to the old 1 − device_wait / wall.

The recorder is a process-global active object with the same discipline
as ``get_tracer()``: the engine holds a direct reference (zero reads per
iteration when armed), external consumers (watchdog HANG_REPORT, the
``/profile`` window dump) take ONE :func:`get_active_flight_recorder`
read, and the disabled path is a single ``is None`` check per iteration.

This module imports **no jax** at module scope — the diagnostics readers
and the jax-free ``accelerate-tpu profile`` CLI may import it from any
host. Only :func:`capture_profile_window` (the on-demand profiler) pulls
jax in, lazily, inside the serving process that already has it.
"""

from __future__ import annotations

import json
import math
import os
import time
from collections import deque

#: the exclusive phases, in stamp order — ``record()`` requires exactly
#: these keyword arguments and the metrics/trace surfaces label by them
ITERATION_PHASES = ("schedule", "prefill", "dispatch", "device_wait", "harvest")

#: the named parts of a phase, in the order the engine stamps them: a
#: ``serve/<phase>/<part>`` span each, and the ``parts`` rows of an entry
ITERATION_PARTS = {
    "prefill": ("operands", "call", "first_pick", "first_fetch", "emit"),
    "dispatch": ("capacity", "operands", "call"),
    "harvest": ("emit", "close"),
}
PART_NAMES = frozenset(
    f"{phase}/{part}" for phase, parts in ITERATION_PARTS.items() for part in parts
)

_active_flight_recorder = None


def get_active_flight_recorder():
    """The process-global recorder (None when no engine armed one) — the
    single read external consumers (watchdog, profiler dump) pay."""
    return _active_flight_recorder


def set_active_flight_recorder(recorder) -> None:
    global _active_flight_recorder
    _active_flight_recorder = recorder


def _checked_intervals(intervals, wall_s: float, phases: dict) -> list:
    """``[(phase, start_s, end_s), ...]`` as given, once they are seen to
    tile ``[0, wall_s]`` without a hole or an overlap and to add up to the
    phase buckets (each boundary is ONE clock read shared by the interval
    it closes and the one it opens, so the joins are exact)."""
    out = [(str(p), float(a), float(b)) for p, a, b in intervals]
    sums = dict.fromkeys(phases, 0.0)
    edge = 0.0
    for phase, start, end in out:
        if start != edge or end < start or phase not in sums:
            raise AssertionError(f"flight intervals do not tile: {out!r}")
        sums[phase] += end - start
        edge = end
    if not math.isclose(edge, wall_s, rel_tol=1e-9, abs_tol=1e-9):
        raise AssertionError(
            f"flight intervals end at {edge!r}, iteration wall {wall_s!r}")
    for phase, total in sums.items():
        if not math.isclose(total, phases[phase], rel_tol=1e-9, abs_tol=1e-6):
            raise AssertionError(
                f"flight intervals give {phase} {total!r}, bucket {phases[phase]!r}")
    return out


def _checked_parts(parts, intervals: list) -> list:
    """``[(<phase>/<part>, start_s, end_s), ...]`` as given, once they are
    seen to be in time order, to overlap nowhere, and each to lie inside one
    interval of its own phase (a part's first and last reads are boundaries
    of its own or the phase's, so the comparisons are exact)."""
    out = [(str(n), float(a), float(b)) for n, a, b in parts]
    edge = 0.0
    for name, start, end in out:
        phase = name.split("/", 1)[0]
        if name not in PART_NAMES or start < edge or end < start or not any(
                p == phase and a <= start and end <= b for p, a, b in intervals):
            raise AssertionError(
                f"flight part {(name, start, end)!r} is unknown, out of order, or "
                f"outside every interval of its phase: {out!r} in {intervals!r}")
        edge = end
    return out


class FlightRecorder:
    """Bounded ring of per-iteration phase breakdowns + cumulative
    totals. Ring entries answer "what were the last K iterations doing"
    (HANG_REPORT, ``trace tail --iterations`` windows, the ``/profile``
    dump); the cumulative totals answer "what is the run's host share"
    (``stats()['host_fraction']``) without rescanning the ring."""

    def __init__(self, history: int = 256):
        self.history = max(1, int(history))
        self._ring: deque[dict] = deque(maxlen=self.history)
        #: what the engine is doing *right now* — updated at phase
        #: boundaries so a wedged engine's HANG_REPORT names the phase it
        #: died in, not just the last completed iteration
        self.current_phase = "idle"
        self.reset()

    def reset(self) -> None:
        """Zero the measurement window (``reset_stats()`` folds this in:
        a warmup→reset→measure cycle reports only post-reset
        iterations for both the ring and the cumulative fractions)."""
        self._ring.clear()
        self.iterations = 0
        self.wall_total_s = 0.0
        self.overlap_hidden_total_s = 0.0
        self.phase_totals_s = {p: 0.0 for p in ITERATION_PHASES}
        self.current_phase = "idle"

    def record(self, iteration: int, t_start: float, wall_s: float,
               overlap_hidden_s: float = 0.0, intervals=None,
               t_start_unix_ns: int | None = None, counters: dict | None = None,
               parts=None, **phases: float) -> dict:
        """Append one iteration. ``phases`` must cover exactly
        :data:`ITERATION_PHASES` and sum to ``wall_s`` — the stamps
        telescope (each phase is the diff of consecutive perf_counter
        reads), so a mismatch means a stamp was dropped or double-counted
        and the attribution is garbage. Asserted, not logged.

        ``overlap_hidden_s`` is *not* a sixth phase: it re-counts the
        portion of the host phases that ran under an in-flight dispatch
        (double-buffered engine), so it is bounded by
        ``wall_s − device_wait`` — also asserted.

        ``intervals`` is the same iteration as ``(phase, start_s, end_s)``
        in stamp order, seconds from ``t_start``; a phase may repeat. They
        must tile ``[0, wall_s]`` and add up, per phase, to the buckets —
        asserted too. ``t_start_unix_ns`` is ``t_start`` on the clock
        ``jax.profiler`` stamps host events with (``time.time_ns()``; an
        xplane's times count from its ``profile_start_time``), so the
        intervals can be laid over a device trace's idle gaps. ``counters``
        are the engine's running sums of the model's scalar step counters as
        of this iteration's harvest (a routed model's ``moe_*_total``): two
        entries' difference is what the device did between them, exactly.
        ``parts`` are the named parts of the phases (:data:`ITERATION_PARTS`)
        on the origin of ``intervals``, which they need: in time order,
        disjoint, each inside an interval of its phase — asserted."""
        if set(phases) != set(ITERATION_PHASES):
            raise AssertionError(
                f"flight phases {sorted(phases)} != {sorted(ITERATION_PHASES)}"
            )
        total = sum(phases.values())
        # telescoping stamps sum exactly; the tolerance only absorbs float
        # ulp on the subtraction chain, never a real accounting hole
        if not math.isclose(total, wall_s, rel_tol=1e-9, abs_tol=1e-6):
            raise AssertionError(
                f"flight phase sum {total!r} != iteration wall {wall_s!r} "
                f"({ {p: phases[p] for p in ITERATION_PHASES} })"
            )
        overlap_hidden_s = float(overlap_hidden_s)
        host_s = wall_s - phases["device_wait"]
        if not (-1e-6 <= overlap_hidden_s <= host_s + 1e-6):
            raise AssertionError(
                f"overlap_hidden_s {overlap_hidden_s!r} outside "
                f"[0, wall - device_wait = {host_s!r}]"
            )
        entry = {"iteration": int(iteration), "t_start": float(t_start),
                 "wall_s": float(wall_s),
                 "overlap_hidden_s": overlap_hidden_s}
        if intervals is not None:
            entry["intervals"] = _checked_intervals(intervals, wall_s, phases)
        if parts is not None:
            entry["parts"] = _checked_parts(parts, entry.get("intervals", []))
        if t_start_unix_ns is not None:
            entry["t_start_unix_ns"] = int(t_start_unix_ns)
        if counters:
            entry["counters"] = dict(counters)
        for p in ITERATION_PHASES:
            entry[f"{p}_s"] = float(phases[p])
            self.phase_totals_s[p] += float(phases[p])
        self._ring.append(entry)
        self.iterations += 1
        self.wall_total_s += float(wall_s)
        self.overlap_hidden_total_s += overlap_hidden_s
        return entry

    def __len__(self) -> int:
        return len(self._ring)

    def tail(self, k: int = 8) -> list[dict]:
        """Newest-last last-``k`` ring entries (crash forensics)."""
        if k <= 0:
            return []
        return list(self._ring)[-k:]

    def window(self, since_perf_t: float) -> list[dict]:
        """Ring entries whose iteration started at/after a perf_counter
        stamp — the ``/profile?seconds=N`` capture window."""
        return [e for e in self._ring if e["t_start"] >= since_perf_t]

    def host_fraction(self) -> float:
        """1 − (device_wait + overlap_hidden)/wall over everything
        recorded since reset — host time *on the critical path*. Hidden
        overlap counts as device time: the accelerator was busy under it.
        Cumulative, so it matches ``trace tail --iterations`` computed
        over the same iterations."""
        if self.wall_total_s <= 0.0:
            return 0.0
        hidden = (
            self.phase_totals_s["device_wait"] + self.overlap_hidden_total_s
        )
        return max(0.0, 1.0 - hidden / self.wall_total_s)

    def _percentiles(self, values: list[float]) -> dict:
        # no numpy on purpose: jax-free consumers import this module
        vs = sorted(values)
        n = len(vs)

        def pct(q: float) -> float:
            if n == 1:
                return vs[0]
            pos = q * (n - 1)
            lo = int(pos)
            hi = min(lo + 1, n - 1)
            return vs[lo] + (vs[hi] - vs[lo]) * (pos - lo)

        return {"p50": pct(0.50), "p99": pct(0.99)}

    def telemetry_fields(self) -> dict:
        """Flat fields for the telemetry step row (and via ingest, the
        metrics gauges) — cheap cumulative reads only."""
        if not self._ring:
            return {}
        walls = [e["wall_s"] for e in self._ring]
        pw = self._percentiles(walls)
        return {
            "host_fraction": self.host_fraction(),
            "iteration_p50_s": pw["p50"],
            "iteration_p99_s": pw["p99"],
            "overlap_hidden_s": self.overlap_hidden_total_s,
            "flight_phase": self.current_phase,
        }

    def summary(self) -> dict:
        """``stats()`` fields: the flat telemetry keys plus per-phase
        p50/p99 over the ring window. Empty when nothing recorded."""
        if not self._ring:
            return {}
        out = self.telemetry_fields()
        out["flight_window"] = len(self._ring)
        out["iteration_phases_s"] = {
            p: self._percentiles([e[f"{p}_s"] for e in self._ring])
            for p in ITERATION_PHASES
        }
        return out


def capture_profile_window(logging_dir: str, seconds: float,
                           engine=None) -> dict:
    """On-demand windowed profiling: run ``jax.profiler`` for
    ``seconds`` against the live process and dump the flight-recorder
    entries that landed inside the window, both under
    ``<logging_dir>/profiles/profile_<stamp>_<pid>/``. The engine (when
    passed) keeps serving from its own thread — this call only sleeps.

    Returns a manifest dict (also written as ``manifest.json``) naming
    the artifacts so ``trace merge`` / the ``profile`` CLI can report
    them without globbing jax's internal layout."""
    import jax  # lazy: this is the only jax touch in the module

    seconds = float(seconds)
    stamp = time.strftime("%Y%m%d_%H%M%S")
    profile_dir = os.path.join(
        logging_dir, "profiles", f"profile_{stamp}_{os.getpid()}"
    )
    os.makedirs(profile_dir, exist_ok=True)

    fl = None
    if engine is not None:
        fl = getattr(engine, "_flight", None)
    if fl is None:
        fl = get_active_flight_recorder()

    start_perf = time.perf_counter()
    iters_before = fl.iterations if fl is not None else 0
    jax.profiler.start_trace(profile_dir)
    try:
        time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()
    elapsed = time.perf_counter() - start_perf

    window = fl.window(start_perf) if fl is not None else []
    flight_path = os.path.join(profile_dir, "flight_window.json")
    with open(flight_path, "w") as f:
        json.dump(
            {
                "seconds_requested": seconds,
                "seconds_measured": elapsed,
                "iterations": len(window),
                "iterations_before": iters_before,
                "host_fraction": fl.host_fraction() if fl is not None else None,
                "phases": list(ITERATION_PHASES),
                "entries": window,
            },
            f, indent=2,
        )

    artifacts = [flight_path]
    for root, _dirs, files in os.walk(profile_dir):
        for name in files:
            p = os.path.join(root, name)
            if p not in artifacts:
                artifacts.append(p)

    manifest = {
        "profile_dir": profile_dir,
        "seconds": elapsed,
        "flight_iterations": len(window),
        "host_fraction": fl.host_fraction() if fl is not None else None,
        "artifacts": sorted(artifacts),
    }
    with open(os.path.join(profile_dir, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=2)

    from ..telemetry import get_active_recorder

    tel = get_active_recorder()
    if tel:
        tel.record_serving(
            kind="profile", profile_dir=profile_dir, seconds=elapsed,
            flight_iterations=len(window),
        )
    return manifest
