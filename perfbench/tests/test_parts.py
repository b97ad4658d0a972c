"""The readers of the parts of an iteration (``layer_metrics/part.py``) and of
the paged counters (``layer_metrics/paged.py``), on flights and gaps written
by hand. Times in microseconds from the session's start ``S``; window 0..200.

Three traced iterations, each 58 long inside the harness's ``engine.step``
span of it (5..65, 66..126, 127..187), and a fourth ten seconds later (not
traced; a copy of A):

    A (5..63):    schedule 5..9 | prefill 9..25: operands 9..12, call 12..15, first_pick 15..18,
                  first_fetch 18..22, emit 22..24 (rest 24..25) | device_wait 25..35 |
                  harvest 35..41: emit 35..40 (rest 40..41) | dispatch 41..57: capacity 41..43,
                  operands 43..49, call 49..56 (rest 56..57) | harvest 57..63: close 57..63
    B (66..124):  schedule 66..72 | prefill 72..82: operands 72..74, call 74..79, emit 79..80
                  (rest 80..82) | device_wait 82..106 | harvest 106..110: emit 106..110 |
                  dispatch 110..120: capacity 110..111, operands 111..115, call 115..120 |
                  harvest 120..124: close 120..124
    C (127..185): schedule 127..130 | prefill 130..130 | device_wait 130..140 |
                  harvest 140..147: emit 140..146 (rest 146..147) | dispatch 147..177:
                  capacity 147..148, operands 148..157, call 157..175 (rest 175..177) |
                  harvest 177..185: close 177..185

The device's gaps, and what ran under each:

    10..20    prefill/operands 2, /call 3, /first_pick 3, /first_fetch 2
    30..44    device_wait 5 (A's ends at 35 inside the gap: a lag of 35 - 30 = 5), harvest/emit 5,
              harvest rest 1, dispatch/capacity 2, dispatch/operands 1
    55..68    dispatch/call 1, dispatch rest 1, harvest/close 6, outside_step 3, schedule 2
    79..84    prefill/emit 1, prefill rest 2, device_wait 2 (B's goes on to 106 and ends with
              the device busy: this gap does not end it, and B has no lag)
    118..121  dispatch/call 2, harvest/close 1
    126..143  outside_step 1, schedule 3, device_wait 10 (C's, 130..140: the gap began before it,
              so the lag is the whole interval, 10), harvest/emit 3
    175..178  dispatch rest 2, harvest/close 1

    idle by part:  prefill   operands 2, call 3, first_pick 3, first_fetch 2, emit 1, rest 2 = 13
                   dispatch  capacity 2, operands 1, call 3, rest 3                          =  9
                   harvest   emit 8, close 8, rest 1                                         = 17
    and device_wait 17, schedule 5, outside_step 4: 65, the gaps' total; per cent of 200.
    fetch lags 5 and 10 (median 7.5), one interval left out.
    host time over the four iterations (A twice): prefill/operands (3 + 2 + 0 + 3) / 4 = 2,
    /first_pick (3 + 0 + 0 + 3) / 4 = 1.5, dispatch/call (7 + 5 + 18 + 7) / 4 = 9.25,
    harvest/close (6 + 4 + 8 + 6) / 4 = 6 microseconds an iteration.
"""

from types import SimpleNamespace

import pytest

from accelerate_tpu.serving.flight import ITERATION_PARTS, FlightRecorder
from perfbench import common
from perfbench.layer_metrics import _spans, paged, part
from perfbench.reduce import xplane

S = 1_790_000_000_000_000_000
US = 1e-6


def _flight(at_us, intervals, parts):
    """A flight entry as the recorder would keep it (which checks the tiling
    and the parts), rows in microseconds from the session's start."""
    rows = lambda xs: [(n, (a - at_us) * US, (b - at_us) * US) for n, a, b in xs]  # noqa: E731
    phases = dict.fromkeys(_spans.PHASES, 0.0)
    for n, a, b in rows(intervals):
        phases[n] += b - a
    return FlightRecorder(1).record(
        1, 0.0, 58 * US, intervals=rows(intervals), parts=rows(parts),
        t_start_unix_ns=S + at_us * 1000, **phases)


A = _flight(5, [("schedule", 5, 9), ("prefill", 9, 25), ("device_wait", 25, 35),
                ("harvest", 35, 41), ("dispatch", 41, 57), ("harvest", 57, 63)],
            [("prefill/operands", 9, 12), ("prefill/call", 12, 15), ("prefill/first_pick", 15, 18),
             ("prefill/first_fetch", 18, 22), ("prefill/emit", 22, 24), ("harvest/emit", 35, 40),
             ("dispatch/capacity", 41, 43), ("dispatch/operands", 43, 49),
             ("dispatch/call", 49, 56), ("harvest/close", 57, 63)])
B = _flight(66, [("schedule", 66, 72), ("prefill", 72, 82), ("device_wait", 82, 106),
                 ("harvest", 106, 110), ("dispatch", 110, 120), ("harvest", 120, 124)],
            [("prefill/operands", 72, 74), ("prefill/call", 74, 79), ("prefill/emit", 79, 80),
             ("harvest/emit", 106, 110), ("dispatch/capacity", 110, 111),
             ("dispatch/operands", 111, 115), ("dispatch/call", 115, 120),
             ("harvest/close", 120, 124)])
C = _flight(127, [("schedule", 127, 130), ("prefill", 130, 130), ("device_wait", 130, 140),
                  ("harvest", 140, 147), ("dispatch", 147, 177), ("harvest", 177, 185)],
            [("harvest/emit", 140, 146), ("dispatch/capacity", 147, 148),
             ("dispatch/operands", 148, 157), ("dispatch/call", 157, 175),
             ("harvest/close", 177, 185)])
LATER = dict(A, t_start_unix_ns=S + 10_000_000_000)
GAPS = [(10, 20), (30, 44), (55, 68), (79, 84), (118, 121), (126, 143), (175, 178)]


def _trace():
    gaps = [(a * 1000.0, b * 1000.0) for a, b in GAPS]
    spans = [xplane.Event("perfbench/engine.step", at * 1000.0, 60_000.0) for at in (5, 66, 127)]
    return {"t0_ns": 0.0, "t1_ns": 200_000.0, "window_ns": 200_000.0, "host_spans": spans,
            "devices": {"/device:TPU:0": {"busy_ns": 135_000.0, "gaps": gaps},
                        "/device:TPU:1": {"busy_ns": 200_000.0, "gaps": []}}}  # (the idlest is read)


def _lc(flights=(A, B, C, LATER), **more):
    return {"trace": _trace(), "recorder": SimpleNamespace(flight=list(flights)), **more}


def _read(name, lc):
    return common.metric_reader(name)(name, lc)


IDLE_US = {"prefill": {"operands": 2, "call": 3, "first_pick": 3, "first_fetch": 2, "emit": 1,
                       "rest": 2},
           "dispatch": {"capacity": 2, "operands": 1, "call": 3, "rest": 3},
           "harvest": {"emit": 8, "close": 8, "rest": 1}}


@pytest.mark.parametrize("phase,name,us", [(p, n, us) for p, d in IDLE_US.items()
                                           for n, us in d.items()])
def test_idle_under_each_part(phase, name, us):
    assert _read(f"part.idle.{phase}.{name}_pct", _lc()) == pytest.approx(100 * us / 200, abs=1e-9)


@pytest.mark.parametrize("phase,us", [("prefill", 13), ("dispatch", 9), ("harvest", 17)])
def test_the_parts_of_a_phase_and_its_rest_are_the_phases_idle_share(phase, us):
    lc = _lc()
    total = sum(_read(f"part.idle.{phase}.{n}_pct", lc) for n in IDLE_US[phase])
    assert total == pytest.approx(_read(f"idle.{phase}_pct", lc), abs=1e-9)
    assert total == pytest.approx(100 * us / 200, abs=1e-9)
    by_phase = _spans.idle_by_phase(lc["trace"]["devices"]["/device:TPU:0"]["gaps"],
                                    [A, B, C, LATER], S)
    assert by_phase[phase] == pytest.approx(us * 1000.0)
    assert sum(by_phase.values()) == pytest.approx(65_000.0)


@pytest.mark.parametrize("name,us", [
    ("prefill.operands", 2.0), ("prefill.call", (3 + 5 + 0 + 3) / 4), ("prefill.first_pick", 1.5),
    ("prefill.first_fetch", 2.0), ("prefill.emit", (2 + 1 + 0 + 2) / 4),
    ("dispatch.capacity", (2 + 1 + 1 + 2) / 4), ("dispatch.operands", (6 + 4 + 9 + 6) / 4),
    ("dispatch.call", 9.25), ("harvest.emit", (5 + 4 + 6 + 5) / 4), ("harvest.close", 6.0)])
def test_host_time_in_a_part_is_the_mean_over_every_iteration_of_the_window(name, us):
    assert _read(f"part.host_ms.{name}", _lc()) == pytest.approx(us / 1000.0)


def test_fetch_lag_takes_the_gap_that_ends_a_wait_and_leaves_out_a_wait_that_ends_busy(capsys):
    assert _read("part.fetch_lag_us.median", _lc()) == pytest.approx(7.5)
    err = capsys.readouterr().err
    assert "over 2 device_wait intervals; 1 more end with the device busy" in err
    # and the host's time in A, B, C (traced) beside LATER's: (7 + 5 + 18) / 3 against 7 us
    assert "in the 3 traced iterations / the other 1:" in err
    assert "dispatch/call 0.0100 / 0.0070" in err and "prefill/first_pick 0.0010 / 0.0030" in err
    gaps = _trace()["devices"]["/device:TPU:0"]["gaps"]
    assert part.fetch_lags_ns(gaps, [A], S, 0.0, 200_000.0) == ([pytest.approx(5_000.0)], 0)
    assert part.fetch_lags_ns(gaps, [B], S, 0.0, 200_000.0) == ([], 1)  # (the gap 79..84 ends nothing)
    assert part.fetch_lags_ns(gaps, [C], S, 0.0, 200_000.0) == ([pytest.approx(10_000.0)], 0)
    assert part.fetch_lags_ns(gaps, [C], S, 0.0, 139_000.0) == ([], 0)  # ends after the traced span
    assert part.fetch_lags_ns(gaps, [LATER], S, 0.0, 200_000.0) == ([], 0)
    assert _read("part.fetch_lag_us.median", _lc(flights=(B, LATER))) is None  # no wait to read


def test_a_part_that_never_ran_reads_zero_and_a_program_without_parts_none():
    """A block model's prefill picks no token: its cell reads 0 under
    ``first_pick``. The parent's flight entries have no ``parts`` field:
    every metric of the family is left out of its line, ``idle.*`` is not."""
    lc = _lc(flights=(B, C))
    assert _read("part.idle.prefill.first_pick_pct", lc) == 0.0
    assert _read("part.host_ms.prefill.first_fetch", lc) == 0.0
    strip = lambda f: {k: v for k, v in f.items() if k != "parts"}  # noqa: E731
    parent = _lc(flights=[strip(f) for f in (A, B, C, LATER)])
    for name in _listed("part"):
        assert _read(name, parent) is None, name
    assert _read("idle.prefill_pct", parent) == pytest.approx(100 * 13 / 200, abs=1e-9)


@pytest.mark.parametrize("lc_without", [
    {},
    {"trace": None, "recorder": SimpleNamespace(flight=[A, B, C])},           # not traced
    {"trace": _trace(), "recorder": SimpleNamespace(flight=[])},              # no flight
    {"trace": _trace(), "recorder": SimpleNamespace(flight=[{"wall_s": 1.0, "parts": []}])},
    {"trace": dict(_trace(), host_spans=[]), "recorder": SimpleNamespace(flight=[A, B, C])},
])
def test_a_part_reader_without_its_source_returns_none(lc_without):
    for name in _listed("part"):
        assert _read(name, dict(lc_without)) is None, name


def _listed(family):
    return [m["name"] for m in common.benchmark()["per_layer"] if m["name"].split(".")[0] == family]


def test_every_part_the_program_stamps_is_listed_with_both_its_metrics():
    pairs = [(ph, p) for ph, ps in ITERATION_PARTS.items() for p in ps]
    want = {f"part.idle.{ph}.{p}_pct" for ph, p in pairs} \
        | {f"part.idle.{ph}.rest_pct" for ph in ITERATION_PARTS} \
        | {f"part.host_ms.{ph}.{p}" for ph, p in pairs} | {"part.fetch_lag_us.median"}
    assert set(_listed("part")) == want and len(want) == 24
    chat = {w["name"] for w in common.benchmark()["workloads"] if w["chips"] == 1}
    for m in common.benchmark()["per_layer"]:
        if m["name"].split(".")[0] in ("part", "paged"):
            assert set(m["workloads"]) == chat and m["moves"] == "tpot_ms.p90"
            assert common.metric_reader(m["name"]).__module__.endswith(m["name"].split(".")[0])


# -- the paged counters ---------------------------------------------------------

STATS0 = {"paged_entries_walked_total": 100, "paged_entries_table_total": 1000,
          "paged_tiles_walked_total": 50, "paged_tile_entries": 8}
STATS1 = {"paged_entries_walked_total": 1100, "paged_entries_table_total": 21000,
          "paged_tiles_walked_total": 300, "paged_tile_entries": 8}


def test_paged_counters_of_the_window():
    """1,000 entries walked of 20,000 the tables hold: 5 % live; in 250
    softmax steps of 8 entries: tiles half full."""
    lc = {"stats0": STATS0, "stats1": STATS1}
    assert _listed("paged") == ["paged.table_live_pct", "paged.tile_fill_pct"]
    assert _read("paged.table_live_pct", lc) == pytest.approx(5.0)
    assert _read("paged.tile_fill_pct", lc) == pytest.approx(50.0)


@pytest.mark.parametrize("s0,s1,live", [
    (None, None, None), ({}, {}, None), ({"iterations": 1}, {"iterations": 9}, None),
    (STATS0, STATS0, None),                                      # nothing dispatched in the window
    ({k: v for k, v in STATS0.items() if k != "paged_tile_entries"},
     {k: v for k, v in STATS1.items() if k != "paged_tile_entries"}, 5.0),  # the parent's stats()
])
def test_a_paged_reader_without_its_counters_returns_none(s0, s1, live):
    lc = {"stats0": s0, "stats1": s1}
    assert paged.read("paged.table_live_pct", lc) == live
    assert paged.read("paged.tile_fill_pct", lc) is None
