"""The trace reducer on the small recorded trace kept beside it (see
``make_fixture.py`` for the hand-checkable layout, in microseconds)."""

import os

import pytest

from perfbench.reduce import xplane

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture.xplane.pb")
US = 1000.0  # nanoseconds


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_trace(xplane.load(FIXTURE), ("paged_attention",))


def test_busy_is_the_union_and_the_gap_is_found(reduced):
    dev = reduced["devices"]["/device:TPU:0"]
    assert reduced["window_ns"] == 100 * US
    assert dev["busy_ns"] == 90 * US
    assert dev["gaps"] == [(60 * US, 70 * US)]


def test_self_times_by_name_sum_to_busy(reduced):
    by_name = reduced["devices"]["/device:TPU:0"]["self_by_name"]
    assert by_name["while"] == 0  # a container: its body has all its time
    assert by_name["paged_attention"] == 30 * US  # found by the name in its stat
    assert by_name["fusion"] == 40 * US
    assert sum(by_name.values()) == 90 * US


def test_exposed_collective_time(reduced):
    dev = reduced["devices"]["/device:TPU:0"]
    assert dev["collective_ns"] == 30 * US
    # all-gather 50..60 alone; all-reduce 70..90 alone until fusion.2 starts at 80
    assert dev["exposed_collective_ns"] == 20 * US


def test_modules_keep_their_runs(reduced):
    mods = reduced["devices"]["/device:TPU:0"]["modules"]
    assert mods == {"jit_decode": [60 * US], "jit_prefill_plain": [30 * US]}


def test_gaps_are_named_by_the_host_span(reduced):
    dev = reduced["devices"]["/device:TPU:0"]
    assert xplane.name_gaps(dev["gaps"], reduced["host_spans"]) == {
        "perfbench/engine.step": 10 * US}
    assert xplane.name_gaps([(0, 10 * US)], reduced["host_spans"]) == {
        "host/unspanned": 10 * US}


def test_interval_helpers():
    assert xplane.union_ns([(0, 5), (3, 8), (10, 12)]) == 10
    assert xplane.base_name("fusion.123") == "fusion"
    ev = [xplane.Event("a", 0, 10), xplane.Event("b", 2, 3), xplane.Event("c", 6, 2)]
    assert sorted((e.name, s) for e, s in xplane.self_times(ev)) == [("a", 5), ("b", 3), ("c", 2)]
