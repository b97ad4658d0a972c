"""The readers of the program's own spans and scopes on the hand-checkable
trace of ``make_fixture_scopes.py`` (sums worked out in its docstring), and
each reader on a context without its source."""

import os
from types import SimpleNamespace

import pytest

from perfbench import common
from perfbench.layer_metrics import _spans
from perfbench.reduce import xplane
from perfbench.tests import make_fixture_scopes

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture_scopes.xplane.pb")
S = 1_790_000_000_000_000_000  # the session's start on the wall clock, ns
A = [("schedule", 0, 4e-6), ("prefill", 4e-6, 20e-6), ("dispatch", 20e-6, 50e-6),
     ("device_wait", 50e-6, 56e-6), ("harvest", 56e-6, 58e-6)]
B = [("schedule", 0, 10e-6), ("prefill", 10e-6, 30e-6), ("dispatch", 30e-6, 45e-6),
     ("device_wait", 45e-6, 56e-6), ("harvest", 56e-6, 58e-6)]


def _flight(at_us, intervals):
    return {"t_start_unix_ns": S + at_us * 1000, "wall_s": 58e-6, "intervals": intervals}


KERNELS = ("paged_attention", "flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv")
#: the fixture is a trace of the Mistral-shaped programs: the cells whose
#: configuration file names that model, and the per-layer metrics that list
#: one of them (a cell of another architecture lists scopes of its own);
#: between them those cells list the nine scopes of that model's programs
BENCH = common.benchmark()
CELLS = {w["name"] for w in BENCH["workloads"]
         if common.find_cell(BENCH, w["name"])[1]["model_type"] == "mistral"}
LISTED = [m["name"] for m in BENCH["per_layer"] if CELLS & set(m.get("workloads", CELLS))]
NINE = frozenset({"embed", "attn_proj", "kv_write", "attn_kernel", "mlp", "head", "sample",
                  "loss", "optimizer"})


class _Engine:
    """Stands for the engine the driver asks for its tables."""

    def scope_table(self, program):
        return make_fixture_scopes.TABLES[("decode", "prefill").index(program)]


def _recorder(flights):
    return SimpleNamespace(flight=flights)


@pytest.fixture(scope="module")
def lc():
    trace = xplane.reduce_trace(xplane.load(FIXTURE), KERNELS)
    flights = [_flight(5, A), _flight(66, B), _flight(10_000_000, A)]  # the last: not traced
    return {"trace": trace, "recorder": _recorder(flights), "per_layer_names": LISTED,
            "scope_tables": make_fixture_scopes.TABLES,
            "stats0": {"first_tokens_total": 10, "ttft_sum_s": 20.0, "ttft_queue_sum_s": 1.0,
                       "ttft_own_prefill_sum_s": 2.0, "ttft_prefill_iterations_sum": 30},
            "stats1": {"first_tokens_total": 14, "ttft_sum_s": 28.0, "ttft_queue_sum_s": 1.4,
                       "ttft_own_prefill_sum_s": 3.0, "ttft_prefill_iterations_sum": 42,
                       "allocated_blocks": 25, "cached_blocks": 40},
            "ttft_ms": [2100.0, 2300.0], "num_blocks": 100}


def _read(name, lc):
    return common.metric_reader(name)(name, lc)


def test_the_committed_fixture_is_what_the_maker_writes(tmp_path):
    fresh = xplane.load(make_fixture_scopes.write(str(tmp_path / "f.pb")))
    kept = xplane.load(FIXTURE)  # (the bytes differ: a proto map has no order)
    assert fresh.devices == kept.devices and fresh.host_spans == kept.host_spans
    assert len(kept.devices["/device:TPU:0"][xplane.OPS_LINE]) == len(make_fixture_scopes.OPS)


@pytest.mark.parametrize("scope,pct", [
    ("embed", 100 * 10 / 120), ("attn_proj", 25.0), ("mlp", 25.0), ("attn_kernel", 100 * 20 / 120),
    ("layer_carry", 100 * 10 / 120), ("optimizer", 12.5), ("unscoped", 100 * 5 / 120),
    ("kv_write", 0.0), ("head", 0.0), ("sample", 0.0), ("loss", 0.0)])
def test_scope_shares(lc, scope, pct):
    assert _read(f"scope.{scope}_pct.train", lc) == pytest.approx(pct)
    assert _read(f"scope.{scope}_pct.chat", lc) == pytest.approx(pct)


def test_scope_shares_sum_to_100_and_unscoped_is_named(lc):
    dev, tables = _spans.busiest(lc["trace"]), _spans.scope_tables(lc)
    assert _spans.cell_scopes(lc) == NINE
    shares = _spans.self_shares(dev, tables, lambda st: _spans.scope_of(st, NINE))
    assert sum(shares.values()) == pytest.approx(100.0)
    assert _spans.unscoped_names(dev, tables, NINE) == [("copy", pytest.approx(5e-6))]


def test_the_driver_takes_the_tables_while_the_program_lives(capsys):
    """``layer_ctx["scope_tables"]`` is all a reader has: nothing reaches
    into the engine, which is freed by then."""
    assert common.scope_tables(_Engine(), "decode", "prefill") == make_fixture_scopes.TABLES
    assert common.scope_tables(object(), "decode") == []  # a program that hands out none

    class Broken(_Engine):
        def scope_table(self, program):
            raise RuntimeError("no executable")

    assert common.scope_tables(Broken(), "decode") == []  # costs the metrics, not the run
    assert "no table (RuntimeError" in capsys.readouterr().err


@pytest.mark.parametrize("p,pct", [("fwd", 100 * 40 / 120), ("remat", 25.0), ("bwd", 25.0),
                                   ("optimizer", 12.5), ("other", 100 * 5 / 120)])
def test_pass_shares(lc, p, pct):
    assert _read(f"pass.{p}_pct", lc) == pytest.approx(pct)


@pytest.mark.parametrize("p,pct", [("fwd", 100 * 10 / 140), ("remat", 100 * 10 / 140),
                                   ("bwd", 0.0), ("optimizer", 0.0), ("other", 0.0)])
def test_exposed_collective_time_by_pass(lc, p, pct):
    assert _read(f"coll_by_pass.{p}_pct", lc) == pytest.approx(pct)


def test_exposed_by_pass_sums_to_the_accepted_metric(lc):
    total = sum(_read(f"coll_by_pass.{p}_pct", lc) for p in _spans.PASSES)
    assert total == pytest.approx(_read("coll.exposed_pct", lc)) == pytest.approx(100 * 20 / 140)


@pytest.mark.parametrize("phase,us", [("schedule", 4), ("prefill", 0), ("dispatch", 1),
                                      ("device_wait", 10), ("harvest", 2), ("outside_step", 3)])
def test_idle_by_engine_phase(lc, phase, us):
    assert _read(f"idle.{phase}_pct", lc) == pytest.approx(100 * us / 140, abs=1e-6)


def test_idle_phases_sum_to_the_device_idle_share(lc):
    total = sum(_read(f"idle.{p}_pct", lc) for p in (*_spans.PHASES, "outside_step"))
    assert total == pytest.approx(_read("device.idle_pct.chat", lc), abs=1e-6)
    assert total == pytest.approx(100 * 20 / 140, abs=1e-6)


def test_the_session_start_is_found_from_the_harness_spans(lc):
    flights = _spans.stamped_flights(lc)
    start, pairs = _spans.session_start_ns(flights, lc["trace"]["host_spans"])
    assert start == S and len(pairs) == 2
    assert _read("idle.flight_overhang_us", lc) == 0.0
    # an iteration that ends 3 us after the harness's span of it sticks out by 3
    late = [dict(flights[0], wall_s=63e-6), flights[1]]
    start, pairs = _spans.session_start_ns(late, lc["trace"]["host_spans"])
    assert _spans.flight_overhang_ns(start, pairs) == pytest.approx(3000.0)
    # one traced iteration is enough: only a span of its own length is a candidate
    start, pairs = _spans.session_start_ns(
        [dict(flights[1], wall_s=58.9e-6)], lc["trace"]["host_spans"])
    assert start == S and len(pairs) == 1 and pairs[0][1].start_ns == 66_000
    # stamps that jitter by a few microseconds still agree on one start
    jitter = [dict(flights[0], t_start_unix_ns=S + 5000 + 4000), flights[1]]
    start, pairs = _spans.session_start_ns(jitter, lc["trace"]["host_spans"])
    assert start == S + 2000 and len(pairs) == 2


@pytest.mark.parametrize("name,value", [
    ("ttft.queue_ms.mean", 100.0), ("ttft.own_prefill_ms.mean", 250.0),
    ("ttft.interleave_ms.mean", 2000.0 - 100.0 - 250.0), ("ttft.prefill_iters.mean", 3.0),
    ("ttft.outside_engine_ms.mean", 2200.0 - 2000.0),
    ("kv.pool_live_pct", 25.0), ("kv.pool_cached_pct", 40.0)])
def test_counters_of_the_window(lc, name, value):
    assert _read(name, lc) == pytest.approx(value)


def test_iteration_time_is_the_median_wall(lc):
    rec = SimpleNamespace(flight=[{"wall_s": 1.0}, {"wall_s": 1.2}, {"wall_s": 5.0}])
    assert _read("sched.iteration_ms", {"recorder": rec}) == 1200.0


def _new_metrics():
    names = [m["name"] for m in common.benchmark()["per_layer"]]
    return [n for n in names if n.split(".")[0] in
            ("idle", "ttft", "scope", "pass", "coll_by_pass")
            or n in ("sched.iteration_ms", "kv.pool_live_pct", "kv.pool_cached_pct")]


def test_every_new_metric_is_listed_and_has_a_reader():
    assert len(_new_metrics()) >= 40
    for name in _new_metrics():
        common.metric_reader(name)


@pytest.mark.parametrize("lc_without", [
    {},                                                      # nothing at all
    {"trace": None, "recorder": None, "stats0": None, "stats1": None},
    {"recorder": SimpleNamespace(flight=[]), "stats0": {"iterations": 1},
     "stats1": {"iterations": 9}, "ttft_ms": [1.0], "num_blocks": 8},  # the parent's counters
])
def test_a_reader_without_its_source_returns_none(lc_without):
    for name in _new_metrics():
        if name != "sched.iteration_ms" or not getattr(lc_without.get("recorder"), "flight", None):
            assert _read(name, lc_without) is None, name


def test_a_traced_run_of_a_program_without_tables_or_stamps_reads_none():
    """The first fixture's trace, no scope table in the context, flights
    that carry no stamps: what a program from before PR 24 gives the readers."""
    plain = os.path.join(os.path.dirname(FIXTURE), "fixture.xplane.pb")
    trace = xplane.reduce_trace(xplane.load(plain), KERNELS)
    chat = {"trace": trace, "stats0": {}, "stats1": {}, "per_layer_names": LISTED,
            "recorder": SimpleNamespace(flight=[{"wall_s": 1.0}])}
    for name in _new_metrics():
        if name != "sched.iteration_ms":
            assert _read(name, chat) is None, name
    assert _spans.scope_tables(chat) == []
    assert _read("scope.mlp_pct.chat", dict(chat, scope_tables=[])) is None


def test_scope_and_pass_of_a_stack():
    bwd = "jit(step)/loss/transpose(jvp(layers))/while/body/closed_call/checkpoint"
    scope_of = lambda stack: _spans.scope_of(stack, NINE)  # noqa: E731
    assert scope_of(f"{bwd}/rematted_computation/mlp/jit(silu)/mul") == "mlp"
    assert _spans.pass_of(f"{bwd}/rematted_computation/mlp/jit(silu)/mul") == "remat"
    assert _spans.pass_of(f"{bwd}/attn_proj/dot_general") == "bwd"
    assert scope_of("jit(decode)/while/body/layers/while/body/dynamic_slice") == "layer_carry"
    assert scope_of("jit(decode)/while/body/copy") == "unscoped"
    kernel = "jit(decode)/while/body/layers/while/body/attn_kernel/pallas_call[name=paged_attention]"
    assert scope_of(kernel) == "attn_kernel"
    assert _spans.scope_of(kernel, NINE - {"attn_kernel"}) == "layer_carry"  # a scope no metric lists
    assert scope_of("jit(step)/loss/jvp(jit(take_along_axis))/gather") == "loss"
    assert _spans.pass_of("jit(step)/optimizer/jit(_where)/select_n") == "optimizer"


def test_an_event_finds_its_stack_by_instruction_and_shape():
    tables = make_fixture_scopes.TABLES
    line = make_fixture_scopes._line
    ev = lambda n, sh: xplane.Event(n, 0, 1, line(n, sh, "fusion"))  # noqa: E731
    assert _spans.scope_of(_spans.name_stack(ev("fusion.2", "bf16[8,32]"), tables), NINE) == "attn_proj"
    assert _spans.scope_of(_spans.name_stack(ev("fusion.2", "f32[8,256]"), tables), NINE) == "head"
    assert _spans.name_stack(ev("fusion.6", "f32[64,32]"), tables) == "jit(step)/optimizer/mul"
    assert _spans.name_stack(ev("copy.1", "f32[64,32]"), tables) == ""
