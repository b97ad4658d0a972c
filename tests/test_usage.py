"""Per-request resource attribution (``serving/usage.py``) — the
conservation-checked usage ledger.

The headline property under test is **conservation, asserted**: the sum
of per-request decode device-time shares equals the engine's cumulative
``device_wait`` accrual, and the sum of per-request KV block-second
integrals equals the pool-occupancy integral — to float tolerance, under
every scheduling feature that edits block ownership or harvest timing
(chunked prefill, radix hit + CoW, swap preemption, deadline expiry,
speculative rounds, async + sync dispatch, a 4-device mesh), across
every kv_dtype. Plus the tenant dimension's round-trip (payload →
engine → rollups → trails), the exported-cardinality cap, and the
disabled path staying one truthiness check.

Tier-1 tests are pure host (ledger arithmetic, CLI plumbing, trail
readers); engine end-to-end conservation rides the slow lane like the
rest of the serving suite.
"""

import argparse
import json
import math
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from accelerate_tpu.serving.usage import (
    DEFAULT_TOP_K,
    OTHER_TENANT,
    UsageLedger,
    cap_by_key,
    normalize_tenant,
)

KV_DTYPES = ("bf16", "int8", "fp8")


# ---------------------------------------------------------------------------
# tenant normalization + cardinality cap (tier-1: pure host)
# ---------------------------------------------------------------------------


def test_normalize_tenant_contract():
    assert normalize_tenant("acme") == "acme"
    assert normalize_tenant("  padded  ") == "padded"
    assert normalize_tenant("x" * 200) == "x" * 64
    for bad in (None, "", "   ", 7, 1.5, ["a"], {"t": 1}, True):
        assert normalize_tenant(bad) == "default"


def test_cap_by_key_top_k_plus_other():
    """K+1 tenants export as the K heaviest + an ``other`` fold summing
    every numeric field of the rest."""
    k = 3
    entries = {
        f"t{i}": {"device_seconds": float(i), "swap_bytes": i, "name": "x"}
        for i in range(k + 2)  # t0..t4, weights 0..4
    }
    capped = cap_by_key(entries, k)
    assert set(capped) == {"t4", "t3", "t2", OTHER_TENANT}
    assert capped[OTHER_TENANT]["device_seconds"] == 1.0  # t0 + t1
    assert capped[OTHER_TENANT]["swap_bytes"] == 1
    assert "name" not in capped[OTHER_TENANT]  # non-numeric fields dropped
    # at or under the cap: pass-through copies, no fold bucket
    small = cap_by_key(dict(list(entries.items())[:k]), k)
    assert OTHER_TENANT not in small and len(small) == k


def test_cap_by_key_merges_literal_other_tenant():
    entries = {
        "other": {"device_seconds": 10.0},
        "a": {"device_seconds": 5.0},
        "b": {"device_seconds": 1.0},
        "c": {"device_seconds": 0.5},
    }
    capped = cap_by_key(entries, 2)
    # "other" won a top-K slot on weight; the fold (b + c) merges into it
    assert capped[OTHER_TENANT]["device_seconds"] == 11.5


# ---------------------------------------------------------------------------
# ledger arithmetic (tier-1: synthetic edges, no engine)
# ---------------------------------------------------------------------------


class _FakeReq:
    def __init__(self, rid, tenant="default", priority="interactive"):
        self.request_id = rid
        self.tenant = tenant
        self.priority = priority
        self.trace_id = f"trace-{rid}"
        self.blocks = []
        self.swap_plan = []
        self.output_tokens = []
        self.finish_reason = "eos"


def _conserved(snap, rel=1e-9):
    assert math.isclose(
        snap["decode_device_seconds"], snap["device_wait_seconds"],
        rel_tol=rel, abs_tol=1e-12,
    ), (snap["decode_device_seconds"], snap["device_wait_seconds"])
    assert math.isclose(
        snap["block_seconds"], snap["pool_block_seconds"],
        rel_tol=rel, abs_tol=1e-12,
    ), (snap["block_seconds"], snap["pool_block_seconds"])


def test_ledger_conservation_synthetic_edges():
    """Interleaved grow/shrink/swap edges with overlapping holders: the
    per-request integrals sum to the pool integral, and decode shares sum
    to the round total, without any engine in the loop."""
    ledger = UsageLedger()
    reqs = [_FakeReq(i, tenant=f"t{i % 2}") for i in range(3)]
    for r in reqs:
        ledger.begin(r)
    for step in range(40):
        r = reqs[step % 3]
        if step % 7 == 3 and r.blocks:
            r.swap_plan = list(r.blocks[: len(r.blocks) // 2])  # swap out
        elif step % 5 == 1:
            r.swap_plan = []
            r.blocks = r.blocks[:-1]  # shrink (eviction edge)
        else:
            r.blocks = r.blocks + [step]  # grow
        ledger.update_blocks(r)
        live = [q for q in reqs if q.request_id in ledger._live]
        ledger.accrue_decode(
            0.001, [(q.request_id, 1 + q.request_id) for q in live]
        )
    summaries = [ledger.finish(r) for r in reqs]
    assert all(s is not None for s in summaries)
    snap = ledger.snapshot()
    _conserved(snap)
    assert math.isclose(
        snap["device_wait_seconds"], 0.040, rel_tol=1e-9
    )
    assert snap["requests_finished"] == 3 and snap["requests_live"] == 0
    assert set(snap["by_tenant"]) == {"t0", "t1"}
    # the answer-row summary mirrors the folded record
    total = sum(s["device_time_s"] for s in summaries)
    assert math.isclose(total, snap["device_seconds"], rel_tol=1e-9)


def test_ledger_finish_exactly_once_and_late_edges_noop():
    ledger = UsageLedger()
    r = _FakeReq(1, tenant="acme")
    ledger.begin(r)
    r.blocks = [0, 1]
    ledger.update_blocks(r)
    first = ledger.finish(r)
    assert first is not None
    assert ledger.finish(r) is None  # exactly-once
    before = ledger.snapshot()
    ledger.update_blocks(r)  # late edge after close: must not resurrect
    ledger.accrue_decode(1.0, [(r.request_id, 1)])
    after = ledger.snapshot()
    assert after["block_seconds"] == before["block_seconds"]
    assert after["decode_device_seconds"] == before["decode_device_seconds"]
    # the partner total still advances (the round happened) — but with no
    # live holder the per-request side is deliberately unattributed
    assert after["device_wait_seconds"] == before["device_wait_seconds"] + 1.0


def test_ledger_decode_equal_split_fallback():
    """A round whose every share weight is zero (all-discarded harvest)
    loses no device time: callers pass equal weights as the fallback."""
    ledger = UsageLedger()
    reqs = [_FakeReq(i) for i in range(2)]
    for r in reqs:
        ledger.begin(r)
    ledger.accrue_decode(0.008, [(r.request_id, 1) for r in reqs])
    for r in reqs:
        ledger.finish(r)
    snap = ledger.snapshot()
    _conserved(snap)
    by_class = snap["by_class"]["interactive"]
    assert math.isclose(by_class["decode_device_seconds"], 0.008, rel_tol=1e-9)


def test_ledger_snapshot_caps_tenants_and_reset():
    ledger = UsageLedger(top_k=2)
    reqs = [_FakeReq(i, tenant=f"tenant-{i}") for i in range(4)]
    for r in reqs:
        ledger.begin(r)
        ledger.accrue_decode(0.001 * (i := r.request_id + 1), [(r.request_id, 1)])
        ledger.finish(r)
    snap = ledger.snapshot()
    assert len(snap["by_tenant"]) == 3  # top 2 + "other"
    assert OTHER_TENANT in snap["by_tenant"]
    assert snap["top_k"] == 2
    assert len(snap["heavy_hitters"]) == 2
    ledger.reset()
    zero = ledger.snapshot()
    assert zero["requests_finished"] == 0
    assert zero["device_seconds"] == 0.0 and zero["by_tenant"] == {}


# ---------------------------------------------------------------------------
# CLI plumbing + workload tenants (tier-1: pure host)
# ---------------------------------------------------------------------------


def _parse_serve(argv, monkeypatch, env=None):
    from accelerate_tpu.commands import serve as serve_cmd

    monkeypatch.delenv("ACCELERATE_SERVE_USAGE", raising=False)
    if env is not None:
        monkeypatch.setenv("ACCELERATE_SERVE_USAGE", env)
    parser = argparse.ArgumentParser()
    serve_cmd.add_parser(parser.add_subparsers())
    return parser.parse_args(argv)


def test_serve_usage_accounting_flag_and_env(monkeypatch):
    assert _parse_serve(["serve"], monkeypatch).usage_accounting is True
    assert _parse_serve(
        ["serve", "--no-usage-accounting"], monkeypatch
    ).usage_accounting is False
    assert _parse_serve(["serve"], monkeypatch, env="0").usage_accounting is False
    assert _parse_serve(
        ["serve", "--usage-accounting"], monkeypatch, env="0"
    ).usage_accounting is True


def test_engine_config_usage_accounting_default_on():
    from accelerate_tpu.serving import EngineConfig

    assert EngineConfig().usage_accounting is True


def test_workload_tenants_spec_round_trip():
    from accelerate_tpu.serving.workload import generate_schedule, parse_trace_spec

    spec = parse_trace_spec("bursty-diurnal:3:2:8:tenants=3")
    assert spec.tenants == 3
    assert spec.as_text() == "bursty-diurnal:3:2:8:tenants=3"
    schedule = generate_schedule(spec)
    tenants = {e["payload"]["tenant"] for e in schedule}
    assert tenants <= {"t0", "t1", "t2"} and len(tenants) >= 2
    # deterministic: same spec, same assignment
    assert schedule == generate_schedule(parse_trace_spec(spec.as_text()))
    # tenants=N changes WHO bills, never the arrival schedule
    plain = generate_schedule(parse_trace_spec("bursty-diurnal:3:2:8"))
    assert "tenant" not in plain[0]["payload"]
    assert [e["t"] for e in plain] == [e["t"] for e in schedule]


def test_workload_tenants_spec_malformed():
    from accelerate_tpu.serving.workload import TraceSpecError, parse_trace_spec

    with pytest.raises(TraceSpecError):
        parse_trace_spec("bursty-diurnal:3:2:8:tenants=x")
    with pytest.raises(TraceSpecError):
        parse_trace_spec("bursty-diurnal:3:2:8:tenants=-1")
    with pytest.raises(TraceSpecError):
        parse_trace_spec("bursty-diurnal:3:2:8:bogus=1")


def test_openai_tenant_and_cost_fields():
    """``x_accelerate_tenant`` rides into the payload; the vendor block
    carries the ledger's measured costs back out."""
    from accelerate_tpu.serving.openai_api import OpenAIFrontend

    captured = {}

    def submit(payload, cb):
        captured.update(payload)
        cb({
            "tokens": [65, 66], "prompt_tokens": 3, "finish_reason": "eos",
            "trace_id": "tr-1", "tenant": "acme", "device_time_s": 0.25,
            "kv_block_seconds": 1.5, "swap_bytes": 4096,
        })

    frontend = OpenAIFrontend(submit)
    kind, status, body = frontend.handle(
        "/v1/completions",
        {"prompt": "hi", "x_accelerate_tenant": "acme", "temperature": 0},
    )
    assert (kind, status) == ("json", 200)
    assert captured["tenant"] == "acme"
    vendor = body["x_accelerate"]
    assert vendor["tenant"] == "acme"
    assert vendor["device_time_s"] == 0.25
    assert vendor["kv_block_seconds"] == 1.5
    assert vendor["swap_bytes"] == 4096


# ---------------------------------------------------------------------------
# metrics ingest + usage report CLI (tier-1: trail readers, no jax)
# ---------------------------------------------------------------------------


def _sample_snapshot():
    return {
        "schema": 1,
        "requests_finished": 2,
        "requests_live": 0,
        "top_k": DEFAULT_TOP_K,
        "device_seconds": 0.5,
        "decode_device_seconds": 0.3,
        "prefill_device_seconds": 0.2,
        "block_seconds": 4.0,
        "swap_bytes": 1024,
        "spec_drafted_tokens": 0,
        "spec_accepted_tokens": 0,
        "grammar_masked_steps": 0,
        "device_wait_seconds": 0.3,
        "pool_block_seconds": 4.0,
        "by_tenant": {
            "acme": {"requests": 1, "tokens": 8, "device_seconds": 0.4,
                     "block_seconds": 3.0, "swap_bytes": 1024},
            "default": {"requests": 1, "tokens": 4, "device_seconds": 0.1,
                        "block_seconds": 1.0, "swap_bytes": 0},
        },
        "by_class": {"interactive": {"requests": 2, "tokens": 12,
                                     "device_seconds": 0.5}},
        "heavy_hitters": [{"request_id": 1, "trace_id": "tr-1",
                           "tenant": "acme", "class": "interactive",
                           "device_seconds": 0.4, "block_seconds": 3.0,
                           "swap_bytes": 1024, "new_tokens": 8,
                           "finish_reason": "eos"}],
    }


def test_ingest_usage_counters_both_surfaces():
    """The same tenant-labeled counters come out of a telemetry step row
    and out of ``observe_engine_stats`` — the one-table-two-surfaces rule."""
    from accelerate_tpu.metrics.ingest import observe_record, observe_engine_stats
    from accelerate_tpu.metrics.openmetrics import render_openmetrics
    from accelerate_tpu.metrics.registry import MetricsRegistry

    snap = _sample_snapshot()
    via_record = MetricsRegistry()
    observe_record(
        via_record,
        {"type": "serving", "kind": "step", "schema": 1, "usage": snap},
    )
    via_stats = MetricsRegistry()
    observe_engine_stats(via_stats, {"usage": snap})
    for registry in (via_record, via_stats):
        text = render_openmetrics(registry)
        assert 'serving_usage_device_seconds_total{tenant="acme"} 0.4' in text
        assert 'serving_usage_block_seconds_total{tenant="acme"} 3' in text
        assert 'serving_usage_swap_bytes_total{tenant="acme"} 1024' in text
        assert 'serving_usage_device_seconds_total{tenant="default"} 0.1' in text
        assert "serving_usage_requests_total 2" in text


def test_ingest_router_by_tenant_counters():
    from accelerate_tpu.metrics.ingest import observe_router_row
    from accelerate_tpu.metrics.openmetrics import render_openmetrics
    from accelerate_tpu.metrics.registry import MetricsRegistry

    registry = MetricsRegistry()
    observe_router_row(registry, {
        "kind": "router", "delivered": 5, "shed": 1,
        "by_tenant": {
            "acme": {"delivered": 3, "shed": 1, "requeued": 2,
                     "deadline_expired": 0},
        },
    })
    text = render_openmetrics(registry)
    assert 'serving_router_delivered_total{tenant="acme"} 3' in text
    assert 'serving_router_shed_total{tenant="acme"} 1' in text
    assert 'serving_router_requeues_total{tenant="acme"} 2' in text
    assert "serving_router_delivered_total 5" in text  # aggregate intact


def _write_run(tmp_path, snap, by_tenant_router=None):
    from accelerate_tpu.telemetry import TelemetryRecorder

    recorder = TelemetryRecorder(logging_dir=str(tmp_path))
    recorder.record_serving("step", tokens_per_sec=1.0, usage=snap)
    recorder.close()
    if by_tenant_router is not None:
        router_dir = tmp_path / "router"
        router_dir.mkdir(exist_ok=True)
        with open(router_dir / "replicas.jsonl", "w") as f:
            f.write(json.dumps({
                "kind": "router", "schema": 1, "delivered": 2,
                "by_tenant": by_tenant_router,
            }) + "\n")


def test_usage_report_conservation_verdict(tmp_path, capsys):
    from accelerate_tpu.commands.usage import build_report, render_report

    _write_run(
        tmp_path, _sample_snapshot(),
        by_tenant_router={"acme": {"delivered": 2, "shed": 0, "requeued": 0,
                                   "deadline_expired": 0}},
    )
    report = build_report(str(tmp_path))
    assert report["conserved"] is True and report["pass"] is True
    run = report["runs"][0]
    assert run["conservation"]["device"]["ok"] is True
    assert run["conservation"]["blocks"]["ok"] is True
    assert run["router_by_tenant"]["acme"]["delivered"] == 2
    text = render_report(report)
    assert "CONSERVED" in text and "tenant acme" in text
    assert "tr-1" in text  # heavy-hitter exemplar links into trace tooling

    # a cooked snapshot that violates conservation FAILS the report
    bad = _sample_snapshot()
    bad["decode_device_seconds"] = bad["device_wait_seconds"] * 2
    bad_dir = tmp_path / "bad"
    bad_dir.mkdir()
    _write_run(bad_dir, bad)
    bad_report = build_report(str(bad_dir))
    assert bad_report["conserved"] is False and bad_report["pass"] is False
    assert "VIOLATED" in render_report(bad_report)


def test_usage_report_cli_json_round_trip(tmp_path, capsys):
    from accelerate_tpu.commands.accelerate_cli import main

    _write_run(tmp_path, _sample_snapshot())
    assert main(["usage", "report", str(tmp_path), "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["schema"] == 1 and report["conserved"] is True
    snap = report["runs"][0]["usage"]
    assert snap["by_tenant"]["acme"]["device_seconds"] == 0.4
    # rendered form agrees with the machine-readable verdict
    assert main(["usage", "report", str(tmp_path), "--by", "class"]) == 0
    assert "interactive" in capsys.readouterr().out


def test_usage_report_without_snapshot(tmp_path, capsys):
    from accelerate_tpu.commands.accelerate_cli import main
    from accelerate_tpu.telemetry import TelemetryRecorder

    recorder = TelemetryRecorder(logging_dir=str(tmp_path))
    recorder.record_serving("step", tokens_per_sec=1.0)  # no usage field
    recorder.close()
    assert main(["usage", "report", str(tmp_path)]) == 0
    assert "no usage snapshot" in capsys.readouterr().out


def test_router_ticket_tenant_property():
    from accelerate_tpu.serving.router import Ticket

    assert Ticket(payload={"tenant": "acme", "prompt": [1]}).tenant == "acme"
    assert Ticket(payload={"prompt": [1]}).tenant == "default"
    assert Ticket(payload={"tenant": 7, "prompt": [1]}).tenant == "default"


def test_monitor_renders_usage_panel():
    from accelerate_tpu.diagnostics.monitor import render_status

    status = {
        "logging_dir": "/tmp/x", "steps": None, "optimizer_steps": None,
        "step_time_s": None, "step_rate": None, "examples_per_sec": None,
        "tokens_per_sec": None, "mfu": None, "recompiles": None,
        "last_record_age_s": None, "skipped_unknown_schema": 0,
        "hosts": [], "stragglers": [], "wedged": [], "hang_reports": [],
        "race_reports": [], "collective_divergence": [], "fleet": [],
        "fleet_dead": [], "scale_decisions": [],
        "serving": {
            "tokens_per_sec": 10.0, "queue_depth": 0, "slot_occupancy": 0.5,
            "free_blocks": 3, "decode_compiles": 1, "completed": 2,
            "ttft_p50_s": 0.1, "ttft_p99_s": 0.2,
            "usage": _sample_snapshot(),
        },
    }
    text = render_status(status)
    assert "usage: device 0.5s" in text
    assert "tenants: acme 0.4s" in text


# ---------------------------------------------------------------------------
# engine end-to-end conservation (slow lane)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig.tiny(vocab_size=64, hidden_size=32, layers=2, heads=4, seq=96)
    return LlamaForCausalLM.from_config(config, seed=0)


def _cfg(**kw):
    from accelerate_tpu.serving import EngineConfig

    base = dict(num_slots=3, block_size=8, max_seq_len=64, prefill_chunk=8)
    base.update(kw)
    return EngineConfig(**base)


def _prompts(seed, sizes=(5, 11, 17, 3, 9)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, size=n).astype(np.int32) for n in sizes]


def _drive_mixed(eng):
    return [
        eng.add_request(p, 3 + 4 * i, tenant=f"t{i % 3}")
        for i, p in enumerate(_prompts(0))
    ]


def _drive_radix_cow(eng):
    base = np.arange(20, dtype=np.int32) % 60
    r1 = eng.add_request(base, 6, tenant="warm")
    eng.run_until_idle(max_iterations=5000)
    shared = np.concatenate([base[:19], np.asarray([61], np.int32)])
    r2 = eng.add_request(shared, 6, tenant="hit")
    return [r1, r2]


def _drive_swap(eng):
    return [
        eng.add_request(
            np.arange(8, dtype=np.int32) + i, max_new_tokens=30,
            tenant=f"t{i}",
        )
        for i in range(2)
    ]


def _drive_deadline(eng):
    doomed = eng.add_request([5, 6, 7], 8, deadline_ms=0.001, tenant="doomed")
    rest = [
        eng.add_request(p, 6, tenant="survivor")
        for p in _prompts(3, sizes=(5, 9))
    ]
    return [doomed] + rest


_SCENARIOS = {
    "chunked_prefill": (_drive_mixed, dict(decode_burst=1)),
    "radix_cow": (_drive_radix_cow, dict(prefix_cache=True)),
    "swap_preempt": (
        _drive_swap,
        dict(num_slots=2, num_blocks=6, swap_gb=0.01, prefix_cache=False),
    ),
    "deadline": (_drive_deadline, {}),
    "spec_k3": (_drive_mixed, dict(spec_k=3, draft="early_exit:1")),
}


def _run_and_assert_conserved(model, drive, **cfg_kw):
    """Run the drive on an async and a sync engine; assert conservation,
    one decode executable, and flight agreement on both."""
    from accelerate_tpu.serving import InferenceEngine

    snaps = []
    for async_dispatch in (True, False):
        eng = InferenceEngine(model, _cfg(async_dispatch=async_dispatch, **cfg_kw))
        reqs = drive(eng)
        eng.run_until_idle(max_iterations=5000)
        stats = eng.stats()
        assert stats["decode_compiles"] == 1
        snap = stats["usage"]
        _conserved(snap)
        assert snap["requests_live"] == 0
        assert snap["requests_finished"] == len(reqs)
        # the ledger's decode total is the flight recorder's device_wait —
        # the same floats, attributed instead of merely bucketed
        if eng._flight is not None:
            assert math.isclose(
                snap["device_wait_seconds"],
                eng._flight.phase_totals_s["device_wait"],
                rel_tol=1e-9, abs_tol=1e-12,
            )
        # every finished request carries its answer-row cost summary
        for r in reqs:
            assert r.usage is not None
            assert r.usage["device_time_s"] >= 0.0
            # a deadline-doomed request can close before it ever holds a
            # block, so the integral's floor is 0, not positive
            assert r.usage["kv_block_seconds"] >= 0.0
        snaps.append((eng, reqs, snap))
    return snaps


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
@pytest.mark.parametrize("scenario", sorted(_SCENARIOS))
def test_conservation_matrix(tiny_model, scenario, kv_dtype):
    drive, cfg_kw = _SCENARIOS[scenario]
    snaps = _run_and_assert_conserved(
        tiny_model, drive, kv_dtype=kv_dtype, **cfg_kw
    )
    for eng, reqs, snap in snaps:
        if scenario == "swap_preempt":
            assert eng.stats()["preemptions"] >= 1
            assert snap["swap_bytes"] > 0
            by = snap["by_tenant"]
            assert sum(v["swap_bytes"] for v in by.values()) == snap["swap_bytes"]
        elif scenario == "deadline":
            assert reqs[0].finish_reason == "deadline_exceeded"
            # the doomed request's account still closed, exactly once
            assert reqs[0].usage is not None
            assert "doomed" in snap["by_tenant"]
        elif scenario == "spec_k3":
            assert snap["spec_drafted_tokens"] > 0
            assert snap["spec_drafted_tokens"] == eng.stats()["spec_drafted_tokens"]
        elif scenario == "radix_cow":
            assert eng.stats()["prefix_hit_tokens"] > 0
            # both the cold and the warm holder billed block-seconds
            assert all(
                v["block_seconds"] > 0 for v in snap["by_tenant"].values()
            )


@pytest.mark.slow
def test_conservation_mesh4(tiny_model):
    import jax

    from accelerate_tpu.mesh import build_mesh
    from accelerate_tpu.utils.dataclasses import MeshPlugin

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs a >= 4-device (virtual) mesh")
    mesh = build_mesh(MeshPlugin(dp=1, fsdp=2, tp=2), devices=devices[:4])

    from accelerate_tpu.serving import InferenceEngine

    eng = InferenceEngine(tiny_model, _cfg(decode_burst=2), mesh=mesh)
    reqs = [
        eng.add_request(p, b, tenant=f"t{i % 2}")
        for i, (p, b) in enumerate(
            zip(_prompts(7, sizes=(5, 12, 9)), (4, 7, 5))
        )
    ]
    eng.run_until_idle(max_iterations=5000)
    stats = eng.stats()
    assert stats["decode_compiles"] == 1
    _conserved(stats["usage"])
    assert all(r.usage is not None for r in reqs)


@pytest.mark.slow
def test_tenant_round_trip_and_disabled_path(tiny_model):
    """Tenant flows add_request → request rows → by_tenant rollups; with
    accounting off the engine carries no ledger and rows carry no costs."""
    from accelerate_tpu.serving import InferenceEngine

    eng = InferenceEngine(tiny_model, _cfg())
    reqs = [
        eng.add_request([1 + i, 2, 3], 4, tenant=t)
        for i, t in enumerate(("acme", "  acme  ", None, ""))
    ]
    eng.run_until_idle(max_iterations=5000)
    assert [r.tenant for r in reqs] == ["acme", "acme", "default", "default"]
    by = eng.stats()["usage"]["by_tenant"]
    assert by["acme"]["requests"] == 2 and by["default"]["requests"] == 2

    off = InferenceEngine(tiny_model, _cfg(usage_accounting=False))
    assert off.usage is None
    offreqs = [off.add_request([1, 2, 3], 4, tenant="acme")]
    off.run_until_idle(max_iterations=5000)
    assert offreqs[0].tenant == "acme"  # the dimension survives
    assert offreqs[0].usage is None  # no costs without the ledger
    assert "usage" not in off.stats()


# ---------------------------------------------------------------------------
# exactly-once usage rows under chaos (slow lane, routed fleet CLI)
# ---------------------------------------------------------------------------

_TINY_ARGS = [
    "--preset", "tiny", "--num-slots", "2", "--block-size", "8",
    "--max-seq-len", "64", "--prefill-chunk", "8", "--decode-burst", "2",
]


@pytest.mark.slow
def test_chaos_exactly_once_usage_rows(tmp_path):
    """Under a seeded kill schedule against a routed fleet, every request
    is answered exactly once and every answer carries its usage costs —
    a redispatched request bills its final (answering) replica only."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    env.pop("ACCELERATE_SERVE_USAGE", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
         "route", "--replicas", "2", "--respawn", "--min-replicas", "2",
         "--logging-dir", str(tmp_path), "--health-interval", "0.2",
         "--chaos-spec", "seed=1;r0:kill@3", *_TINY_ARGS],
        env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True,
    )
    results = []

    def read():
        for line in proc.stdout:
            line = line.strip()
            if line:
                results.append(line)

    threading.Thread(target=read, daemon=True).start()
    try:
        for i in range(8):
            proc.stdin.write(json.dumps({
                "id": i, "prompt": [1 + (i % 5), 7, 3], "max_new_tokens": 4,
                "tenant": f"t{i % 2}",
            }) + "\n")
            proc.stdin.flush()
        deadline = time.monotonic() + 240
        while len(results) < 8 and time.monotonic() < deadline:
            if proc.poll() is not None:
                break
            time.sleep(0.1)
        proc.stdin.close()
        rc = proc.wait(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    assert rc == 0
    parsed = [json.loads(line) for line in results]
    assert sorted(r.get("id") for r in parsed) == list(range(8))
    assert not [r for r in parsed if "error" in r]
    for r in parsed:
        # exactly one usage summary per answer, from the answering replica
        assert r["tenant"] == f"t{r['id'] % 2}"
        assert r["device_time_s"] >= 0.0
        assert r["kv_block_seconds"] > 0.0
