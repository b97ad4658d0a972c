"""Diagnostics subsystem tests: span tracing into Chrome trace files,
cross-host merge with clock-offset correction, the hang watchdog (stalled
step → HANG_REPORT with the stalled thread's stack + open span stack; a
healthy loop must NOT fire), the monitor status engine, the CLI surface,
and the PR's telemetry satellites (atexit/idempotent close, empty-ring
summary, unknown_skip counting, compile-record mono timestamps)."""

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import optax
import pytest

from accelerate_tpu import Accelerator
from accelerate_tpu.diagnostics import (
    NULL_TRACER,
    Tracer,
    Watchdog,
    collect_status,
    get_tracer,
    merge_traces,
    render_status,
    set_active_tracer,
    trace_span,
    validate_chrome_trace,
)
from accelerate_tpu.diagnostics.watchdog import _set_active_watchdog, get_active_watchdog
from accelerate_tpu.telemetry import TelemetryRecorder, set_active_recorder
from accelerate_tpu.test_utils import RegressionDataset, RegressionModel, SimpleLoader

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clear_diagnostics_globals():
    """Tracing/watchdog/telemetry all register process-wide state; tests
    must not leak it into each other."""
    yield
    from accelerate_tpu import lazy

    wd = get_active_watchdog()
    if wd is not None:
        wd.stop()
    _set_active_watchdog(None)
    set_active_tracer(None)
    set_active_recorder(None)
    lazy.set_compile_callback(None)


def _toy(tmp_path, **kwargs):
    acc = Accelerator(project_dir=str(tmp_path), **kwargs)
    model, opt, dl = acc.prepare(
        RegressionModel(a=0.0, b=0.0),
        optax.sgd(0.1),
        SimpleLoader(RegressionDataset(length=64), batch_size=16),
    )
    return acc, model, opt, dl


def _train(acc, model, opt, dl, epochs=1):
    for _ in range(epochs):
        for batch in dl:
            out = model(**batch)
            acc.backward(out.loss)
            opt.step()
            opt.zero_grad()


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


def test_toy_loop_writes_valid_trace_and_heartbeat(tmp_path):
    """Acceptance loop: 20 steps with telemetry+diagnostics produce a
    per-host trace file that merges into a schema-valid Chrome trace with
    the built-in spans, plus a heartbeat file with the step count."""
    acc, model, opt, dl = _toy(tmp_path, telemetry=True, diagnostics=True)
    _train(acc, model, opt, dl, epochs=5)  # 64/16 × 5 = 20 steps
    acc.end_training()

    trace_dir = tmp_path / "traces"
    assert (trace_dir / "host_0.trace.json").exists()
    merged = merge_traces(str(trace_dir), str(tmp_path / "merged.json"))
    validate_chrome_trace(merged)
    names = {e["name"] for e in merged["traceEvents"]}
    assert {"prepare", "backward/dispatch", "step/dispatch",
            "compile/trace_lower", "compile/compile", "dataloader/fetch"} <= names
    # merged output is well-formed standalone JSON, loadable by Perfetto
    reloaded = json.load(open(tmp_path / "merged.json"))
    validate_chrome_trace(reloaded)
    # spans carry sane timings: positive durations, rebased to t≥0
    complete = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    assert complete and all(e["dur"] >= 0 and e["ts"] >= 0 for e in complete)
    # 20 steps → 20 step/dispatch spans
    assert sum(1 for e in complete if e["name"] == "step/dispatch") == 20

    hb = json.load(open(tmp_path / "diagnostics" / "heartbeat_0.json"))
    assert hb["step"] == 20 and hb["ema_step_s"] > 0


def test_trace_survives_crash_without_close(tmp_path):
    """The append format must be parseable with no close() — the whole
    point is a SIGKILL'd run's trace still loads."""
    tracer = Tracer(logging_dir=str(tmp_path), host=0)
    with tracer.span("phase_a", step=1):
        pass
    tracer.flush()  # but never close()
    merged = merge_traces(str(tmp_path / "traces"))
    validate_chrome_trace(merged)
    assert any(e["name"] == "phase_a" for e in merged["traceEvents"])
    tracer.close()


def test_trace_merge_corrects_host_clock_offsets(tmp_path):
    """Two hosts whose monotonic clocks disagree wildly but whose wall
    clocks agree must land on ONE timeline: same-wall-time events align
    after the per-host wall-minus-mono correction."""
    trace_dir = tmp_path / "traces"
    trace_dir.mkdir()
    # host 0: mono origin 1000s, offset wall-mono = 500; event at wall 1503
    # host 1: mono origin 2000s, offset wall-mono = -500; event at wall 1503
    for host, (mono_ts, offset) in enumerate({0: (1003.0, 500.0), 1: (2003.0, -500.0)}.values()):
        lines = [
            "[\n",
            json.dumps({"name": "clock_sync", "ph": "M", "pid": host, "tid": 0,
                        "args": {"wall_minus_mono_s": offset}}) + ",\n",
            json.dumps({"name": "step", "ph": "X", "ts": mono_ts * 1e6,
                        "dur": 1000.0, "pid": host, "tid": 1}) + ",\n",
        ]
        (trace_dir / f"host_{host}.trace.json").write_text("".join(lines))
    merged = merge_traces(str(trace_dir))
    steps = [e for e in merged["traceEvents"] if e["name"] == "step"]
    assert len(steps) == 2
    # both events happened at the same wall instant → identical merged ts
    assert abs(steps[0]["ts"] - steps[1]["ts"]) < 1.0  # µs
    assert merged["metadata"]["merged_hosts"] == [0, 1]


def test_trace_merge_handles_restart_epochs_in_one_file(tmp_path):
    """Auto-resume appends a second monotonic epoch (fresh perf_counter
    origin + fresh clock_sync) to the SAME host file; each event must use
    the most recent clock_sync above it, so the resumed run's spans land
    at their true wall positions instead of the dead process's offset."""
    trace_dir = tmp_path / "traces"
    trace_dir.mkdir()
    lines = [
        "[\n",
        # first life: mono origin ~1000, wall = mono + 500 → event at wall 1501
        json.dumps({"name": "clock_sync", "ph": "M", "pid": 0, "tid": 0,
                    "args": {"wall_minus_mono_s": 500.0}}) + ",\n",
        json.dumps({"name": "step", "ph": "X", "ts": 1001.0 * 1e6,
                    "dur": 10.0, "pid": 0, "tid": 1}) + ",\n",
        # restart: mono origin resets to ~3, wall = mono + 1600 → wall 1603
        json.dumps({"name": "clock_sync", "ph": "M", "pid": 0, "tid": 0,
                    "args": {"wall_minus_mono_s": 1600.0}}) + ",\n",
        json.dumps({"name": "step", "ph": "X", "ts": 3.0 * 1e6,
                    "dur": 10.0, "pid": 0, "tid": 1}) + ",\n",
    ]
    (trace_dir / "host_0.trace.json").write_text("".join(lines))
    merged = merge_traces(str(trace_dir))
    steps = sorted(
        (e for e in merged["traceEvents"] if e["name"] == "step"),
        key=lambda e: e["ts"],
    )
    # wall gap is 1603 - 1501 = 102 s, regardless of the epoch reset
    assert steps[1]["ts"] - steps[0]["ts"] == pytest.approx(102.0 * 1e6)


def test_watchdog_only_mode_spans_defer_deadline_and_heartbeat(tmp_path):
    """tracing=False + watchdog=True: trace_span call sites still feed the
    watchdog progress (a long compile inside a span must not false-fire)
    and keep the heartbeat fresh for the monitor's staleness check."""
    set_active_tracer(None)
    wd = Watchdog(
        logging_dir=str(tmp_path),
        floor_seconds=0.4,
        check_interval_seconds=0.05,
        heartbeat_interval_seconds=0.0,  # unthrottled for the test
        host=0,
    ).start()
    try:
        hb_path = tmp_path / "diagnostics" / "heartbeat_0.json"
        t_end = time.time() + 1.0  # > floor: would fire without the touches
        while time.time() < t_end:
            with trace_span("compile/compile", label="fused_step"):
                time.sleep(0.05)  # "compiling" — progress only via the span
        assert not wd.fired
        assert not os.path.exists(wd.report_path)
        hb = json.load(open(hb_path))
        assert time.time() - hb["ts"] < 1.0  # refreshed by the touches
    finally:
        wd.stop()


def test_disabled_mode_is_strict_noop(tmp_path):
    """diagnostics off (the default): NULL tracer, no watchdog thread, no
    traces/ dir, and trace_span costs an inactive profiler annotation."""
    acc, model, opt, dl = _toy(tmp_path)
    assert acc.tracer is NULL_TRACER and not acc.tracer
    assert acc.watchdog is None
    assert get_tracer() is NULL_TRACER
    assert get_active_watchdog() is None
    _train(acc, model, opt, dl)
    assert not (tmp_path / "traces").exists()
    assert not (tmp_path / "diagnostics").exists()
    # no tracer, no watchdog: a span is a bare profiler annotation, inactive
    # (and writing nothing) unless a jax.profiler session records
    span = trace_span("anything", k=1)
    assert isinstance(span, jax.profiler.TraceAnnotation)
    with span:
        span.set_metadata(more=2)
    assert not (tmp_path / "traces").exists()
    # the loop still trains
    assert float(np.asarray(model.params["a"])) != 0.0


def test_open_span_stack_tracks_nesting(tmp_path):
    tracer = Tracer(logging_dir=None, host=0)
    with tracer.span("outer"):
        with tracer.span("inner", step=3):
            spans = tracer.open_spans()
            (frames,) = spans.values()
            assert [f["name"] for f in frames] == ["outer", "inner"]
            assert frames[1]["attrs"] == {"step": 3}
    assert tracer.open_spans() == {}
    tracer.close()


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------


def test_watchdog_fires_on_stalled_step_with_stack_and_spans(tmp_path):
    """A stalled step past the deadline must produce HANG_REPORT_<host>.json
    containing the stalled thread's Python stack and the open span stack,
    and name the innermost open span as the stalled phase."""
    tel = TelemetryRecorder(logging_dir=None, memory_interval=0)
    tel.record_event("marker", note="pre-hang")
    tracer = Tracer(logging_dir=str(tmp_path), host=0)
    set_active_tracer(tracer)
    wd = Watchdog(
        logging_dir=str(tmp_path),
        multiplier=3.0,
        floor_seconds=0.3,
        check_interval_seconds=0.05,
        telemetry=tel,
        host=0,
    ).start()
    try:
        for _ in range(3):
            time.sleep(0.02)
            wd.step_completed()
        with tracer.span("collective/wedged_allreduce", op="psum"):
            deadline = time.time() + 5.0
            while not os.path.exists(wd.report_path) and time.time() < deadline:
                time.sleep(0.05)  # the artificial wedge the watchdog sees
        assert os.path.exists(wd.report_path), "watchdog never fired"
        report = json.load(open(wd.report_path))
        assert report["stalled_phase"] == "collective/wedged_allreduce"
        frames = [f for frames in report["open_spans"].values() for f in frames]
        assert any(f["name"] == "collective/wedged_allreduce" for f in frames)
        # the stalled (main) thread's stack shows where it sits — this file
        stacks = "\n".join("\n".join(s) for s in report["threads"].values())
        assert "test_diagnostics" in stacks and "sleep" in stacks
        # the telemetry tail rode along
        assert any(r.get("kind") == "marker" for r in report["telemetry_tail"])
        assert report["elapsed_s"] > report["deadline_s"] >= 0.3
    finally:
        wd.stop()
        tracer.close()
        tel.close()


def test_watchdog_grace_phase_defers_deadline(tmp_path):
    """A stall inside a grace phase (compile/checkpoint/prepare — host-
    local, legitimately unbounded) must NOT fire the step deadline; the
    same stall inside a collective span must (see the stalled-step test)."""
    tracer = Tracer(logging_dir=None, host=0)
    set_active_tracer(tracer)
    wd = Watchdog(
        logging_dir=str(tmp_path),
        floor_seconds=0.2,
        check_interval_seconds=0.05,
        host=0,
    ).start()
    try:
        with tracer.span("compile/compile", label="fused_step"):
            time.sleep(0.8)  # ≫ floor, but grace_seconds (1800) governs
        assert not wd.fired
        assert not os.path.exists(wd.report_path)
    finally:
        wd.stop()
        tracer.close()


def test_watchdog_fire_publishes_fired_heartbeat(tmp_path):
    """_fire writes a heartbeat while fired is still True, so the monitor's
    wedged check sees the watchdog's own verdict, not just staleness."""
    wd = Watchdog(
        logging_dir=str(tmp_path),
        floor_seconds=0.2,
        check_interval_seconds=0.05,
        heartbeat_interval_seconds=3600.0,  # only forced writes land
        host=0,
    ).start()
    try:
        deadline = time.time() + 5.0
        while not os.path.exists(wd.report_path) and time.time() < deadline:
            time.sleep(0.05)
        hb = json.load(open(tmp_path / "diagnostics" / "heartbeat_0.json"))
        assert hb["fired"] is True
        status = collect_status(str(tmp_path))
        assert status["wedged"] == [0]
    finally:
        wd.stop()


def test_watchdog_does_not_fire_on_healthy_loop(tmp_path):
    wd = Watchdog(
        logging_dir=str(tmp_path),
        multiplier=5.0,
        floor_seconds=0.4,
        check_interval_seconds=0.05,
        host=0,
    ).start()
    try:
        t_end = time.time() + 1.2  # ≫ floor: plenty of chances to misfire
        while time.time() < t_end:
            time.sleep(0.02)
            wd.step_completed()
        assert not os.path.exists(wd.report_path)
        assert not wd.fired
    finally:
        wd.stop()


def test_watchdog_raises_preemption_flag_on_hang(tmp_path):
    """preempt_on_hang closes the loop with PR 2: a fired watchdog raises
    the active PreemptionHandler's flag so the consensus emergency-save
    path takes over at the next step boundary."""
    from accelerate_tpu.resilience.preemption import PreemptionHandler

    handler = PreemptionHandler(handle_signals=False)
    handler.install()
    wd = Watchdog(
        logging_dir=str(tmp_path),
        floor_seconds=0.2,
        check_interval_seconds=0.05,
        preempt_on_hang=True,
        host=0,
    ).start()
    try:
        deadline = time.time() + 5.0
        while not handler.preemption_requested and time.time() < deadline:
            time.sleep(0.05)
        assert handler.preemption_requested
        assert (handler.reason or "").startswith("watchdog-hang")
    finally:
        wd.stop()
        handler.uninstall()


_WEDGED_STEP_SCRIPT = textwrap.dedent(
    """
    import os, sys, time
    import numpy as np, optax
    from accelerate_tpu import Accelerator, DiagnosticsPlugin
    from accelerate_tpu.diagnostics import trace_span
    from accelerate_tpu.test_utils import RegressionModel

    project_dir = sys.argv[1]
    acc = Accelerator(
        project_dir=project_dir,
        telemetry=True,
        fault_tolerance=True,
        diagnostics=DiagnosticsPlugin(
            watchdog_floor_seconds=0.6,
            watchdog_check_seconds=0.05,
            watchdog_multiplier=3.0,
            preempt_on_hang=True,
        ),
    )
    model, opt = acc.prepare(RegressionModel(a=0.0, b=0.0), optax.adam(0.05))
    x = np.arange(16, dtype=np.float32)
    for step in range(100):
        out = model(x=x, y=2 * x + 3)
        acc.backward(out.loss)   # checks the preemption flag at the boundary
        opt.step(); opt.zero_grad()
        if step == 2:
            print("WEDGING", flush=True)
            with trace_span("collective/wedged_allreduce"):
                time.sleep(2.5)  # >> deadline: the watchdog must fire here
    print("UNREACHABLE_COMPLETED", flush=True)
    """
)


def test_wedged_step_subprocess_exits_with_hang_report(tmp_path):
    """End-to-end acceptance: an artificially wedged step in a real loop →
    the watchdog writes HANG_REPORT naming the stalled phase AND raises the
    preemption flag, so the run emergency-saves and exits cleanly (143)
    instead of burning the slice."""
    script = tmp_path / "wedged.py"
    script.write_text(_WEDGED_STEP_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, str(script), str(tmp_path / "proj")],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert "UNREACHABLE_COMPLETED" not in proc.stdout
    assert proc.returncode == 143, proc.stderr[-2000:]
    report_path = tmp_path / "proj" / "HANG_REPORT_0.json"
    assert report_path.exists(), proc.stderr[-2000:]
    report = json.load(open(report_path))
    assert report["stalled_phase"] == "collective/wedged_allreduce"
    assert report["threads"]  # all-thread stacks captured
    # PR 2's machinery finished the job: sentinel + emergency checkpoint
    sentinel = tmp_path / "proj" / "checkpoints" / "PREEMPTED.json"
    assert sentinel.exists()
    assert json.load(open(sentinel))["reason"].startswith("watchdog-hang")


# ---------------------------------------------------------------------------
# monitor
# ---------------------------------------------------------------------------


def _write_heartbeat(tmp_path, host, step, ts, ema=0.1, fired=False):
    hb_dir = tmp_path / "diagnostics"
    hb_dir.mkdir(exist_ok=True)
    (hb_dir / f"heartbeat_{host}.json").write_text(
        json.dumps(
            {"host": host, "pid": 1, "step": step, "ts": ts,
             "ema_step_s": ema, "last_step_s": ema, "fired": fired}
        )
    )


def test_monitor_collect_status_names_wedged_and_stragglers(tmp_path):
    now = 10_000.0
    _write_heartbeat(tmp_path, 0, step=100, ts=now - 1)          # healthy leader
    _write_heartbeat(tmp_path, 1, step=60, ts=now - 2)           # behind on steps
    _write_heartbeat(tmp_path, 2, step=100, ts=now - 500)        # heartbeat-silent
    status = collect_status(str(tmp_path), now=now)
    assert [h["host"] for h in status["hosts"]] == [0, 1, 2]
    assert status["wedged"] == [2]
    assert status["stragglers"] == [1]
    text = render_status(status)
    assert "WEDGED" in text and "STRAGGLER" in text


def test_monitor_reads_telemetry_tail_and_hang_reports(tmp_path):
    tel_dir = tmp_path / "telemetry"
    tel_dir.mkdir()
    now = time.time()
    with open(tel_dir / "telemetry.jsonl", "w") as f:
        for i in range(30):
            f.write(json.dumps({
                "type": "step", "step": i + 1, "optimizer_steps": i + 1,
                "step_time_s": 0.25, "recompiles": 2, "mfu": 0.41,
                "tokens_per_sec": 1000.0, "ts": now,
            }) + "\n")
    (tmp_path / "HANG_REPORT_3.json").write_text(
        json.dumps({"host": 3, "stalled_phase": "collective/gather",
                    "elapsed_s": 99.0, "ts": now})
    )
    status = collect_status(str(tmp_path), now=now)
    assert status["steps"] == 30
    assert status["step_rate"] == pytest.approx(4.0)
    assert status["mfu"] == pytest.approx(0.41)
    assert status["recompiles"] == 2
    assert status["hang_reports"][0]["stalled_phase"] == "collective/gather"
    assert "HANG" in render_status(status)


def test_monitor_cli_once_flags_unhealthy_run(tmp_path, capsys):
    from accelerate_tpu.commands.accelerate_cli import main

    assert main(["monitor", str(tmp_path), "--once"]) == 0
    (tmp_path / "HANG_REPORT_0.json").write_text(
        json.dumps({"host": 0, "stalled_phase": "x", "elapsed_s": 1.0})
    )
    assert main(["monitor", str(tmp_path), "--once"]) == 2
    assert "HANG" in capsys.readouterr().out


def test_trace_merge_cli(tmp_path):
    from accelerate_tpu.commands.accelerate_cli import main

    tracer = Tracer(logging_dir=str(tmp_path), host=0)
    with tracer.span("phase"):
        pass
    tracer.close()
    out = tmp_path / "merged.json"
    assert main(["trace", "merge", str(tmp_path), "-o", str(out)]) == 0
    validate_chrome_trace(json.load(open(out)))


# ---------------------------------------------------------------------------
# telemetry satellites
# ---------------------------------------------------------------------------


def test_summary_survives_empty_ring_buffer():
    rec = TelemetryRecorder(logging_dir=None, memory_interval=0)
    try:
        s = rec.summary()  # no records at all: must not warn or NaN
        assert s["steps"] == 0 and "step_time_s" not in s
        from accelerate_tpu.telemetry import _percentiles

        assert _percentiles([]) == {}
    finally:
        rec.close()


def test_unknown_skip_counted_separately():
    rec = TelemetryRecorder(logging_dir=None, memory_interval=0)
    try:
        rec.record_step(dispatch_s=0.01, skipped=False)
        rec.record_step(dispatch_s=0.01, skipped=None)   # fp16 flag on device
        rec.record_step(dispatch_s=0.01, skipped=None)
        rec.record_step(dispatch_s=0.01, skipped=True)
        s = rec.summary()
        assert s["unknown_skip"] == 2
        assert s["skipped_steps"] == 1
        # unknowns optimistically count toward optimizer_steps; true skips don't
        assert s["optimizer_steps"] == 3
        records = [r for r in rec.records if r["type"] == "step"]
        assert [r["skipped"] for r in records] == [False, None, None, True]
    finally:
        rec.close()


def test_close_is_idempotent_and_atexit_registered(tmp_path):
    import atexit

    rec = TelemetryRecorder(logging_dir=str(tmp_path), memory_interval=0)
    rec.record_event("x")
    rec.close()
    rec.close()  # second close must be a no-op, not an error
    assert rec.jsonl_path and os.path.exists(rec.jsonl_path)
    # after close, atexit must hold no reference (unregister happened);
    # registering/unregistering again proves the pair is balanced
    atexit.unregister(rec.close)  # no-op if already unregistered
    records = [json.loads(line) for line in open(rec.jsonl_path)]
    assert records[-1]["kind"] == "x"


def test_compile_records_carry_mono_timestamps(tmp_path):
    """Compile records keep wall-clock ``ts`` and add monotonic phase
    timestamps (the trace clock) — the contract trace export relies on."""
    acc, model, opt, dl = _toy(tmp_path, telemetry=True)
    _train(acc, model, opt, dl)
    compiles = [r for r in acc.telemetry.records if r["type"] == "compile"]
    assert compiles
    for r in compiles:
        assert r["ts"] > 1e9  # wall clock
        mono = r["mono"]
        assert mono["lower_start"] <= mono["compile_start"] <= mono["compile_end"]
    acc.telemetry.close()
