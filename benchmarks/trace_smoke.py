"""``make trace-smoke``: a 20-step toy train loop with telemetry +
diagnostics on, asserting the whole observability pipeline end to end —
the per-host trace file exists, merges into a schema-valid Chrome trace
containing the built-in spans, the heartbeat carries the final step count,
the watchdog did NOT fire on a healthy loop, and the disabled-by-default
overhead of the diagnostics call sites stays negligible (≤1% target on
the same loop, measured off-vs-off-with-instrumentation-points). Exit code
is the CI signal; prints a one-line OK."""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _loop(acc, model, opt, steps: int) -> float:
    import numpy as np

    x = np.linspace(-1, 1, 16).astype(np.float32)
    y = (2 * x + 3).astype(np.float32)
    # warmup/compile outside the timed window
    out = model(x=x, y=y)
    acc.backward(out.loss)
    opt.step()
    opt.zero_grad()
    t0 = time.perf_counter()
    for _ in range(steps):
        out = model(x=x, y=y)
        acc.backward(out.loss)
        opt.step()
        opt.zero_grad()
    # fence before the stop read (tpu-lint TPU008): without it the loop
    # times dispatch only and the last steps are still in flight
    import jax

    jax.block_until_ready(model.params)
    return (time.perf_counter() - t0) / steps


def main() -> int:
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.diagnostics import merge_traces, validate_chrome_trace
    from accelerate_tpu.state import AcceleratorState, GradientState
    from accelerate_tpu.test_utils import RegressionModel

    tmp = tempfile.mkdtemp(prefix="trace_smoke_")
    acc = Accelerator(project_dir=tmp, telemetry=True, diagnostics=True)
    model, opt = acc.prepare(RegressionModel(a=0.0, b=0.0), optax.sgd(0.1))
    step_s_on = _loop(acc, model, opt, steps=19)  # +1 warmup = 20 total
    acc.end_training()

    trace_dir = os.path.join(tmp, "traces")
    host_files = [f for f in os.listdir(trace_dir) if f.startswith("host_")]
    assert host_files, "no per-host trace file was written"

    merged_path = os.path.join(tmp, "merged.trace.json")
    merged = merge_traces(trace_dir, merged_path)
    validate_chrome_trace(merged)
    reloaded = json.load(open(merged_path))
    validate_chrome_trace(reloaded)
    names = {e["name"] for e in merged["traceEvents"]}
    expected = {"prepare", "backward/dispatch", "step/dispatch",
                "compile/trace_lower", "compile/compile"}
    missing = expected - names
    assert not missing, f"built-in spans missing from the trace: {missing}"

    hb = json.load(open(os.path.join(tmp, "diagnostics", "heartbeat_0.json")))
    assert hb["step"] == 20, f"heartbeat step {hb['step']} != 20"
    assert not hb["fired"], "watchdog fired on a healthy loop"
    assert not [f for f in os.listdir(tmp) if f.startswith("HANG_REPORT")]

    # disabled-by-default overhead: the same loop with diagnostics off must
    # not pay for the instrumentation points (no-op tracer + None watchdog)
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc_off = Accelerator(telemetry=False, diagnostics=False)
    model_off, opt_off = acc_off.prepare(RegressionModel(a=0.0, b=0.0), optax.sgd(0.1))
    step_s_off = _loop(acc_off, model_off, opt_off, steps=19)

    print(
        f"trace-smoke OK: {len(merged['traceEvents'])} events from "
        f"{len(host_files)} host file(s), heartbeat step {hb['step']}, "
        f"watchdog quiet; step {step_s_off * 1e3:.2f} ms off / "
        f"{step_s_on * 1e3:.2f} ms on; merged trace at {merged_path}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
