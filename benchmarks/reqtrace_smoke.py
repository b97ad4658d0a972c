"""Request-trace smoke: a 2-replica routed fleet serves a mixed trace with
request tracing armed, then the per-process trace files must stitch into
one coherent story:

* every completed request has a **complete span chain** — router submit →
  engine arrive → admit → first token → finish — under one trace_id;
* **zero orphaned flows** (every router dispatch arrow lands on a replica
  admission) and **exactly-once finish events**;
* a client-supplied trace_id survives submit → replica row → trace file
  **verbatim**;
* ``trace tail`` reproduces each request's TTFT from its spans to within
  5 ms of the engine-reported value and emits a phase-attribution table;
* the ``/metrics``-style exposition carries ``trace_id`` exemplars on the
  latency histograms and round-trips through the strict parser.

Run directly (``make reqtrace-smoke``).
"""

import json
import os
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the router host never imports jax, exactly like production
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ENGINE_ARGS = [
    "--preset", "tiny", "--num-slots", "2", "--block-size", "8",
    "--max-seq-len", "96", "--prefill-chunk", "8", "--decode-burst", "2",
]
N_REQUESTS = 14


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    return env


def _payload(i):
    p = {"id": i, "prompt": [1 + i % 7, 5, 11, 2], "max_new_tokens": 4 + i % 5}
    if i % 4 == 0:
        p["trace_id"] = f"client-{i:04d}"
    if i % 3 == 0:
        p["priority"] = "batch"
    return p


def main() -> int:
    logdir = os.path.join(tempfile.mkdtemp(prefix="reqtrace_smoke_"), "fleet")
    proc = subprocess.Popen(
        [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
         "route", "--replicas", "2", "--logging-dir", logdir,
         "--health-interval", "0.2", *ENGINE_ARGS],
        env=_env(), stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True,
    )
    results: list[str] = []
    threading.Thread(
        target=lambda: [results.append(l.strip()) for l in proc.stdout if l.strip()],
        daemon=True,
    ).start()
    try:
        for i in range(N_REQUESTS):
            proc.stdin.write(json.dumps(_payload(i)) + "\n")
        proc.stdin.flush()
        deadline = time.monotonic() + 300
        while len(results) < N_REQUESTS and time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(f"route exited early rc={proc.returncode}")
            time.sleep(0.1)
        proc.stdin.close()
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    assert rc == 0, f"route exited {rc}"
    rows = {r["id"]: r for r in map(json.loads, results)}
    assert len(rows) == N_REQUESTS, f"lost answers: {sorted(rows)}"
    errors = [r for r in rows.values() if "error" in r]
    assert not errors, f"error rows: {errors}"

    # every answer row carries a trace id; client-supplied ones verbatim
    for i, row in rows.items():
        assert row.get("trace_id"), f"row {i} without trace_id"
        if i % 4 == 0:
            assert row["trace_id"] == f"client-{i:04d}", row

    # merge the fleet's files: every request stitched cross-process, zero
    # orphan flows, one engine finish apiece
    from accelerate_tpu.diagnostics.reqtrace import (
        collect_request_flows,
        render_tail_report,
        request_timeline,
        tail_report,
    )
    from accelerate_tpu.diagnostics.tracing import (
        discover_trace_files,
        merge_traces,
        validate_chrome_trace,
    )

    paths = discover_trace_files(logdir)
    assert len(paths) == 3, f"expected router + 2 replica files, got {paths}"
    merged = merge_traces(
        paths=paths, output_path=os.path.join(logdir, "merged.trace.json")
    )
    validate_chrome_trace(merged)
    flows_meta = merged["metadata"]["request_flows"]
    assert flows_meta["trace_ids"] == N_REQUESTS, flows_meta
    assert flows_meta["cross_process"] == N_REQUESTS, flows_meta
    assert flows_meta["orphan_flows"] == 0, flows_meta

    flows = collect_request_flows(logdir)
    timelines = {tid: request_timeline(tid, evs) for tid, evs in flows.items()}
    for row in rows.values():
        t = timelines[row["trace_id"]]
        assert t["complete"], f"incomplete span chain: {t}"
        assert t["engine_finish_events"] == 1, f"finish not exactly-once: {t}"
        # span-derived TTFT vs the engine-reported answer-row value
        assert abs(t["ttft_s"] - row["ttft_s"]) < 0.005, (t["ttft_s"], row["ttft_s"])

    report = tail_report(logdir, k=5)
    assert report["measured_requests"] == N_REQUESTS
    assert report["incomplete"] == 0
    assert abs(sum(report["attribution"].values()) - 100.0) < 1e-6
    print(render_tail_report(report))

    # exemplar round trip: replay the replica telemetry trails through the
    # shared ingest mapping and render/parse the exposition strictly
    from accelerate_tpu.metrics.ingest import observe_record
    from accelerate_tpu.metrics.openmetrics import parse_openmetrics, render_openmetrics
    from accelerate_tpu.metrics.registry import MetricsRegistry

    registry = MetricsRegistry(gate_main_process=False)
    import glob

    for trail in glob.glob(os.path.join(logdir, "replica_*", "telemetry",
                                        "telemetry.jsonl")):
        with open(trail) as f:
            for line in f:
                try:
                    observe_record(registry, json.loads(line))
                except json.JSONDecodeError:
                    pass
    families = parse_openmetrics(render_openmetrics(registry))
    exemplars = families["accelerate_serving_ttft_seconds"]["exemplars"]
    assert exemplars, "no ttft exemplars on the scrape"
    exemplar_ids = {e["exemplar"]["labels"]["trace_id"] for e in exemplars}
    assert exemplar_ids <= set(timelines), (exemplar_ids, set(timelines))
    classes = {e["labels"].get("class") for e in exemplars}
    assert classes <= {"interactive", "batch"}, classes

    print(
        f"REQTRACE_SMOKE OK: {N_REQUESTS} requests, "
        f"{flows_meta['cross_process']} cross-process flows, 0 orphans, "
        f"{len(exemplar_ids)} exemplar trace_id(s) on the scrape"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
