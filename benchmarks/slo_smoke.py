"""SLO closed-loop smoke: the seeded ``overbudget-storm`` scenario on a
real 2-replica fleet, run twice.

What it pins, end to end:

1. **Determinism** — the two runs' ``WORKLOAD.json`` manifests carry the
   identical ``schedule_sha256`` (same spec ⇒ byte-identical schedule);
2. **The closed loop** — the storm's impossible ``deadline_ms`` budgets
   breach the armed windowed objectives, and the supervisor's SLO policy
   logs ``kind:"scale_decision"`` rows *with the evidence attached*
   (objective, burn rate, dominant phase);
3. **Scorecard agreement** — ``slo report`` verdicts round-trip through
   ``--json``, and the exporter's ``slo_burn_rate{objective=…}`` gauges
   agree with :func:`~accelerate_tpu.metrics.slo.evaluate_from_dir` on
   the firing set (monitor, report, and /metrics tell one story);
4. **Serving invariants survive** — exactly-once delivery (every request
   answered exactly once, expiries included) and ``decode_compiles == 1``
   per replica.

Run directly (``make slo-smoke``).
"""

import json
import os
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# replicas are separate single-device processes — the parent never imports
# jax, exactly like the production router host
os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: the seeded scenario both legs replay (93 requests, 42 deadline-bound —
#: 20 at 5/25 ms, impossible on any host). Dispatch is uncapped, so the
#: pressure lands inside the replicas: engines evict the impossible
#: deadlines mid-decode (partial answers, finish_reason=
#: "deadline_exceeded") and the router's ``fleet_deadline_expired``
#: totals counter carries them to the windowed error-rate objective
SPEC_TEXT = "overbudget-storm:7:4:20"

#: bounded-queue admission control: past this depth, batch-class arrivals
#: shed with explicit over-capacity error rows (deterministic breach fuel)
MAX_QUEUE_DEPTH = 8

ENGINE_ARGS = [
    "--preset", "tiny", "--num-slots", "4", "--block-size", "8",
    "--max-seq-len", "96", "--prefill-chunk", "8", "--decode-burst", "2",
]

#: armed for the parent's windowed evaluation only (replicas just serve):
#: the error-rate budget is tiny so one expiry in the window fires it, and
#: MIN_GOODPUT_PCT=101 fires whenever a goodput ledger exists at all
SLO_ENV = {
    "ACCELERATE_SLO_MAX_ERROR_RATE": "0.0001",
    "ACCELERATE_SLO_MAX_ERROR_RATE_WINDOW_S": "60",
    "ACCELERATE_SLO_MIN_GOODPUT_PCT": "101",
    "ACCELERATE_SLO_MIN_GOODPUT_PCT_WINDOW_S": "60",
}


def _replica_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""  # single-device replicas: fast start, no oversubscription
    for k in list(env):
        if k.startswith("ACCELERATE_SLO_"):
            del env[k]  # SLO evaluation belongs to the router host, not replicas
    return env


def _decision_rows(logdir):
    rows = []
    try:
        with open(os.path.join(logdir, "router", "replicas.jsonl")) as f:
            for line in f:
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if row.get("kind") == "scale_decision":
                    rows.append(row)
    except OSError:
        pass
    return rows


def _leg(spec, logdir):
    """One full traced run: generate → serve through a supervised 2-replica
    fleet with the SLO policy armed → assert the closed loop's artifacts."""
    from accelerate_tpu.metrics.slo import evaluate_from_dir
    from accelerate_tpu.serving.replica import spawn_replica, wait_until_ready
    from accelerate_tpu.serving.router import Router
    from accelerate_tpu.serving.supervisor import (
        ReplicaSupervisor,
        SupervisorConfig,
    )
    from accelerate_tpu.serving.workload import (
        generate_schedule,
        run_schedule,
        write_workload_manifest,
    )

    schedule = generate_schedule(spec)
    write_workload_manifest(logdir, spec, schedule)

    def spawn_fn(replica_id):
        return spawn_replica(replica_id, list(ENGINE_ARGS), env=_replica_env())

    replicas = [spawn_fn(i) for i in range(2)]

    # the same throttled evaluate_from_dir closure the route CLI wires up
    slo_cache = {"ts": 0.0, "verdict": None}

    def slo_fn():
        now = time.monotonic()
        if now - slo_cache["ts"] >= 0.5:
            slo_cache["ts"] = now
            slo_cache["verdict"] = evaluate_from_dir(logdir)
        return slo_cache["verdict"]

    supervisor = ReplicaSupervisor(
        spawn_fn,
        SupervisorConfig(min_replicas=2, max_replicas=3, scale_interval_s=0.25),
        slo_fn=slo_fn,
    )
    router = Router(
        replicas, logging_dir=logdir, health_interval=0.2,
        supervisor=supervisor, max_queue_depth=MAX_QUEUE_DEPTH,
    )
    leg = {"n_requests": len(schedule)}
    try:
        wait_until_ready(replicas, timeout=300)

        # deliveries land via callback so a double-fire is observable —
        # ticket.result alone would silently overwrite a duplicate
        deliveries = []
        submitted = run_schedule(
            schedule, lambda p: router.submit(p, callback=deliveries.append)
        )
        assert submitted == len(schedule), (submitted, len(schedule))
        if not router.wait_idle(timeout=600):
            raise RuntimeError("router never went idle")

        # -- exactly-once delivery (expiries are answers too) --------------
        assert len(deliveries) == len(schedule), (
            f"{len(deliveries)} deliveries for {len(schedule)} requests "
            "— a request was dropped or double-delivered"
        )
        ids = [d.get("id") for d in deliveries]
        assert len(ids) == len(set(ids)), "duplicated delivery"
        # expiries surface two ways: router-side (queue expiry/shed → an
        # "error" answer) and engine-side (slot evicted mid-decode → a
        # *partial* answer with finish_reason="deadline_exceeded"). The
        # storm's ≤25 ms budgets guarantee at least the latter.
        errors = [
            d for d in deliveries
            if "error" in d or d.get("finish_reason") == "deadline_exceeded"
        ]
        assert errors, (
            "the storm never shed or expired a request — not a storm"
        )
        leg["expired_or_shed"] = len(errors)

        # -- the breach fired and the supervisor decided, with evidence ----
        deadline = time.monotonic() + 15
        decisions = _decision_rows(logdir)
        while (
            not any(d.get("objective") for d in decisions)
            and time.monotonic() < deadline
        ):
            time.sleep(0.2)
            decisions = _decision_rows(logdir)
        verdict = evaluate_from_dir(logdir)
        leg["firing"] = sorted(f["rule"] for f in verdict["firing"])
        assert "max_error_rate" in leg["firing"], (
            f"expiries never breached the windowed error-rate objective: "
            f"{verdict['objectives']}"
        )
        assert decisions, "no scale_decision rows in the fleet trail"
        evidenced = [
            d for d in decisions
            if d.get("objective") and isinstance(d.get("burn_rate"), (int, float))
        ]
        assert evidenced, f"decision rows lack breach evidence: {decisions}"
        leg["scale_decisions"] = len(decisions)
        leg["decision_actions"] = sorted({d.get("action") for d in decisions})

        # -- one decode executable per (initial) replica --------------------
        compiles = []
        for r in replicas:
            with urllib.request.urlopen(r.base_url + "/stats", timeout=10) as resp:
                stats = json.loads(resp.read())
            compiles.append(stats["decode_compiles"])
        assert compiles == [1, 1], (
            f"deadline chaos recompiled a replica: decode_compiles={compiles}"
        )
        leg["decode_compiles"] = compiles

        # -- scorecard: text and --json agree, gauges agree -----------------
        from accelerate_tpu.commands.slo import build_report, render_report

        report = build_report(logdir)
        text = render_report(report)
        roundtrip = json.loads(json.dumps(report, default=str))
        assert roundtrip["scenarios"][0]["verdict"] == \
            report["scenarios"][0]["verdict"]
        assert report["scenarios"][0]["verdict"] == "fail", report["scenarios"][0]
        assert "overbudget-storm" in text and "overall: FAIL" in text, text
        assert roundtrip["pass"] is False
        leg["report_verdict"] = report["scenarios"][0]["verdict"]
        leg["schedule_sha256"] = roundtrip["scenarios"][0]["schedule_sha256"]

        from accelerate_tpu.metrics.exporter import LoggingDirExporter

        exporter = LoggingDirExporter(logdir)
        exp_firing = sorted(f["rule"] for f in exporter.refresh())
        assert exp_firing == leg["firing"], (
            f"/metrics and slo report disagree: {exp_firing} vs {leg['firing']}"
        )
        rendered = exporter.render()
        for name in verdict["objectives"]:
            assert f'slo_burn_rate{{objective="{name}"}}' in rendered, name
            assert f'slo_budget_remaining{{objective="{name}"}}' in rendered, name
        leg["slo_gauges_agree"] = True

        clean = router.drain(timeout=120)
        assert clean, "drain did not exit cleanly"
    finally:
        router.close()
    return leg


def run(platform: str = "cpu") -> dict:
    from accelerate_tpu.serving.workload import parse_trace_spec

    spec = parse_trace_spec(SPEC_TEXT)
    saved = {k: os.environ.get(k) for k in SLO_ENV}
    os.environ.update(SLO_ENV)
    try:
        legs = []
        for _ in range(2):
            with tempfile.TemporaryDirectory() as logdir:
                legs.append(_leg(spec, logdir))
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    assert legs[0]["schedule_sha256"] == legs[1]["schedule_sha256"], (
        "same spec, different schedules: "
        f"{legs[0]['schedule_sha256']} vs {legs[1]['schedule_sha256']}"
    )
    return {
        "spec": SPEC_TEXT,
        "n_requests": legs[0]["n_requests"],
        "schedules_identical": True,
        "schedule_sha256": legs[0]["schedule_sha256"],
        "decode_compiles": legs[0]["decode_compiles"],
        "scale_decisions": [leg["scale_decisions"] for leg in legs],
        "decision_actions": sorted(
            set(legs[0]["decision_actions"]) | set(legs[1]["decision_actions"])
        ),
        "firing": legs[0]["firing"],
        "expired_or_shed": [leg["expired_or_shed"] for leg in legs],
        "report_verdict": legs[0]["report_verdict"],
        "slo_gauges_agree": all(leg["slo_gauges_agree"] for leg in legs),
    }


def main() -> int:
    r = run()
    print(
        f"slo-smoke OK: {r['spec']} x2 — {r['n_requests']} requests/leg, "
        f"schedules identical ({r['schedule_sha256'][:12]})\n"
        f"  breach fired {r['firing']}, "
        f"{r['scale_decisions']} scale decision(s) with evidence "
        f"(actions {r['decision_actions']}), "
        f"{r['expired_or_shed']} expiries/leg answered exactly once\n"
        f"  slo report verdict '{r['report_verdict']}' round-trips --json, "
        f"/metrics gauges agree, decode_compiles={r['decode_compiles']}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
