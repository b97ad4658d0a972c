"""Self-healing fleet smoke: a seeded fault schedule (kill -9, injected
503 burst, response delay) against a supervised 2-replica fleet. Asserts
the invariants that make the robustness story honest:

* every submitted request is answered **exactly once** (callback-counted —
  ``ticket.result`` alone would silently overwrite a duplicate);
* **zero orphaned processes** — every pid the fleet ever spawned
  (including respawned incarnations) is gone after drain;
* the fleet **recovers to the target replica count** via supervised
  respawn (crash-loop backoff visible in the fleet trail);
* goodput under faults is reported as a **ratio** of the clean-leg
  goodput on the identical trace — never an absolute wall-clock gate,
  per the timing-noise rule (this box's clock swings ±5x).

Run directly (``make chaos-smoke``).
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# replicas are separate single-device processes — the parent never imports
# jax, exactly like the production router host
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ENGINE_ARGS = [
    "--preset", "tiny", "--num-slots", "4", "--block-size", "8",
    "--max-seq-len", "96", "--prefill-chunk", "8", "--decode-burst", "2",
]

#: the seeded schedule: replica 0 dies at its 5th request (with requests in
#: flight), replica 1 answers a 503 burst (router requeues, not final) and
#: injects a response delay — all keyed on request ordinals, so the same
#: spec against the same trace produces the same failure sequence
CHAOS_SPEC = "seed=1;r0:kill@5;r1:err503@2:2;r1:delay@3:0.05"
MIN_REPLICAS = 2


def _replica_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    env.pop("ACCELERATE_CHAOS_SPEC", None)
    return env


def _payload(i, n_new=8):
    p = {"id": i, "prompt": [1 + i % 7, 5, 11, 2], "max_new_tokens": n_new}
    if i % 3 == 0:
        p["session_id"] = f"chat-{i % 2}"
    return p


def _spawn_fleet(n, logdir, chaos_spec=None, supervised=False):
    from accelerate_tpu.serving.replica import spawn_replica, wait_until_ready
    from accelerate_tpu.serving.router import Router
    from accelerate_tpu.serving.supervisor import ReplicaSupervisor, SupervisorConfig

    args = list(ENGINE_ARGS)
    if chaos_spec:
        args += ["--chaos-spec", chaos_spec]

    spawned_pids = []

    def spawn_fn(replica_id):
        handle = spawn_replica(replica_id, list(args), env=_replica_env())
        spawned_pids.append(handle.pid)
        return handle

    replicas = [spawn_fn(i) for i in range(n)]
    supervisor = None
    if supervised:
        supervisor = ReplicaSupervisor(
            spawn_fn,
            SupervisorConfig(min_replicas=n, max_replicas=n,
                             backoff_base_s=0.25, seed=0),
        )
    router = Router(
        replicas, logging_dir=logdir, health_interval=0.2, supervisor=supervisor
    )
    try:
        wait_until_ready(replicas, timeout=300)
    except Exception:
        router.close()
        raise
    return router, spawned_pids


def _run_trace(router, n, offset=0):
    """Submit ``n`` requests, wait for every answer; deliveries land via
    callback so a double-fire is observable. Returns (deliveries, wall,
    tokens)."""
    deliveries = []
    t0 = time.perf_counter()
    tickets = [
        router.submit(_payload(offset + i), callback=deliveries.append)
        for i in range(n)
    ]
    if not router.wait_idle(timeout=600):
        raise RuntimeError("router never went idle")
    # nothing to fence: the timed work is HTTP round-trips to replica
    # subprocesses, results arrive as materialized JSON
    # tpu-lint: ignore[TPU008]
    wall = time.perf_counter() - t0
    assert len(deliveries) == len(tickets), (
        f"{len(deliveries)} deliveries for {len(tickets)} requests — "
        "a request was dropped or double-delivered"
    )
    ids = [r.get("id") for r in deliveries]
    assert len(ids) == len(set(ids)), "duplicated delivery"
    tokens = sum(len(r.get("tokens", [])) for r in deliveries if isinstance(r, dict))
    return deliveries, wall, tokens


def _assert_no_orphans(pids, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        alive = []
        for pid in pids:
            try:
                os.kill(pid, 0)
                alive.append(pid)
            except OSError:
                pass
        if not alive:
            return
        time.sleep(0.25)
    raise AssertionError(f"orphaned process(es) after the run: {alive}")


def run(platform: str = "cpu", n_requests: int = 16) -> dict:
    result: dict = {"n_requests": n_requests, "chaos_spec": CHAOS_SPEC}

    # LockWatch rides the whole run: the router/supervisor locks are
    # wrapped in order-graph shims, and the seeded kill/503/delay schedule
    # must complete with ZERO lock-order violations (the runtime half of
    # `accelerate-tpu race-check`)
    from accelerate_tpu.analysis.lockwatch import (
        LockWatch,
        get_active_lockwatch,
        set_active_lockwatch,
    )

    prior_watch = get_active_lockwatch()
    watch = LockWatch(host="chaos_smoke")
    set_active_lockwatch(watch)

    # the process-global watch must be restored even when a leg fails —
    # a leaked armed watch would wrap every later lock in this process
    try:
        # -- leg 1: clean supervised fleet (the baseline goodput) --------------
        with tempfile.TemporaryDirectory() as logdir:
            router, pids = _spawn_fleet(MIN_REPLICAS, logdir, supervised=True)
            try:
                deliveries, clean_wall, clean_tokens = _run_trace(router, n_requests)
                errors = [r for r in deliveries if "error" in r]
                assert not errors, f"clean leg errored: {errors}"
                assert router.drain(timeout=120), "clean drain failed"
            finally:
                router.close()
            _assert_no_orphans(pids)

        # -- leg 2: identical trace under the seeded fault schedule ------------
        with tempfile.TemporaryDirectory() as logdir:
            router, pids = _spawn_fleet(
                MIN_REPLICAS, logdir, chaos_spec=CHAOS_SPEC, supervised=True
            )
            try:
                deliveries, fault_wall, fault_tokens = _run_trace(router, n_requests)
                errors = [r for r in deliveries if "error" in r]
                assert not errors, f"faults leaked as error rows: {errors}"

                # the fleet must RECOVER to the target count via respawn
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline:
                    stats = router.stats()
                    if stats["ready"] >= MIN_REPLICAS:
                        break
                    time.sleep(0.25)
                stats = router.stats()
                assert stats["ready"] >= MIN_REPLICAS, (
                    f"fleet never recovered: {stats['ready']}/{MIN_REPLICAS} ready"
                )
                assert stats["supervisor"]["respawns"] >= 1, (
                    "the kill never triggered a supervised respawn"
                )
                result["respawns"] = stats["supervisor"]["respawns"]
                result["requeues"] = stats["requeues"]
                result["recovery_ratio"] = stats["ready"] / MIN_REPLICAS
                # crash-loop backoff is visible in the fleet trail
                trail = os.path.join(logdir, "router", "replicas.jsonl")
                rows = [json.loads(line) for line in open(trail) if line.strip()]
                assert any(
                    r.get("replica_id") == 0 and r.get("backoff_s", 0) > 0
                    for r in rows
                ), "backoff never reached the fleet trail"
                assert any(
                    r.get("replica_id") == 0 and r.get("restarts", 0) >= 1
                    for r in rows
                ), "restart count never reached the fleet trail"
                assert router.drain(timeout=120), "post-chaos drain failed"
            finally:
                router.close()
            _assert_no_orphans(pids)
    finally:
        set_active_lockwatch(prior_watch)

    assert watch.violations == 0, (
        f"LockWatch saw {watch.violations} lock-order violation(s) under "
        f"chaos: {watch.report()['reports']}"
    )
    result["lock_order_violations"] = watch.violations
    result["locks_watched"] = sorted(watch.hold_histograms())

    result["clean_tok_s"] = clean_tokens / clean_wall if clean_wall > 0 else 0.0
    result["fault_tok_s"] = fault_tokens / fault_wall if fault_wall > 0 else 0.0
    result["chaos_goodput_ratio"] = (
        result["fault_tok_s"] / result["clean_tok_s"]
        if result["clean_tok_s"] > 0 else 0.0
    )
    return result


def main() -> int:
    r = run()
    print(
        f"chaos-smoke OK: {r['n_requests']} + {r['n_requests']} requests under "
        f"'{r['chaos_spec']}' — exactly-once delivery, zero orphans, "
        f"{r['respawns']} respawn(s), recovery {r['recovery_ratio']:.0%} of "
        f"target fleet, {r['lock_order_violations']} lock-order violation(s) "
        f"with LockWatch armed on {len(r['locks_watched'])} lock(s)\n"
        f"  goodput under faults {r['fault_tok_s']:.1f} tok/s vs clean "
        f"{r['clean_tok_s']:.1f} tok/s -> chaos_goodput_ratio "
        f"{r['chaos_goodput_ratio']:.2f} ({r['requeues']} requeue(s); CPU "
        f"dispatch-bound, ratio only)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
