"""Router durability + scale-out smoke: 2 replicas, mixed sticky/free
traffic, one replica killed -9 mid-run — zero lost or duplicated requests,
then a clean drain. Also measures the scale-out ratio (2-replica fleet
tok/s over a 1-replica baseline on the same trace) and per-replica slot
occupancy from the fleet JSONL — ratios only, never absolute wall-clock
gates, per the timing-noise rule (this box's clock swings ±5x; the
credible ratio is a real multi-chip host).

Run directly (``make route-smoke``).
"""

import json
import os
import signal
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


def _replica_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""  # single-device replicas: fast start, no oversubscription
    return env


def _payload(i, sticky_every=3, n_new=8):
    p = {"id": i, "prompt": [1 + i % 7, 5, 11, 2], "max_new_tokens": n_new}
    if i % sticky_every == 0:
        p["session_id"] = f"chat-{i % 2}"  # sticky lane
    return p


def _run_trace(router, n, offset=0):
    """Submit ``n`` mixed sticky/free requests, wait for every answer, and
    return (tickets, wall_seconds, tokens)."""
    t0 = time.perf_counter()
    tickets = [router.submit(_payload(offset + i)) for i in range(n)]
    if not router.wait_idle(timeout=600):
        raise RuntimeError("router never went idle")
    # nothing to fence: the timed work is HTTP round-trips to replica
    # subprocesses and the results arrive as fully materialized JSON
    # tpu-lint: ignore[TPU008]
    wall = time.perf_counter() - t0
    tokens = sum(
        len(t.result.get("tokens", [])) for t in tickets if isinstance(t.result, dict)
    )
    return tickets, wall, tokens


def _spawn_fleet(n, logdir):
    from accelerate_tpu.serving.replica import spawn_replica, wait_until_ready
    from accelerate_tpu.serving.router import Router

    replicas = [
        spawn_replica(i, list(ENGINE_ARGS), env=_replica_env()) for i in range(n)
    ]
    router = Router(replicas, logging_dir=logdir, health_interval=0.2)
    try:
        wait_until_ready(replicas, timeout=300)
    except Exception:
        router.close()
        raise
    return replicas, router


def _occupancy_by_replica(logdir):
    path = os.path.join(logdir, "router", "replicas.jsonl")
    sums, counts = {}, {}
    try:
        with open(path) as f:
            for line in f:
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
                slots = row.get("num_slots") or 0
                if row.get("state") == "ready" and slots:
                    rid = row["replica_id"]
                    sums[rid] = sums.get(rid, 0.0) + row.get("active_slots", 0) / slots
                    counts[rid] = counts.get(rid, 0) + 1
    except OSError:
        pass
    return {rid: sums[rid] / counts[rid] for rid in sums if counts.get(rid)}


def run(platform: str = "cpu", n_requests: int = 16) -> dict:
    result: dict = {"n_requests": n_requests}

    # -- leg 1: 2-replica fleet — measured trace, then the kill ------------
    with tempfile.TemporaryDirectory() as logdir:
        replicas, router = _spawn_fleet(2, logdir)
        try:
            tickets, fleet_wall, fleet_tokens = _run_trace(router, n_requests)
            lost = [t for t in tickets if not isinstance(t.result, dict)
                    or "error" in t.result]
            assert not lost, f"fleet leg lost {len(lost)} requests"
            result["occupancy_by_replica"] = _occupancy_by_replica(logdir)

            # kill -9 one replica with a second wave in flight (long budgets
            # hold the wave open well past the kill even on a fast box);
            # deliveries land via callback so a double-fire is *observable*
            # — ticket.result alone would silently overwrite a duplicate
            deliveries = []
            wave = [router.submit(_payload(n_requests + i, n_new=32),
                                  callback=deliveries.append)
                    for i in range(n_requests // 2)]
            victim = replicas[0]
            deadline = time.monotonic() + 30
            while victim.in_flight == 0 and time.monotonic() < deadline:
                time.sleep(0.01)  # wait until the victim really holds work
            assert victim.in_flight > 0, "dispatch never placed work on the victim"
            os.kill(victim.pid, signal.SIGKILL)
            if not router.wait_idle(timeout=600):
                raise RuntimeError("router never recovered from the kill")
            answered = [t.result for t in wave]
            assert len(deliveries) == len(wave), (
                f"{len(deliveries)} deliveries for {len(wave)} requests "
                "— a request was dropped or double-delivered after the kill"
            )
            ids = [r.get("id") for r in deliveries]
            assert len(ids) == len(set(ids)), "duplicated delivery after kill"
            errors = [r for r in answered if "error" in r]
            assert not errors, f"kill lost requests: {errors}"
            deadline = time.monotonic() + 10  # the 0.2s health loop must notice
            while router.stats()["dead"] != 1 and time.monotonic() < deadline:
                time.sleep(0.05)
            stats = router.stats()
            assert stats["dead"] == 1, f"router missed the death: {stats}"
            assert stats["requeues"] >= 1, f"kill landed on an idle replica: {stats}"
            result["requeues"] = stats["requeues"]
            result["killed_replica"] = victim.replica_id
            clean = router.drain(timeout=120)
            assert clean, "post-kill drain did not exit cleanly"
        finally:
            router.close()

    # -- leg 2: 1-replica baseline on the identical trace ------------------
    with tempfile.TemporaryDirectory() as logdir:
        _, router = _spawn_fleet(1, logdir)
        try:
            tickets, single_wall, single_tokens = _run_trace(router, n_requests)
            assert all("error" not in t.result for t in tickets)
            router.drain(timeout=120)
        finally:
            router.close()

    result["fleet_tok_s"] = fleet_tokens / fleet_wall if fleet_wall > 0 else 0.0
    result["single_tok_s"] = single_tokens / single_wall if single_wall > 0 else 0.0
    result["route_goodput_ratio"] = (
        result["fleet_tok_s"] / result["single_tok_s"]
        if result["single_tok_s"] > 0 else 0.0
    )
    return result


def main() -> int:
    r = run()
    occ = "  ".join(
        f"r{rid}={v:.0%}" for rid, v in sorted(r["occupancy_by_replica"].items())
    )
    print(
        f"route-smoke OK: {r['n_requests']} + {r['n_requests'] // 2} requests, "
        f"kill -9 replica {r['killed_replica']} survived "
        f"({r['requeues']} requeue(s), zero lost/duplicated)\n"
        f"  fleet {r['fleet_tok_s']:.1f} tok/s vs single {r['single_tok_s']:.1f} "
        f"tok/s -> route_goodput_ratio {r['route_goodput_ratio']:.2f} "
        f"(CPU dispatch-bound; ratio only, occupancy {occ})"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
