"""Flight-recorder smoke: a real serve subprocess decodes a small request
mix with the flight recorder armed, an on-demand ``/profile`` window is
captured mid-traffic, and then every observability surface must agree:

* the **phase-sum invariant holds** on every recorded iteration — the
  five exclusive phases (schedule / prefill / dispatch / device_wait /
  harvest) sum to the iteration wall time (they are telescoping
  ``perf_counter`` stamps, so a mismatch means a dropped stamp);
* ``stats()['host_fraction']`` and ``trace tail --iterations`` computed
  from the emitted trace events **agree** on the host-vs-device split
  (the ROADMAP item-5 number) — two independent code paths, one answer;
* the ``/profile?seconds=N`` capture lands ``flight_window.json`` +
  ``manifest.json`` under ``<logging_dir>/profiles/`` and the engine
  keeps serving through and after the window with ``decode_compiles``
  still 1 (profiling never perturbs the compiled executable);
* the HBM watermarks ride ``stats()`` (estimate-labelled on CPU).

Run directly (``make flight-smoke``).
"""

import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ENGINE_ARGS = [
    "--preset", "tiny", "--num-slots", "2", "--block-size", "8",
    "--max-seq-len", "96", "--prefill-chunk", "8", "--decode-burst", "2",
]
N_REQUESTS = 8
PHASES = ("schedule", "prefill", "dispatch", "device_wait", "harvest")


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    return env


def main() -> int:
    logdir = os.path.join(tempfile.mkdtemp(prefix="flight_smoke_"), "run")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
         "serve", *ENGINE_ARGS, "--http", str(port), "--logging-dir", logdir],
        env=_env(), stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    base = f"http://127.0.0.1:{port}"
    try:
        deadline = time.monotonic() + 300
        while True:
            if proc.poll() is not None:
                raise RuntimeError(f"serve exited early rc={proc.returncode}")
            try:
                with urllib.request.urlopen(f"{base}/healthz", timeout=2) as r:
                    if json.loads(r.read()).get("state") == "ready":
                        break
            except (OSError, ValueError):
                pass
            if time.monotonic() > deadline:
                raise RuntimeError("serve never became ready")
            time.sleep(0.25)

        def gen(i):
            body = json.dumps({
                "id": i, "prompt": [1 + i % 7, 5, 11, 2],
                "max_new_tokens": 12 + i % 5,
            }).encode()
            req = urllib.request.Request(
                f"{base}/generate", data=body,
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=180) as r:
                return json.loads(r.read())

        assert gen(0)["finish_reason"] == "length"

        # capture the profiler window WHILE traffic decodes
        worker = threading.Thread(
            target=lambda: [gen(i) for i in range(1, N_REQUESTS)], daemon=True
        )
        worker.start()
        with urllib.request.urlopen(f"{base}/profile?seconds=0.5",
                                    timeout=120) as r:
            manifest = json.loads(r.read())
        worker.join(timeout=300)
        assert not worker.is_alive(), "traffic wedged behind the profiler"

        window_path = os.path.join(manifest["profile_dir"],
                                   "flight_window.json")
        assert os.path.isfile(window_path), manifest
        assert os.path.isfile(
            os.path.join(manifest["profile_dir"], "manifest.json")
        )
        with open(window_path) as f:
            window = json.load(f)
        assert window["phases"] == list(PHASES)
        assert window["iterations"] == len(window["entries"])
        # the tentpole invariant, re-checked offline on every entry the
        # window captured: exclusive phases telescope to the wall time
        for e in window["entries"]:
            total = sum(e[f"{p}_s"] for p in PHASES)
            assert abs(total - e["wall_s"]) < 1e-6, e

        # the engine kept serving and never re-traced
        assert gen(99)["finish_reason"] == "length"
        with urllib.request.urlopen(f"{base}/stats", timeout=10) as r:
            stats = json.loads(r.read())
        assert stats["decode_compiles"] == 1, stats
        assert 0.0 < stats["host_fraction"] <= 1.0, stats
        assert stats["hbm_used_bytes"] > 0, stats
        assert stats["hbm_bytes_source"] in ("memory_stats", "estimate")

        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            scrape = r.read().decode()
        for needle in ("serving_host_fraction", "serving_iteration_seconds",
                       "serving_hbm_used_bytes"):
            assert needle in scrape, needle
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)

    # offline: the trace-derived attribution must agree with the engine
    from accelerate_tpu.diagnostics.reqtrace import (
        iteration_report,
        render_iteration_report,
    )
    from accelerate_tpu.diagnostics.tracing import discover_profile_artifacts

    report = iteration_report(logdir, k=8)
    assert report["iterations"] > 0, "no serve/flight events in the traces"
    assert abs(sum(report["attribution"].values()) - 100.0) < 1e-6
    # two independent surfaces, one host-share answer: the engine's
    # cumulative stats() vs the offline reader over the emitted events.
    # The trace sees every iteration; /stats snapshots slightly later —
    # allow a small drift window.
    assert abs(report["host_fraction"] - stats["host_fraction"]) < 0.05, (
        report["host_fraction"], stats["host_fraction"],
    )
    assert discover_profile_artifacts(logdir) == [manifest["profile_dir"]]
    print(render_iteration_report(report))

    print(
        f"FLIGHT_SMOKE OK: {report['iterations']} iterations, "
        f"host fraction {report['host_fraction']:.1%} (engine "
        f"{stats['host_fraction']:.1%}), "
        f"{window['iterations']} in the {manifest['seconds']:.2f}s "
        f"profile window, decode_compiles=1"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
