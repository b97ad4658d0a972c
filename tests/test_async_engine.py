"""Double-buffered dispatch (``EngineConfig.async_dispatch``) — the
sync-vs-async contract of ROADMAP item 5.

The bar under test: the async loop changes WHEN tokens surface (one
``step()`` late, landed by the drain flush), never WHICH tokens — output
is token-identical to the synchronous engine across every kv_dtype and
every scheduling feature that edits engine state while a round is in
flight (chunked prefill, radix hit + CoW, swap preemption, deadline
expiry, speculative rounds, sampling lanes + grammar). One compiled
decode executable on both legs, exactly-once finishes under fences and
chaos, LockWatch-clean, and the flight recorder's ``overlap_hidden_s``
accounting consistent by construction.

Tier-1 tests cover the config/CLI plumbing (pure host); engine
end-to-end parity rides the slow lane like the rest of the serving
suite.
"""

import argparse
import io
import json
import os
import queue as queue_mod
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from accelerate_tpu.serving import EngineConfig, InferenceEngine, RequestState

KV_DTYPES = ("bf16", "int8", "fp8")


@pytest.fixture(scope="module")
def tiny_model():
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig.tiny(vocab_size=64, hidden_size=32, layers=2, heads=4, seq=96)
    return LlamaForCausalLM.from_config(config, seed=0)


def _cfg(**kw):
    base = dict(num_slots=3, block_size=8, max_seq_len=64, prefill_chunk=8)
    base.update(kw)
    return EngineConfig(**base)


def _prompts(seed, sizes=(5, 11, 17, 3, 9)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, size=n).astype(np.int32) for n in sizes]


# ---------------------------------------------------------------------------
# config + CLI plumbing (tier-1: pure host)
# ---------------------------------------------------------------------------


def test_async_dispatch_default_on():
    assert EngineConfig().async_dispatch is True


def test_serve_cli_sync_engine_flag_and_env(monkeypatch):
    """`--sync-engine` flips the escape hatch; ACCELERATE_SYNC_ENGINE=1
    sets the default (0/empty means async — the flag never un-sets env)."""
    from accelerate_tpu.commands import serve as serve_cmd

    def parse(argv):
        parser = argparse.ArgumentParser()
        serve_cmd.add_parser(parser.add_subparsers())
        return parser.parse_args(argv)

    monkeypatch.delenv("ACCELERATE_SYNC_ENGINE", raising=False)
    assert parse(["serve"]).sync_engine is False
    assert parse(["serve", "--sync-engine"]).sync_engine is True
    monkeypatch.setenv("ACCELERATE_SYNC_ENGINE", "1")
    assert parse(["serve"]).sync_engine is True
    monkeypatch.setenv("ACCELERATE_SYNC_ENGINE", "0")
    assert parse(["serve"]).sync_engine is False


def test_route_forwards_sync_engine_to_replicas():
    from accelerate_tpu.commands.route import _serve_args

    ns = argparse.Namespace(
        preset="tiny", dtype="f32", num_slots=2, block_size=8, max_seq_len=64,
        prefill_chunk=8, decode_burst=2, max_new_tokens=4, eos_token_id=None,
        temperature=None, seed=0, kv_dtype=None, chaos_spec=None, spec_k=None,
        draft=None, logprobs_topn=None, mesh=False, sync_engine=True,
    )
    assert "--sync-engine" in _serve_args(ns)
    ns.sync_engine = False
    assert "--sync-engine" not in _serve_args(ns)


# ---------------------------------------------------------------------------
# sync-vs-async token parity across kv_dtypes x scheduling features
# ---------------------------------------------------------------------------


def _pair(model, drive, **cfg_kw):
    """Run the same `drive` trace on an async and a sync engine. Asserts
    the headline invariants (token identity, one decode executable each,
    zero leaked blocks, zero hidden overlap on the sync leg) and hands
    back both engines + request lists for scenario-specific checks."""

    def leg(async_dispatch):
        eng = InferenceEngine(
            model, _cfg(async_dispatch=async_dispatch, **cfg_kw)
        )
        reqs = drive(eng)
        eng.run_until_idle(max_iterations=5000)
        return eng, reqs, [list(r.output_tokens) for r in reqs]

    a_eng, a_reqs, a_toks = leg(True)
    s_eng, s_reqs, s_toks = leg(False)
    assert a_toks == s_toks, "async dispatch changed the emitted tokens"
    for eng in (a_eng, s_eng):
        st = eng.stats()
        assert st["decode_compiles"] == 1
        assert st["allocated_blocks"] == 0
        assert eng._inflight is None  # run_until_idle really drained
    assert s_eng._flight.overlap_hidden_total_s == 0.0
    return a_eng, s_eng, a_reqs, s_reqs


def _drive_mixed(eng):
    # 17-token prompt > prefill_chunk 8 forces chunked prefill; staggered
    # budgets finish mid-wave so admission churns while rounds are in flight
    return [eng.add_request(p, 3 + 4 * i) for i, p in enumerate(_prompts(0))]


def _drive_radix_cow(eng):
    base = np.arange(20, dtype=np.int32) % 60
    r1 = eng.add_request(base, 6)
    eng.run_until_idle(max_iterations=5000)
    # full-block hit (16-token shared prefix) + mid-block CoW divergence
    shared = np.concatenate([base[:19], np.asarray([61], np.int32)])
    r2 = eng.add_request(shared, 6)
    return [r1, r2]


def _drive_swap(eng):
    return [
        eng.add_request(np.arange(8, dtype=np.int32) + i, max_new_tokens=30)
        for i in range(2)
    ]


def _drive_deadline(eng):
    # a microscopic budget expires while queued — deterministic on both
    # legs (the sweep runs before admission); bystanders decode normally
    doomed = eng.add_request([5, 6, 7], 8, deadline_ms=0.001)
    rest = [eng.add_request(p, 6) for p in _prompts(3, sizes=(5, 9))]
    return [doomed] + rest


def _drive_lanes(eng):
    ps = _prompts(2)
    return [
        eng.add_request(ps[0], 6),
        eng.add_request(
            ps[1], 6,
            sampling={"do_sample": True, "temperature": 0.8, "seed": 5},
        ),
        eng.add_request(
            ps[3], 6,
            sampling={"do_sample": True, "temperature": 0.9, "seed": 6},
            grammar={"type": "regex", "pattern": "[0-9]+"},
        ),
    ]


_SCENARIOS = {
    "chunked_prefill": (_drive_mixed, dict(decode_burst=1)),
    "radix_cow": (_drive_radix_cow, dict(prefix_cache=True)),
    "swap_preempt": (
        _drive_swap,
        dict(num_slots=2, num_blocks=6, swap_gb=0.01, prefix_cache=False),
    ),
    "deadline": (_drive_deadline, {}),
    "spec_k3": (_drive_mixed, dict(spec_k=3, draft="early_exit:1")),
    "lanes": (_drive_lanes, {}),
}


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
@pytest.mark.parametrize("scenario", sorted(_SCENARIOS))
def test_async_token_parity(tiny_model, scenario, kv_dtype):
    drive, cfg_kw = _SCENARIOS[scenario]
    a_eng, s_eng, a_reqs, s_reqs = _pair(
        tiny_model, drive, kv_dtype=kv_dtype, **cfg_kw
    )
    if scenario == "swap_preempt":
        # the pressure really bit on both legs: the async one exercised the
        # fence-then-batched-gather swap-out against an in-flight round
        for eng in (a_eng, s_eng):
            st = eng.stats()
            assert st["preemptions"] >= 1
            assert st["swapped_out_blocks"] == st["swapped_in_blocks"] > 0
        assert all(r.finish_reason == "length" for r in a_reqs)
    elif scenario == "deadline":
        assert a_reqs[0].finish_reason == "deadline_exceeded"
        assert s_reqs[0].finish_reason == "deadline_exceeded"
        assert not a_reqs[0].output_tokens
    elif scenario == "radix_cow":
        assert a_eng.stats()["prefix_hit_tokens"] > 0
        assert s_eng.stats()["prefix_hit_tokens"] > 0
    elif scenario == "spec_k3":
        assert a_eng.stats()["spec_drafted_tokens"] > 0
    elif scenario == "lanes":
        # the constrained slot only ever emitted digit bytes on both legs
        assert a_reqs[2].output_tokens
        assert all(48 <= t <= 57 for t in a_reqs[2].output_tokens)


@pytest.mark.slow
def test_async_mesh4_parity_one_executable(tiny_model):
    """Async over fsdp=2 x tp=2: token-identical to the sync mesh engine
    AND the async single-device engine, one decode executable under GSPMD."""
    import jax

    from accelerate_tpu.mesh import build_mesh
    from accelerate_tpu.utils.dataclasses import MeshPlugin

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs a >= 4-device (virtual) mesh")
    mesh = build_mesh(MeshPlugin(dp=1, fsdp=2, tp=2), devices=devices[:4])

    geometry = dict(num_slots=3, block_size=8, max_seq_len=64, prefill_chunk=8,
                    decode_burst=2)
    prompts = _prompts(7, sizes=(5, 12, 9))
    budgets = [4, 7, 5]

    def run(mesh_arg, async_dispatch):
        eng = InferenceEngine(
            tiny_model,
            _cfg(async_dispatch=async_dispatch, **geometry),
            mesh=mesh_arg,
        )
        reqs = [eng.add_request(p, b) for p, b in zip(prompts, budgets)]
        eng.run_until_idle(max_iterations=5000)
        return eng, [list(r.output_tokens) for r in reqs]

    mesh_async, toks_mesh_async = run(mesh, True)
    _, toks_mesh_sync = run(mesh, False)
    _, toks_single_async = run(None, True)
    assert toks_mesh_async == toks_mesh_sync == toks_single_async
    st = mesh_async.stats()
    assert st["decode_compiles"] == 1
    assert st["prefill_compiles"] == 1
    assert st["mesh"] == {"fsdp": 2, "tp": 2}


# ---------------------------------------------------------------------------
# overlap accounting (the flight recorder learned to hide host time)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_async_overlap_accounting(tiny_model):
    """The async leg records hidden overlap (> 0 on a real workload),
    every ring entry bounds it by wall - device_wait, and host_fraction
    follows the documented formula on both legs (sync reduces to the
    pre-item-5 1 - device_wait/wall)."""
    a_eng, s_eng, _, _ = _pair(tiny_model, _drive_mixed, decode_burst=1)
    fl = a_eng._flight
    assert fl.overlap_hidden_total_s > 0.0
    for e in fl.tail(len(fl)):
        assert -1e-6 <= e["overlap_hidden_s"] <= (
            e["wall_s"] - e["device_wait_s"] + 1e-6
        )
    expect = max(
        0.0,
        1.0
        - (fl.phase_totals_s["device_wait"] + fl.overlap_hidden_total_s)
        / fl.wall_total_s,
    )
    assert fl.host_fraction() == pytest.approx(expect, abs=1e-12)
    sf = s_eng._flight
    assert sf.host_fraction() == pytest.approx(
        max(0.0, 1.0 - sf.phase_totals_s["device_wait"] / sf.wall_total_s),
        abs=1e-12,
    )
    # the stat surfaces: stats() and telemetry both carry the new field
    assert a_eng.stats()["overlap_hidden_s"] == fl.overlap_hidden_total_s
    assert s_eng.stats()["overlap_hidden_s"] == 0.0


# ---------------------------------------------------------------------------
# run_until_idle drain-boundary + exactly-once (the satellite bugfix pins)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_run_until_idle_cap_counts_drain_flush(tiny_model):
    """Regression pin for the one-late boundary: a cap that lands exactly
    on the final drain flush succeeds and returns the finish once; a cap
    that lands between dispatch and harvest raises, and the follow-up
    drain still returns the finish exactly once (never dropped, never
    duplicated)."""

    def fresh():
        eng = InferenceEngine(tiny_model, _cfg(async_dispatch=True))
        req = eng.add_request([1, 2, 3, 4, 5], max_new_tokens=4)
        return eng, req

    # measure the exact iteration count, drain flush included, and the
    # step at which the finish surfaces (the final harvest; the last
    # iteration after it is the scheduler evicting the finished slot)
    eng, req = fresh()
    n = 0
    finish_step = None
    while eng.scheduler.has_work() or eng._inflight is not None:
        eng.step()
        n += 1
        if finish_step is None and req.state is RequestState.FINISHED:
            finish_step = n
        assert n < 5000
    assert req.state is RequestState.FINISHED
    assert finish_step is not None and finish_step >= 2
    assert n >= 2  # at least one dispatch + the one-late drain harvest

    eng, req = fresh()
    done = eng.run_until_idle(max_iterations=n)
    assert done.count(req) == 1
    assert eng._inflight is None

    # cap one short of the finishing harvest: the final round has been
    # dispatched but not harvested when the cap fires, and no finish has
    # been collected yet, so nothing is lost to the raise
    eng, req = fresh()
    with pytest.raises(RuntimeError, match="not idle"):
        eng.run_until_idle(max_iterations=finish_step - 1)
    assert eng._inflight is not None  # the cap really landed mid-flight
    done = eng.run_until_idle()
    assert done.count(req) == 1
    assert eng.stats()["completed"] == 1


@pytest.mark.slow
def test_exactly_once_finishes_under_swap_fence(tiny_model):
    """Step-by-step drive of the swap-pressure workload: every request is
    returned by exactly one step() call even when a mid-schedule fence
    force-harvests the in-flight round into the backlog."""
    eng = InferenceEngine(
        tiny_model,
        _cfg(async_dispatch=True, num_slots=2, num_blocks=6, swap_gb=0.01,
             prefix_cache=False),
    )
    reqs = [
        eng.add_request(np.arange(8, dtype=np.int32) + i, max_new_tokens=30)
        for i in range(2)
    ]
    seen = []
    it = 0
    while eng.scheduler.has_work() or eng._inflight is not None:
        assert it < 5000
        seen.extend(r.request_id for r in eng.step())
        it += 1
    assert sorted(seen) == sorted(r.request_id for r in reqs)
    assert eng.stats()["preemptions"] >= 1
    assert all(r.finish_reason == "length" for r in reqs)


@pytest.mark.slow
def test_stream_yields_every_token_async(tiny_model):
    """stream() under the async loop still yields every token exactly
    once — the trailing flush after FINISHED drains the one-late tail."""
    eng = InferenceEngine(tiny_model, _cfg(async_dispatch=True))
    toks = list(eng.stream([3, 1, 4, 1, 5], max_new_tokens=6))
    ref_eng = InferenceEngine(tiny_model, _cfg(async_dispatch=False))
    ref = ref_eng.add_request([3, 1, 4, 1, 5], max_new_tokens=6)
    ref_eng.run_until_idle(max_iterations=5000)
    assert toks == ref.output_tokens


# ---------------------------------------------------------------------------
# LockWatch: the serve front end's loop with the async engine underneath
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_lockwatch_clean_async_engine_loop(tiny_model):
    """The serve front end (engine loop thread + concurrent /healthz
    probes) with LockWatch armed over the async engine: every request
    answered, zero lock-order violations."""
    from accelerate_tpu.analysis.lockwatch import (
        LockWatch,
        get_active_lockwatch,
        set_active_lockwatch,
    )
    from accelerate_tpu.commands.serve import ServeHealth, _engine_loop

    saved = get_active_lockwatch()
    watch = LockWatch(stream=io.StringIO())
    set_active_lockwatch(watch)
    try:
        engine = InferenceEngine(tiny_model, _cfg(async_dispatch=True))
        health = ServeHealth(replica_id=0)  # constructed armed -> watched
        health.mark_ready()
        inbox = queue_mod.Queue()
        results = []
        stop = threading.Event()
        loop = threading.Thread(
            target=_engine_loop, args=(engine, inbox, results.append, stop),
            kwargs=dict(health=health), daemon=True,
        )
        loop.start()
        probe_stop = threading.Event()

        def probe():  # the /healthz handler's concurrent reads
            while not probe_stop.is_set():
                health.payload(engine)
                time.sleep(0.001)

        prober = threading.Thread(target=probe, daemon=True)
        prober.start()
        for i in range(6):
            inbox.put(
                ({"id": i, "prompt": [1 + i % 5, 7, 3], "max_new_tokens": 6},
                 None)
            )
        deadline = time.monotonic() + 240
        while len(results) < 6 and time.monotonic() < deadline:
            time.sleep(0.05)
        stop.set()
        loop.join(timeout=120)
        probe_stop.set()
        prober.join(timeout=10)
        assert len(results) == 6, f"unanswered requests: {6 - len(results)}"
        assert not [r for r in results if "error" in r]
        assert watch.violations == 0, watch.report()
        assert engine.stats()["decode_compiles"] == 1
    finally:
        set_active_lockwatch(saved)


# ---------------------------------------------------------------------------
# chaos: exactly-once through real processes with the async loop (default)
# ---------------------------------------------------------------------------

_TINY_ARGS = [
    "--preset", "tiny", "--num-slots", "2", "--block-size", "8",
    "--max-seq-len", "64", "--prefill-chunk", "8", "--decode-burst", "2",
]


def _cli_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    env.pop("ACCELERATE_TELEMETRY", None)
    env.pop("ACCELERATE_CHAOS_SPEC", None)
    env.pop("ACCELERATE_SYNC_ENGINE", None)  # the async loop IS under test
    return env


def _start_reader(proc, sink):
    def read():
        for line in proc.stdout:
            line = line.strip()
            if line:
                sink.append(line)

    t = threading.Thread(target=read, daemon=True)
    t.start()
    return t


def _wait_results(sink, n, timeout, proc=None):
    deadline = time.monotonic() + timeout
    while len(sink) < n and time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            break
        time.sleep(0.1)
    return [json.loads(line) for line in sink]


def _req(i, session=None, n_new=4):
    payload = {"id": i, "prompt": [1 + (i % 5), 7, 3], "max_new_tokens": n_new}
    if session is not None:
        payload["session_id"] = session
    return json.dumps(payload) + "\n"


@pytest.mark.slow
@pytest.mark.parametrize("spec", ["seed=1;r0:kill@3", "r0:stop@2"])
def test_chaos_exactly_once_async_loop(tmp_path, spec):
    """Under a seeded kill -9 / SIGSTOP schedule against a routed fleet of
    async-default replicas, every submitted request is answered exactly
    once and the tokens for identical prompts agree across replicas (the
    async loop never forked the decode output)."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
         "route", "--replicas", "2", "--respawn", "--min-replicas", "2",
         "--logging-dir", str(tmp_path), "--health-interval", "0.2",
         "--chaos-spec", spec, *_TINY_ARGS],
        env=_cli_env(), stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True,
    )
    results = []
    _start_reader(proc, results)
    try:
        # warmup pins sessions: chat-0 -> replica 0, chat-1 -> replica 1
        for i in range(4):
            proc.stdin.write(_req(i, session=f"chat-{i % 2}"))
        proc.stdin.flush()
        assert len(_wait_results(results, 4, timeout=240, proc=proc)) == 4, (
            f"fleet never answered warmup; rc={proc.poll()}"
        )
        # the wave trips the schedule on replica 0 with requests in flight
        for i in range(4, 10):
            proc.stdin.write(_req(i, session=f"chat-{i % 2}", n_new=8))
        proc.stdin.flush()
        parsed = _wait_results(results, 10, timeout=240, proc=proc)
        assert len(parsed) == 10, f"rc={proc.poll()} results={len(parsed)}"
        proc.stdin.close()
        rc = proc.wait(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    assert rc == 0
    parsed = [json.loads(line) for line in results]
    ids = sorted(r.get("id") for r in parsed)
    assert ids == list(range(10)), f"lost/duplicated: {ids}"
    assert not [r for r in parsed if "error" in r], "chaos lost requests"
    # identical prompts -> identical greedy tokens, whichever replica (and
    # whichever respawn generation) answered: token identity survived chaos
    by_prompt = {}
    for r in parsed:
        key = (r["id"] % 5, len(r["tokens"]))
        by_prompt.setdefault(key, set()).add(tuple(r["tokens"]))
    for key, variants in by_prompt.items():
        assert len(variants) == 1, f"prompt {key} answered divergently"
