"""Speculative decoding in the continuous-batching engine.

The contract under test: a spec-armed engine (``EngineConfig(spec_k=k,
draft="early_exit:N")``) is **token-identical** to the non-spec engine at
every ``kv_dtype`` and across every scheduler interaction (chunked
prefill, radix prefix hits, swap preemption, deadline expiry, eos), while
still compiling exactly ONE decode executable — the spec round (draft scan
+ ``[num_slots, k+1]`` verify + shared acceptance) *is* that executable.

Tier-1 (pure host / no compiles): draft-spec parsing, config refusals,
the shard-check draft tier, metrics/monitor field plumbing. The engine
end-to-end legs ride the slow lane like the rest of the serving suite.
"""

import numpy as np
import pytest

from accelerate_tpu.serving import (
    EngineConfig,
    InferenceEngine,
    parse_draft_spec,
)

# ---------------------------------------------------------------------------
# draft-spec parsing + config refusals (tier-1)
# ---------------------------------------------------------------------------


def test_parse_draft_spec_early_exit():
    spec = parse_draft_spec("early_exit:2", num_layers=16)
    assert (spec.kind, spec.layers) == ("early_exit", 2)
    assert str(spec) == "early_exit:2"
    # whitespace tolerated; depth bound enforced against the target
    assert parse_draft_spec(" early_exit:1 ", num_layers=2).layers == 1


@pytest.mark.parametrize(
    "bad, match",
    [
        ("early_exit:0", "must be >= 1"),
        ("early_exit:2", "must be < the target"),  # num_layers=2 below
        ("early_exit:x", "not an integer"),
        ("", "malformed draft spec"),
        ("mystery", "unknown draft spec"),
        ("ckpts/draft.safetensors", "not supported yet"),
    ],
)
def test_parse_draft_spec_rejects(bad, match):
    with pytest.raises(ValueError, match=match):
        parse_draft_spec(bad, num_layers=2)


@pytest.fixture(scope="module")
def tiny_model():
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig.tiny(vocab_size=64, hidden_size=32, layers=2, heads=4, seq=96)
    return LlamaForCausalLM.from_config(config, seed=0)


def _cfg(**kw):
    base = dict(num_slots=3, block_size=8, max_seq_len=64, prefill_chunk=8)
    base.update(kw)
    return EngineConfig(**base)


def test_engine_refuses_bad_spec_configs(tiny_model):
    with pytest.raises(ValueError, match="logprobs"):
        InferenceEngine(
            tiny_model, _cfg(spec_k=4, draft="early_exit:1", logprobs_topn=2)
        )
    with pytest.raises(ValueError, match="must be < the target"):
        InferenceEngine(tiny_model, _cfg(spec_k=4, draft="early_exit:2"))
    with pytest.raises(ValueError, match="not supported yet"):
        InferenceEngine(tiny_model, _cfg(spec_k=4, draft="ckpts/d.safetensors"))
    with pytest.raises(ValueError, match="spec_k must be >= 1"):
        InferenceEngine(tiny_model, _cfg(spec_k=-1))


def test_engine_stats_carry_spec_fields(tiny_model):
    eng = InferenceEngine(tiny_model, _cfg(spec_k=4, draft="early_exit:1"))
    st = eng.stats()
    assert st["spec_k"] == 4 and st["spec_draft"] == "early_exit:1"
    assert st["spec_drafted_tokens"] == 0 and st["spec_accept_rate"] == 0.0
    # spec off: the fields are absent entirely (monitor keys off spec_k)
    assert "spec_k" not in InferenceEngine(tiny_model, _cfg()).stats()


# ---------------------------------------------------------------------------
# shard-check draft tier (tier-1: abstract shapes only)
# ---------------------------------------------------------------------------


def test_draft_params_tier_prices_the_layer_slice(tiny_model):
    """The draft tier is exactly draft_layers/num_layers of the stacked
    layer params, under the same partition rules as the full stack."""
    from accelerate_tpu.analysis.shardplan import plan_draft_params, plan_params

    sizes = {ax: 1 for ax in ("dp", "pp", "fsdp", "ep", "cp", "tp")}
    rules = tiny_model.partition_rules
    params = tiny_model.params
    full_layers = sum(
        p.bytes_per_device
        for p in plan_params({"layers": params["layers"]}, sizes, rules=rules)
    )
    draft = plan_draft_params(params, sizes, rules, draft_layers=1)
    draft_bytes = sum(p.bytes_per_device for p in draft)
    assert draft_bytes * 2 == full_layers  # 1 of 2 layers
    assert all(p.tier == "draft_params" for p in draft)
    assert all(p.path.startswith("draft.layers.") for p in draft)


def test_engine_preflight_refusal_names_the_draft_tier(tiny_model):
    """With spec armed, the SP004 pre-flight budgets target + draft + pools
    and the refusal message names the draft share."""
    with pytest.raises(ValueError, match=r"SP004.*draft"):
        InferenceEngine(
            tiny_model,
            _cfg(spec_k=4, draft="early_exit:1", hbm_budget_gb=1e-6),
        )
    # generous budget: the report carries the draft tier and starts fine
    eng = InferenceEngine(
        tiny_model, _cfg(spec_k=4, draft="early_exit:1", hbm_budget_gb=8.0)
    )
    report = eng.hbm_preflight
    assert report["draft_layers"] == 1 and report["draft_bytes"] > 0
    assert report["total_bytes"] == (
        report["params_bytes"] + report["draft_bytes"] + report["pool_bytes"]
    )


# ---------------------------------------------------------------------------
# metrics + monitor plumbing (tier-1: synthetic rows, no engine dispatch)
# ---------------------------------------------------------------------------


def test_spec_metrics_round_trip_render_parse():
    """Accept-rate telemetry fields round-trip through BOTH export
    surfaces — the telemetry step-row path and the live stats()-dict path —
    into the documented serving_spec_* names."""
    from accelerate_tpu.metrics.ingest import observe_engine_stats, observe_record
    from accelerate_tpu.metrics.openmetrics import (
        parse_openmetrics,
        render_openmetrics,
        sample_value,
    )
    from accelerate_tpu.metrics.registry import MetricsRegistry

    reg = MetricsRegistry(gate_main_process=False)
    observe_record(reg, {
        "type": "serving", "kind": "step", "spec_k": 4,
        "spec_drafted_tokens": 120, "spec_accepted_tokens": 90,
        "spec_accept_rate": 0.75,
    })
    families = parse_openmetrics(render_openmetrics(reg))
    assert families["accelerate_serving_spec_drafted_tokens"]["type"] == "counter"
    assert sample_value(families, "accelerate_serving_spec_drafted_tokens") == 120
    assert sample_value(families, "accelerate_serving_spec_accepted_tokens") == 90
    assert sample_value(families, "accelerate_serving_spec_accept_rate") == 0.75

    # the stats() path ratchets the same counters (set_total semantics)
    observe_engine_stats(reg, {
        "spec_drafted_tokens": 200, "spec_accepted_tokens": 150,
        "spec_accept_rate": 0.75,
    })
    families = parse_openmetrics(render_openmetrics(reg))
    assert sample_value(families, "accelerate_serving_spec_drafted_tokens") == 200
    assert sample_value(families, "accelerate_serving_spec_accepted_tokens") == 150


# ---------------------------------------------------------------------------
# engine end-to-end (slow lane: compiles the tiny model)
# ---------------------------------------------------------------------------

KV_DTYPES = ("bf16", "int8", "fp8")


def _run_trace(model, spec_k, prompts, budgets, **cfg_kw):
    eng = InferenceEngine(
        model,
        _cfg(spec_k=spec_k, draft="early_exit:1" if spec_k else "early_exit:2",
             **cfg_kw),
    )
    reqs = [eng.add_request(p, b) for p, b in zip(prompts, budgets)]
    eng.run_until_idle(max_iterations=5000)
    return eng, [list(r.output_tokens) for r in reqs]


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_spec_token_parity_across_kv_dtypes(tiny_model, kv_dtype):
    """The headline bar: spec-armed output == non-spec output, token for
    token, at every kv_dtype — on a mixed-length trace whose prompts force
    chunked prefill (17 > prefill_chunk 8) and whose budgets finish
    mid-round. One decode executable each side."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32) for n in (5, 11, 17, 3, 9)]
    budgets = [3 + 4 * i for i in range(5)]
    base, base_toks = _run_trace(tiny_model, 0, prompts, budgets, kv_dtype=kv_dtype)
    spec, spec_toks = _run_trace(tiny_model, 4, prompts, budgets, kv_dtype=kv_dtype)
    assert spec_toks == base_toks
    st = spec.stats()
    assert st["decode_compiles"] == 1 and st["prefill_compiles"] == 1
    assert base.stats()["decode_compiles"] == 1
    assert st["spec_drafted_tokens"] > 0
    assert 0.0 <= st["spec_accept_rate"] <= 1.0
    assert st["allocated_blocks"] == 0  # rollback never leaked a block


@pytest.mark.slow
@pytest.mark.parametrize("spec_k", [1, 3, 8])
def test_spec_parity_across_k(tiny_model, spec_k):
    """k is a throughput knob, never a correctness one — including k=8
    rounds that overshoot short budgets by most of the round."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32) for n in (6, 13)]
    _, base_toks = _run_trace(tiny_model, 0, prompts, [7, 5])
    _, spec_toks = _run_trace(tiny_model, spec_k, prompts, [7, 5])
    assert spec_toks == base_toks


@pytest.mark.slow
def test_spec_eos_parity(tiny_model):
    """eos raised mid-round: the host emit loop cuts the accepted run at
    the eos exactly like the non-spec burst loop does."""
    from accelerate_tpu.generation import generate

    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 64, size=9).astype(np.int32)
    ref = np.asarray(
        generate(tiny_model, prompt[None, :], max_new_tokens=8, use_cache=True)
    )[0]
    eos = int(ref[len(prompt) + 2])

    def run(spec_k):
        eng = InferenceEngine(
            tiny_model,
            _cfg(num_slots=2, eos_token_id=eos, spec_k=spec_k,
                 draft="early_exit:1" if spec_k else "early_exit:2"),
        )
        req = eng.add_request(prompt, max_new_tokens=8)
        eng.run_until_idle(max_iterations=5000)
        return req

    r0, r4 = run(0), run(4)
    assert r4.output_tokens == r0.output_tokens
    assert r4.finish_reason == "eos" and len(r4.output_tokens) < 8


@pytest.mark.slow
def test_spec_radix_prefix_hit_parity(tiny_model):
    """A warm radix hit hands the spec engine cached blocks whose draft
    layers were written by a previous request's prefill/verify — valid by
    construction (the draft IS the target's first layers), so warm output
    == cold output == non-spec output."""
    base = np.arange(20, dtype=np.int32) % 60
    shared = np.concatenate([base[:19], np.asarray([61], np.int32)])

    def run(spec_k, prefix_cache):
        eng = InferenceEngine(
            tiny_model,
            _cfg(num_slots=2, prefix_cache=prefix_cache, spec_k=spec_k,
                 draft="early_exit:1" if spec_k else "early_exit:2"),
        )
        r1 = eng.add_request(base, 6)
        eng.run_until_idle(max_iterations=5000)
        r2 = eng.add_request(shared, 6)  # full-block hit + mid-block CoW
        eng.run_until_idle(max_iterations=5000)
        return eng, (r1.output_tokens, r2.output_tokens)

    warm_eng, warm = run(4, True)
    _, cold = run(4, False)
    _, base_toks = run(0, True)
    assert warm == cold == base_toks
    st = warm_eng.stats()
    assert st["prefix_hit_tokens"] > 0  # the warm leg really hit the cache
    assert st["decode_compiles"] == 1


@pytest.mark.slow
def test_spec_swap_preemption_parity(tiny_model):
    """Pool pressure with the host swap tier: preempted + restored rows
    carry the draft layers byte-exactly (they are just pool layers), so
    the spec engine completes un-truncated and token-identical to the
    non-spec engine under the same pressure."""
    prompts = [np.arange(8, dtype=np.int32), np.arange(8, dtype=np.int32) + 1]

    def run(spec_k):
        eng = InferenceEngine(
            tiny_model,
            _cfg(num_slots=2, prefix_cache=False, num_blocks=6, swap_gb=0.01,
                 spec_k=spec_k, draft="early_exit:1" if spec_k else "early_exit:2"),
        )
        reqs = [eng.add_request(p, max_new_tokens=30) for p in prompts]
        eng.run_until_idle(max_iterations=5000)
        return eng, reqs

    spec_eng, spec_reqs = run(4)
    _, base_reqs = run(0)
    assert [r.finish_reason for r in spec_reqs] == ["length", "length"]
    assert [r.output_tokens for r in spec_reqs] == [r.output_tokens for r in base_reqs]
    st = spec_eng.stats()
    assert st["preemptions"] >= 1
    assert st["swapped_out_blocks"] == st["swapped_in_blocks"] > 0
    assert st["decode_compiles"] == 1


@pytest.mark.slow
def test_spec_deadline_expiry_interaction(tiny_model):
    """An already-expired queued request dies with deadline_exceeded while
    the spec lanes keep decoding — and the survivors stay token-identical
    to the non-spec engine under the same mix."""
    rng = np.random.default_rng(5)
    live_prompt = rng.integers(0, 64, size=7).astype(np.int32)

    def run(spec_k):
        eng = InferenceEngine(
            tiny_model,
            _cfg(num_slots=2, spec_k=spec_k,
                 draft="early_exit:1" if spec_k else "early_exit:2"),
        )
        doomed = eng.add_request(np.arange(5, dtype=np.int32), 6,
                                 deadline_ms=1e-3)
        live = eng.add_request(live_prompt, 9, deadline_ms=60_000.0)
        import time

        time.sleep(0.002)  # the doomed deadline elapses while queued
        eng.run_until_idle(max_iterations=5000)
        return eng, doomed, live

    spec_eng, spec_doomed, spec_live = run(4)
    _, base_doomed, base_live = run(0)
    for doomed in (spec_doomed, base_doomed):
        assert doomed.finish_reason == "deadline_exceeded"
    assert spec_live.output_tokens == base_live.output_tokens
    assert spec_live.finish_reason == "length"
    assert spec_eng.stats()["deadline_expired_total"] == 1
    assert spec_eng.stats()["decode_compiles"] == 1


# ---------------------------------------------------------------------------
# sharded mesh: the one-executable assertion with spec armed
# ---------------------------------------------------------------------------


def _mesh4():
    import jax

    from accelerate_tpu.mesh import build_mesh
    from accelerate_tpu.utils.dataclasses import MeshPlugin

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs a >= 4-device (virtual) mesh")
    return build_mesh(MeshPlugin(dp=1, fsdp=2, tp=2), devices=devices[:4])


@pytest.mark.slow
def test_spec_sharded_mesh_parity_one_executable(tiny_model):
    """The spec round over fsdp=2 x tp=2 (GSPMD NamedSharding, draft slice
    included) is token-identical to the single-device spec engine AND to
    the non-spec engine, with decode_compiles == 1 on the mesh."""
    mesh = _mesh4()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32) for n in (5, 12, 9)]
    budgets = [4, 7, 5]

    def run(spec_k, mesh_arg):
        eng = InferenceEngine(
            tiny_model,
            _cfg(spec_k=spec_k, decode_burst=2,
                 draft="early_exit:1" if spec_k else "early_exit:2"),
            mesh=mesh_arg,
        )
        reqs = [eng.add_request(p, b) for p, b in zip(prompts, budgets)]
        eng.run_until_idle(max_iterations=5000)
        return eng, [list(r.output_tokens) for r in reqs]

    _, single_spec = run(4, None)
    sharded_eng, sharded_spec = run(4, mesh)
    _, base_toks = run(0, None)
    assert sharded_spec == single_spec == base_toks
    stats = sharded_eng.stats()
    assert stats["decode_compiles"] == 1
    assert stats["mesh"] == {"fsdp": 2, "tp": 2}


# ---------------------------------------------------------------------------
# telemetry + monitor (slow: runs the engine under a recorder)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_spec_telemetry_rows_and_monitor_line(tiny_model, tmp_path):
    from accelerate_tpu.diagnostics.monitor import collect_status, render_status
    from accelerate_tpu.telemetry import TelemetryRecorder, set_active_recorder

    recorder = TelemetryRecorder(logging_dir=str(tmp_path))
    set_active_recorder(recorder)
    try:
        eng = InferenceEngine(
            tiny_model,
            _cfg(num_slots=2, stats_interval=2, spec_k=4, draft="early_exit:1"),
        )
        rng = np.random.default_rng(4)
        for i in range(3):
            eng.add_request(rng.integers(0, 64, size=5 + i).astype(np.int32), 6)
        eng.run_until_idle(max_iterations=5000)
    finally:
        set_active_recorder(None)
        recorder.close()

    steps = [
        r for r in recorder.records
        if r.get("type") == "serving" and r.get("kind") == "step"
    ]
    assert steps, "stats_interval=2 must have emitted step rows"
    last = steps[-1]
    assert last["spec_k"] == 4 and last["spec_draft"] == "early_exit:1"
    assert last["spec_drafted_tokens"] > 0
    assert 0.0 <= last["spec_accept_rate"] <= 1.0
    assert last["spec_accepted_tokens"] <= last["spec_drafted_tokens"]

    status = collect_status(str(tmp_path))
    srv = status["serving"]
    assert srv["spec_k"] == 4 and srv["spec_drafted_tokens"] > 0
    rendered = render_status(status)
    assert "spec: k=4 (early_exit:1)" in rendered
