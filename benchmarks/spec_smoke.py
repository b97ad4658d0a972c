"""Speculative serving smoke: spec-on vs spec-off on the IDENTICAL trace.

Interleaved legs (SPEC/OFF/SPEC/OFF/...) of the same Poisson mixed-length
trace through the same engine geometry and the same model — the only
difference is ``EngineConfig(spec_k=..., draft="early_exit:1")`` — with a
median per side and **ratios only** (the timing-noise rule). Headline
keys: ``spec_serve_tpot_ratio`` (spec TPOT p50 / off TPOT p50, < 1 is a
win), ``spec_serve_accept_rate`` (the rate the trace actually achieved),
and ``spec_serve_goodput_ratio`` (mixed-traffic goodput must not regress).
Both legs assert the one-decode-executable contract inside
``run_engine_leg``; token parity is asserted here request-for-request.

The model is a 4-layer tiny slice whose layers past the first have their
output projections (``wo``, ``w_down``) scaled by 0.02 — the deep suffix
is near-transparent, so the ``early_exit:1`` draft agrees with the target
at a high, repeatable accept rate while costing 1/4 of a target forward
(the c_draft/c_target regime where speculation pays even on a CPU box,
where the k+1-wide verify is genuinely ~k+1x compute rather than the
~1x weight-read of the memory-bound TPU decode). That is deliberate: on
random weights truncated-depth agreement sits at its floor (see
docs/source/concept_guides/performance.md), and a smoke gates on the
machinery's win AT a usable accept rate — the achieved rate is reported
beside the ratio, never assumed. Trained checkpoints reach comparable
agreement with distilled drafts; the floor case is covered by the
parity matrix in tests/test_spec_serving.py.
"""

from __future__ import annotations

import json
import os
import sys
from dataclasses import replace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.serve_bench import make_trace, run_engine_leg, warm_engine

#: draft depth / round size of the smoke (the TPOT lever at accept ~= 1)
SPEC_K = 8


def build_model():
    """Tiny 4-layer llama, layers 2-4's output projections scaled to
    near-transparency (high draft agreement at 1/4 draft cost — module
    doc)."""
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig.tiny(
        vocab_size=256, hidden_size=64, layers=4, heads=4, seq=256
    )
    model = LlamaForCausalLM.from_config(config, seed=0)
    layers = dict(model.params["layers"])
    for key in ("wo", "w_down"):
        arr = np.array(layers[key])
        arr[1:] *= 0.02
        layers[key] = jnp.asarray(arr)
    model.params = {**model.params, "layers": layers}
    return model, config


def workload(platform: str):
    from accelerate_tpu.serving import EngineConfig

    model, config = build_model()
    # decode-dominated mix: short prompts, geometric outputs with a real
    # tail, arrivals well above capacity so TPOT measures sustained decode
    trace = make_trace(
        n_requests=32, arrival_rate_per_s=500.0, prompt_range=(4, 24),
        mean_new_tokens=24, max_new_cap=64, vocab_size=config.vocab_size,
    )
    spec_cfg = EngineConfig(
        num_slots=8, block_size=16, max_seq_len=128, prefill_chunk=32,
        spec_k=SPEC_K, draft="early_exit:1",
    )
    off_cfg = replace(spec_cfg, spec_k=0)
    return model, spec_cfg, off_cfg, trace


def run(platform: str, legs: int = 3) -> dict:
    model, spec_cfg, off_cfg, trace = workload(platform)
    spec_engine = warm_engine(model, spec_cfg, trace)
    off_engine = warm_engine(model, off_cfg, trace)

    def leg(engine, cfg):
        out = run_engine_leg(model, cfg, trace, engine=engine)
        out["accept_rate"] = engine.stats().get("spec_accept_rate")
        return out

    spec_legs, off_legs = [], []
    for _ in range(legs):
        spec_legs.append(leg(spec_engine, spec_cfg))
        off_legs.append(leg(off_engine, off_cfg))

    # token parity, request for request, on a fresh replay of the trace
    # (run_engine_leg drains between legs, so per-request tokens are
    # re-derived here rather than fished out of leg internals)
    def replay_tokens(engine):
        reqs = [engine.add_request(tr.prompt, tr.max_new_tokens) for tr in trace]
        engine.run_until_idle(max_iterations=100_000)
        return [list(r.output_tokens) for r in reqs]

    spec_tokens = replay_tokens(spec_engine)
    off_tokens = replay_tokens(off_engine)
    assert spec_tokens == off_tokens, (
        "speculative engine output diverged from the non-spec engine — "
        "greedy acceptance must be lossless"
    )

    med = legs // 2
    # ratios are taken PAIRWISE over adjacent interleaved legs (spec leg i
    # vs off leg i ran back to back, sharing the box's weather), then the
    # median pair wins — a cross-leg median-vs-median on a ±2x box pairs
    # a warm leg against a cold one and reports contention, not spec
    pair_ratios = sorted(
        s["tpot_s"]["p50"] / o["tpot_s"]["p50"]
        for s, o in zip(spec_legs, off_legs)
        if s.get("tpot_s", {}).get("p50") and o.get("tpot_s", {}).get("p50")
    )
    goodput_ratios = sorted(
        s["serve_tok_s"] / o["serve_tok_s"]
        for s, o in zip(spec_legs, off_legs)
        if o["serve_tok_s"]
    )
    spec = sorted(spec_legs, key=lambda r: r.get("tpot_s", {}).get("p50", 0.0))[med]
    off = sorted(off_legs, key=lambda r: r.get("tpot_s", {}).get("p50", 0.0))[med]
    spec_tpot = spec.get("tpot_s", {}).get("p50")
    off_tpot = off.get("tpot_s", {}).get("p50")
    accept = max(
        (l["accept_rate"] for l in spec_legs if l.get("accept_rate") is not None),
        default=0.0,
    )
    result = {
        "spec_serve_tpot_ratio": (
            pair_ratios[len(pair_ratios) // 2] if pair_ratios else None
        ),
        "spec_serve_accept_rate": accept,
        "spec_serve_goodput_ratio": (
            goodput_ratios[len(goodput_ratios) // 2] if goodput_ratios else None
        ),
        "spec_k": SPEC_K,
        "draft": "early_exit:1",
        "spec_tpot_p50_s": spec_tpot,
        "off_tpot_p50_s": off_tpot,
        "spec_legs_tok_s": [round(l["serve_tok_s"], 1) for l in spec_legs],
        "off_legs_tok_s": [round(l["serve_tok_s"], 1) for l in off_legs],
        "decode_compiles": [spec["decode_compiles"], off["decode_compiles"]],
        "token_parity": True,
        "n_requests": len(trace),
    }
    return result


def main() -> int:
    import jax

    platform = jax.devices()[0].platform
    result = run(platform)
    print(json.dumps(result, indent=2, default=float))
    failures = []
    if not result["spec_serve_accept_rate"] or result["spec_serve_accept_rate"] < 0.3:
        failures.append(
            f"accept rate {result['spec_serve_accept_rate']} < 0.3: the "
            "near-transparent suffix should make the draft agree — the "
            "draft/verify plumbing is broken, not the acceptance"
        )
    ratio = result["spec_serve_tpot_ratio"]
    if ratio is None or ratio >= 1.0:
        failures.append(
            f"spec_serve_tpot_ratio {ratio} >= 1.0 at accept rate "
            f"{result['spec_serve_accept_rate']:.2f}: speculation must cut "
            "TPOT when the draft agrees"
        )
    good = result["spec_serve_goodput_ratio"]
    if good is None or good < 0.9:
        failures.append(
            f"spec_serve_goodput_ratio {good} < 0.9: mixed-traffic goodput "
            "must not regress with speculation on"
        )
    for f in failures:
        print(f"SPEC_SMOKE FAIL: {f}", file=sys.stderr)
    print(
        "SPEC_SMOKE "
        f"{(ratio or 0.0):.4f} {result['spec_serve_accept_rate']:.4f} "
        f"{(good or 0.0):.4f} "
        f"{result['decode_compiles'][0]} {result['decode_compiles'][1]}"
    )
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
