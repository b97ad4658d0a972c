"""Serving goodput: continuous-batching engine vs static-batch generate().

Replays a Poisson-arrival, mixed-length request trace (uniform prompt
lengths, geometric output lengths — the canonical serving mix where static
batching burns decode slots as padding) against

(a) the :class:`~accelerate_tpu.serving.InferenceEngine` (slot-scheduled
    decode over the block-paged KV cache), and
(b) a static-batch baseline: requests grouped into arrival-order batches of
    ``num_slots``, each batch run through ``generate(use_cache=True)`` with
    ``max_new_tokens`` = the batch's largest budget — every request in the
    batch waits for the slowest one, which is exactly the regime
    iteration-level scheduling removes (Orca OSDI '22, vLLM SOSP '23).

Both legs run the same model/weights with compile time excluded (warmup
request / warmup batch before the clock starts). Reported: ``serve_tok_s``
(goodput — emitted tokens per wall second), ``static_tok_s``, TTFT/TPOT
percentiles (engine), mean slot occupancy, and the decode-compile count
(must be exactly 1 across the whole engine run — the one-executable
contract).

Arrivals are replayed in wall time: a request is submitted only once the
clock passes its Poisson arrival offset, so queueing and TTFT are real,
not simulated. Run standalone (``python benchmarks/serve_bench.py``); the
benchmark's chat cells are ``perfbench/``'s, not this file.
"""

from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import dataclass

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@dataclass
class TraceRequest:
    arrival_s: float  # offset from trace start
    prompt: "np.ndarray"
    max_new_tokens: int


def make_trace(
    n_requests: int,
    arrival_rate_per_s: float,
    prompt_range: tuple[int, int],
    mean_new_tokens: int,
    max_new_cap: int,
    vocab_size: int,
    seed: int = 0,
):
    """Poisson arrivals; uniform prompt lengths; geometric output budgets
    clipped to ``max_new_cap`` (heavy right tail → the static baseline's
    padding waste is realistic, not adversarial)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rate_per_s, size=n_requests))
    lo, hi = prompt_range
    trace = []
    for t in arrivals:
        plen = int(rng.integers(lo, hi + 1))
        new = int(min(1 + rng.geometric(1.0 / mean_new_tokens), max_new_cap))
        trace.append(
            TraceRequest(
                arrival_s=float(t),
                prompt=rng.integers(0, vocab_size, size=plen).astype(np.int32),
                max_new_tokens=new,
            )
        )
    return trace


def make_shared_prefix_trace(
    n_requests: int,
    arrival_rate_per_s: float,
    prefix_len: int,
    tail_range: tuple[int, int],
    mean_new_tokens: int,
    max_new_cap: int,
    vocab_size: int,
    shared_frac: float = 0.8,
    seed: int = 0,
):
    """The production-chat mix: ``shared_frac`` of requests open with ONE
    common system prompt of ``prefix_len`` tokens followed by a short
    unique tail; the rest are cold (fully random prompts of comparable
    total length). Prefill work is prefix-dominated by construction, so a
    prefix-sharing engine collapses TTFT on the shared fraction while the
    no-sharing engine re-prefills the same tokens every time."""
    import numpy as np

    rng = np.random.default_rng(seed)
    system_prompt = rng.integers(0, vocab_size, size=prefix_len).astype(np.int32)
    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rate_per_s, size=n_requests))
    lo, hi = tail_range
    trace = []
    for t in arrivals:
        tail_len = int(rng.integers(lo, hi + 1))
        new = int(min(1 + rng.geometric(1.0 / mean_new_tokens), max_new_cap))
        if rng.random() < shared_frac:
            prompt = np.concatenate(
                [system_prompt, rng.integers(0, vocab_size, size=tail_len).astype(np.int32)]
            )
        else:
            prompt = rng.integers(
                0, vocab_size, size=prefix_len + tail_len
            ).astype(np.int32)
        trace.append(
            TraceRequest(arrival_s=float(t), prompt=prompt, max_new_tokens=new)
        )
    return trace


def warm_engine(model, engine_config, trace):
    """Build the engine and compile its two programs on a dummy request."""
    from accelerate_tpu.serving import InferenceEngine

    engine = InferenceEngine(model, engine_config)
    engine.add_request(trace[0].prompt[: max(2, len(trace[0].prompt) // 2)], 2)
    engine.run_until_idle(max_iterations=10_000)
    return engine


def run_engine_leg(model, engine_config, trace, engine=None) -> dict:
    """Wall-clock replay through the engine. Compile excluded: the engine
    is pre-warmed (or warmed here) and ``reset_stats()`` drops the
    warmup's idle-engine TTFT and drain iterations from every reported
    percentile; the decode-compile counter survives the reset and must
    still read 1 afterwards — across repeated legs too."""
    if engine is None:
        engine = warm_engine(model, engine_config, trace)
    engine.reset_stats()

    t0 = time.perf_counter()
    pending = list(trace)
    while pending or engine.scheduler.has_work():
        # wall-clock arrival simulation, not a compute measurement;
        # engine.step() device_gets every iteration, so the `elapsed`
        # read is fenced by construction
        # tpu-lint: ignore[TPU008] — intentional wall-clock replay
        now = time.perf_counter() - t0
        while pending and pending[0].arrival_s <= now:
            tr = pending.pop(0)
            engine.add_request(tr.prompt, tr.max_new_tokens, arrival_time=t0 + tr.arrival_s)
        if engine.scheduler.has_work():
            engine.step()
        elif pending:
            time.sleep(min(0.002, max(0.0, pending[0].arrival_s - now)))
    elapsed = time.perf_counter() - t0

    stats = engine.stats()
    useful = stats["tokens_emitted"]
    out = {
        "serve_tok_s": useful / elapsed if elapsed > 0 else 0.0,
        "elapsed_s": elapsed,
        "tokens": useful,
        "completed": stats["completed"],
        "occupancy": stats["slot_occupancy_mean"],
        "decode_compiles": stats["decode_compiles"],
        "prefill_compiles": stats["prefill_compiles"],
        "prefix_hit_ratio": stats.get("prefix_hit_ratio", 0.0),
        "preemptions": stats.get("preemptions", 0),
        # flight-recorder attribution over this leg only (reset_stats()
        # above zeroed the recorder): the async_smoke host-hiding gauges
        "host_fraction": stats.get("host_fraction"),
        "overlap_hidden_s": stats.get("overlap_hidden_s", 0.0),
    }
    for key in ("ttft_s", "tpot_s"):
        if key in stats:
            out[key] = stats[key]
    assert stats["decode_compiles"] == 1, (
        f"decode step recompiled: {stats['decode_compiles']} executables "
        "(the [num_slots, 1] program must be traced exactly once)"
    )
    return out


def run_static_leg(model, trace, batch_size: int, prewarmed: set | None = None) -> dict:
    """Static-batch baseline: arrival-order batches of ``batch_size``
    through ``generate(use_cache=True)``; a batch starts only when its last
    member has arrived AND the previous batch finished (one device, no
    overlap) — its decode length is the batch max, so short completions pad."""
    import numpy as np

    batches = [trace[i : i + batch_size] for i in range(0, len(trace), batch_size)]

    # warm every distinct (batch rows, prompt bucket, decode length) shape so
    # the timed region contains zero static-path compiles — the baseline's
    # best case, keeping the goodput ratio about scheduling, not caching.
    # Decode length is the batch's EXACT max budget (bucketing it up would
    # unfairly inflate the baseline's padding waste). A caller-shared
    # ``prewarmed`` set skips the (expensive, full-decode) warm runs on
    # repeated legs — the compiled programs are cached on the apply_fn.
    warmed = prewarmed if prewarmed is not None else set()
    for batch in batches:
        shape = (
            len(batch),
            _bucket(max(len(tr.prompt) for tr in batch)),
            max(tr.max_new_tokens for tr in batch),
        )
        if shape not in warmed:
            warmed.add(shape)
            rows, plen, new = shape
            ids = np.zeros((rows, plen), np.int32)
            mask = np.ones((rows, plen), np.int32)
            np.asarray(generate_ref(model, ids, mask, new))

    t0 = time.perf_counter()
    done_at = 0.0  # virtual clock: device busy until here (offsets from t0)
    total_tokens = 0
    for batch in batches:
        ready = max(tr.arrival_s for tr in batch)
        start = max(done_at, ready)
        now = time.perf_counter() - t0
        if start > now:
            time.sleep(start - now)
        _pad_generate(model, batch)
        done_at = time.perf_counter() - t0
        total_tokens += sum(tr.max_new_tokens for tr in batch)
    elapsed = done_at
    return {
        "static_tok_s": total_tokens / elapsed if elapsed > 0 else 0.0,
        "elapsed_s": elapsed,
        "tokens": total_tokens,
        "batches": len(batches),
    }


def _bucket(n: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return b


def _pad_generate(model, batch):
    """One static batch: right-pad prompts to the batch's bucketed max,
    decode everyone to the batch's exact max budget — the padding waste
    static batching pays by construction. Power-of-two prompt buckets keep
    the whole trace on a handful of pre-warmed executables."""
    import numpy as np

    plen = _bucket(max(len(tr.prompt) for tr in batch))
    new = max(tr.max_new_tokens for tr in batch)
    ids = np.zeros((len(batch), plen), np.int32)
    mask = np.zeros((len(batch), plen), np.int32)
    for i, tr in enumerate(batch):
        ids[i, : len(tr.prompt)] = tr.prompt
        mask[i, : len(tr.prompt)] = 1
    out = generate_ref(model, ids, mask, new)
    np.asarray(out)
    return out


def generate_ref(model, ids, mask, new):
    from accelerate_tpu.generation import generate

    return generate(model, ids, max_new_tokens=new, use_cache=True, attention_mask=mask)


def default_workload(platform: str):
    """(model, engine config, trace) sized for the attached backend."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.serving import EngineConfig

    if platform == "cpu":  # smoke sizing
        config = LlamaConfig.tiny(vocab_size=256, hidden_size=64, layers=2, heads=4, seq=128)
        model = LlamaForCausalLM.from_config(config, seed=0)
        engine_cfg = EngineConfig(
            num_slots=8, block_size=8, max_seq_len=128, prefill_chunk=32
        )
        # arrival rate well above capacity: goodput (not arrival) limited.
        # NOTE the CPU leg is a *smoke* of the machinery, not a credible
        # ratio: at tiny-model shapes both legs are dispatch-bound and this
        # box's wall clock swings ±5x — the acceptance ratio is the TPU run
        trace = make_trace(
            n_requests=64, arrival_rate_per_s=500.0, prompt_range=(4, 24),
            mean_new_tokens=12, max_new_cap=96, vocab_size=config.vocab_size,
        )
    else:
        # the bench flagship slice (~700M), bf16 resident weights — same
        # model the decode_tok_s row measures
        config = LlamaConfig.flagship_700m(max_position_embeddings=512)
        model = LlamaForCausalLM.from_config(config, seed=0)
        model.params = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            model.params,
        )
        engine_cfg = EngineConfig(
            num_slots=16, block_size=16, max_seq_len=512, prefill_chunk=128
        )
        # arrival rate ~10x a slot's decode rate: the queue stays non-empty,
        # so the ratio measures sustained goodput, not arrival gaps
        trace = make_trace(
            n_requests=64, arrival_rate_per_s=400.0, prompt_range=(32, 160),
            mean_new_tokens=24, max_new_cap=96, vocab_size=config.vocab_size,
        )
    return model, engine_cfg, trace


def run(platform: str, legs: int = 3) -> dict:
    """Interleaved engine/static legs (E/S/E/S/E/S), median-of-``legs`` per
    side — on a box with ±5x wall-clock swings a single-shot ratio is a
    contention artifact waiting to happen (the r5 fp8 lesson). Warmup
    (engine programs + every static shape) happens once, outside all legs."""
    model, engine_cfg, trace = default_workload(platform)
    engine = warm_engine(model, engine_cfg, trace)
    prewarmed: set = set()
    eng_legs, static_legs = [], []
    for _ in range(legs):
        eng_legs.append(run_engine_leg(model, engine_cfg, trace, engine=engine))
        static_legs.append(
            run_static_leg(model, trace, engine_cfg.num_slots, prewarmed=prewarmed)
        )
    eng = sorted(eng_legs, key=lambda r: r["serve_tok_s"])[legs // 2]
    static = sorted(static_legs, key=lambda r: r["static_tok_s"])[legs // 2]
    return {
        "engine": eng,
        "static": static,
        "engine_legs_tok_s": [round(r["serve_tok_s"], 1) for r in eng_legs],
        "static_legs_tok_s": [round(r["static_tok_s"], 1) for r in static_legs],
        "goodput_ratio": (
            eng["serve_tok_s"] / static["static_tok_s"]
            if static["static_tok_s"] else None
        ),
        "num_slots": engine_cfg.num_slots,
        "block_size": engine_cfg.block_size,
        "n_requests": len(trace),
    }


def radix_workload(platform: str):
    """(model, engine config, 80%-shared-prefix trace) for the prefix-
    sharing leg. Prompts are prefix-dominated (the production chat shape);
    tails and output budgets stay short so prefill — the work sharing
    removes — is the bottleneck under load."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.serving import EngineConfig

    if platform == "cpu":  # smoke sizing (see default_workload's caveat)
        config = LlamaConfig.tiny(vocab_size=256, hidden_size=64, layers=2, heads=4, seq=128)
        model = LlamaForCausalLM.from_config(config, seed=0)
        engine_cfg = EngineConfig(
            num_slots=8, block_size=8, max_seq_len=128, prefill_chunk=16
        )
        trace = make_shared_prefix_trace(
            n_requests=48, arrival_rate_per_s=500.0, prefix_len=64,
            tail_range=(4, 12), mean_new_tokens=8, max_new_cap=24,
            vocab_size=config.vocab_size,
        )
    else:
        config = LlamaConfig.flagship_700m(max_position_embeddings=512)
        model = LlamaForCausalLM.from_config(config, seed=0)
        model.params = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            model.params,
        )
        engine_cfg = EngineConfig(
            num_slots=16, block_size=16, max_seq_len=512, prefill_chunk=128
        )
        trace = make_shared_prefix_trace(
            n_requests=64, arrival_rate_per_s=400.0, prefix_len=256,
            tail_range=(8, 48), mean_new_tokens=24, max_new_cap=96,
            vocab_size=config.vocab_size,
        )
    return model, engine_cfg, trace


def run_radix(platform: str, legs: int = 3) -> dict:
    """Prefix sharing on vs off (the FCFS/no-sharing PR 4 engine) on the
    SAME 80%-shared-prefix trace and model — interleaved R/C legs,
    median-of-``legs`` per side, ratios only (the timing-noise rule). The
    sharing engine's radix cache warms on leg 1 and stays warm (the
    steady-state a long-lived server sits in); both engines keep the
    one-decode-executable contract, asserted inside every leg."""
    from dataclasses import replace

    model, engine_cfg, trace = radix_workload(platform)
    sharing_cfg = replace(engine_cfg, prefix_cache=True)
    cold_cfg = replace(engine_cfg, prefix_cache=False)
    sharing_engine = warm_engine(model, sharing_cfg, trace)
    cold_engine = warm_engine(model, cold_cfg, trace)
    share_legs, cold_legs = [], []
    for _ in range(legs):
        share_legs.append(run_engine_leg(model, sharing_cfg, trace, engine=sharing_engine))
        cold_legs.append(run_engine_leg(model, cold_cfg, trace, engine=cold_engine))
    share = sorted(share_legs, key=lambda r: r["serve_tok_s"])[legs // 2]
    cold = sorted(cold_legs, key=lambda r: r["serve_tok_s"])[legs // 2]
    return {
        "sharing": share,
        "no_sharing": cold,
        "sharing_legs_tok_s": [round(r["serve_tok_s"], 1) for r in share_legs],
        "no_sharing_legs_tok_s": [round(r["serve_tok_s"], 1) for r in cold_legs],
        "radix_goodput_ratio": (
            share["serve_tok_s"] / cold["serve_tok_s"]
            if cold["serve_tok_s"] else None
        ),
        "prefix_hit_ratio": share["prefix_hit_ratio"],
        "ttft_p50_sharing_s": share.get("ttft_s", {}).get("p50"),
        "ttft_p50_cold_s": cold.get("ttft_s", {}).get("p50"),
        "num_slots": engine_cfg.num_slots,
        "block_size": engine_cfg.block_size,
        "n_requests": len(trace),
    }


if __name__ == "__main__":
    import jax

    platform = jax.devices()[0].platform
    if len(sys.argv) > 1 and sys.argv[1] == "radix":
        result = run_radix(platform)
    else:
        result = run(platform)
    print(json.dumps(result, indent=2, default=float))
    sys.exit(0)
