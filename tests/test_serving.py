"""Continuous-batching serving engine (``accelerate_tpu/serving/``).

Host-side scheduling/accounting tests run in the tier-1 lane (no compiles);
engine end-to-end tests (token parity, chunked prefill, compile counting)
are compile-heavy and ride the slow lane like the generation suite.
"""

import numpy as np
import pytest

from accelerate_tpu.serving import (
    BlockAllocator,
    EngineConfig,
    InferenceEngine,
    Request,
    RequestState,
    SlotScheduler,
    blocks_needed,
)

# ---------------------------------------------------------------------------
# block freelist accounting (tier-1: pure host)
# ---------------------------------------------------------------------------


def test_allocator_accounting_no_leak():
    alloc = BlockAllocator(num_blocks=9)  # 8 usable + null
    assert alloc.free_count == 8
    a = alloc.allocate(3)
    b = alloc.allocate(5)
    assert alloc.free_count == 0 and alloc.allocated_count == 8
    assert not alloc.can_allocate(1)
    alloc.free(a)
    alloc.free(b)
    assert alloc.free_count == 8 and alloc.allocated_count == 0
    assert 0 not in a + b  # the null block is never handed out


def test_allocator_double_free_raises():
    alloc = BlockAllocator(num_blocks=4)
    blocks = alloc.allocate(2)
    alloc.free(blocks)
    with pytest.raises(ValueError, match="double free"):
        alloc.free(blocks)


def test_allocator_rejects_null_and_overdraft():
    alloc = BlockAllocator(num_blocks=4)
    with pytest.raises(ValueError, match="null block"):
        alloc.free([0])
    with pytest.raises(RuntimeError, match="out of KV blocks"):
        alloc.allocate(4)  # only 3 usable


def test_blocks_needed():
    assert blocks_needed(0, 8) == 0
    assert blocks_needed(1, 8) == 1
    assert blocks_needed(8, 8) == 1
    assert blocks_needed(9, 8) == 2


# ---------------------------------------------------------------------------
# scheduler admission / eviction (tier-1: pure host)
# ---------------------------------------------------------------------------


def _sched(num_slots=2, num_blocks=9, block_size=8, max_seq=32):
    return SlotScheduler(num_slots, BlockAllocator(num_blocks), block_size, max_seq)


def test_scheduler_fcfs_admission_and_eviction():
    sched = _sched()
    reqs = [sched.submit(Request(prompt=[1] * 4, max_new_tokens=4)) for _ in range(3)]
    admitted = sched.admit()
    assert [r.request_id for r in admitted] == [r.request_id for r in reqs[:2]]
    assert sched.queue_depth == 1 and sched.occupancy == 1.0
    assert all(r.state is RequestState.PREFILL and r.blocks for r in admitted)

    # finishing slot 0 frees its blocks and opens the slot for request 3
    admitted[0].state = RequestState.FINISHED
    freed_blocks = list(admitted[0].blocks)
    evicted = sched.evict_finished()
    assert evicted == [reqs[0]] and admitted[0].blocks == []
    assert sched.allocator.can_allocate(len(freed_blocks))
    third = sched.admit()
    assert third == [reqs[2]] and reqs[2].slot == 0


def test_scheduler_admission_bounded_by_freelist():
    # 4 usable blocks; each request's prompt (9 tokens) + first decode
    # block needs ceil(10/8)=2 blocks → only two admissions fit the pool
    sched = _sched(num_slots=3, num_blocks=5, block_size=8, max_seq=32)
    for _ in range(3):
        sched.submit(Request(prompt=[1] * 9, max_new_tokens=4))
    admitted = sched.admit()
    assert len(admitted) == 2
    assert sched.queue_depth == 1  # head-of-line blocked on blocks, not slots


def test_scheduler_rejects_over_budget_request():
    sched = _sched(max_seq=16)
    with pytest.raises(ValueError, match="max_seq_len"):
        sched.submit(Request(prompt=[1] * 10, max_new_tokens=10))
    with pytest.raises(ValueError, match="empty prompt"):
        sched.submit(Request(prompt=[], max_new_tokens=2))


def test_scheduler_rejects_unadmittable_prompt():
    """A prompt whose admission footprint exceeds the whole pool must be
    rejected at submit() — queued forever, it would head-of-line block
    admit() and spin run_until_idle() for good."""
    sched = _sched(num_slots=2, num_blocks=4, block_size=8, max_seq=64)  # 3 usable
    with pytest.raises(ValueError, match="KV blocks"):
        sched.submit(Request(prompt=[1] * 40, max_new_tokens=4))


def test_grow_for_decode_capped_at_request_budget():
    """Burst lookahead must not demand blocks past the request's own
    prompt+max_new: under pool pressure that would truncate requests whose
    real remaining tokens already fit (review finding)."""
    sched = _sched(num_slots=1, num_blocks=3, block_size=8, max_seq=64)  # 2 usable
    req = sched.submit(Request(prompt=[1] * 8, max_new_tokens=4))
    (admitted,) = sched.admit()
    assert len(admitted.blocks) == 2
    req.prefill_pos = 8
    req.output_tokens = [1] * 3  # context 10, one token of budget left
    # a burst of 8 would reach position 18 (3 blocks) — but the budget ends
    # at 12, which the 2 allocated blocks already cover
    assert sched.grow_for_decode(req, tokens_ahead=8)
    assert len(req.blocks) == 2


def test_grow_for_decode_allocates_incrementally():
    sched = _sched(num_slots=1, num_blocks=9, block_size=8, max_seq=64)
    req = sched.submit(Request(prompt=[1] * 8, max_new_tokens=24))
    (admitted,) = sched.admit()
    assert len(admitted.blocks) == 2  # prompt block + first decode block
    req.prefill_pos = 8
    req.output_tokens = [1] * 9  # context 16 → next write crosses a boundary
    assert sched.grow_for_decode(req, tokens_ahead=1)
    assert len(req.blocks) == 3
    # a burst lookahead allocates the whole span it will write
    assert sched.grow_for_decode(req, tokens_ahead=16)
    assert len(req.blocks) == blocks_needed(16 + 16, 8)


# ---------------------------------------------------------------------------
# mesh sharding policy (tier-1: pure placement decisions, no compiles)
# ---------------------------------------------------------------------------


def _mesh4():
    import jax

    from accelerate_tpu.mesh import build_mesh
    from accelerate_tpu.utils.dataclasses import MeshPlugin

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs a >= 4-device (virtual) mesh")
    return build_mesh(MeshPlugin(dp=1, fsdp=2, tp=2), devices=devices[:4])


def test_paged_kv_sharding_policy():
    """The pool shards its folded head lanes over tp, whole kv heads per
    shard (K/V are produced tp-sharded by wk/wv), and falls back to
    replicated when tp doesn't divide the head count."""
    from jax.sharding import PartitionSpec as P

    from accelerate_tpu.parallel.sharding import paged_kv_sharding

    mesh = _mesh4()
    assert paged_kv_sharding(mesh, num_kv_heads=4).spec == P(None, None, None, "tp")
    assert paged_kv_sharding(mesh, num_kv_heads=3).spec == P()


# ---------------------------------------------------------------------------
# sharded-engine parity (the acceptance bar: mesh decode == single device)
# ---------------------------------------------------------------------------


def test_sharded_engine_matches_single_device(tiny_model):
    """Token-identical greedy output between the mesh-sharded engine
    (fsdp=2 x tp=2 over 4 virtual CPU devices) and the single-device
    engine, with the one-compiled-decode-executable contract still holding
    under GSPMD and zero leaked blocks."""
    mesh = _mesh4()
    geometry = dict(num_slots=3, block_size=8, max_seq_len=64, prefill_chunk=8,
                    decode_burst=2)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32) for n in (5, 12, 9)]
    budgets = [4, 7, 5]

    def run(mesh_arg):
        engine = InferenceEngine(tiny_model, EngineConfig(**geometry), mesh=mesh_arg)
        reqs = [engine.add_request(p, b) for p, b in zip(prompts, budgets)]
        engine.run_until_idle(max_iterations=5000)
        return engine, [list(r.output_tokens) for r in reqs]

    single_engine, single_tokens = run(None)
    sharded_engine, sharded_tokens = run(mesh)
    assert sharded_tokens == single_tokens
    stats = sharded_engine.stats()
    assert stats["decode_compiles"] == 1  # sharding never broke the contract
    assert stats["prefill_compiles"] == 1
    assert stats["allocated_blocks"] == 0
    assert stats["mesh"] == {"fsdp": 2, "tp": 2}
    assert single_engine.stats()["decode_compiles"] == 1
    # the pool really is distributed: each device holds 1/tp of the kv heads
    shard_shapes = {s.data.shape for s in sharded_engine._kp.addressable_shards}
    full = sharded_engine._kp.shape
    assert shard_shapes == {(*full[:3], full[3] // 2)}


# ---------------------------------------------------------------------------
# engine end-to-end (slow lane: compiles the tiny model)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig.tiny(vocab_size=64, hidden_size=32, layers=2, heads=4, seq=96)
    return LlamaForCausalLM.from_config(config, seed=0)


@pytest.mark.slow
@pytest.mark.parametrize("decode_burst", [1, 4])
def test_continuous_matches_static_greedy(tiny_model, decode_burst):
    """Token-for-token parity with generate(use_cache=True) for a mixed-
    length multi-request trace, across burst granularities, with exactly
    one decode executable and zero leaked blocks."""
    from accelerate_tpu.generation import generate

    engine = InferenceEngine(
        tiny_model,
        EngineConfig(num_slots=3, block_size=8, max_seq_len=64,
                     prefill_chunk=8, decode_burst=decode_burst),
    )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32) for n in (5, 11, 17, 3, 9)]
    reqs = [engine.add_request(p, max_new_tokens=3 + 4 * i) for i, p in enumerate(prompts)]
    done = engine.run_until_idle(max_iterations=5000)
    assert len(done) == len(reqs)
    for p, r in zip(prompts, reqs):
        ref = np.asarray(
            generate(tiny_model, p[None, :], max_new_tokens=r.max_new_tokens, use_cache=True)
        )[0]
        got = np.concatenate([p, np.asarray(r.output_tokens, np.int32)])
        np.testing.assert_array_equal(got, ref)
    stats = engine.stats()
    assert stats["decode_compiles"] == 1
    assert stats["allocated_blocks"] == 0
    # the radix cache (on by default) retains finished prompts' full
    # blocks; free + cached must still account for every usable block
    assert (
        stats["free_blocks"] + stats["cached_blocks"]
        == engine.allocator.num_blocks - 1
    )


@pytest.mark.slow
def test_compile_count_one_decode_executable_multi_wave(tiny_model):
    """Admission waves with different prompt/output geometry must reuse the
    same decode executable — the engine's core contract."""
    engine = InferenceEngine(
        tiny_model,
        EngineConfig(num_slots=2, block_size=8, max_seq_len=64, prefill_chunk=8),
    )
    rng = np.random.default_rng(1)
    for wave in ((4, 2), (13, 9), (21, 5), (7, 17)):
        plen, new = wave
        engine.add_request(rng.integers(0, 64, size=plen).astype(np.int32), new)
        engine.run_until_idle(max_iterations=5000)
    stats = engine.stats()
    assert stats["decode_compiles"] == 1
    assert stats["prefill_compiles"] == 1
    assert stats["completed"] == 4


@pytest.mark.slow
def test_chunked_prefill_matches_one_shot_logits(tiny_model):
    """Prefilling a prompt in chunks through the paged path yields the same
    last-token logits as the dense one-shot prefill (decode correctness
    then follows from the shared cached_attention)."""
    import jax.numpy as jnp

    model = tiny_model
    cfg = model.config
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 64, size=(1, 13)).astype(np.int32)

    dense = model.apply_fn(model.params, input_ids=ids, use_cache=True, max_cache_len=16)
    ref = np.asarray(dense["logits"][:, -1, :])

    bs, nb, mb = 8, 6, 4
    shape = (cfg.num_hidden_layers, nb, bs, cfg.num_key_value_heads * cfg.head_dim)
    pages = {"k": jnp.zeros(shape), "v": jnp.zeros(shape)}
    bt = np.zeros((1, mb), np.int32)
    bt[0, :2] = [1, 2]
    chunked = None
    for start in range(0, 16, 8):  # two chunks of 8 (last padded by 3)
        end = min(start + 8, 13)
        if start >= 13:
            break
        chunk = np.zeros((1, 8), np.int32)
        chunk[0, : end - start] = ids[0, start:end]
        mask = np.zeros((1, 8), bool)
        mask[0, : end - start] = True
        out = model.apply_fn(
            model.params, input_ids=chunk, paged_kv=pages, block_tables=bt,
            cache_positions=np.asarray([start], np.int32), paged_write_mask=mask,
        )
        pages = out["paged_kv"]
        chunked = np.asarray(out["logits"][0, (13 - 1) - start, :])[None] if end == 13 else chunked
    np.testing.assert_allclose(chunked, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_eos_finishes_early_and_matches_generate(tiny_model):
    from accelerate_tpu.generation import generate

    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 64, size=9).astype(np.int32)
    # pick the 3rd greedy token as the eos so the engine must stop early
    ref_free = np.asarray(generate(tiny_model, prompt[None, :], max_new_tokens=8, use_cache=True))[0]
    eos = int(ref_free[len(prompt) + 2])
    ref = np.asarray(
        generate(tiny_model, prompt[None, :], max_new_tokens=8, use_cache=True, eos_token_id=eos)
    )[0]

    engine = InferenceEngine(
        tiny_model,
        EngineConfig(num_slots=2, block_size=8, max_seq_len=64, prefill_chunk=8,
                     eos_token_id=eos),
    )
    req = engine.add_request(prompt, max_new_tokens=8)
    engine.run_until_idle(max_iterations=5000)
    assert req.finish_reason == "eos"
    got = np.concatenate([prompt, np.asarray(req.output_tokens, np.int32)])
    np.testing.assert_array_equal(got, ref[: len(got)])
    assert req.output_tokens[-1] == eos and len(req.output_tokens) < 8


@pytest.mark.slow
def test_pool_exhaustion_truncates_not_deadlocks(tiny_model):
    """A drained freelist force-finishes the victim with
    finish_reason="out_of_blocks" instead of stalling the engine."""
    engine = InferenceEngine(
        tiny_model,
        EngineConfig(num_slots=2, block_size=8, max_seq_len=64, prefill_chunk=8,
                     num_blocks=4),  # 3 usable blocks for 2 slots
    )
    r1 = engine.add_request(np.arange(8, dtype=np.int32), max_new_tokens=30)
    r2 = engine.add_request(np.arange(8, dtype=np.int32) + 1, max_new_tokens=30)
    done = engine.run_until_idle(max_iterations=5000)
    assert len(done) == 2
    reasons = {r.finish_reason for r in (r1, r2)}
    assert "out_of_blocks" in reasons
    assert engine.stats()["allocated_blocks"] == 0  # truncation still frees


@pytest.mark.slow
def test_stream_yields_tokens_incrementally(tiny_model):
    engine = InferenceEngine(
        tiny_model,
        EngineConfig(num_slots=2, block_size=8, max_seq_len=64, prefill_chunk=8,
                     decode_burst=2),
    )
    prompt = np.arange(6, dtype=np.int32)
    toks = list(engine.stream(prompt, max_new_tokens=7))
    assert len(toks) == 7
    from accelerate_tpu.generation import generate

    ref = np.asarray(generate(tiny_model, prompt[None, :], max_new_tokens=7, use_cache=True))[0]
    np.testing.assert_array_equal(np.asarray(toks, np.int32), ref[6:])


@pytest.mark.slow
def test_requires_paged_kv_flag():
    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    model = GPT2LMHeadModel.from_config(GPT2Config.tiny(layers=2, seq=64), seed=0)
    with pytest.raises(ValueError, match="supports_paged_kv"):
        InferenceEngine(model, EngineConfig(num_slots=2, max_seq_len=64))


@pytest.mark.slow
def test_serving_telemetry_rows_and_monitor(tiny_model, tmp_path):
    """The engine's telemetry rows land in the JSONL trail and surface in
    the monitor snapshot/rendering (serving health end-to-end)."""
    from accelerate_tpu.diagnostics.monitor import collect_status, render_status
    from accelerate_tpu.telemetry import TelemetryRecorder, set_active_recorder

    recorder = TelemetryRecorder(logging_dir=str(tmp_path))
    set_active_recorder(recorder)
    try:
        engine = InferenceEngine(
            tiny_model,
            EngineConfig(num_slots=2, block_size=8, max_seq_len=64,
                         prefill_chunk=8, stats_interval=2),
        )
        rng = np.random.default_rng(4)
        for i in range(3):
            engine.add_request(rng.integers(0, 64, size=5 + i).astype(np.int32), 4)
        engine.run_until_idle(max_iterations=5000)
    finally:
        set_active_recorder(None)
        recorder.close()

    kinds = [r.get("kind") for r in recorder.records if r.get("type") == "serving"]
    assert "request" in kinds and "step" in kinds
    req_rows = [
        r for r in recorder.records
        if r.get("type") == "serving" and r.get("kind") == "request"
    ]
    assert len(req_rows) == 3
    assert all(r["ttft_s"] is not None and r["new_tokens"] == 4 for r in req_rows)

    status = collect_status(str(tmp_path))
    assert status["serving"] is not None
    assert status["serving"]["completed"] == 3
    assert status["serving"]["decode_compiles"] == 1
    assert "serving:" in render_status(status)


# ---------------------------------------------------------------------------
# quantized KV cache: the kv_dtype parity matrix
# {bf16, int8, fp8} x {dense-equivalence, prefix-hit/CoW, swap round-trip,
# sharded mesh}. Tolerances here are THE documented numbers
# (docs/source/usage_guides/serving.md); within one engine a kv_dtype is
# deterministic, so the sharing/swap/mesh legs assert token-identity.
# ---------------------------------------------------------------------------

#: |paged last-token logits - dense decode logits| ceiling per kv_dtype on
#: the tiny f32 model (storage rounding only — same attention math)
KV_LOGIT_ATOL = {"bf16": 0.06, "int8": 0.12, "fp8": 0.35}

KV_DTYPES = ("bf16", "int8", "fp8")


def test_engine_kv_stats_and_capacity_math(tiny_model):
    """stats() carries the kv_dtype policy rows, and the byte math is the
    documented formula: 2 pools x layers x n_kv x (hd x itemsize + 4-byte
    scale when quantized)."""
    cfg = tiny_model.config
    expect = {
        "auto": 2 * cfg.num_hidden_layers * cfg.num_key_value_heads * cfg.head_dim * 4,
        "bf16": 2 * cfg.num_hidden_layers * cfg.num_key_value_heads * cfg.head_dim * 2,
        "int8": 2 * cfg.num_hidden_layers * cfg.num_key_value_heads * (cfg.head_dim + 4),
    }
    for kv_dtype, bytes_per_token in expect.items():
        eng = InferenceEngine(
            tiny_model,
            EngineConfig(num_slots=2, block_size=8, max_seq_len=64,
                         kv_dtype=kv_dtype),
        )
        st = eng.stats()
        assert st["kv_bytes_per_token"] == bytes_per_token
        assert st["kv_bytes_per_block"] == bytes_per_token * 8
        assert st["kv_slot_capacity"] == 2  # full residency: both slots fit
        has_scales = eng._ks is not None
        assert has_scales == (kv_dtype == "int8")
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        InferenceEngine(
            tiny_model, EngineConfig(num_slots=2, max_seq_len=64, kv_dtype="int4")
        )


@pytest.mark.parametrize("spec_k", [0, 2])
def test_paged_entries_counters_follow_the_rows_lengths(tiny_model, spec_k, monkeypatch):
    """``paged_entries_walked_total`` / ``paged_entries_table_total`` move, at
    every dispatch, by what the rows' lengths give: a prefill chunk walks its
    one row up to the chunk's last position, a decode burst walks each live
    row up to its context at each step and one entry of every free slot, all
    times the model's layers — while contexts grow over block edges across
    several dispatches of the ONE decode executable. A speculative round is
    ``spec_k`` single-query steps of the one-layer draft and one verify
    forward of ``spec_k + 1`` queries through both layers.
    ``paged_tiles_walked_total`` moves by the same rows' softmax steps: a
    row's entries over the kernel's tile, rounded up (the tile set to two
    entries here, so that a row of three takes two steps)."""
    import importlib

    tile = 2
    monkeypatch.setattr(importlib.import_module("accelerate_tpu.ops.paged_attention"),
                        "_TILE", tile)
    bs, chunk, burst, slots, layers = 8, 8, 2, 3, 2
    engine = InferenceEngine(tiny_model, EngineConfig(
        num_slots=slots, block_size=bs, max_seq_len=64, prefill_chunk=chunk,
        decode_burst=burst, prefix_cache=False, spec_k=spec_k, draft="early_exit:1",
    ))
    mb = engine.config.blocks_per_slot
    assert mb == 8 and engine.stats()["paged_entries_table_total"] == 0

    # what was dispatched, read off the executables' own operands
    seen = {"prefill": [], "decode": []}

    def recorded(kind, fn, position_arg):
        def call(*args):
            seen[kind].append(np.array(args[position_arg]))
            return fn(*args)
        return call

    engine._prefill_fn = recorded("prefill", engine._prefill_fn, 3)
    engine._decode_fn = recorded("decode", engine._decode_fn, 3)
    rng = np.random.default_rng(3)
    requests = [
        engine.add_request(rng.integers(0, 64, size=n).astype(np.int32), new)
        for n, new in ((11, 9), (5, 14))
    ]
    engine.run_until_idle(max_iterations=500)
    assert [len(r.output_tokens) for r in requests] == [9, 14]

    # the prompts' chunks: 11 tokens start at 0 and 8, 5 tokens at 0
    assert sorted(int(p[0]) for p in seen["prefill"]) == [0, 0, 8]
    walked = layers * sum((int(p[0]) + chunk - 1) // bs + 1 for p in seen["prefill"])
    tiles = layers * sum(-(-((int(p[0]) + chunk - 1) // bs + 1) // tile) for p in seen["prefill"])
    table = layers * len(seen["prefill"]) * mb
    # several decode dispatches, a request's context growing by the burst
    # from its prompt's length, over a block's edge (11 -> 17: entries 2 -> 3)
    firsts = [[int(pos0[slot]) for pos0 in seen["decode"] if pos0[slot]]
              for slot in range(slots)]
    if not spec_k:
        assert sorted(firsts) == [[], [5, 7, 9, 11, 13, 15, 17], [11, 13, 15, 17]]
    assert all(len(f) > 2 and f == sorted(set(f)) for f in firsts if f)
    # (single-query steps, layers they run through), then (queries, layers)
    # of one more call from the same positions
    calls = [(spec_k, 1, 1), (1, spec_k + 1, layers)] if spec_k else [(burst, 1, layers)]
    for pos0 in seen["decode"]:
        assert pos0.shape == (slots,)
        for steps, queries, depth in calls:
            for step in range(steps):
                rows = [int(p + step + queries - 1) // bs + 1 for p in pos0]  # a free slot: 1
                walked += depth * sum(rows)
                tiles += depth * sum(-(-n // tile) for n in rows)
            table += depth * steps * slots * mb
    stats = engine.stats()
    assert stats["decode_compiles"] == 1 and stats["prefill_compiles"] == 1
    assert stats["paged_entries_walked_total"] == walked
    assert stats["paged_entries_table_total"] == table
    assert stats["paged_tiles_walked_total"] == tiles
    assert stats["paged_tile_entries"] == tile  # what a reader divides by: the kernel's own
    assert 0 < walked < table and walked / tile <= tiles < walked
    engine.reset_stats()
    assert engine.stats()["paged_entries_walked_total"] == 0
    assert engine.stats()["paged_tiles_walked_total"] == 0


@pytest.mark.parametrize("spec_k", [0, 2])
def test_step_programs_get_a_copy_of_the_block_tables(tiny_model, spec_k):
    """The CPU backend takes an aligned numpy operand without copying it, so
    a chunk or a round still in flight reads what the host writes there
    next — and ``_sync_block_table`` zeroes a row before it refills it. No
    step program is handed the mirror itself."""
    engine = InferenceEngine(tiny_model, EngineConfig(
        num_slots=3, block_size=8, max_seq_len=64, prefill_chunk=8, decode_burst=2,
        spec_k=spec_k, draft="early_exit:1",
    ))
    handed = []

    def recorded(fn):
        def call(*args):
            handed.append(args[2])
            return fn(*args)
        return call

    engine._prefill_fn = recorded(engine._prefill_fn)
    engine._decode_fn = recorded(engine._decode_fn)
    rng = np.random.default_rng(5)
    for n in (19, 6):
        engine.add_request(rng.integers(0, 64, size=n).astype(np.int32), 6)
    engine.run_until_idle(max_iterations=500)
    assert {t.shape for t in handed} == {(1, 8), (3, 8)}  # chunks' rows, rounds' tables
    assert not any(np.shares_memory(t, engine._block_tables) for t in handed)


def test_swap_pool_quantized_scales_byte_exact():
    """A quantized SwapPool round-trips payload AND f32 scale rows
    byte-exactly (a quantized block without its exact scales is garbage),
    and prices both into bytes_per_block."""
    from accelerate_tpu.serving import SwapPool

    shape = (2, 4, 2, 8)  # layers, bs, n_kv, hd
    per_block = 2 * int(np.prod(shape)) + 2 * 4 * int(np.prod(shape[:-1]))
    pool = SwapPool(num_layers=2, block_size=4, num_kv_heads=2, head_dim=8,
                    dtype=np.int8, capacity_gb=2 * per_block / (1 << 30),
                    quantized=True)
    assert pool.bytes_per_block == per_block
    assert pool.capacity_blocks == 2
    rng = np.random.default_rng(0)
    k = rng.integers(-127, 128, size=shape).astype(np.int8)
    v = rng.integers(-127, 128, size=shape).astype(np.int8)
    ks = rng.random(shape[:-1]).astype(np.float32)
    vs = rng.random(shape[:-1]).astype(np.float32)
    h = pool.store(k, v, ks, vs)
    k2, v2, ks2, vs2 = pool.load(h)
    np.testing.assert_array_equal(k, k2)
    np.testing.assert_array_equal(v, v2)
    np.testing.assert_array_equal(ks, ks2)  # byte-exact, not allclose
    np.testing.assert_array_equal(vs, vs2)
    with pytest.raises(ValueError, match="needs scale rows"):
        pool.store(k, v)


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_kv_dtype_paged_logits_match_dense(tiny_model, kv_dtype):
    """Dense-equivalence leg: chunk-prefilling through a quantized pool
    yields last-token logits within the documented tolerance of the dense
    one-shot prefill (the acceptance bar's logit contract)."""
    import jax.numpy as jnp

    from accelerate_tpu.ops.fp8 import kv_storage_dtype

    model = tiny_model
    cfg = model.config
    rng = np.random.default_rng(11)
    ids = rng.integers(0, 64, size=(1, 13)).astype(np.int32)
    dense = model.apply_fn(model.params, input_ids=ids, use_cache=True, max_cache_len=16)
    ref = np.asarray(dense["logits"][:, -1, :], np.float32)

    store_dtype, quantized = kv_storage_dtype(kv_dtype)
    bs, nb, mb = 8, 6, 4
    shape = (cfg.num_hidden_layers, nb, bs, cfg.num_key_value_heads * cfg.head_dim)
    pages = {"k": jnp.zeros(shape, store_dtype), "v": jnp.zeros(shape, store_dtype)}
    if quantized:
        scale_shape = (*shape[:-1], cfg.num_key_value_heads)
        pages["k_scale"] = jnp.ones(scale_shape, jnp.float32)
        pages["v_scale"] = jnp.ones(scale_shape, jnp.float32)
    bt = np.zeros((1, mb), np.int32)
    bt[0, :2] = [1, 2]
    got = None
    for start in range(0, 16, 8):
        end = min(start + 8, 13)
        if start >= 13:
            break
        chunk = np.zeros((1, 8), np.int32)
        chunk[0, : end - start] = ids[0, start:end]
        mask = np.zeros((1, 8), bool)
        mask[0, : end - start] = True
        out = model.apply_fn(
            model.params, input_ids=chunk, paged_kv=pages, block_tables=bt,
            cache_positions=np.asarray([start], np.int32), paged_write_mask=mask,
        )
        pages = out["paged_kv"]
        if quantized:
            assert "k_scale" in pages and "v_scale" in pages
        if end == 13:
            got = np.asarray(out["logits"][0, (13 - 1) - start, :], np.float32)[None]
    assert np.abs(got - ref).max() < KV_LOGIT_ATOL[kv_dtype]


@pytest.mark.slow
def test_kv_bf16_greedy_token_identical_to_generate():
    """At kv_dtype="bf16" on a bf16 model the engine's greedy output stays
    token-identical to generate(use_cache=True) — bf16 storage is a cast,
    not a quantization, so the PR 4 parity contract survives the fused
    kernel unchanged."""
    import jax.numpy as jnp

    from accelerate_tpu.generation import generate
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig.tiny(vocab_size=64, hidden_size=32, layers=2, heads=4, seq=96)
    model = LlamaForCausalLM.from_config(config, seed=0, dtype=jnp.bfloat16)
    engine = InferenceEngine(
        model,
        EngineConfig(num_slots=3, block_size=8, max_seq_len=64, prefill_chunk=8,
                     kv_dtype="bf16"),
    )
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32) for n in (5, 11, 17)]
    reqs = [engine.add_request(p, max_new_tokens=8) for p in prompts]
    engine.run_until_idle(max_iterations=5000)
    for p, r in zip(prompts, reqs):
        ref = np.asarray(
            generate(model, p[None, :], max_new_tokens=8, use_cache=True)
        )[0]
        np.testing.assert_array_equal(
            np.concatenate([p, np.asarray(r.output_tokens, np.int32)]), ref
        )
    assert engine.stats()["decode_compiles"] == 1


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_kv_dtype_prefix_hit_and_cow_parity(tiny_model, kv_dtype):
    """Prefix-hit/CoW leg: a warm engine serving a shared-prefix prompt
    (full-block hit + partial-block CoW divergence) emits the same tokens
    as a cold engine at the same kv_dtype — adopted quantized blocks and
    CoW copies reuse the exact stored bytes + scales, so within one
    kv_dtype the cache is invisible."""
    def run(warm):
        eng = InferenceEngine(
            tiny_model,
            EngineConfig(num_slots=2, block_size=8, max_seq_len=64,
                         prefill_chunk=8, kv_dtype=kv_dtype, prefix_cache=warm),
        )
        base = np.arange(20, dtype=np.int32) % 60
        r1 = eng.add_request(base, 6)
        eng.run_until_idle(max_iterations=5000)
        # full-block hit (same 16-token prefix) + mid-block divergence
        shared = np.concatenate([base[:19], np.asarray([61], np.int32)])
        r2 = eng.add_request(shared, 6)
        eng.run_until_idle(max_iterations=5000)
        return eng, r1.output_tokens, r2.output_tokens

    warm_eng, w1, w2 = run(True)
    _, c1, c2 = run(False)
    assert (w1, w2) == (c1, c2)
    st = warm_eng.stats()
    assert st["prefix_hit_tokens"] > 0  # the warm leg really hit the cache
    assert st["decode_compiles"] == 1


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_kv_dtype_swap_round_trip_parity(tiny_model, kv_dtype):
    """Swap leg: under pool pressure with the host swap tier on, both
    requests complete un-truncated and token-identical to a
    full-residency run at the same kv_dtype — quantized payload + scale
    rows survived swap-out -> swap-in exactly."""
    geom = dict(num_slots=2, block_size=8, max_seq_len=64, prefill_chunk=8,
                prefix_cache=False, kv_dtype=kv_dtype)
    prompts = [np.arange(8, dtype=np.int32), np.arange(8, dtype=np.int32) + 1]

    def run(num_blocks=None, swap_gb=0.0):
        eng = InferenceEngine(
            tiny_model, EngineConfig(num_blocks=num_blocks, swap_gb=swap_gb, **geom)
        )
        reqs = [eng.add_request(p, max_new_tokens=30) for p in prompts]
        eng.run_until_idle(max_iterations=5000)
        return eng.stats(), reqs

    swap_stats, swapped = run(num_blocks=6, swap_gb=0.01)
    assert [r.finish_reason for r in swapped] == ["length", "length"]
    assert swap_stats["preemptions"] >= 1
    assert swap_stats["swapped_out_blocks"] == swap_stats["swapped_in_blocks"] > 0
    assert swap_stats["decode_compiles"] == 1
    _, full = run()
    for s, f in zip(swapped, full):
        assert s.output_tokens == f.output_tokens


@pytest.mark.slow
def test_kv_int8_sharded_mesh_parity(tiny_model):
    """Sharded-mesh leg: the int8 engine over fsdp=2 x tp=2 is
    token-identical to the single-device int8 engine, the scale arrays
    shard their kv-head dim alongside the pools, and the
    one-decode-executable contract holds."""
    mesh = _mesh4()
    geometry = dict(num_slots=3, block_size=8, max_seq_len=64, prefill_chunk=8,
                    decode_burst=2, kv_dtype="int8")
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32) for n in (5, 12, 9)]

    def run(mesh_arg):
        engine = InferenceEngine(tiny_model, EngineConfig(**geometry), mesh=mesh_arg)
        reqs = [engine.add_request(p, b) for p, b in zip(prompts, (4, 7, 5))]
        engine.run_until_idle(max_iterations=5000)
        return engine, [list(r.output_tokens) for r in reqs]

    _, single_tokens = run(None)
    sharded, sharded_tokens = run(mesh)
    assert sharded_tokens == single_tokens
    assert sharded.stats()["decode_compiles"] == 1
    full = sharded._ks.shape
    shard_shapes = {s.data.shape for s in sharded._ks.addressable_shards}
    assert shard_shapes == {(*full[:3], full[3] // 2)}
