"""SmallThinker through the serving engine (ISSUE 44): layers that attend the
whole context with no position term beside layers that rotate and see a
sliding window - two cache kinds, a pool and a block table each, the window
kind's blocks given back behind the window -, a router that reads the layer's
input ahead of attention, and ReLU-gated experts; held against the plain
reference of ``perfbench/reference/smallthinker.py``: float32 at ``highest``,
a masked softmax over the whole sequence, every expert over every token, no
cache, nothing shared with the program.

All on the CPU at a small size with seeded weights (``perfbench.weights``,
the recipe the benchmark's check uses). Tolerances, each with its reason, are
beside the comparison they belong to.
"""

import functools
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import accelerate_tpu.models.smallthinker as st  # noqa: E402
from accelerate_tpu.big_modeling import init_empty_weights  # noqa: E402
from accelerate_tpu.models import (  # noqa: E402
    KNOWN_MODEL_TYPES,
    config_from_hf_json,
    model_factory_for_config,
)
from accelerate_tpu.models.cache import CacheSpec, PagedKind  # noqa: E402
from accelerate_tpu.ops.moe import expert_ffn, route  # noqa: E402
from accelerate_tpu.ops.paged_attention import (  # noqa: E402
    paged_attention,
    _CHUNK_TILE,
    tile_entries,
    tiles_walked,
    window_walk,
)
from accelerate_tpu.serving import EngineConfig, InferenceEngine  # noqa: E402
from accelerate_tpu.serving.blocks import blocks_needed  # noqa: E402
from accelerate_tpu.serving.sampling import SamplingParams  # noqa: E402
from accelerate_tpu.serving.scheduler import RequestState  # noqa: E402
from perfbench import weights  # noqa: E402
from perfbench.reference import smallthinker as reference  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONFIG_FILE = os.path.join(ROOT, "perfbench", "configs", "smallthinker-21b-a3b-serve-v5e1.json")
SEED = 13
#: a window of 10 positions over blocks of 4: not a multiple of the block, so
#: the window's edge falls inside a block
WINDOW, BLOCK, CHUNK, BURST = 10, 4, 16, 4
#: attention scores with a spread, as the benchmark's file: with no position
#: term a full layer's scores are flat under the plain recipe
SCALES = {"layers.full.wq": 3.0, "layers.window.wq": 3.0}
REFERENCE_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "moe_ffn_hidden_size", "moe_num_primary_experts",
    "moe_num_active_primary_experts", "sliding_window_size", "rope_theta", "rms_norm_eps",
)

# float32 against float32 at ``highest``: what is left is the order of
# summation (the kernel's walk over blocks and tiles against one softmax over
# the sequence, the grouped product against plain products). Over these
# sequences a sound run reads 2e-6 and under; each planted fault below reads
# 1e-3 and more
LOGPROB_TOLERANCE = 3e-5


def _reference_config(c, **changed) -> dict:
    return {**{k: getattr(c, k) for k in REFERENCE_KEYS},
            "rope_layout": list(c.rope_layout),
            "sliding_window_layout": list(c.sliding_window_layout),
            "weight_scales": SCALES, **changed}


def _model(**kw):
    c = st.SmallThinkerConfig.tiny(sliding_window_size=WINDOW, **kw)
    with init_empty_weights():
        model = st.SmallThinkerForCausalLM.from_config(c)
    model.params = weights.make_tree(SEED, model.params, dtype=jnp.float32, scales=SCALES)
    return model, c


@pytest.fixture(scope="module")
def tiny():
    return _model()


def _engine(model, **kw):
    geometry = dict(num_slots=4, max_seq_len=128, prefill_chunk=CHUNK, block_size=BLOCK,
                    logprobs_topn=1, decode_burst=BURST)
    geometry.update(kw)
    return InferenceEngine(model, EngineConfig(**geometry))


def _ask(engine, prompt, new_tokens=12):
    return engine.add_request(list(prompt), new_tokens, sampling=SamplingParams(logprobs=1))


def _reference_logprobs(cfg, request):
    ids = np.asarray(request.prompt + request.output_tokens[:-1], np.int32)
    rows = np.arange(len(request.prompt) - 1, len(ids))
    padded = np.zeros((128,), np.int32)
    padded[: len(ids)] = ids
    logits = np.asarray(reference.logits_at(cfg, SEED, padded, len(ids), rows, "float32"),
                        np.float64)
    top = logits.max(-1, keepdims=True)
    logp = logits - (top + np.log(np.exp(logits - top).sum(-1, keepdims=True)))
    served = np.asarray(request.output_tokens)
    return logp[np.arange(len(rows)), served], logits.argmax(-1) == served


def _reported(request):
    return np.asarray([e["logprob"] for e in request.logprobs])


# -- the whole-sequence forward and the cached step against the reference --------


@pytest.mark.parametrize("impl", ["blockwise", "reference", "flash"])
def test_apply_agrees_with_the_plain_reference(tiny, impl):
    """``apply`` (whole sequences: what ``generate()`` and training call) at a
    context nine times the window, on the dispatcher's three routes (the flash
    kernels in the interpreter), against the reference's logits at every
    position: 3e-5 of a logit, float32 against float32 (reads 4e-6)."""
    from accelerate_tpu.ops.attention import attention_context

    model, c = tiny
    ids = np.random.default_rng(2).integers(0, 256, size=(1, 96)).astype(np.int32)
    with attention_context(impl=impl, block_q=32, block_kv=128):
        got = np.asarray(model.apply_fn(model.params, input_ids=ids)["logits"][0])
    want = np.asarray(reference.logits_at(
        _reference_config(c), SEED, ids[0], 96, np.arange(96), "float32"))
    assert np.abs(got - want).max() < 3e-5


def test_the_loss_is_the_cross_entropy_of_the_logits_and_has_a_gradient(tiny):
    model, _ = tiny
    ids = np.arange(48, dtype=np.int32).reshape(2, 24)
    out = model.apply_fn(model.params, input_ids=ids, labels=ids)
    logp = jax.nn.log_softmax(out["logits"][:, :-1].astype(jnp.float32), axis=-1)
    want = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1).mean()
    assert abs(float(out["loss"]) - float(want)) < 1e-5
    grads = jax.grad(lambda p: model.apply_fn(p, input_ids=ids, labels=ids)["loss"])(model.params)
    for kind in st.KINDS:
        assert float(jnp.abs(grads["layers"][kind]["gate"]).max()) > 0  # through the weights


#: prompt -> new tokens. 3 + 6 never reaches the window (9 positions); 7 + 12
#: crosses it inside a decode burst; 16 and 32 end on a chunk's edge; 37, 50
#: and 77 cross it inside their first chunk and run chunks wholly past it.
#: Seven requests over four slots, so slots and window blocks are reused
PROMPTS = {3: 6, 7: 12, 16: 12, 37: 12, 50: 12, 32: 12, 77: 12}


@pytest.fixture(scope="module", params=["lax", "gather", "pallas"])
def served(request, tiny):
    model, c = tiny
    impl = request.param
    mp = pytest.MonkeyPatch()
    mp.setattr(sys.modules["accelerate_tpu.ops.paged_attention"], "paged_attention",
               functools.partial(paged_attention, impl=impl, interpret=True))
    try:
        engine = _engine(model)
        rng = np.random.default_rng(0)
        requests = {n: _ask(engine, rng.integers(0, 256, size=n).tolist(), new)
                    for n, new in PROMPTS.items()}
        engine.run_until_idle()
    finally:
        mp.undo()
    return engine, requests, _reference_config(c)


@pytest.mark.parametrize("prompt_len", list(PROMPTS))
def test_chunked_prefill_then_decode_through_both_pools_agree_with_the_full_forward(
        served, prompt_len):
    """The engine's chunks of 16 and bursts of 4 one-token steps over blocks
    of 4, the full kind's table walked whole and the window kind's from the
    entry that holds the oldest visible position (its blocks behind that given
    back), on each route of ``paged_attention``, against ONE full forward of
    the reference over prompt and served tokens, at every served position."""
    _, requests, cfg = served
    r = requests[prompt_len]
    assert len(r.output_tokens) == PROMPTS[prompt_len]
    want, is_best = _reference_logprobs(cfg, r)
    assert is_best.all()
    assert np.abs(_reported(r) - want).max() < LOGPROB_TOLERANCE


def test_one_decode_and_one_prefill_executable_and_the_pools_are_back(served):
    engine, _, _ = served
    s = engine.stats()
    assert (s["decode_compiles"], s["prefill_compiles"]) == (1, 1)
    assert s["kv_kinds"] == 2 and s["prefix_cache"] is False
    assert "keep a window of the past" in s["prefix_cache_off_reason"]
    # every block of both kinds is back where it came from
    assert engine.allocator.allocated_count == 0
    assert s["allocated_blocks_window"] == 0
    assert s["free_blocks_window"] == s["window_num_blocks"] - 1


# -- the tolerance holds the mechanisms out ---------------------------------------


@pytest.fixture(scope="module")
def long_request(tiny):
    """One request far past the window, served on the default route."""
    model, c = tiny
    engine = _engine(model)
    r = _ask(engine, np.random.default_rng(3).integers(0, 256, size=61).tolist(), 16)
    engine.run_until_idle()
    return r, c


def test_the_sound_reference_reads_under_the_tolerance(long_request):
    r, c = long_request
    want, is_best = _reference_logprobs(_reference_config(c), r)
    assert is_best.all() and np.abs(_reported(r) - want).max() < LOGPROB_TOLERANCE


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_reference_with_a_planted_fault_fails_the_tolerance(long_request, fault):
    """Each mechanism shows in the served log-probabilities by far more than
    the tolerance: a reference that ignores the window, one whose window is a
    position too long, one that rotates the full layers, one that routes on
    the normed residual AFTER attention, one with ``silu`` for ``relu``, one
    that drops each token's last choice."""
    r, c = long_request
    want, _ = _reference_logprobs(_reference_config(c, _fault=fault), r)
    assert np.abs(_reported(r) - want).max() > 30 * LOGPROB_TOLERANCE


def test_fp8_pools_move_the_log_probabilities_far_more_than_the_tolerance(tiny, long_request):
    """The benchmark's control: ``kv_dtype="fp8"`` quantizes BOTH kinds'
    pools, each with its scales beside it."""
    model, c = tiny
    r, _ = long_request
    narrow = _engine(model, kv_dtype="fp8")
    b = _ask(narrow, r.prompt, 16)
    narrow.run_until_idle()
    assert set(narrow._cache) == {"k", "v", "k_scale", "v_scale", "k_window", "v_window",
                                  "k_scale_window", "v_scale_window"}
    assert narrow._cache["k_window"].dtype == jnp.float8_e4m3fn
    assert narrow._cache["k_scale_window"].shape == (*narrow._cache["k_window"].shape[:3], 2)
    n = min(i for i, (x, y) in enumerate(zip(r.output_tokens + [-1], b.output_tokens + [-2]))
            if x != y)  # the tokens in common: the same conditioning
    moved = np.abs(_reported(r)[:max(n, 1)] - _reported(b)[:max(n, 1)]).max()
    assert moved > 100 * LOGPROB_TOLERANCE


# -- the allocator: a window kind's blocks -----------------------------------------


def test_a_window_kinds_blocks_stay_under_the_bound_and_go_back_as_counted(tiny):
    """Over a long generation the window kind holds at most ``ceil((window -
    1 + chunk) / bs) + 1`` blocks a slot, the full kind's grow with the
    context; what the window kind gave back while the request ran is what
    ``window_blocks_freed_total`` says; and no block goes back (or is handed
    out again) while a round in flight can still read it."""
    model, c = tiny
    engine = _engine(model, num_slots=2, max_seq_len=160)
    bound = -(-(WINDOW - 1 + CHUNK) // BLOCK) + 1
    assert engine.window_blocks_per_slot == {"window": bound}
    assert engine.window_num_blocks == {"window": 2 * bound + 1}
    alloc = engine.window_allocators["window"]
    handed, in_flight = [], {}

    def readable():
        """Blocks a window layer of the round in flight may read."""
        if engine._inflight is None or not in_flight:
            return set()
        out = set()
        for req in engine._inflight.live:
            pos = int(in_flight["pos0"][req.slot])
            lo, hi = max(pos - WINDOW + 1, 0) // BLOCK, (pos + BURST - 1) // BLOCK
            out |= {int(b) for b in in_flight["tables"][req.slot, 1, lo:hi + 1] if b}
        return out

    real_free, real_allocate, real_decode = alloc.free, alloc.allocate, engine._decode_fn

    def free(blocks):
        assert not set(blocks) & readable()
        return real_free(blocks)

    def allocate(n):
        got = real_allocate(n)
        assert not set(got) & readable()
        handed.extend(got)
        return got

    def decode(params, cache, tables, pos0, *rest):
        in_flight.update(tables=np.array(tables), pos0=np.array(pos0))
        return real_decode(params, cache, tables, pos0, *rest)

    alloc.free, alloc.allocate, engine._decode_fn = free, allocate, decode
    rng = np.random.default_rng(5)
    a = _ask(engine, rng.integers(0, 256, size=45).tolist(), 90)
    b = _ask(engine, rng.integers(0, 256, size=9).tolist(), 70)
    held_at_end, full_held = {}, []
    while engine.scheduler.has_work() or engine._inflight is not None:
        engine.step()
        for r in (a, b):
            assert len(r.window_blocks.get("window", {})) <= bound
            if r.state is RequestState.FINISHED and r.window_blocks:
                held_at_end[r.request_id] = len(r.window_blocks["window"])
        if a.state is RequestState.DECODE:
            full_held.append(len(a.blocks))
    assert (len(a.output_tokens), len(b.output_tokens)) == (90, 70)
    # the full kind's blocks grow with the context, to the whole of it
    assert full_held == sorted(full_held) and full_held[-1] == blocks_needed(45 + 90 - 1, BLOCK)
    s = engine.stats()
    assert s["window_blocks_freed_total"] == len(handed) - sum(held_at_end.values()) > 30
    assert alloc.allocated_count == 0 and alloc.free_count == 2 * bound
    for r in (a, b):
        want, is_best = _reference_logprobs(_reference_config(c), r) if len(
            r.prompt) + len(r.output_tokens) <= 128 else (None, None)
        if want is not None:
            assert is_best.all() and np.abs(_reported(r) - want).max() < LOGPROB_TOLERANCE


def test_a_preempted_request_gives_both_kinds_back_and_continues_with_the_same_tokens(tiny):
    """A full-kind pool too small for three growing requests (no swap tier
    for a model with a window kind): one gives its blocks of BOTH kinds back,
    re-queues, and is prefilled again over prompt and emitted tokens; what it
    serves is what it serves alone."""
    model, c = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (30, 27, 25)]
    alone = []
    for p in prompts:
        engine = _engine(model, max_seq_len=96)
        alone.append(_ask(engine, p, 40))
        engine.run_until_idle()
    engine = _engine(model, num_slots=3, num_blocks=34, max_seq_len=96)
    given_back = []
    real = engine.scheduler.release_window_blocks
    engine.scheduler.release_window_blocks = lambda req: given_back.append(
        (req.state, real(req))) or given_back[-1][1]
    requests = [_ask(engine, p, 40) for p in prompts]
    engine.run_until_idle()
    s = engine.stats()
    assert s["preemptions"] >= 1 and s["out_of_blocks_total"] == 0 and s["decode_compiles"] == 1
    # a victim (still decoding when it was taken out) gave window blocks back
    assert any(state is not RequestState.FINISHED and n > 0 for state, n in given_back)
    assert engine.window_allocators["window"].allocated_count == 0
    for r, lone in zip(requests, alone):
        assert r.output_tokens == lone.output_tokens and r.finish_reason == "length"
        want, is_best = _reference_logprobs(_reference_config(c), r)
        assert is_best.all() and np.abs(_reported(r) - want).max() < LOGPROB_TOLERANCE


# -- stats(): the new counters against a hand count ---------------------------------


def test_stats_count_the_walk_by_kind_as_the_positions_say(tiny):
    model, c = tiny
    engine = _engine(model, num_slots=2, max_seq_len=128)
    seen = {"prefill": [], "decode": []}

    def recorded(kind, fn):
        def call(*args):
            seen[kind].append(np.array(args[3]))
            return fn(*args)
        return call

    engine._prefill_fn = recorded("prefill", engine._prefill_fn)
    engine._decode_fn = recorded("decode", engine._decode_fn)
    request = _ask(engine, np.random.default_rng(5).integers(0, 256, size=70).tolist(), 12)
    engine.run_until_idle()
    assert len(request.output_tokens) == 12 and len(seen["prefill"]) == 5

    mb, n_full, n_window = 32, 2, 3
    rows = [(int(p[0]), CHUNK) for p in seen["prefill"]]
    rows += [(int(p) + step, 1) for pos0 in seen["decode"] for step in range(BURST) for p in pos0]
    full = window = behind = tiles = 0
    for first, queries in rows:
        end = min((first + queries - 1) // BLOCK + 1, mb)
        lo = min(max(first - WINDOW + 1, 0) // BLOCK, end)
        full += n_full * end
        window += n_window * (end - lo)
        behind += n_window * lo
        tiles += n_full * -(-end // 8) + n_window * (-(-end // 8) - lo // 8)
    s = engine.stats()
    assert s["paged_entries_walked_full_total"] == full
    assert s["paged_entries_walked_window_total"] == window
    assert s["paged_entries_behind_window_total"] == behind > window
    assert s["paged_entries_walked_total"] == full + window
    assert s["paged_tiles_walked_total"] == tiles
    # chunks of 16 queries x 2 heads a kv head and decode rows: one block of rows each
    assert s["paged_chunk_steps_total"] == 0
    assert s["paged_entries_table_total"] == len(rows) * mb * (n_full + n_window)
    assert (s["kv_window"], s["kv_full_layers"], s["kv_window_layers"]) == (WINDOW, 2, 3)
    # float32 K and V of 2 kv heads of 16: 256 B a position and layer
    assert s["kv_bytes_per_position_full"] == 2 * 256
    assert s["kv_bytes_per_position_window"] == 3 * 256
    assert s["kv_bytes_per_token"] == 5 * 256
    assert s["window_blocks_per_slot"] == 8 and s["window_num_blocks"] == 17
    assert s["window_pool_bytes"] == 17 * BLOCK * 3 * 256
    assert s["moe_layers"] == 5 and s["moe_pairs_routed_total"] > 0
    # the flight entries carry the running totals (a reader differences them)
    stamped = engine._flight.tail(1)[0]["counters"]
    assert stamped["paged_entries_behind_window_total"] == behind
    assert stamped["window_blocks_freed_total"] == s["window_blocks_freed_total"] > 0


def test_window_walk_and_tiles_walked_are_what_the_kernel_reads():
    """The host's count and the kernel's trip count come from one rule: a row
    whose first query stands at 37, 16 queries, window 10, blocks of 4."""
    lo, end = window_walk(np.asarray([37, 0, 5]), 16, WINDOW, BLOCK, 32)
    assert lo.tolist() == [7, 0, 0] and end.tolist() == [14, 4, 6]
    assert window_walk(37, 16, 0, BLOCK, 32)[0] == 0
    assert tiles_walked(end, 32, first=lo).tolist() == [2, 1, 1]
    assert tiles_walked(end, 32).tolist() == [2, 1, 1]
    lo, end = window_walk(np.asarray([100]), 1, WINDOW, BLOCK, 64)
    assert (lo.tolist(), end.tolist()) == ([22], [26])
    assert tiles_walked(end, 64, first=lo).tolist() == [2]  # entries 22-25: tiles 2 and 3
    # a chunk's call (more than 256 stacked rows) walks the same entries a wide
    # tile a step, laid from entry 0 too; 256 rows are still a decode row's tile
    wide = tile_entries(256, stacked=257)
    assert (tile_entries(256), tile_entries(256, stacked=256), wide) == (8, 8, _CHUNK_TILE)
    assert tile_entries(20, stacked=257) == 20 and tile_entries(256, latent=True, stacked=257) == 32
    lo, end = window_walk(np.asarray([400, 0]), 300, WINDOW, BLOCK, 256)
    assert (lo.tolist(), end.tolist()) == ([97, 0], [175, 75])
    assert tiles_walked(end, 256, first=lo, stacked=600).tolist() == [
        -(-175 // wide) - 97 // wide, -(-75 // wide)]
    assert tiles_walked(end, 256, stacked=600).tolist() == [-(-175 // wide), -(-75 // wide)]


def test_a_chunks_dispatch_books_its_wide_steps_and_the_tiles_they_hold(tiny):
    """Chunks of 136 queries x 2 heads a kv head are 272 stacked rows, more
    than a grid step's one block: the kernel takes them ``_CHUNK_TILE``
    entries a softmax step, ``paged_chunk_steps_total`` counts those steps
    (layers x steps, by kind from where its walk starts) and
    ``paged_tiles_walked_total`` books each as the ``paged_tile_entries``-entry
    tiles it holds beside the decode rows' own; a reset zeroes both."""
    model, c = tiny
    chunk = 136
    engine = _engine(model, num_slots=2, max_seq_len=512, prefill_chunk=chunk)
    seen = {"prefill": [], "decode": []}

    def recorded(kind, fn):
        def call(*args):
            seen[kind].append(np.array(args[3]))
            return fn(*args)
        return call

    engine._prefill_fn = recorded("prefill", engine._prefill_fn)
    engine._decode_fn = recorded("decode", engine._decode_fn)
    request = _ask(engine, np.random.default_rng(6).integers(0, 256, size=300).tolist(), 5)
    engine.run_until_idle()
    assert len(request.output_tokens) == 5
    assert [int(p[0]) for p in seen["prefill"]] == [0, 136, 272]

    mb, n_full, n_window = 128, 2, 3
    wide, narrow = tile_entries(mb, stacked=2 * chunk), tile_entries(mb)
    assert (wide, narrow) == (_CHUNK_TILE, 8) and wide % narrow == 0

    def steps(first, queries, tile):
        end = min((first + queries - 1) // BLOCK + 1, mb)
        lo = min(max(first - WINDOW + 1, 0) // BLOCK, end)
        return n_full * -(-end // tile) + n_window * (-(-end // tile) - lo // tile)

    chunk_steps = sum(steps(int(p[0]), chunk, wide) for p in seen["prefill"])
    decode_tiles = sum(steps(int(p) + step, 1, narrow)
                       for pos0 in seen["decode"] for step in range(BURST) for p in pos0)
    s = engine.stats()
    assert s["paged_tile_entries"] == narrow
    assert s["paged_chunk_steps_total"] == chunk_steps > 0
    assert s["paged_tiles_walked_total"] == chunk_steps * (wide // narrow) + decode_tiles
    fill = s["paged_entries_walked_total"] / (s["paged_tiles_walked_total"] * narrow)
    assert 0 < fill <= 1
    engine.reset_stats()
    assert engine.stats()["paged_chunk_steps_total"] == 0
    # an engine that only decodes from here on books none
    engine._count_paged_entries(np.asarray([300, 0]), 1, n_full + n_window)
    assert engine.stats()["paged_chunk_steps_total"] == 0 < engine.stats()["paged_tiles_walked_total"]


# -- one kind of layer: the programs and the defaults are what they were -------------


def test_a_spec_of_one_kind_is_what_it_was():
    plain = CacheSpec(paged_layers=8, kv_heads=8, head_dim=128)
    assert plain.kinds == () and plain.window_kinds == ()
    assert plain.paged_kinds == (PagedKind("full", 8, 8, 128),)
    assert plain.bytes_per_token(jnp.bfloat16) == 32768  # the Mistral cell's
    assert plain.window_pools(40, 16384, 16, 1024) == {}
    assert plain.window_pool_bytes(40, 16384, 16, 1024, jnp.bfloat16) == 0
    with pytest.raises(ValueError, match="the first keeps the whole past"):
        CacheSpec(paged_layers=2, kv_heads=2, head_dim=16,
                  kinds=(PagedKind("window", 2, 2, 16, window=8),))
    with pytest.raises(ValueError, match="kinds hold 3 layers, paged_layers says 2"):
        CacheSpec(paged_layers=2, kv_heads=2, head_dim=16,
                  kinds=(PagedKind("full", 1, 2, 16), PagedKind("window", 2, 2, 16, window=8)))


def test_the_published_cut_is_priced_as_the_issue_reckons():
    """40 slots at 16,384, chunk 1,024, blocks of 16: 321 window blocks a
    slot, 2.52 GB of window pool beside 2.68 GB of full pool."""
    c = config_from_hf_json(CONFIG_FILE)
    spec = st.cache_spec(c)
    full, window = spec.paged_kinds
    assert (full.layers, window.layers, window.window) == (2, 6, 4096)
    assert full.bytes_per_token(jnp.bfloat16) == 4096
    assert window.bytes_per_token(jnp.bfloat16) == 12288
    assert window.resident_tokens(12000, 1024) == 4095 + 1024
    assert window.resident_tokens(700, 1024) == 700 and full.resident_tokens(12000, 1) == 12000
    assert spec.window_pools(40, 16384, 16, 1024) == {"window": (321, 40 * 321 + 1)}
    assert spec.window_pool_bytes(40, 16384, 16, 1024, jnp.bfloat16) == 12841 * 16 * 12288
    assert 2.52e9 < 12841 * 16 * 12288 < 2.53e9 and 2.68e9 < 40961 * 16 * 4096 < 2.69e9


_DEFAULTS_SCRIPT = """
import hashlib, json, sys
import jax, jax.numpy as jnp
from accelerate_tpu.ops.moe import expert_ffn, route
x = jax.ShapeDtypeStruct((24, 64), jnp.float32)
gate = jax.ShapeDtypeStruct((64, 8), jnp.float32)
w_in = jax.ShapeDtypeStruct((3, 8, 64, 48), jnp.float32)
w_out = jax.ShapeDtypeStruct((3, 8, 24, 64), jnp.float32)
def routed(x, gate, w_in, w_out):
    experts, weights = route(x, gate, None, 2)
    return expert_ffn(x, experts, weights, w_in, w_out, layer=1)
text = jax.jit(routed).lower(x, gate, w_in, w_out).as_text()
print("DIGESTS " + json.dumps({"routed": hashlib.sha256(text.encode()).hexdigest()}))
"""

_ENGINE_SCRIPT = """
import hashlib, json, sys
from accelerate_tpu.models.deepseek_v3 import DeepseekV3Config, DeepseekV3ForCausalLM
from accelerate_tpu.serving import EngineConfig, InferenceEngine
from accelerate_tpu.serving.sampling import SamplingParams
model = DeepseekV3ForCausalLM.from_config(DeepseekV3Config.tiny(), seed=0)
engine = InferenceEngine(model, EngineConfig(
    num_slots=4, max_seq_len=128, prefill_chunk=16, block_size=8, logprobs_topn=1, decode_burst=4))
engine.add_request(list(range(3, 40)), 6, sampling=SamplingParams(logprobs=1))
engine.run_until_idle()
out = {}
for program in ("decode", "prefill"):
    jitted, operands = engine._dispatched[program]
    out[program] = hashlib.sha256(jitted.lower(*operands).as_text().encode()).hexdigest()
print("DIGESTS " + json.dumps(out))
"""

_SMALLTHINKER_SCRIPT = _ENGINE_SCRIPT.replace(
    "deepseek_v3 import DeepseekV3Config, DeepseekV3ForCausalLM",
    "smallthinker import SmallThinkerConfig, SmallThinkerForCausalLM").replace(
    "DeepseekV3ForCausalLM.from_config(DeepseekV3Config.tiny()",
    "SmallThinkerForCausalLM.from_config(SmallThinkerConfig.tiny()")

#: sha256 of the StableHLO text of programs that this PR's parameters must not
#: move, taken on the parent commit (8c4cc1f: before ``route`` took ``logits``,
#: ``expert_ffn`` an ``activation``, the paged kernel a ``window`` and the cache
#: spec its kinds). ``tests/test_lfm2.py`` keeps the llama (the Mistral cell's),
#: hybrid, LFM2 and SDAR programs; here are the DeepSeek-V3 engine's two and the
#: routed product at its defaults. A PR that changes these programs on purpose
#: takes the new digests. PR 46 took ``deepseek`` ``prefill`` (on the tree it built
#: on 42d3b0d: a chunk's step is asked for the one row the first token is picked
#: from); ``deepseek`` ``decode`` and ``defaults`` ``routed`` are the parent's still
PARENT_PROGRAMS = {
    "deepseek": {"decode": "16dba12fa31d7584643a755dab50ede39ac9e852bcc147cfb5a22234288a17a7", "prefill": "f73eb985c0a741f6fd4b87ff141bd43af133a5988ea6c9f17c6090527fc61f6c"},
    # taken on 987753d (the parent of PR 47, which gave the six families' steps
    # one frame and one write-then-attend block): the two-kind engine's programs
    "smallthinker": {"decode": "656361d222ffd8304af2d55a13afd9540ec731f850f3f4ec83bd4739181ee48b", "prefill": "295eeb5d0f5f6fdc6b58867a0c5702dcaeed912905d2672614df8a9d5ee11f95"},
    "defaults": {"routed": "6f9888adb74f24f71c5651d3cacd2f50b1e7b1d15237f28f42442efb55fe7cd5"},
}


@pytest.mark.parametrize("name, script", [("deepseek", _ENGINE_SCRIPT),
                                          ("defaults", _DEFAULTS_SCRIPT),
                                          ("smallthinker", _SMALLTHINKER_SCRIPT)])
def test_a_model_of_one_kind_and_the_defaults_compile_the_parents_programs(name, script):
    """In a process of its own, as ``tests/test_lfm2.py`` says why."""
    import subprocess

    done = subprocess.run(
        [sys.executable, "-c", script], cwd=ROOT, capture_output=True, text=True,
        timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    assert done.returncode == 0, done.stderr[-2000:]
    got = json.loads(next(l for l in done.stdout.splitlines() if l.startswith("DIGESTS "))[8:])
    assert got == PARENT_PROGRAMS[name]


def test_route_takes_the_callers_logits_and_the_experts_a_relu():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(12, 16)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    logits = jnp.dot(x, gate, precision=jax.lax.Precision.HIGHEST)
    for scoring in ("sigmoid", "softmax"):
        a = route(x, gate, None, 3, scoring=scoring)
        b = route(None, None, None, 3, scoring=scoring, logits=logits)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_allclose(a[1], b[1], rtol=0, atol=1e-7)
    # softmax over all, top k, renormalised with no guard: the softmax over the chosen
    experts, w = route(None, None, None, 3, scoring="softmax", norm_eps=0.0, logits=logits)
    top = jax.lax.top_k(logits, 3)[0]
    np.testing.assert_allclose(w, jax.nn.softmax(top, axis=-1), rtol=0, atol=1e-6)
    w_in = jnp.asarray(rng.normal(size=(8, 16, 10)), jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(8, 5, 16)), jnp.float32)
    y, counts = expert_ffn(x, experts, w, w_in, w_out, activation="relu")
    want = np.zeros((12, 16), np.float32)
    for t in range(12):
        for e, wt in zip(np.asarray(experts[t]), np.asarray(w[t])):
            g, u = np.split(np.asarray(x[t]) @ np.asarray(w_in[e]), 2)
            want[t] += wt * ((np.maximum(g, 0) * u) @ np.asarray(w_out[e]))
    np.testing.assert_allclose(y, want, rtol=0, atol=2e-4)
    assert int(counts.sum()) == 36
    with pytest.raises(ValueError, match="unknown expert activation 'gelu'"):
        expert_ffn(x, experts, w, w_in, w_out, activation="gelu")


# -- the published file -> the model ----------------------------------------------------


def _published(tmp_path, **changes):
    with open(CONFIG_FILE) as f:
        d = json.load(f)
    d.update(changes)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(d))
    return str(path)


def test_the_benchmarks_config_builds_two_periods_of_the_published_model(tmp_path):
    assert "smallthinker" in KNOWN_MODEL_TYPES
    c = config_from_hf_json(CONFIG_FILE)
    assert type(c).__name__ == "SmallThinkerConfig"
    assert [(k, i) for k, i, _ in st.layer_plan(c)] == [
        ("full", 0), ("window", 0), ("window", 1), ("window", 2),
        ("full", 1), ("window", 3), ("window", 4), ("window", 5)]
    assert [r for _, _, r in st.layer_plan(c)] == [False, True, True, True] * 2
    with init_empty_weights():
        model = model_factory_for_config(c)(c)
    flat = weights.flat_names(model.params)
    with open(CONFIG_FILE) as f:
        assert {k: tuple(a.shape) for k, a in flat.items()} == reference.leaf_shapes(json.load(f))
    assert sum(int(np.prod(a.shape)) for a in flat.values()) == 3_966_937_600  # the issue's
    assert model.step_counter_shapes["moe_expert_pairs"] == (8, 64)
    # rope_layout is read for what it says, not derived from the window's
    odd = config_from_hf_json(_published(tmp_path, rope_layout=[1, 0, 1, 1, 0, 1, 1, 0]))
    assert [r for _, _, r in st.layer_plan(odd)] == [True, False, True, True, False, True, True,
                                                     False]


@pytest.mark.parametrize("changes, said", [
    (dict(moe_primary_router_apply_softmax=False), "the 4B sibling's sigmoid router"),
    (dict(rope_layout=[0, 1, 1, 1]), "rope_layout names 4 layers"),
    (dict(sliding_window_layout=[1, 1, 1, 1, 0, 1, 1, 1]), "starts with a window layer"),
    (dict(sliding_window_layout=[0] * 8), "names no window layer"),
    (dict(moe_num_active_primary_experts=65), "moe_num_active_primary_experts 65"),
    (dict(tie_word_embeddings=True), "the head is untied"),
    (dict(norm_topk_prob=False), "norm_topk_prob false"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling .* built as published"),
    (dict(hidden_act="silu"), "hidden_act 'silu'"),
])
def test_what_cannot_be_built_as_published_is_refused_by_name(tmp_path, changes, said):
    with pytest.raises(ValueError, match=said):
        config_from_hf_json(_published(tmp_path, **changes))


def test_a_published_window_is_no_longer_dropped_in_silence(tmp_path):
    """For the types ``models/llama.py`` builds a non-null ``sliding_window``
    is refused with the reason; the benchmark's Mistral and SDAR files carry
    ``null`` and build what they built."""
    configs = os.path.join(ROOT, "perfbench", "configs")
    for name, kind in (("mistral-7b-serve-v5e1.json", "LlamaConfig"),
                       ("mistral-7b-train-v5e4.json", "LlamaConfig"),
                       ("sdar-30b-a3b-serve-v5e1.json", "SdarMoeConfig")):
        with open(os.path.join(configs, name)) as f:
            assert json.load(f)["sliding_window"] is None
        assert type(config_from_hf_json(os.path.join(configs, name))).__name__ == kind
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"model_type": "mistral", "sliding_window": 4096}))
    with pytest.raises(ValueError, match="mistral with sliding_window 4096: .* attends the "
                                         "whole context in every layer"):
        config_from_hf_json(str(path))
    path.write_text(json.dumps({"model_type": "llama", "sliding_window": 4096,
                                "use_sliding_window": False}))
    assert type(config_from_hf_json(str(path))).__name__ == "LlamaConfig"


@pytest.mark.parametrize("geometry, said", [
    (dict(swap_gb=0.01), "swap_gb=0.01 is not supported .* 3 layers that keep a window of 10 "
                         "positions: _swap_out mirrors one pool's blocks"),
    (dict(spec_k=2, logprobs_topn=0), "spec_k=2 is not supported .* the early-exit draft reads "
                                      "the first layers of ONE pool"),
    (dict(denoise_steps=2), "only a model that declares block_decode"),
    (dict(state_dtype="bf16"), "keeps no per-slot state"),
])
def test_what_the_engine_refuses_at_bring_up(tiny, geometry, said):
    with pytest.raises(ValueError, match=said):
        _engine(tiny[0], **geometry)


def test_a_mesh_is_refused_for_two_pools(tiny):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 1, 1, 1, 1, 2),
                ("dp", "pp", "fsdp", "ep", "cp", "tp"))
    with pytest.raises(ValueError, match="mesh= is not supported .* the window kind's pool and "
                                         "its table have no placement yet"):
        InferenceEngine(tiny[0], EngineConfig(num_slots=2, max_seq_len=64), mesh=mesh)


def test_preflight_and_auto_blocks_price_a_window_kind_by_what_a_slot_keeps(capsys):
    """At the published widths, shapes only: the engine's pre-flight books the
    window kind's pool as a fixed cost, refuses a budget the cell's geometry
    does not fit, and ``--auto-blocks`` sizes the FULL kind's pool from what
    is left after it."""
    import argparse

    from accelerate_tpu.commands import serve

    c = config_from_hf_json(CONFIG_FILE)
    with init_empty_weights():
        model = model_factory_for_config(c)(c, dtype=jnp.bfloat16)
    params_bytes = 2 * sum(int(np.prod(a.shape)) for a in jax.tree.leaves(model.params))
    assert 7.93e9 < params_bytes < 7.94e9
    window_bytes = 12841 * 16 * 12288
    cli = argparse.ArgumentParser()
    serve.add_parser(cli.add_subparsers())
    flags = ["serve", "--model-config", CONFIG_FILE, "--dtype", "bf16", "--num-slots", "40",
             "--max-seq-len", "16384", "--prefill-chunk", "1024", "--auto-blocks"]
    n = serve._auto_num_blocks(cli.parse_args([*flags, "--hbm-gb", "12.5"]), model, None)
    want = (int(12.5 * (1 << 30) * 0.95) - params_bytes - window_bytes) // (16 * 4096)
    assert n == want and 30_000 < n < 40 * 1024 + 1
    err = capsys.readouterr().err
    assert "window kind 12841 blocks (321 a slot x 40 slots + the null block)" in err
    assert "0.07 MB/block/device" in err
    with pytest.raises(ValueError, match="SP004"):
        serve._auto_num_blocks(cli.parse_args([*flags, "--hbm-gb", "9.5"]), model, None)


def test_serve_builds_the_engine_of_the_published_config(tmp_path, capsys):
    """``serve --model-config`` with the benchmark's file at tiny sizes:
    ``config_from_hf_json`` and ``model_factory_for_config``, no wrapper; the
    window kind's derived pool is printed, and a budget it does not fit is
    refused before anything allocates."""
    import argparse

    from accelerate_tpu.commands import serve

    with open(CONFIG_FILE) as f:
        small = json.load(f)["rehearsal"]
    small = {k: v for k, v in small.items() if k not in ("serve_flags", "check")}
    cli = argparse.ArgumentParser()
    serve.add_parser(cli.add_subparsers())
    flags = ["serve", "--model-config", _published(tmp_path, **small), "--num-slots", "2",
             "--max-seq-len", "64", "--prefill-chunk", "16"]
    engine = serve._make_engine(cli.parse_args(flags))
    assert "serve: window kind: 9 blocks (4 a slot x 2 slots + the null block)" in \
        capsys.readouterr().err
    request = engine.add_request(list(range(50)), 6)
    engine.run_until_idle()
    s = engine.stats()
    assert len(request.output_tokens) == 6 and s["decode_compiles"] == 1 and s["kv_kinds"] == 2
    with pytest.raises(ValueError, match="SP004"):
        serve._make_engine(cli.parse_args([*flags, "--hbm-gb", "0.0001"]))
