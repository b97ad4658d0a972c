"""Granite-4.0-H through the serving engine (ISSUE 27): Mamba-2 layers with a
per-slot recurrent state beside the paged KV of the attention layers, held
against the plain reference of ``perfbench/reference/granite_hybrid.py`` —
float32 at ``highest``, the token-by-token recurrence, no chunks, no cache,
nothing shared with the program.

All on the CPU at a small size with seeded weights (``perfbench.weights``,
the recipe the benchmark's check uses, with scales that give the state a
memory of a hundred tokens). Tolerances, each with its reason, are beside
the comparison they belong to.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import accelerate_tpu.models.granite_hybrid as gh  # noqa: E402
from accelerate_tpu.big_modeling import init_empty_weights  # noqa: E402
from accelerate_tpu.models import (  # noqa: E402
    LlamaConfig,
    LlamaForCausalLM,
    config_from_hf_json,
    model_factory_for_config,
)
from accelerate_tpu.models.cache import cache_spec_of  # noqa: E402
from accelerate_tpu.ops import ssm  # noqa: E402
from accelerate_tpu.serving import EngineConfig, InferenceEngine  # noqa: E402
from accelerate_tpu.serving.sampling import SamplingParams  # noqa: E402
from perfbench import common, probe, weights  # noqa: E402
from perfbench.reference import granite_hybrid as reference  # noqa: E402

SEED = 5
#: per-token decay exp(dt * A) between 0.9 and 0.999, a quiet embedding
#: under a tied head, logits with a spread
SCALES = {"layers.mamba.dt_bias": -4.0, "layers.mamba.A_log": 0.5,
          "embed_tokens": 0.05, "norm": 8.0, "layers.attention.wq": 8.0}
PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "shared_intermediate_size", "mamba_n_heads",
    "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv", "mamba_expand",
    "mamba_chunk_size", "embedding_multiplier", "residual_multiplier",
    "attention_multiplier", "logits_scaling", "rms_norm_eps",
)


def _reference_config(c) -> dict:
    cfg = {k: getattr(c, k) for k in PUBLISHED_KEYS}
    return {**cfg, "layer_types": list(c.layer_types), "weight_scales": SCALES}


@pytest.fixture(scope="module")
def tiny():
    """(model with seeded float32 weights, the reference's configuration)."""
    c = gh.GraniteHybridConfig.tiny()
    with init_empty_weights():
        model = gh.GraniteHybridForCausalLM.from_config(c)
    model.params = weights.make_tree(SEED, model.params, dtype=jnp.float32, scales=SCALES)
    return model, _reference_config(c)


def _engine(model, **kw):
    geometry = dict(num_slots=4, max_seq_len=128, prefill_chunk=16, block_size=8,
                    logprobs_topn=1, decode_burst=4)
    geometry.update(kw)
    return InferenceEngine(model, EngineConfig(**geometry))


def _ask(engine, prompt, new_tokens=12):
    return engine.add_request(list(prompt), new_tokens, sampling=SamplingParams(logprobs=1))


def _reference_logprobs(cfg, request):
    """The reference's full forward over prompt + served tokens: the
    log-probability of every served token, and whether it was the best."""
    ids = np.asarray(request.prompt + request.output_tokens[:-1], np.int32)
    rows = np.arange(len(request.prompt) - 1, len(ids))
    padded = np.zeros((128,), np.int32)
    padded[: len(ids)] = ids
    logits = np.asarray(
        reference.logits_at(cfg, SEED, padded, len(ids), rows, "float32"), np.float64)
    top = logits.max(-1, keepdims=True)
    logp = logits - (top + np.log(np.exp(logits - top).sum(-1, keepdims=True)))
    served = np.asarray(request.output_tokens)
    return logp[np.arange(len(rows)), served], logits.argmax(-1) == served


def _reported(request):
    return np.asarray([e["logprob"] for e in request.logprobs])


@pytest.fixture(scope="module")
def served(tiny):
    """Six prompts over four slots: every slot is reused, prompts end
    mid-chunk (5, 37, 50), on a chunk's edge (16) and span several chunks."""
    model, cfg = tiny
    engine = _engine(model)
    rng = np.random.default_rng(0)
    requests = {n: _ask(engine, rng.integers(0, 256, size=n).tolist()) for n in (37, 16, 5, 50, 33, 20)}
    engine.run_until_idle()
    return engine, requests, cfg


# float32 against float32: what is left is the order of summation — the
# chunked scan and the kernel's per-step update against a token loop. Over
# these sequences it reads 7e-7; a state dropped at a chunk's edge reads
# 1e-1, one held in bfloat16 1e-3, an fp8 KV pool 2e-3.
LOGPROB_TOLERANCE = 2e-5


@pytest.mark.parametrize("prompt_len", [5, 16, 20, 33, 37, 50])
def test_prefill_in_chunks_then_decode_agrees_with_the_full_forward_pass(served, prompt_len):
    _, requests, cfg = served
    request = requests[prompt_len]
    want, is_best = _reference_logprobs(cfg, request)
    assert len(request.output_tokens) == 12 and is_best.all()
    assert np.abs(_reported(request) - want).max() < LOGPROB_TOLERANCE


def test_one_decode_and_one_prefill_executable_and_what_stats_says(served):
    engine, _, _ = served
    s = engine.stats()
    assert s["decode_compiles"] == 1 and s["prefill_compiles"] == 1
    assert (s["kv_layers"], s["state_layers"]) == (1, 3)
    # ssm [8, 16, 16] float32 + conv [3, 128 + 2 * 16] float32, three layers
    assert s["state_bytes_per_slot"] == 3 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert s["state_bytes_total"] == 4 * s["state_bytes_per_slot"]
    assert s["state_resets_total"] == 6  # one per request placed
    assert s["kv_bytes_per_token"] == 2 * 1 * 2 * 16 * 4  # K and V of ONE layer
    assert s["kv_slot_capacity"] == 4
    assert s["prefix_cache"] is False and "per-slot state" in s["prefix_cache_off_reason"]
    assert engine.radix is None
    assert engine._kp.shape[0] == 1 and engine._cache["ssm"].shape[:2] == (3, 4)
    assert engine._cache["ssm"].dtype == jnp.float32


@pytest.mark.parametrize("chunk", [4, 16, 7])
def test_chunked_scan_agrees_with_the_token_by_token_recurrence(chunk):
    b, s, h, p, n = 2, 37, 4, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 3)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    b_mat, c_mat = jax.random.normal(ks[3], (b, s, n)), jax.random.normal(ks[4], (b, s, n))
    state = jax.random.normal(ks[5], (b, h, p, n))
    # the last five tokens of row 1 are padding: dt = 0 and x = 0 there
    live = jnp.arange(s)[None, :] < jnp.asarray([s, s - 5])[:, None]
    dt = jnp.where(live[..., None], dt, 0.0)
    x = jnp.where(live[..., None, None], x, 0.0)
    y, last = ssm.ssd_chunk_scan(x, dt, a, b_mat, c_mat, state, chunk)

    def step(st, inp):
        x_t, dt_t, b_t, c_t = inp
        st = (jnp.exp(dt_t * a)[..., None, None] * st
              + jnp.einsum("bh,bhp,bn->bhpn", dt_t, x_t, b_t, precision="highest"))
        return st, jnp.einsum("bhpn,bn->bhp", st, c_t, precision="highest")

    want_last, want_y = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b_mat, c_mat)))
    # float32, sums of a few dozen terms in another order
    np.testing.assert_allclose(y, jnp.moveaxis(want_y, 0, 1), atol=2e-5)
    np.testing.assert_allclose(last, want_last, atol=2e-5)
    # the padded tail moved nothing: row 1's state is the state after s - 5 tokens
    _, upto = ssm.ssd_chunk_scan(x[1:, : s - 5], dt[1:, : s - 5], a, b_mat[1:, : s - 5],
                                 c_mat[1:, : s - 5], state[1:], chunk)
    np.testing.assert_allclose(last[1:], upto, atol=2e-5)


def _update_inputs(layers=3, slots=5, h=8, p=16, n=128, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    state = jax.random.normal(ks[0], (layers, slots, h, p, n)).astype(dtype)
    x = jax.random.normal(ks[1], (slots, h, p), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (slots, h)) - 4)
    a = -jnp.exp(jax.random.normal(ks[3], (h,)) * 0.1)
    b_vec = jax.random.normal(ks[4], (slots, n), jnp.bfloat16)
    c_vec = jax.random.normal(ks[5], (slots, n), jnp.bfloat16)
    active = jnp.asarray([True, False, True, True, False])
    return state, x, dt, a, b_vec, c_vec, active


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("heads", [8, 32])  # one block of heads a slot, and two
def test_ssm_state_update_kernel_agrees_with_its_jnp_twin(heads, dtype):
    state, *operands = _update_inputs(h=heads, dtype=dtype)
    for layer in (0, 2):
        want_state, want_y = ssm.ssm_state_update(state, layer, *operands, impl="jnp")
        got_state, got_y = ssm.ssm_state_update(
            state, layer, *operands, impl="pallas", interpret=True)
        # the same float32 arithmetic in another order (bfloat16 storage:
        # one rounding of the same values, the y of 128 of them)
        tol = 1e-5 if dtype == jnp.float32 else 0.05
        np.testing.assert_allclose(np.asarray(got_state, np.float32),
                                   np.asarray(want_state, np.float32), atol=tol)
        np.testing.assert_allclose(got_y, want_y, atol=tol * 16)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_a_masked_lane_of_the_kernel_keeps_its_state_bit_for_bit(impl):
    state, *operands, active = _update_inputs()
    new, y = ssm.ssm_state_update(state, 1, *operands, active, impl=impl, interpret=True)
    off = ~np.asarray(active)
    assert np.array_equal(np.asarray(new)[1][off], np.asarray(state)[1][off])
    assert not np.array_equal(np.asarray(new)[1][~off], np.asarray(state)[1][~off])
    assert np.array_equal(np.asarray(new)[[0, 2]], np.asarray(state)[[0, 2]])  # other layers
    assert not np.asarray(y)[off].any()


#: which of five slots decode: nothing (a legal call), one at either end of
#: the walk, some, and every one (the walk does what a grid over slots did)
LIVE_PATTERNS = {
    "none": [False] * 5, "first": [True, False, False, False, False],
    "last": [False, False, False, False, True], "ragged": [False, True, True, False, True],
    "all": [True] * 5,
}


def _as_f32(*arrays):
    return [np.asarray(a.astype(jnp.float32)) for a in arrays]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("heads", [8, 32])
@pytest.mark.parametrize("pattern", list(LIVE_PATTERNS))
def test_the_walk_over_the_live_slots_agrees_with_the_jnp_twin(pattern, heads, dtype):
    state, *operands, _ = _update_inputs(h=heads, dtype=dtype)
    active = jnp.asarray(LIVE_PATTERNS[pattern])
    on = np.asarray(active)
    for layer in (0, 2):
        want_state, want_y = _as_f32(*ssm.ssm_state_update(
            state, layer, *operands, active, impl="jnp"))
        got_state, got_y = _as_f32(*ssm.ssm_state_update(
            state, layer, *operands, active, impl="pallas", interpret=True))
        tol = 1e-5 if dtype == jnp.float32 else 0.05
        np.testing.assert_allclose(got_state, want_state, atol=tol)
        np.testing.assert_allclose(got_y, want_y, atol=tol * 16)
        # what is not live is not touched: the dead slots of this layer, and
        # every other layer, to the last bit; a dead slot's y is a plain 0
        before = _as_f32(state)[0]
        assert np.array_equal(got_state[layer][~on], before[layer][~on])
        others = [i for i in range(3) if i != layer]
        assert np.array_equal(got_state[others], before[others])
        assert not got_y[~on].any()
        assert on.any() == (not np.array_equal(got_state[layer], before[layer]))


@pytest.mark.parametrize("poison", [np.nan, np.inf])
@pytest.mark.parametrize("heads", [8, 32])
@pytest.mark.parametrize("pattern", ["none", "first", "last", "ragged"])
def test_a_dead_slot_is_never_read_whatever_its_operands_hold(pattern, heads, poison):
    """The kernel's work follows the live list, so what a dead slot's ``x``,
    ``dt``, ``B`` and ``C`` hold reaches nothing: its state stays, its ``y``
    is 0, and every live slot reads what it reads beside clean operands."""
    state, x, dt, a, b_vec, c_vec, _ = _update_inputs(h=heads)
    active = jnp.asarray(LIVE_PATTERNS[pattern])
    on = np.asarray(active)
    dead = jnp.asarray(~on)

    def poisoned(t):
        return jnp.where(dead.reshape((-1,) + (1,) * (t.ndim - 1)), poison, t).astype(t.dtype)

    clean = ssm.ssm_state_update(state, 1, x, dt, a, b_vec, c_vec, active,
                                 impl="pallas", interpret=True)
    dirty = ssm.ssm_state_update(state, 1, poisoned(x), poisoned(dt), a, poisoned(b_vec),
                                 poisoned(c_vec), active, impl="pallas", interpret=True)
    assert np.array_equal(np.asarray(dirty[0]), np.asarray(clean[0]))
    assert np.array_equal(np.asarray(dirty[1]), np.asarray(clean[1]))
    assert np.array_equal(np.asarray(dirty[0])[1][~on], np.asarray(state)[1][~on])
    assert not np.asarray(dirty[1])[~on].any() and np.isfinite(np.asarray(dirty[0])).all()


@pytest.mark.parametrize("pattern", list(LIVE_PATTERNS))
def test_a_precomputed_live_list_and_the_one_derived_from_active_give_the_same_bits(pattern):
    state, *operands, _ = _update_inputs(h=32)
    active = jnp.asarray(LIVE_PATTERNS[pattern])
    idx, n_live = ssm.live_slots(active)
    on = np.asarray(active)
    assert int(n_live[0]) == on.sum() and idx.shape == (5,) and idx.dtype == jnp.int32
    assert np.asarray(idx)[: on.sum()].tolist() == np.flatnonzero(on).tolist()
    derived = ssm.ssm_state_update(state, 2, *operands, active, impl="pallas", interpret=True)
    handed = ssm.ssm_state_update(
        state, 2, *operands, active, impl="pallas", interpret=True, live=(idx, n_live))
    for got, want in zip(handed, derived):
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kind", ["hybrid", "llama"])
def test_state_slot_counters_follow_the_lanes_the_decode_executable_was_handed(tiny, kind):
    """``state_slots_live_total`` / ``state_slots_held_total`` move, at every
    decode dispatch, by the live lanes of the mask the ONE decode executable
    is handed against every slot, times the burst's steps and the layers that
    keep state: what the state kernel walks against what the cache holds. A
    model with no such layer reads 0 in both."""
    if kind == "hybrid":
        model, state_layers = tiny[0], 3
    else:
        model = LlamaForCausalLM.from_config(
            LlamaConfig.tiny(vocab_size=64, hidden_size=32, layers=2, heads=4, seq=128), seed=0)
        state_layers = 0
    engine = _engine(model, logprobs_topn=0)
    assert engine.stats()["state_layers"] == state_layers
    masks = []
    decode = engine._decode_fn

    def recorded(*args):
        masks.append(np.array(args[5]))
        return decode(*args)

    engine._decode_fn = recorded
    rng = np.random.default_rng(1)
    for n, new in ((9, 14), (20, 6), (5, 10)):
        engine.add_request(rng.integers(0, 64, size=n).tolist(), new)
    engine.run_until_idle()
    live = sum(int(m.sum()) for m in masks)
    assert len(masks) > 2 and all(m.shape == (4, 1) for m in masks)
    assert 0 < live < 4 * len(masks)  # three requests over four slots: never all live
    stats = engine.stats()
    assert stats["decode_compiles"] == 1
    assert stats["state_slots_live_total"] == live * 4 * state_layers  # decode_burst 4
    assert stats["state_slots_held_total"] == len(masks) * 4 * 4 * state_layers
    engine.reset_stats()
    stats = engine.stats()
    assert stats["state_slots_live_total"] == stats["state_slots_held_total"] == 0


def test_a_masked_lane_of_the_decode_step_leaves_state_and_tail_bit_identical(tiny):
    model, _ = tiny
    spec, slots = model.cache_spec, 4
    rng = np.random.default_rng(3)
    cache = {"k": jnp.zeros((1, 40, 8, 32)), "v": jnp.zeros((1, 40, 8, 32))}
    for name, leaf in spec.slot_state.items():
        cache[name] = jnp.asarray(rng.normal(size=leaf.array_shape(slots)), leaf.dtype or jnp.float32)
    before = {k: np.asarray(v) for k, v in cache.items()}
    active = np.asarray([[True], [False], [False], [True]])
    tables = np.zeros((slots, 16), np.int32)
    tables[0, 0], tables[3, 0] = 1, 2
    out = model.apply_fn(
        model.params, input_ids=rng.integers(0, 256, size=(slots, 1)).astype(np.int32),
        paged_kv=cache, block_tables=tables, cache_positions=np.zeros((slots,), np.int32),
        paged_write_mask=active,
    )["paged_kv"]
    for name in spec.slot_state:
        after = np.asarray(out[name])
        assert np.array_equal(after[:, [1, 2]], before[name][:, [1, 2]]), name
        assert not np.array_equal(after[:, [0, 3]], before[name][:, [0, 3]]), name


def test_a_reused_slot_starts_from_zero_state(tiny):
    """One slot, two requests one after the other: the second's
    log-probabilities are those it gets from an engine nobody used."""
    model, cfg = tiny
    rng = np.random.default_rng(7)
    first, second = (rng.integers(0, 256, size=n).tolist() for n in (41, 23))
    engine = _engine(model, num_slots=1)
    _ask(engine, first)
    engine.run_until_idle()
    again = _ask(engine, second)
    engine.run_until_idle()
    assert engine.stats()["state_resets_total"] == 2
    fresh = _ask(fresh_engine := _engine(model, num_slots=1), second)
    fresh_engine.run_until_idle()
    assert again.output_tokens == fresh.output_tokens
    assert np.array_equal(_reported(again), _reported(fresh))
    want, _ = _reference_logprobs(cfg, again)
    assert np.abs(_reported(again) - want).max() < LOGPROB_TOLERANCE


def test_a_preempted_request_is_recomputed_and_reproduces_its_logits(tiny):
    """A pool too small for three growing requests: one gives its blocks
    back, re-queues, has its slot's state zeroed and is prefilled again
    over prompt and emitted tokens; what it reports agrees with the
    reference as if nothing had happened."""
    model, cfg = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (30, 28, 26)]
    # 3 requests x (30 + 40 tokens) need 27 blocks of 8; 16 are there
    engine = _engine(model, num_slots=3, num_blocks=17, max_seq_len=96)
    requests = [_ask(engine, p, new_tokens=40) for p in prompts]
    engine.run_until_idle()
    s = engine.stats()
    assert s["preemptions"] >= 1 and s["out_of_blocks_total"] == 0
    assert s["state_resets_total"] == 3 + s["preemptions"]
    assert any(r.preemptions for r in requests)
    for r in requests:
        assert len(r.output_tokens) == 40 and r.finish_reason == "length"
        want, is_best = _reference_logprobs(cfg, r)
        # a resumed request's state was rebuilt by the chunked scan where
        # the first pass had stepped it: the same float32 noise as above
        assert is_best.all() and np.abs(_reported(r) - want).max() < LOGPROB_TOLERANCE


@pytest.mark.parametrize("armed, why", [
    (dict(swap_gb=0.01), "swap_gb"),
    (dict(spec_k=2, logprobs_topn=0), "spec_k"),
    (dict(mesh=True), "mesh="),
])
def test_what_assumes_blocks_are_all_of_the_past_is_refused_at_bring_up(tiny, armed, why):
    model, _ = tiny
    mesh = None
    if armed.pop("mesh", False):
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("tp",))
    config = EngineConfig(num_slots=2, max_seq_len=64, prefill_chunk=16, block_size=8, **armed)
    with pytest.raises(ValueError, match="per-slot state") as e:
        InferenceEngine(model, config, mesh=mesh)
    assert why in str(e.value) and "Reach 5" in str(e.value)


#: served by the parent commit (1c835b2) on the CPU: the engine's programs
#: take the cache as one donated dict now, and llama declares "every layer
#: paged, no slot state" through the same contract
PARENT_TOKENS = [
    [27, 11, 27, 43, 38, 52, 49, 52, 52, 52], [11, 11, 11, 11, 38, 38, 38, 38, 38, 38],
    [20, 9, 11, 9, 11, 9, 11, 9, 11, 40], [44, 27, 0, 48, 11, 30, 32, 48, 11, 32],
]


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_llama_through_the_same_cache_contract_serves_the_parents_tokens(kv_dtype):
    config = LlamaConfig.tiny(vocab_size=64, hidden_size=32, layers=3, heads=4, seq=96)
    model = LlamaForCausalLM.from_config(config, seed=0)
    spec = cache_spec_of(model)
    assert (spec.paged_layers, spec.kv_heads, spec.head_dim, spec.slot_state) == (3, 4, 8, {})
    engine = InferenceEngine(model, EngineConfig(
        num_slots=3, block_size=8, max_seq_len=64, prefill_chunk=8, decode_burst=2,
        kv_dtype=kv_dtype))
    rng = np.random.default_rng(0)
    requests = [engine.add_request(rng.integers(0, 64, size=n).astype(np.int32), 10)
                for n in (11, 5, 20, 9)]
    engine.run_until_idle()
    assert [r.output_tokens for r in requests] == PARENT_TOKENS
    s = engine.stats()
    assert s["prefix_cache"] is True and s["state_bytes_total"] == 0 and s["kv_layers"] == 3
    assert sorted(engine._cache) == (["k", "k_scale", "v", "v_scale"] if kv_dtype == "int8" else ["k", "v"])


# -- the benchmark's check at the rehearsal size: it has teeth --------------------


def _rehearse(monkeypatch, how):
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, "granite4h-micro-chat-short")
    config, traffic = common.apply_rehearsal(config, traffic)
    extra = {}
    if how == "state held in bfloat16":
        # the file's control: the program's own --state-dtype bf16
        extra["serve_flags"] = probe.control_flags(config)
        assert extra["serve_flags"][-2:] == ["--state-dtype", "bf16"]
    if how == "fp8 KV pool":
        extra["serve_flags"] = [*config["serve_flags"], "--kv-dtype", "fp8"]
    if how == "state dropped at a prefill chunk's edge":
        scan = gh.ssd_chunk_scan
        monkeypatch.setattr(gh, "ssd_chunk_scan", lambda x, dt, a, b, c, state, chunk: scan(
            x, dt, a, b, c, jnp.zeros_like(state), chunk))
    ctx = common.Ctx(cell=cell, config=config, traffic=traffic, seed=2_700_000_021,
                     seconds=2.0, trace=False, rehearse=True, **extra)
    return common.load_driver("serve_engine").run(ctx)


def test_the_rehearsal_of_the_new_cell_is_correct(monkeypatch):
    out = _rehearse(monkeypatch, "sound")
    assert out["correct"] and out["failed"] == 0 and out["observed"]["compiles_in_window"] == 0
    limits = out["check"]["limits"]
    # a tenth of the limit: the sound float32 run reads 2e-7
    assert out["check"]["numbers"]["logprob_err_mean"] < limits["logprob_err_mean"] / 10


@pytest.mark.parametrize("how", [
    "state dropped at a prefill chunk's edge", "state held in bfloat16", "fp8 KV pool"])
def test_a_run_that_loses_precision_or_state_fails_the_rehearsal_limits(monkeypatch, how):
    out = _rehearse(monkeypatch, how)
    numbers, limits = out["check"]["numbers"], out["check"]["limits"]
    assert out["failed"] == 0 and not out["correct"] and not out["check"]["ok"]
    assert numbers["logprob_err_mean"] > 2 * limits["logprob_err_mean"], numbers


# -- the published file -> the model ------------------------------------------------


def _published(tmp_path, **changes):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "perfbench", "configs", "granite-4.0-h-micro-serve-v5e1.json")) as f:
        d = json.load(f)
    d.update(changes)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(d))
    return str(path)


def test_the_published_config_builds_the_published_model(tmp_path):
    config = config_from_hf_json(_published(tmp_path))
    assert type(config).__name__ == "GraniteHybridConfig"
    assert (config.n_mamba, config.n_attention) == (36, 4)
    assert [i for i, k in enumerate(config.layer_types) if k == "attention"] == [5, 15, 25, 35]
    assert (config.head_dim, config.d_inner, config.conv_dim) == (64, 4096, 4352)
    assert (config.embedding_multiplier, config.residual_multiplier,
            config.attention_multiplier, config.logits_scaling) == (12, 0.22, 0.015625, 8)
    with init_empty_weights():
        model = model_factory_for_config(config)(config)
    flat = weights.flat_names(model.params)
    assert "lm_head" not in flat  # the head is the embedding
    assert sum(int(np.prod(a.shape)) for a in flat.values()) == 3_191_396_096
    assert {k: tuple(a.shape) for k, a in flat.items()} == reference.leaf_shapes(
        {**dataclasses.asdict(config), "layer_types": list(config.layer_types)})
    spec = model.cache_spec
    assert spec.paged_layers == 4 and spec.kv_heads * spec.head_dim == 512
    assert spec.slot_state["ssm"].array_shape(64) == (36, 64, 64, 64, 128)
    assert spec.slot_state["conv"].array_shape(64) == (36, 64, 3, 4352)
    assert spec.state_bytes_per_slot("bfloat16") == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert gh.layer_runs(config.layer_types) == [
        ("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 9), ("attention", 1, 1),
        ("mamba", 14, 9), ("attention", 2, 1), ("mamba", 23, 9), ("attention", 3, 1),
        ("mamba", 32, 4)]


@pytest.mark.parametrize("changes, said", [
    (dict(num_local_experts=8), "num_local_experts 8"),
    (dict(mamba_n_groups=3), "does not divide"),
    (dict(mamba_n_groups=8), "one group"),
    (dict(position_embedding_type="rope"), "position_embedding_type"),
    (dict(model_type="granite_of_another_kind"), "known: llama, mistral"),
])
def test_what_cannot_be_built_as_published_is_refused_not_guessed_at(tmp_path, changes, said):
    with pytest.raises(ValueError, match=said):
        config_from_hf_json(_published(tmp_path, **changes))


def test_a_published_head_dim_is_no_longer_ignored(tmp_path):
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"model_type": "mistral", "hidden_size": 64, "head_dim": 32,
                                "num_attention_heads": 4, "num_key_value_heads": 2,
                                "num_hidden_layers": 1, "vocab_size": 64, "intermediate_size": 128}))
    config = config_from_hf_json(str(path))
    assert config.head_dim == 32
    with init_empty_weights():
        model = model_factory_for_config(config)(config)
    assert model.params["layers"]["wq"].shape == (1, 64, 4 * 32)
    assert LlamaConfig(hidden_size=64, num_attention_heads=4).head_dim == 16


def test_the_whole_sequence_forward_agrees_with_the_reference_and_can_be_trained(tiny):
    model, cfg = tiny
    ids = np.random.default_rng(2).integers(0, 256, size=(2, 40)).astype(np.int32)
    out = model.apply_fn(model.params, input_ids=ids, labels=ids)
    for row in range(2):
        want = reference.logits_at(cfg, SEED, ids[row], 40, np.arange(40), "float32")
        np.testing.assert_allclose(out["logits"][row], want, atol=2e-5)
    grads = jax.grad(lambda p: model.apply_fn(p, input_ids=ids, labels=ids)["loss"])(model.params)
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))
    assert float(jnp.abs(grads["layers"]["mamba"]["A_log"]).max()) > 0


# -- serve --model-config ---------------------------------------------------------


def _serve_args(*flags):
    import argparse

    from accelerate_tpu.commands import serve

    cli = argparse.ArgumentParser()
    serve.add_parser(cli.add_subparsers())
    return serve, cli.parse_args(["serve", *flags])


def _published_small(tmp_path) -> str:
    """The published file at its rehearsal's widths."""
    with open(_published(tmp_path)) as f:
        d = json.load(f)
    small = {k: v for k, v in d["rehearsal"].items() if k not in ("serve_flags", "check")}
    return _published(tmp_path, **small)


def test_serve_builds_the_engine_of_a_published_config(tmp_path):
    """``serve --model-config`` with the rehearsal's widths: the model is
    the zoo's own mapping of the file, and the engine serves it through
    ``serve._make_engine`` as it serves a preset."""
    serve, args = _serve_args("--model-config", _published_small(tmp_path), "--num-slots", "2",
                              "--max-seq-len", "64", "--prefill-chunk", "16")
    engine = serve._make_engine(args)
    assert engine.stats()["state_layers"] == 3 and engine.stats()["prefix_cache"] is False
    request = engine.add_request(list(range(20)), 5)
    engine.run_until_idle()
    assert len(request.output_tokens) == 5


def test_serve_refuses_an_unknown_model_type_with_the_known_ones(tmp_path, capsys):
    serve, args = _serve_args("--model-config", _published(tmp_path, model_type="mamba9"))
    with pytest.raises(ValueError, match=r"unsupported model_type 'mamba9' \(known: llama, mistral"):
        serve._make_engine(args)


def test_serve_refuses_swap_for_a_model_with_slot_state(tmp_path):
    serve, args = _serve_args("--model-config", _published_small(tmp_path), "--swap-gb", "0.01")
    with pytest.raises(ValueError, match="swap_gb=0.01 is not supported"):
        serve._make_engine(args)


def test_auto_blocks_and_the_hbm_preflight_price_the_slot_state(tmp_path, capsys):
    """The state is a fixed cost beside the parameters: under one budget
    that does not reach full residency, twice the slots leave fewer
    blocks, by the added state's bytes over a block's."""
    blocks = {}
    for slots in (8, 16):
        serve, args = _serve_args(
            "--model-config", _published_small(tmp_path), "--num-slots", str(slots),
            "--max-seq-len", "512", "--prefill-chunk", "16", "--auto-blocks",
            "--hbm-gb", "0.002")
        engine = serve._make_engine(args)
        stats = engine.stats()
        assert "+ slot state" in capsys.readouterr().err
        assert engine.hbm_preflight["state_bytes"] == stats["state_bytes_total"] > 0
        blocks[slots] = engine.allocator.num_blocks
    fewer = 8 * stats["state_bytes_per_slot"] // stats["kv_bytes_per_block"]
    assert blocks[16] < blocks[8] < 8 * 32 + 1
    assert blocks[8] - blocks[16] in (fewer, fewer + 1)


@pytest.mark.parametrize("state_dtype, ssm_dtype, per_slot", [
    ("auto", "float32", 3 * (8 * 16 * 16 * 4 + 3 * 160 * 4)),
    ("bf16", "bfloat16", 3 * (8 * 16 * 16 * 2 + 3 * 160 * 4)),
])
def test_state_dtype_stores_the_recurrent_state_and_nothing_else(tiny, state_dtype, ssm_dtype, per_slot):
    """``state_dtype`` is the slot state's ``kv_dtype``: the recurrent state
    (the leaf the model keeps at a precision of its own) is stored at that
    width, the convolution's tail stays in the compute dtype (float32 here),
    and ``stats()`` prices what is held."""
    model, _ = tiny
    engine = _engine(model, state_dtype=state_dtype)
    assert str(engine._cache["ssm"].dtype) == ssm_dtype
    assert str(engine._cache["conv"].dtype) == "float32"
    s = engine.stats()
    assert s["state_dtype"] == ssm_dtype and s["state_bytes_per_slot"] == per_slot
    request = _ask(engine, list(range(3, 40)))
    engine.run_until_idle()
    assert len(request.output_tokens) == 12 and engine.stats()["decode_compiles"] == 1


def test_state_dtype_is_refused_where_there_is_no_state_and_where_it_is_no_width(tiny):
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    llama = LlamaForCausalLM.from_config(LlamaConfig.tiny())
    with pytest.raises(ValueError, match="keeps no per-slot state"):
        _engine(llama, state_dtype="bf16", logprobs_topn=0)
    with pytest.raises(ValueError, match="want one of auto, bf16"):
        _engine(tiny[0], state_dtype="fp8")


def test_serve_state_dtype_reaches_the_engine_and_auto_blocks(tmp_path, capsys):
    """``--state-dtype bf16`` halves what a slot's recurrent state costs, and
    ``--auto-blocks`` sizes the pool from that."""
    held = {}
    for width in ("auto", "bf16"):
        serve, args = _serve_args(
            "--model-config", _published_small(tmp_path), "--num-slots", "8",
            "--max-seq-len", "512", "--prefill-chunk", "16", "--auto-blocks",
            "--hbm-gb", "0.002", "--state-dtype", width)
        engine = serve._make_engine(args)
        capsys.readouterr()
        held[width] = (engine.stats()["state_bytes_per_slot"], engine.allocator.num_blocks,
                       engine.hbm_preflight["state_bytes"])
    assert held["bf16"][0] < held["auto"][0] and held["bf16"][1] > held["auto"][1]
    assert held["bf16"][2] == 8 * held["bf16"][0]


def test_a_request_preempted_before_its_first_token_starts_over_and_is_whole_again(tiny):
    """Recompute-preemption of a request still in prefill: nothing was
    emitted, so nothing is replayed; it is prefilled again from its first
    token, serves what an engine nobody disturbed serves, and neither flag
    outlives the re-admission."""
    model, cfg = tiny
    prompt = np.random.default_rng(3).integers(0, 256, size=40).tolist()
    engine = _engine(model, num_slots=2)
    request = _ask(engine, prompt)
    engine.step()  # one chunk of three has run
    assert request.prefill_pos == 16 and not request.output_tokens
    assert engine._preempt_by_recompute(request)
    assert request.recompute and request.preempted and request.blocks == [] and request.slot is None
    engine.run_until_idle()
    assert not request.recompute and not request.preempted and request.preemptions == 1
    calm = _ask(calm_engine := _engine(model, num_slots=2), prompt)
    calm_engine.run_until_idle()
    assert request.output_tokens == calm.output_tokens
    assert np.array_equal(_reported(request), _reported(calm))
    assert engine.stats()["state_resets_total"] == 2


def test_serve_preset_works_as_before():
    serve, args = _serve_args("--preset", "tiny", "--num-slots", "2", "--max-seq-len", "64")
    assert args.model_config is None
    model = serve._build_model(args)
    assert type(model.config).__name__ == "LlamaConfig" and model.config.hidden_size == 64
    engine = serve._make_engine(args)
    assert engine.stats()["prefix_cache"] is True and engine.stats()["state_layers"] == 0
