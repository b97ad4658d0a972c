"""DeepSeek-V3 through the serving engine (ISSUE 42): a latent (head-less)
paged pool attended in the absorbed form, a chip's share of the routed
experts under a group-limited sigmoid router with a shared expert, YaRN
rotation — held against the plain reference of
``perfbench/reference/deepseek_v3.py``: float32 at ``highest``, the EXPANDED
form (every head's keys and values made through ``W_kvb``), every held expert
over every token, no cache, nothing shared with the program.

All on the CPU at a small size with seeded weights (``perfbench.weights``,
the recipe the benchmark's check uses). Tolerances, each with its reason, are
beside the comparison they belong to.
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

import accelerate_tpu.models.deepseek_v3 as ds  # noqa: E402
from accelerate_tpu.big_modeling import init_empty_weights  # noqa: E402
from accelerate_tpu.models import (  # noqa: E402
    KNOWN_MODEL_TYPES,
    config_from_hf_json,
    model_factory_for_config,
)
from accelerate_tpu.models.cache import CacheSpec  # noqa: E402
from accelerate_tpu.ops.layers import yarn_frequencies, yarn_mscale  # noqa: E402
from accelerate_tpu.ops.moe import route  # noqa: E402
from accelerate_tpu.serving import EngineConfig, InferenceEngine  # noqa: E402
from accelerate_tpu.serving.flight import PART_NAMES  # noqa: E402
from accelerate_tpu.serving.sampling import SamplingParams  # noqa: E402
from perfbench import weights  # noqa: E402
from perfbench.reference import deepseek_v3 as reference  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_FILE = os.path.join(os.path.dirname(HERE), "perfbench", "configs",
                           "deepseek-v3-serve-v5e1.json")
SEED = 11
#: attention scores with a spread (the query's norm weight scales them), as
#: the benchmark's file
SCALES = {"layers.attn.q_norm": 1.6}
REFERENCE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace",
    "n_routed_experts", "router_experts", "first_held_expert", "n_shared_experts",
    "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob", "routed_scaling_factor",
    "rms_norm_eps", "rope_theta", "rope_scaling",
)

# float32 against float32, absorbed against expanded: what is left is the
# order of summation (q_nope (W_k c) against (q_nope W_k) c, the kernel's walk
# and the grouped product against plain einsums). Over these sequences it
# reads 2e-6; a rotation without YaRN's ramp reads 4e-2, the softmax scale
# without m^2 1e-1, a router that ignores its groups 2e-1
LOGPROB_TOLERANCE = 3e-5


def _reference_config(c) -> dict:
    return {**{k: getattr(c, k) for k in REFERENCE_KEYS}, "weight_scales": SCALES}


def _model(**kw):
    """The tiny model holding experts 0-3 of a router over 8 (2 groups of
    4), with the benchmark's seeded weights."""
    c = ds.DeepseekV3Config.tiny(**{"n_routed_experts": 4, "router_experts": 8, **kw})
    with init_empty_weights():
        model = ds.DeepseekV3ForCausalLM.from_config(c)
    model.params = weights.make_tree(SEED, model.params, dtype=jnp.float32, scales=SCALES)
    return model, c


@pytest.fixture(scope="module")
def tiny():
    return _model()


def _engine(model, **kw):
    geometry = dict(num_slots=4, max_seq_len=128, prefill_chunk=16, block_size=8,
                    logprobs_topn=1, decode_burst=4)
    geometry.update(kw)
    return InferenceEngine(model, EngineConfig(**geometry))


def _ask(engine, prompt, new_tokens=12, **sampling):
    return engine.add_request(list(prompt), new_tokens,
                              sampling=SamplingParams(logprobs=1, **sampling))


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


def test_apply_agrees_with_the_plain_reference(tiny):
    """``apply`` (whole sequences, expanded) against the reference's logits
    at every position: 3e-5 of a logit, float32 against float32 at
    ``highest`` (the products' order; reads 4e-6)."""
    model, c = tiny
    ids = np.random.default_rng(2).integers(0, 256, size=(1, 96)).astype(np.int32)
    got = np.asarray(model.apply_fn(model.params, input_ids=ids)["logits"][0])
    want = np.asarray(reference.logits_at(
        _reference_config(c), SEED, ids[0], 96, np.arange(96), "float32"))
    assert np.abs(got - want).max() < 3e-5


#: prompts inside one block (5), on a block's and a chunk's edge (16, 32), and
#: across several blocks and chunk boundaries (21, 37, 50, 77); seven prompts
#: over four slots, so slots are reused
PROMPTS = (37, 16, 5, 50, 21, 32, 77)


@pytest.fixture(scope="module")
def served(tiny):
    model, c = tiny
    engine = _engine(model)
    rng = np.random.default_rng(0)
    requests = {n: _ask(engine, rng.integers(0, 256, size=n).tolist()) for n in PROMPTS}
    engine.run_until_idle()
    return engine, requests, _reference_config(c)


@pytest.mark.parametrize("prompt_len", PROMPTS)
def test_chunked_prefill_then_decode_through_the_latent_pool_agree_with_the_full_forward(
        served, prompt_len):
    """Absorbed against expanded: the engine's chunks of 16 and bursts of 4
    one-token steps over blocks of 8, against ONE full forward of the
    reference over prompt and served tokens."""
    _, requests, cfg = served
    r = requests[prompt_len]
    want, is_best = _reference_logprobs(cfg, r)
    assert is_best.all()
    assert np.abs(_reported(r) - want).max() < LOGPROB_TOLERANCE


def test_one_decode_and_one_prefill_executable_and_what_stats_says(served):
    engine, _, _ = served
    s = engine.stats()
    assert s["decode_compiles"] == 1 and s["prefill_compiles"] == 1
    assert "retrace_report" not in s
    assert s["prefix_cache"] is True and s["kv_layers"] == 4 and s["state_layers"] == 0
    # 32 + 8 = 40 values a token and layer, stored in a whole tile of 128 lanes
    assert s["latent_rank"] == 32
    assert s["latent_bytes_per_token"] == s["kv_bytes_per_token"] == 4 * 128 * 4
    assert set(engine._cache) == {"k"} and engine._cache["k"].shape[-1] == 128
    assert (s["moe_layers"], s["moe_experts"], s["moe_top_k"], s["moe_router_experts"]) == (
        3, 4, 2, 8)
    pairs = np.asarray(s["moe_expert_pairs"])
    assert pairs.shape == (3, 4) and pairs.sum() == s["moe_pairs_routed_total"]
    # every routed layer saw the same live tokens, two pairs each, here or elsewhere
    routed = s["moe_pairs_routed_total"] + s["moe_pairs_elsewhere_total"]
    assert s["moe_pairs_elsewhere_total"] > 0 and routed % (3 * 2) == 0
    assert s["moe_experts_touched_total"] <= s["moe_dispatches_total"] * 3 * 4
    assert s["paged_entries_walked_total"] > 0 and s["paged_tiles_walked_total"] > 0
    engine.reset_stats()
    z = engine.stats()
    assert z["moe_pairs_routed_total"] == z["moe_pairs_elsewhere_total"] == 0
    assert z["paged_entries_walked_total"] == 0 and z["latent_bytes_per_token"] == 2048


def test_paged_tiles_are_counted_at_the_tiles_the_latent_kernel_takes(tiny, monkeypatch):
    """``paged_tiles_walked_total x paged_tile_entries`` against the entries
    a served prompt's chunks and its decode steps walked, rounded up to the
    tile the kernel really takes at each shape (read off a trace of the
    Pallas route at the engine's decode and chunk shapes, not off the
    constant): a table of 64 entries of 8 is wider than the tile, a prompt of
    300 tokens walks 38 of them, and the free slot walks one."""
    pa = sys.modules["accelerate_tpu.ops.paged_attention"]
    model, c = tiny
    bs, chunk, burst, slots = 8, 16, 4, 2
    engine = _engine(model, num_slots=slots, max_seq_len=512, block_size=bs,
                     prefill_chunk=chunk, decode_burst=burst, prefix_cache=False)
    mb, layers, reported = 64, 4, engine.stats()["paged_tile_entries"]
    assert engine.config.blocks_per_slot == mb > reported

    taken, real = {}, pa._latent_kernel

    def spy(*refs, tile, s, **kw):
        taken[s] = tile
        return real(*refs, tile=tile, s=s, **kw)

    monkeypatch.setattr(pa, "_latent_kernel", spy)
    width, shaped = engine._cache["k"].shape[-1], jax.ShapeDtypeStruct
    for b, s in ((slots, 1), (1, chunk)):
        jax.eval_shape(
            lambda q, pool, bt, idx: pa.latent_attention(
                q, pool, 0, bt, idx, rank=c.kv_lora_rank, scale=1.0, impl="pallas",
                interpret=True),
            shaped((b, s, c.num_attention_heads, width), jnp.float32),
            shaped(engine._cache["k"].shape, jnp.float32),
            shaped((b, mb), jnp.int32), shaped((b,), jnp.int32))
    assert set(taken) == {1, chunk} and all(t % reported == 0 for t in taken.values())

    seen = {"prefill": [], "decode": []}

    def recorded(kind, fn):
        def call(*args):
            seen[kind].append(np.array(args[3]))
            return fn(*args)
        return call

    engine._prefill_fn = recorded("prefill", engine._prefill_fn)
    engine._decode_fn = recorded("decode", engine._decode_fn)
    request = _ask(engine, np.random.default_rng(5).integers(0, 256, size=300).tolist(), 12)
    engine.run_until_idle()
    assert len(request.output_tokens) == 12 and len(seen["prefill"]) == 19

    walked = covered = 0
    rows = [((int(p[0]) + chunk - 1) // bs + 1, chunk) for p in seen["prefill"]]
    rows += [((int(p) + step) // bs + 1, 1)
             for pos0 in seen["decode"] for step in range(burst) for p in pos0]
    for entries, s in rows:
        walked += layers * entries
        covered += layers * -(-entries // taken[s]) * taken[s]
    stats = engine.stats()
    assert stats["paged_entries_walked_total"] == walked
    assert stats["paged_tiles_walked_total"] * reported == covered
    assert walked < covered < 3 * walked  # two tiles for 38 entries; a free slot's one


def test_no_compile_at_a_context_the_warm_up_never_saw(tiny):
    """Warmed as the benchmark's driver warms an engine (one prompt of
    ``prefill_chunk + 5`` tokens), then contexts several times as long, more
    rows, other occupancies: the same two executables."""
    model, _ = tiny
    engine = _engine(model, max_seq_len=256)
    engine.add_request(list(range(21)), 8)
    engine.run_until_idle()
    engine.reset_stats()
    before = {k: engine.stats()[k] for k in ("decode_compiles", "prefill_compiles")}
    rng = np.random.default_rng(3)
    requests = [_ask(engine, rng.integers(0, 256, size=n).tolist(), m)
                for n, m in ((200, 30), (3, 5), (120, 17), (64, 9), (90, 40))]
    engine.run_until_idle()
    assert all(r.finish_reason == "length" for r in requests)
    s = engine.stats()
    assert {k: s[k] for k in before} == before == {"decode_compiles": 1, "prefill_compiles": 1}
    assert "retrace_report" not in s


def test_the_phases_named_parts_cover_the_new_step(tiny):
    """PR 40's ``serve/<phase>/<part>`` spans come of the engine's loop, not
    of a model: a latent model's iterations carry every one of them."""
    model, _ = tiny
    engine = _engine(model, flight_history=64)
    _ask(engine, range(40), 9)
    engine.run_until_idle()
    entries = engine._flight.tail(64)
    assert {name for e in entries for name, _, _ in e["parts"]} == set(PART_NAMES)
    assert all("moe_pairs_elsewhere_total" in e["counters"] for e in entries)


# -- the pool's precision ---------------------------------------------------------


def test_an_fp8_latent_pool_moves_the_log_probabilities_far_more_than_the_tolerance(tiny):
    """The benchmark's control: ``kv_dtype="fp8"`` keeps each row in 8 bits
    under one amax scale (``ops/fp8.py``). At float32 otherwise the served
    log-probabilities move by 1e-2 and more: hundreds of times the room
    between a sound run (2e-6) and the tolerance."""
    model, c = tiny
    prompt = np.random.default_rng(4).integers(0, 256, size=45).tolist()
    cold, narrow = _engine(model), _engine(model, kv_dtype="fp8")
    a, b = _ask(cold, prompt, 8), _ask(narrow, prompt, 8)
    cold.run_until_idle()
    narrow.run_until_idle()
    assert set(narrow._cache) == {"k", "k_scale"}
    assert narrow._cache["k"].dtype == jnp.float8_e4m3fn
    assert narrow._cache["k_scale"].shape == (*narrow._cache["k"].shape[:3], 1)
    s = narrow.stats()
    assert s["kv_dtype"] == "float8_e4m3fn" and s["latent_bytes_per_token"] == 4 * (128 + 4)
    n = min(i for i, (x, y) in enumerate(zip(a.output_tokens + [-1], b.output_tokens + [-2]))
            if x != y)  # the tokens in common: the same conditioning
    moved = np.abs(_reported(a)[:max(n, 1)] - _reported(b)[:max(n, 1)]).max()
    assert moved > 100 * LOGPROB_TOLERANCE
    want, _ = _reference_logprobs(_reference_config(c), a)
    assert np.abs(_reported(a) - want).max() < LOGPROB_TOLERANCE


# -- blocks are the whole of a request's past ---------------------------------------


def test_a_prefix_cache_hit_serves_the_same_logits(tiny):
    """A latent block depends on nothing past its end. A second request with
    the same prompt maps the cached blocks; a third shares 21 tokens of it, a
    hit that ends inside a block, copied on write (the one pool leaf); both
    serve what a cold engine serves."""
    model, c = tiny
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, 256, size=38).tolist()
    fork = prompt[:21] + rng.integers(0, 256, size=12).tolist()
    cold = []
    for p in (prompt, fork):
        engine = _engine(model)
        cold.append(_ask(engine, p, 10))
        engine.run_until_idle()
    engine = _engine(model)
    first = _ask(engine, prompt, 10)
    engine.run_until_idle()
    again, forked = _ask(engine, prompt, 10), _ask(engine, fork, 10)
    engine.run_until_idle()
    assert again.matched_tokens == 32 and forked.matched_tokens == 21  # whole blocks; 2 + 5/8
    assert engine.stats()["prefix_hit_tokens"] == 53
    for got, want in ((first, cold[0]), (again, cold[0]), (forked, cold[1])):
        assert got.output_tokens == want.output_tokens
        np.testing.assert_allclose(_reported(got), _reported(want), rtol=0, atol=1e-6)
        ref, is_best = _reference_logprobs(_reference_config(c), got)
        assert is_best.all() and np.abs(_reported(got) - ref).max() < LOGPROB_TOLERANCE


def test_a_preempted_request_is_recomputed_and_continues_with_the_same_tokens(tiny):
    """A pool too small for three growing requests (no swap tier for a latent
    pool): one gives its blocks back, re-queues, and is prefilled again over
    prompt and emitted tokens; what it serves is what it serves alone."""
    model, c = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (30, 27, 25)]
    alone = []
    for p in prompts:
        engine = _engine(model, max_seq_len=96)
        alone.append(_ask(engine, p, 40))
        engine.run_until_idle()
    engine = _engine(model, num_slots=3, num_blocks=17, max_seq_len=96)
    requests = [_ask(engine, p, 40) for p in prompts]
    engine.run_until_idle()
    s = engine.stats()
    assert s["preemptions"] >= 1 and s["out_of_blocks_total"] == 0 and s["decode_compiles"] == 1
    for r, lone in zip(requests, alone):
        assert r.output_tokens == lone.output_tokens and r.finish_reason == "length"
        want, is_best = _reference_logprobs(_reference_config(c), r)
        assert is_best.all() and np.abs(_reported(r) - want).max() < LOGPROB_TOLERANCE


def test_a_grammar_constrains_a_latent_model_like_any_other(tiny):
    """The grammar lanes live in ``pick_tokens``, past the model: a request
    held to digits serves digits."""
    model, _ = tiny
    engine = _engine(model, grammar_slots=1, logprobs_topn=0)
    r = engine.add_request(list(range(20)), 6, grammar={"type": "regex", "pattern": "[0-9]+"})
    engine.run_until_idle()
    assert len(r.output_tokens) == 6 and all(chr(t).isdigit() for t in r.output_tokens)
    assert engine.stats()["grammar_masked_steps"] == 6


# -- the shares add up ---------------------------------------------------------------


def test_every_ranks_routed_part_and_the_shared_expert_once_equal_the_uncut_layer():
    """Two ranks of ``ep`` = 2 hold experts 0-3 and 4-7 of one routed layer.
    Each computes the shared expert and its own experts' part for the pairs
    routed to them; the ranks' routed parts, with the shared expert counted
    once, are the uncut layer — the program's and the reference's. The pairs
    a rank leaves out are the pairs the other one computes."""
    _, whole = _model(n_routed_experts=8, router_experts=8)
    with init_empty_weights():
        model = ds.DeepseekV3ForCausalLM.from_config(whole)
    stack = weights.make_tree(SEED, model.params, dtype=jnp.float32)["layers"]["moe"]
    norm = jnp.ones((64,), jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 64)), jnp.float32)
    live = jnp.asarray(np.random.default_rng(2).random((2, 24)) < 0.8)

    uncut, pairs, nowhere = ds._routed_ff(whole, stack, norm, 1, x, live)
    assert int(nowhere) == 0 and int(pairs.sum()) == int(live.sum()) * 2
    y = np.asarray(x).reshape(48, 64)
    y = y / np.sqrt((y * y).mean(-1, keepdims=True) + whole.rms_norm_eps)
    g, u = np.split(y @ np.asarray(stack["shared_in"][1]), 2, axis=-1)
    shared = ((g / (1 + np.exp(-g))) * u @ np.asarray(stack["shared_out"][1])).reshape(2, 24, 64)

    parts, given = [], []
    for rank in range(2):
        c = dataclasses.replace(whole, n_routed_experts=4, first_held_expert=4 * rank)
        share = {**stack, "w_in": stack["w_in"][:, 4 * rank:4 * rank + 4],
                 "w_out": stack["w_out"][:, 4 * rank:4 * rank + 4]}
        out, held, elsewhere = ds._routed_ff(c, share, norm, 1, x, live)
        parts.append(np.asarray(out - x) - shared)
        given.append((np.asarray(held), int(elsewhere)))
    assert np.abs(sum(parts) + shared - np.asarray(uncut - x)).max() < 2e-5
    assert np.array_equal(np.concatenate([given[0][0], given[1][0]]), np.asarray(pairs))
    assert given[0][1] == given[1][0].sum() and given[1][1] == given[0][0].sum()

    # and the reference's uncut layer, live rows (a dead lane routes nowhere: its
    # routed part is 0 in the program, and the reference knows no lanes)
    cfg = _reference_config(whole)
    w = {name: jnp.asarray(stack[name][1]) for name in reference.MOE_LEAVES}
    want = np.asarray(reference.routed_ff(cfg, w, jnp.asarray(y))).reshape(2, 24, 64)
    mask = np.asarray(live)
    assert np.abs(np.asarray(uncut - x)[mask] - want[mask]).max() < 2e-5


# -- the grouped router --------------------------------------------------------------


def _plain_router(logits, bias, k, n_group, topk_group, scaling):
    """``numpy``, a token at a time, ties to the lower index."""
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    experts, weights_ = [], []
    for s in scores:
        b = s + bias
        groups = b.reshape(n_group, -1)
        marks = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        kept = np.argsort(-marks, kind="stable")[:topk_group]
        masked = np.where(np.isin(np.arange(n_group), kept)[:, None], groups, 0.0).reshape(-1)
        chosen = np.argsort(-masked, kind="stable")[:k]
        experts.append(chosen)
        weights_.append(s[chosen] / (s[chosen].sum() + 1e-20) * scaling)
    return np.asarray(experts), np.asarray(weights_)


def test_the_grouped_router_against_a_plain_one():
    """32 experts in 8 groups of 4, the best 3 groups kept, top 4: the same
    experts in the same order with the same weights as a plain ``numpy``
    router; no expert outside the kept groups; the bias moves the choice and
    not the weights."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(200, 16)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    bias = rng.normal(size=(32,)).astype(np.float32) * 0.3
    logits = np.asarray(jnp.dot(x, gate, precision=jax.lax.Precision.HIGHEST))
    experts, w = route(x, gate, jnp.asarray(bias), 4, True, 2.5, n_group=8, topk_group=3,
                       norm_eps=1e-20)
    want_e, want_w = _plain_router(logits, bias.astype(np.float64), 4, 8, 3, 2.5)
    assert np.array_equal(np.asarray(experts), want_e)
    assert np.abs(np.asarray(w) - want_w).max() < 1e-6
    assert (np.asarray(w).sum(axis=1) - 2.5 < 1e-5).all()
    # at most 3 groups a token
    assert max(len(set(row // 4)) for row in np.asarray(experts)) <= 3
    # without the bias other experts are chosen, and an expert chosen both times
    # has the score-only weight up to the renormalisation over its set
    plain_e, _ = route(x, gate, None, 4, False, 1.0, n_group=8, topk_group=3)
    biased_e, biased_w = route(x, gate, jnp.asarray(bias), 4, False, 1.0, n_group=8,
                               topk_group=3)
    assert not np.array_equal(np.asarray(plain_e), np.asarray(biased_e))
    scores = 1.0 / (1.0 + np.exp(-logits))
    assert np.abs(np.take_along_axis(scores, np.asarray(biased_e), 1)
                  - np.asarray(biased_w)).max() < 1e-6
    # ungrouped, the groups kept are all of them
    a = route(x, gate, jnp.asarray(bias), 4, True, 2.5, n_group=8, topk_group=8)
    b = route(x, gate, jnp.asarray(bias), 4, True, 2.5)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))


# -- the rotation ---------------------------------------------------------------------


def test_yarn_frequencies_and_the_scale_against_closed_form_values():
    """DeepSeek-V3's numbers: 64 rope lanes, theta 1e4, factor 40 over 4096,
    beta 32 / 1. The ramp runs from pair 10 to pair 23; the fast pairs keep
    ``theta^(-2i/64)``, the slow ones are stretched 40 x; ``m = 0.1 ln 40 + 1``
    and the softmax scale is ``192^-0.5 m^2``."""
    f = yarn_frequencies(64, 10000.0, 40, 4096, 32, 1)
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    lo = int(np.floor(64 * np.log(4096 / (2 * np.pi * 32)) / (2 * np.log(10000.0))))
    hi = int(np.ceil(64 * np.log(4096 / (2 * np.pi * 1)) / (2 * np.log(10000.0))))
    assert (lo, hi) == (10, 23)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-12)
    mid = 16
    ramp = (mid - 10) / 13
    np.testing.assert_allclose(f[mid], plain[mid] * (ramp / 40 + 1 - ramp), rtol=1e-12)
    assert (np.diff(f) < 0).all()
    np.testing.assert_allclose(reference.yarn_inv_freq(
        {"qk_rope_head_dim": 64, "rope_theta": 10000,
         "rope_scaling": {"factor": 40, "original_max_position_embeddings": 4096,
                          "beta_fast": 32, "beta_slow": 1}}), f, rtol=1e-12)
    m = yarn_mscale(40, 1)
    assert abs(m - 1.3688879454113936) < 1e-12 and yarn_mscale(40, 0) == yarn_mscale(1, 1) == 1
    c = config_from_hf_json(CONFIG_FILE)
    assert abs(c.softmax_scale - 192 ** -0.5 * m * m) < 1e-15
    assert abs(c.softmax_scale - 0.13523) < 1e-5
    assert ds.DeepseekV3Config.tiny(rope_scaling=None).softmax_scale == 24 ** -0.5
    # the angles are made from absolute positions: position 100,000 is no table row
    x = jnp.ones((1, 1, 8), jnp.float32)
    far = ds._rope(ds.DeepseekV3Config.tiny(), x, jnp.asarray([[100_000]]))
    assert np.isfinite(np.asarray(far)).all() and abs(float((far * far).sum()) - 8) < 1e-4


# -- the published file, the pool's bytes ----------------------------------------------


def _published(tmp_path, **changes):
    with open(CONFIG_FILE) as f:
        d = json.load(f)
    d.update(changes)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(d))
    return str(path)


def test_the_benchmarks_config_builds_the_chips_share_of_the_published_model(tmp_path):
    assert "deepseek_v3" in KNOWN_MODEL_TYPES
    c = config_from_hf_json(CONFIG_FILE)
    assert type(c).__name__ == "DeepseekV3Config"
    assert (c.hidden_size, c.num_attention_heads, c.q_lora_rank, c.kv_lora_rank) == (
        7168, 128, 1536, 512)
    assert (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) == (128, 64, 128)
    assert (c.n_dense, c.n_moe, c.n_routed_experts, c.router_experts, c.held) == (
        1, 4, 16, 256, (0, 16))
    assert (c.n_group, c.topk_group, c.num_experts_per_tok, c.routed_scaling_factor) == (
        8, 4, 8, 2.5)
    with init_empty_weights():
        model = model_factory_for_config(c)(c)
    shapes = {k: tuple(a.shape) for k, a in weights.flat_names(model.params).items()}
    with open(CONFIG_FILE) as f:
        assert shapes == reference.leaf_shapes(json.load(f))
    # ISSUE 42's arithmetic: 4,565,630,976 matrix parameters, and the norms' vectors
    # and the selection bias beside them
    vectors = sum(int(np.prod(s)) for k, s in shapes.items()
                  if k.endswith(("_norm", "norm", "expert_bias")))
    assert sum(int(np.prod(s)) for s in shapes.values()) - vectors == 4_565_630_976
    spec = model.cache_spec
    assert spec == CacheSpec(paged_layers=5, kv_heads=1, head_dim=576, latent_rank=512)
    assert spec.pool_leaves == ("k",) and spec.pool_width == 640
    # 576 values a token and layer kept 640 wide: 6,400 B in bfloat16 (5,760 B of
    # values); the fp8 pool a byte a lane and one float32 scale a row
    assert spec.bytes_per_token(jnp.bfloat16) == 5 * 640 * 2 == 6400
    assert spec.bytes_per_token(jnp.float8_e4m3fn, quantized=True) == 5 * (640 + 4)
    # the uncut published file: 61 layers, 256 experts held, the whole vocabulary
    whole = config_from_hf_json(_published(
        tmp_path, num_hidden_layers=61, first_k_dense_replace=3, n_routed_experts=256,
        vocab_size=129280))
    assert (whole.n_dense, whole.n_moe, whole.held) == (3, 58, None)


def test_a_latent_spec_is_one_vector_for_all_heads():
    with pytest.raises(ValueError, match="one vector a token for all heads: kv_heads 8"):
        CacheSpec(paged_layers=2, kv_heads=8, head_dim=128, latent_rank=64)
    plain = CacheSpec(paged_layers=8, kv_heads=8, head_dim=128)
    assert plain.pool_leaves == ("k", "v") and plain.pool_width == 1024
    assert plain.bytes_per_token(jnp.bfloat16) == 32768  # the Mistral cell's
    assert plain.bytes_per_token(jnp.int8, quantized=True) == 2 * 8 * 8 * (128 + 4)


def test_preflight_and_auto_blocks_price_the_latent_pool_from_shapes(tmp_path, capsys):
    """At the published widths, shapes only: the pre-flight books ONE pool of
    rows 640 wide (not two of 576), refuses a budget the cell's geometry does
    not fit, and ``--auto-blocks`` sizes the pool from the same bytes."""
    import argparse

    from accelerate_tpu.analysis.shardplan import engine_preflight, plan_kv_pool
    from accelerate_tpu.commands import serve

    c = config_from_hf_json(CONFIG_FILE)
    with init_empty_weights():
        model = model_factory_for_config(c)(c, dtype=jnp.bfloat16)
    spec = model.cache_spec
    blocks = 40 * 1024 + 1
    shape = (spec.paged_layers, blocks, 16, spec.pool_width)
    report = engine_preflight(model.params, model.partition_rules, None, shape, 1,
                              jnp.bfloat16, 15.75, pool_leaves=spec.pool_leaves)
    assert report["pool_bytes"] == blocks * 16 * 6400 == 4_194_406_400
    assert report["params_bytes"] == 2 * sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(model.params))
    assert 9.13e9 < report["params_bytes"] < 9.14e9 and not report["over"]
    assert engine_preflight(model.params, model.partition_rules, None, shape, 1,
                            jnp.bfloat16, 12.0, pool_leaves=spec.pool_leaves)["over"]
    plans = plan_kv_pool(5, 1, 640, 1, 16, 16384, {}, num_blocks=1, dtype="fp8",
                         pool_leaves=("k",))
    assert [(p.path, p.bytes_per_device) for p in plans] == [
        ("kv_pool.k", 5 * 16 * 640), ("kv_pool.k_scale", 5 * 16 * 4)]

    cli = argparse.ArgumentParser()
    serve.add_parser(cli.add_subparsers())
    args = cli.parse_args(["serve", "--model-config", CONFIG_FILE, "--dtype", "bf16",
                           "--num-slots", "40", "--max-seq-len", "16384", "--hbm-gb", "13.0",
                           "--auto-blocks"])
    n = serve._auto_num_blocks(args, model, None)
    # 13 GiB less 5 % less 9.13 GB of parameters, in blocks of 16 x 6,400 B
    want = (int(13.0 * (1 << 30) * 0.95) - report["params_bytes"]) // (16 * 6400)
    assert n == want and 40_000 < n < 40 * 1024 + 1
    assert "0.10 MB/block/device" in capsys.readouterr().err


# -- what is refused, and why ------------------------------------------------------------


@pytest.mark.parametrize("changes, said", [
    (dict(scoring_func="softmax"), "scoring_func 'softmax': built as published"),
    (dict(topk_method="greedy"), "topk_method 'greedy'"),
    (dict(rope_scaling={"type": "linear", "factor": 4}), "rope_scaling type 'linear': only 'yarn'"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "lacks .'original_max_position_embeddings'"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings True"),
    (dict(attention_bias=True), "attention_bias True"),
    (dict(moe_layer_freq=2), "moe_layer_freq 2"),
    (dict(n_group=7), "n_group 7 / topk_group 4 of 256 router outputs"),
    (dict(num_experts_per_tok=200), "num_experts_per_tok 200"),
    (dict(first_held_expert=250), "experts 250..265 held of a router over 256"),
    (dict(first_k_dense_replace=9), "first_k_dense_replace 9 of num_hidden_layers 5"),
])
def test_what_cannot_be_built_as_published_is_refused_by_name(tmp_path, changes, said):
    with pytest.raises(ValueError, match=said):
        config_from_hf_json(_published(tmp_path, **changes))


@pytest.mark.parametrize("geometry, said", [
    (dict(swap_gb=0.01), "swap_gb=0.01 is not supported .* one latent vector of 40 values .* "
                         "mirrors a K and a V row per kv head"),
    (dict(kv_dtype="int8"), "kv_dtype=int8 is not supported .* fp8 .* is the quantized latent pool"),
    (dict(spec_k=2, logprobs_topn=0), "declares no early_exit_apply"),
    (dict(denoise_steps=2), "only a model that declares block_decode"),
    (dict(state_dtype="bf16"), "keeps no per-slot state"),
])
def test_what_the_engine_refuses_at_bring_up(tiny, geometry, said):
    with pytest.raises(ValueError, match=said):
        _engine(tiny[0], **geometry)


def test_a_mesh_is_refused_for_a_latent_pool(tiny):
    from jax.sharding import Mesh

    from accelerate_tpu.parallel.sharding import paged_kv_sharding

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 1, 1, 1, 1, 2),
                ("dp", "pp", "fsdp", "ep", "cp", "tp"))
    with pytest.raises(ValueError, match="mesh= is not supported .* no kv head to shard over tp"):
        InferenceEngine(tiny[0], EngineConfig(num_slots=2, max_seq_len=64), mesh=mesh)
    with pytest.raises(ValueError, match="no kv head to put over tp"):
        paged_kv_sharding(mesh, 1, latent=True)


def test_serve_builds_the_engine_of_the_published_config(tmp_path):
    """``serve --model-config`` with the benchmark's file at tiny sizes:
    ``config_from_hf_json`` and ``model_factory_for_config``, no wrapper."""
    import argparse

    from accelerate_tpu.commands import serve

    with open(CONFIG_FILE) as f:
        small = json.load(f)["rehearsal"]
    small = {k: v for k, v in small.items() if k not in ("serve_flags", "check")}
    cli = argparse.ArgumentParser()
    serve.add_parser(cli.add_subparsers())
    args = cli.parse_args(["serve", "--model-config", _published(tmp_path, **small),
                           "--num-slots", "2", "--max-seq-len", "64", "--prefill-chunk", "16"])
    engine = serve._make_engine(args)
    request = engine.add_request(list(range(21)), 6)
    engine.run_until_idle()
    s = engine.stats()
    assert len(request.output_tokens) == 6 and s["decode_compiles"] == 1
    assert (s["kv_layers"], s["latent_rank"], s["moe_experts"], s["moe_router_experts"]) == (
        3, 32, 4, 8)
    assert set(engine._cache) == {"k"}
