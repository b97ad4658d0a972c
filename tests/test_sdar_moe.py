"""SDAR-30B-A3B-Chat through the serving engine (ISSUE 38): a decode round
that commits a block of four tokens a sequence — denoise passes, then a
clean pass that writes the cache — over block-causal paged attention and 128
routed experts under a softmax router, held against the plain reference of
``perfbench/reference/sdar_moe.py``: float32 at ``highest``, every expert
over every token, no cache, no rounds, nothing shared with the program, and
for every served token the conditioning its denoise pass saw.

All on the CPU at a small size with seeded weights (``perfbench.weights``,
the recipe the benchmark's check uses). Tolerances, each with its reason,
are beside the comparison they belong to.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import accelerate_tpu.models.sdar_moe as sdar  # noqa: E402
from accelerate_tpu.big_modeling import init_empty_weights  # noqa: E402
from accelerate_tpu.models import (  # noqa: E402
    KNOWN_MODEL_TYPES,
    LlamaConfig,
    LlamaForCausalLM,
    config_from_hf_json,
    model_factory_for_config,
)
from accelerate_tpu.serving import EngineConfig, InferenceEngine  # noqa: E402
from accelerate_tpu.serving.sampling import SamplingParams  # noqa: E402
from perfbench import weights  # noqa: E402
from perfbench.reference import sdar_moe as reference  # noqa: E402

SEED = 7
#: an embedding as loud as the residual's updates (the mask token's row must
#: not drown), and attention scores with a spread (q is normed, so its
#: norm's weight scales them)
SCALES = {"layers.q_norm": 3.0}
PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "norm_topk_prob", "rms_norm_eps", "rope_theta",
    "block_length", "mask_token_id",
)
#: the catalog's ``config`` of SDAR-30B-A3B-Chat, every key of it
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


def _reference_config(c, denoise_steps) -> dict:
    cfg = {k: getattr(c, k) for k in PUBLISHED_KEYS}
    return {**cfg, "denoise_steps": denoise_steps, "weight_scales": SCALES}


def _model(**kw):
    c = sdar.SdarMoeConfig.tiny(**kw)
    with init_empty_weights():
        model = sdar.SdarMoeForCausalLM.from_config(c)
    model.params = weights.make_tree(SEED, model.params, dtype=jnp.float32, scales=SCALES)
    return model, c


@pytest.fixture(scope="module")
def tiny():
    return _model()


def _engine(model, **kw):
    geometry = dict(num_slots=4, max_seq_len=128, prefill_chunk=16, block_size=8,
                    logprobs_topn=1, decode_burst=2, denoise_steps=2)
    geometry.update(kw)
    return InferenceEngine(model, EngineConfig(**geometry))


def _ask(engine, prompt, new_tokens=13, **sampling):
    return engine.add_request(list(prompt), new_tokens,
                              sampling=SamplingParams(logprobs=1, **sampling))


def _reference_logprobs(cfg, request):
    """The reference over prompt + served tokens: the log-probability of
    every served token under the conditioning of the pass that fixed it,
    and whether it was the best there."""
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


#: prompts that end on a block's edge (16, 20), one, two and three positions
#: into a block (5, 37; 50, 2; 23), shorter than a block (2), and spanning
#: several chunks; seven prompts over four slots, so slots are reused
PROMPTS = (37, 16, 5, 50, 23, 2, 20)

# float32 against float32: what is left is the order of summation (the
# grouped product and the paged kernel's walk against plain einsums). Over
# these sequences it reads 3e-6; a block served under the causal mask reads
# 2e-1, a commit pass left out 3e-1, a softmax router scored as a sigmoid 1e-1
LOGPROB_TOLERANCE = 3e-5


@pytest.fixture(scope="module", params=[1, 2, 4], ids=lambda t: f"T{t}")
def served(request, tiny):
    """The prompts through one engine at ``denoise_steps`` T (1: a block a
    pass; 2; 4 = B: a token a pass), 13 new tokens each: every answer ends
    one position into a block."""
    model, c = tiny
    engine = _engine(model, denoise_steps=request.param)
    rng = np.random.default_rng(0)
    requests = {n: _ask(engine, rng.integers(0, 250, size=n).tolist()) for n in PROMPTS}
    engine.run_until_idle()
    return engine, requests, _reference_config(c, request.param), request.param


@pytest.mark.parametrize("prompt_len", PROMPTS)
def test_prefill_then_rounds_agree_with_the_reference_conditioning(served, prompt_len):
    _, requests, cfg, _ = served
    request = requests[prompt_len]
    want, is_best = _reference_logprobs(cfg, request)
    assert len(request.output_tokens) == 13 and request.finish_reason == "length"
    assert is_best.all()
    assert np.abs(_reported(request) - want).max() < LOGPROB_TOLERANCE


def test_one_decode_and_one_prefill_executable_and_what_stats_counts(served):
    engine, requests, _, t = served
    s = engine.stats()
    assert s["decode_compiles"] == 1 and s["prefill_compiles"] == 1
    assert "retrace_report" not in s
    assert (s["block_length"], s["denoise_steps"]) == (4, t)
    # tokens as emitted, forwards as run; nothing derived from the other
    assert s["block_tokens_emitted_total"] == s["tokens_emitted"] == 13 * len(PROMPTS)
    assert s["block_forwards_total"] == s["block_rounds_total"] * (t + 1)
    assert s["block_denoise_forwards_total"] == s["block_rounds_total"] * t
    assert s["block_commit_forwards_total"] == s["block_rounds_total"]
    assert s["block_rounds_total"] % engine.config.decode_burst == 0
    # every request's rounds commit 16 positions of which 13 + the prompt's
    # tail are kept; whole bursts run, so more is committed than that
    assert s["block_positions_committed_total"] >= s["block_tokens_emitted_total"]
    assert s["block_slot_forwards_total"] >= 4 * (t + 1) * len(PROMPTS)
    # the model counts a dispatch a forward: every round's and every chunk's
    chunks = sum(-(-(n // 4 * 4) // 16) for n in PROMPTS)
    assert s["moe_dispatches_total"] == s["block_forwards_total"] + chunks
    assert (s["moe_layers"], s["moe_experts"], s["moe_top_k"]) == (3, 8, 2)
    assert s["block_round_refuses"].keys() == {"grammar", "spec_k", "repetition_penalty", "mesh"}
    assert s["prefix_cache"] is True


@pytest.mark.parametrize("tile, chunk", [(2, 16), (8, 16), (8, 144)])
def test_block_rounds_book_the_paged_kernels_tiles(tiny, tile, chunk, monkeypatch):
    """``paged_tiles_walked_total`` under block-round dispatch, against the
    positions the executables were handed: every forward of every round
    (``denoise_steps`` + 1 a round) asks a block of queries a row from the
    block's start, a chunk its whole blocks, and a row's entries are taken
    ``tile`` a softmax step (clamped to the table's width), in every layer.
    A chunk of 144 queries x 2 heads a kv head is more than one block of
    stacked rows: its steps are ``_CHUNK_TILE`` entries wide (the table's 64
    allow it), booked as the tiles they hold and counted as
    ``paged_chunk_steps_total``; a round's four queries never are."""
    import importlib

    ops = importlib.import_module("accelerate_tpu.ops.paged_attention")
    monkeypatch.setattr(ops, "_TILE", tile)
    engine = _engine(tiny[0], prefill_chunk=chunk, max_seq_len=512 if chunk > 16 else 128)
    bs, burst, b, t = engine.config.block_size, engine.config.decode_burst, 4, 2
    mb, layers = engine.config.blocks_per_slot, engine.stats()["kv_layers"]
    seen = {"prefill": [], "decode": []}

    def recorded(kind, fn):
        def call(*args):
            seen[kind].append((np.array(args[3]), np.shape(args[4])[-1]))
            return fn(*args)
        return call

    engine._prefill_fn = recorded("prefill", engine._prefill_fn)
    engine._decode_fn = recorded("decode", engine._decode_fn)
    rng = np.random.default_rng(1)
    for n in (37, 5, 50) if chunk == 16 else (330, 5):
        _ask(engine, rng.integers(0, 250, size=n).tolist())
    engine.run_until_idle()
    width = min(tile, mb)
    wide = min(ops._CHUNK_TILE, mb) if 2 * chunk > ops._ROW_BLOCK else width
    walked = tiles = chunk_steps = 0
    for pos0, queries in seen["prefill"]:
        rows = [min((int(pos0[0]) + queries - 1) // bs + 1, mb)]
        walked += layers * sum(rows)
        steps = layers * sum(-(-n // wide) for n in rows)
        tiles += steps * -(-wide // width)
        chunk_steps += steps if wide > width else 0
    for pos0, _ in seen["decode"]:
        for r in range(burst):
            rows = [min((int(p) + b * r + b - 1) // bs + 1, mb) for p in pos0]
            walked += layers * (t + 1) * sum(rows)
            tiles += layers * (t + 1) * sum(-(-n // width) for n in rows)
    stats = engine.stats()
    assert seen["prefill"] and len(seen["decode"]) > 2
    assert stats["paged_entries_walked_total"] == walked
    assert stats["paged_tiles_walked_total"] == tiles
    assert stats["paged_chunk_steps_total"] == chunk_steps
    assert (chunk_steps > 0) == (chunk > 16) and stats["paged_tile_entries"] == width
    assert walked / width <= tiles < walked
    engine.reset_stats()
    assert engine.stats()["paged_tiles_walked_total"] == 0
    assert engine.stats()["paged_chunk_steps_total"] == 0


def test_a_flight_entry_carries_the_block_totals_as_of_its_harvest(tiny):
    engine = _engine(tiny[0])
    _ask(engine, range(3, 25), 9)
    engine.run_until_idle()
    last = engine._flight.tail(1)[0]["counters"]
    s = engine.stats()
    for name in ("block_rounds_total", "block_forwards_total", "block_tokens_emitted_total",
                 "block_positions_committed_total", "block_slot_forwards_total",
                 "moe_dispatches_total", "moe_experts_touched_total"):
        assert last[name] == s[name], name
    assert s["block_tokens_emitted_total"] == 9
    engine.reset_stats()
    assert engine.stats()["block_rounds_total"] == 0


def _noised(c, ids, p, denoise_steps, prompt_len):
    """The sequence the pass that fixes position ``p`` sees, cut at the end
    of ``p``'s block: clean before ``p``'s sub-block (and the prompt), the
    mask token from there on."""
    b, sub = c.block_length, c.block_length // denoise_steps
    first = p // b * b
    clean_until = max(first + (p - first) // sub * sub, prompt_len)
    seq = np.full((first + b,), c.mask_token_id, np.int32)
    seq[:clean_until] = ids[:clean_until]
    return seq


@pytest.mark.parametrize("denoise_steps", [1, 2, 4])
def test_the_whole_sequence_forward_under_the_block_rule_agrees_with_the_reference(
        tiny, denoise_steps):
    """Brute force, a forward a token: the program's own whole-sequence
    ``apply`` (no cache, block-causal mask) over the noised sequence gives
    at ``p`` the logits the reference's two streams give for row ``p - 1``:
    the reference's conditioning is the one a per-token forward has."""
    model, c = tiny
    rng = np.random.default_rng(3)
    prompt_len, n = 10, 9
    ids = rng.integers(0, 250, size=prompt_len + n).astype(np.int32)
    rows = np.arange(prompt_len - 1, prompt_len + n - 1)
    padded = np.zeros((128,), np.int32)
    padded[: len(ids) - 1] = ids[:-1]
    want = np.asarray(reference.logits_at(
        _reference_config(c, denoise_steps), SEED, padded, len(ids) - 1, rows, "float32"))
    apply = jax.jit(lambda x: model.apply_fn(model.params, input_ids=x)["logits"])
    for row, p in zip(range(n), rows + 1):
        seq = _noised(c, ids, p, denoise_steps, prompt_len)
        full = np.full((24,), c.mask_token_id, np.int32)  # one shape: later blocks are unseen
        full[: len(seq)] = seq
        got = np.asarray(apply(full[None]))[0, p]
        # logits of spread 1: float32 summation order
        np.testing.assert_allclose(got, want[row], rtol=0, atol=3e-5)


def test_block_length_1_is_the_causal_model_on_the_one_token_step():
    """``block_length`` 1: nothing is declared, the engine serves the model
    by its one-token decode step, and every route masks causally: the
    served tokens are the argmax of the program's causal whole-sequence
    forward, shifted as a causal model is."""
    model, c = _model(block_length=1)
    assert model.block_decode is None
    engine = _engine(model, denoise_steps=None)
    assert "block_length" not in engine.stats()
    request = _ask(engine, range(5, 28), 9)
    engine.run_until_idle()
    ids = np.asarray(request.prompt + request.output_tokens[:-1], np.int32)
    logits = np.asarray(model.apply_fn(model.params, input_ids=ids[None])["logits"], np.float64)[0]
    rows = logits[len(request.prompt) - 1:]
    assert (rows.argmax(-1) == np.asarray(request.output_tokens)).all()
    top = rows.max(-1, keepdims=True)
    logp = rows - (top + np.log(np.exp(rows - top).sum(-1, keepdims=True)))
    assert np.abs(_reported(request) - logp.max(-1)).max() < LOGPROB_TOLERANCE
    with pytest.raises(ValueError, match="decodes one token a step"):
        _engine(model, denoise_steps=2)


@pytest.mark.parametrize("cut", [1, 2, 6])
def test_eos_inside_a_block_ends_the_request_there(tiny, cut):
    """The token at output place ``cut`` is made the EOS: the request ends
    with it, mid-block, whatever the round committed behind it; tokens and
    log-probabilities before it are what the uncut run served."""
    model, _ = tiny
    prompt = list(range(40, 59))  # 19 tokens: the first round opens three positions in
    engine = _engine(model)
    whole = _ask(engine, prompt, 12)
    engine.run_until_idle()
    eos = whole.output_tokens[cut]
    first = whole.output_tokens.index(eos)
    engine = _engine(model, eos_token_id=eos)
    request = _ask(engine, prompt, 12)
    engine.run_until_idle()
    assert request.finish_reason == "eos"
    assert request.output_tokens == whole.output_tokens[: first + 1]
    np.testing.assert_array_equal(_reported(request), _reported(whole)[: first + 1])
    s = engine.stats()
    assert s["block_tokens_emitted_total"] == first + 1
    assert s["block_positions_committed_total"] > first + 1


@pytest.mark.parametrize("budget", [1, 2, 3, 4, 5])
def test_max_new_tokens_inside_a_block(tiny, budget):
    model, c = tiny
    engine = _engine(model)
    whole = _ask(engine, range(60, 77), 8)  # 17 tokens: one position into a block
    cutoff = _ask(engine, range(60, 77), budget)
    engine.run_until_idle()
    assert cutoff.finish_reason == "length" and len(cutoff.output_tokens) == budget
    assert cutoff.output_tokens == whole.output_tokens[:budget]
    want, is_best = _reference_logprobs(_reference_config(c, 2), cutoff)
    assert is_best.all() and np.abs(_reported(cutoff) - want).max() < LOGPROB_TOLERANCE


def test_a_preempted_request_is_recomputed_and_continues_with_the_same_tokens(tiny):
    """A pool too small for three growing requests and no swap tier: one
    gives its blocks back, re-queues, and is prefilled again over prompt and
    emitted tokens (whole blocks; the rest opens its next round); what it
    serves agrees with the reference as if nothing had happened."""
    model, c = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 250, size=n).tolist() for n in (30, 27, 25)]
    alone = []
    for p in prompts:
        engine = _engine(model, max_seq_len=96)
        alone.append(_ask(engine, p, 40))
        engine.run_until_idle()
    # 3 requests x (30 + 40 tokens) need 27 blocks of 8; 16 are there
    engine = _engine(model, num_slots=3, num_blocks=17, max_seq_len=96)
    requests = [_ask(engine, p, 40) for p in prompts]
    engine.run_until_idle()
    s = engine.stats()
    assert s["preemptions"] >= 1 and s["out_of_blocks_total"] == 0
    assert any(r.preemptions for r in requests) and s["decode_compiles"] == 1
    for r, lone in zip(requests, alone):
        assert len(r.output_tokens) == 40 and r.finish_reason == "length"
        assert r.output_tokens == lone.output_tokens
        want, is_best = _reference_logprobs(_reference_config(c, 2), r)
        assert is_best.all() and np.abs(_reported(r) - want).max() < LOGPROB_TOLERANCE


def test_a_swapped_out_request_comes_back_and_continues_with_the_same_tokens(tiny):
    """The same squeeze with a swap tier: the victim's blocks go to the host
    and come back; a request swapped out before its first round holds no
    token yet and none is fed."""
    model, c = tiny
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 250, size=n).tolist() for n in (30, 27, 25)]
    engine = _engine(model, num_slots=3, num_blocks=17, max_seq_len=96, swap_gb=0.01)
    requests = [_ask(engine, p, 40) for p in prompts]
    engine.run_until_idle()
    s = engine.stats()
    assert s["preemptions"] >= 1 and s["swapped_in_blocks"] >= 1 and s["out_of_blocks_total"] == 0
    for r in requests:
        assert len(r.output_tokens) == 40
        want, is_best = _reference_logprobs(_reference_config(c, 2), r)
        assert is_best.all() and np.abs(_reported(r) - want).max() < LOGPROB_TOLERANCE


def test_one_decode_executable_over_a_window_with_arrivals(tiny):
    """Requests arrive while others decode, lengths and budgets all
    different, async and then sync: one decode executable, and the two
    loops serve the same tokens."""
    model, _ = tiny
    rng = np.random.default_rng(5)
    plan = [(rng.integers(0, 250, size=int(n)).tolist(), int(m))
            for n, m in zip(rng.integers(2, 60, size=9), rng.integers(1, 20, size=9))]
    served = {}
    for mode in (True, False):
        engine = _engine(model, async_dispatch=mode)
        requests, pending = [], list(plan)
        for step in range(400):
            if pending and step % 2 == 0:
                requests.append(_ask(engine, *pending.pop(0)))
            if not pending and not engine.scheduler.has_work() and engine._inflight is None:
                break
            engine.step()
        assert all(r.finish_reason == "length" for r in requests)
        s = engine.stats()
        assert s["decode_compiles"] == 1 and s["prefill_compiles"] == 1
        assert "retrace_report" not in s
        served[mode] = [r.output_tokens for r in requests]
    assert served[True] == served[False]


def test_a_prefix_cache_hit_serves_the_same_logits(tiny):
    """Pages are 8 positions here and a block 4, so a page's keys and values
    depend on no token past its end. A second request with the same prompt
    maps the cached pages (the hit cut back to a block's edge), a third
    shares 21 tokens of it — a hit that ends inside a page, copied on
    write — and both serve what a cold engine serves."""
    model, c = tiny
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, 250, size=38).tolist()
    fork = prompt[:21] + rng.integers(0, 250, size=12).tolist()
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
    assert again.matched_tokens == 32 and forked.matched_tokens == 20  # 37 -> 36 -> pages; 21 -> 20
    assert engine.stats()["prefix_hit_tokens"] == 52
    for got, want in ((first, cold[0]), (again, cold[0]), (forked, cold[1])):
        assert got.output_tokens == want.output_tokens
        np.testing.assert_allclose(_reported(got), _reported(want), rtol=0, atol=1e-6)
        ref, is_best = _reference_logprobs(_reference_config(c, 2), got)
        assert is_best.all() and np.abs(_reported(got) - ref).max() < LOGPROB_TOLERANCE


def test_a_sampled_lane_draws_each_position_from_its_own_row(tiny):
    """Sampling: the same (seed, prompt) serves the same tokens from another
    engine beside other traffic (keys derive from the request's seed and the
    token's output position), another seed serves others, and a greedy
    neighbour is served its argmax either way."""
    model, _ = tiny
    prompt = list(range(100, 122))
    a = _engine(model)
    one = _ask(a, prompt, 14, do_sample=True, temperature=1.5, seed=3)
    a.run_until_idle()
    b = _engine(model, decode_burst=3)
    _ask(b, range(7, 20), 5)
    two = _ask(b, prompt, 14, do_sample=True, temperature=1.5, seed=3)
    other = _ask(b, prompt, 14, do_sample=True, temperature=1.5, seed=4)
    greedy = _ask(b, prompt, 14)
    b.run_until_idle()
    plain = _ask(a, prompt, 14)
    a.run_until_idle()
    assert one.output_tokens == two.output_tokens != other.output_tokens
    assert greedy.output_tokens == plain.output_tokens != one.output_tokens
    assert b.stats()["pick_draw_dispatches_total"] > 0


@pytest.mark.parametrize("how, said", [
    (dict(grammar={"type": "regex", "pattern": "[0-9]+"}), "grammar is not supported beside"),
    (dict(sampling=SamplingParams(repetition_penalty=1.3)), "repetition_penalty is not supported"),
])
def test_what_cannot_hold_under_parallel_unmasking_is_refused_at_the_door(tiny, how, said):
    engine = _engine(tiny[0])
    with pytest.raises(ValueError, match=said):
        engine.add_request(list(range(9)), 4, **how)
    assert engine.scheduler.queue_depth == 0


@pytest.mark.parametrize("geometry, said", [
    (dict(spec_k=2, logprobs_topn=0), "spec_k is not supported .* diffusion over blocks of 4"),
    (dict(denoise_steps=3), "denoise_steps 3 does not divide"),
    (dict(denoise_steps=0), "denoise_steps 0 does not divide"),
    (dict(block_size=6), "block_size 6 is not a multiple"),
    (dict(prefill_chunk=18), "prefill_chunk 18 is not a multiple"),
])
def test_what_the_engine_refuses_at_bring_up(tiny, geometry, said):
    with pytest.raises(ValueError, match=said):
        _engine(tiny[0], **geometry)


def test_denoise_steps_is_refused_for_a_model_that_decodes_one_token_a_step():
    model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0)
    with pytest.raises(ValueError, match="only a model that declares block_decode"):
        InferenceEngine(model, EngineConfig(num_slots=2, max_seq_len=64, denoise_steps=2))


def test_denoise_steps_defaults_to_a_token_a_pass(tiny):
    engine = _engine(tiny[0], denoise_steps=None)
    assert engine.stats()["denoise_steps"] == 4


# -- the published file -> the model ------------------------------------------------


def _published(tmp_path, **changes) -> str:
    path = os.path.join(tmp_path, "config.json")
    with open(path, "w") as f:
        json.dump({**CATALOG, **changes}, f)
    return path


def test_the_published_config_builds_the_published_model(tmp_path):
    assert "sdar_moe" in KNOWN_MODEL_TYPES
    c = config_from_hf_json(_published(tmp_path))
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (
        2048, 32, 4, 128)
    assert (c.num_experts, c.num_experts_per_tok, c.moe_intermediate_size) == (128, 8, 768)
    assert (c.num_hidden_layers, c.vocab_size, c.tie_word_embeddings) == (48, 151936, False)
    assert (c.rms_norm_eps, c.rope_theta, c.norm_topk_prob) == (1e-6, 1e6, True)
    # not in the file: the family's convention
    assert (c.block_length, c.mask_token_id) == (4, 151669)
    with init_empty_weights():
        model = model_factory_for_config(c)(c)
    shapes = {k: tuple(a.shape) for k, a in weights.flat_names(model.params).items()}
    assert shapes == reference.leaf_shapes({**CATALOG})
    assert sum(int(np.prod(s)) for s in shapes.values()) == 30_532_122_624
    spec = model.cache_spec
    assert (spec.paged_layers, spec.kv_heads, spec.head_dim, spec.slot_state) == (48, 4, 128, {})
    assert model.block_decode == sdar.BlockDecode(4, 151669)


@pytest.mark.parametrize("changes, said", [
    (dict(mlp_only_layers=[0, 1]), r"mlp_only_layers \[0, 1\]"),
    (dict(decoder_sparse_step=2), "decoder_sparse_step 2"),
    (dict(attention_bias=True), "attention_bias True"),
    (dict(use_sliding_window=True), "use_sliding_window True"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(tie_word_embeddings=True), "the head is untied"),
    (dict(num_experts_per_tok=129), "num_experts_per_tok 129 of num_experts 128"),
    (dict(mask_token_id=151936), "mask_token_id 151936 is not a row"),
])
def test_what_cannot_be_built_as_published_is_refused_not_guessed_at(tmp_path, changes, said):
    with pytest.raises(ValueError, match=said):
        config_from_hf_json(_published(tmp_path, **changes))


def test_serve_builds_the_engine_of_a_published_config(tmp_path):
    import argparse

    from accelerate_tpu.commands import serve

    small = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=2,
                 moe_intermediate_size=32, max_position_embeddings=512, mask_token_id=255)
    cli = argparse.ArgumentParser()
    serve.add_parser(cli.add_subparsers())
    args = cli.parse_args(["serve", "--model-config", _published(tmp_path, **small),
                           "--num-slots", "2", "--max-seq-len", "64", "--prefill-chunk", "16",
                           "--denoise-steps", "2", "--decode-burst", "3"])
    engine = serve._make_engine(args)
    request = engine.add_request(list(range(21)), 6)
    engine.run_until_idle()
    s = engine.stats()
    assert len(request.output_tokens) == 6 and s["decode_compiles"] == 1
    assert (s["block_length"], s["denoise_steps"], s["kv_layers"], s["state_layers"]) == (4, 2, 2, 0)
    # 20 prefilled positions and whole bursts of rounds of 3 forwards of 4 rows
    rows, rest = divmod(s["moe_pairs_routed_total"], 2 * 2)
    assert rest == 0 and (rows - 20) % (3 * 3 * 4) == 0 and rows >= 20 + 3 * 2 * 4
