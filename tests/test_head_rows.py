"""The head and the pick run on the rows somebody reads (ISSUE 46): one row
of a prompt's chunk, ``B / T`` rows a slot of a denoise pass, none of a block
model's chunk. A paged step is told which positions' logits are wanted
(``logit_positions``) and gathers those hidden rows before the final norm and
the head (:func:`accelerate_tpu.ops.layers.logit_rows`); nothing of a row's
arithmetic changes.

Three things are held here, all on the CPU at tiny widths:

* **by compiled text**, as ``tests/test_paged_pool_in_place.py`` pins the
  pools: a served family's compiled chunk holds no ``[prefill_chunk, vocab]``
  array, SDAR's chunk no vocabulary-wide array at all, and SDAR's round
  ``[slots * B / T, vocab]`` where it held ``[slots * B, vocab]``;
* **the numbers of a chunk**: the row the chunk's program returns is row
  ``last_idx`` of the model's step called without the argument;
* **the numbers of a round**: tokens, log-probabilities and top-N are those
  of the round as the parent commit ran it (every row through the head and
  the pick, the sub-block kept), written out below.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
from accelerate_tpu.models.deepseek_v3 import DeepseekV3Config, DeepseekV3ForCausalLM
from accelerate_tpu.models.granite_hybrid import GraniteHybridConfig, GraniteHybridForCausalLM
from accelerate_tpu.models.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM
from accelerate_tpu.models.sdar_moe import SdarMoeConfig, SdarMoeForCausalLM
from accelerate_tpu.models.smallthinker import SmallThinkerConfig, SmallThinkerForCausalLM
from accelerate_tpu.serving import EngineConfig, InferenceEngine
from accelerate_tpu.serving.sampling import SamplingParams, pick_tokens

#: no other width of a tiny model, of the engine's geometry or of a product of them
VOCAB = 250
CHUNK, SLOTS, BLOCK = 16, 4, 4

FAMILIES = {
    "llama": lambda: LlamaForCausalLM.from_config(LlamaConfig.tiny(vocab_size=VOCAB), seed=0),
    "hybrid": lambda: GraniteHybridForCausalLM.from_config(
        GraniteHybridConfig.tiny(vocab_size=VOCAB), seed=0),  # tied head
    "lfm2": lambda: Lfm2MoeForCausalLM.from_config(
        Lfm2MoeConfig.tiny(vocab_size=VOCAB), seed=0),  # tied head
    "sdar": lambda: SdarMoeForCausalLM.from_config(SdarMoeConfig.tiny(vocab_size=VOCAB), seed=0),
    "deepseek": lambda: DeepseekV3ForCausalLM.from_config(
        DeepseekV3Config.tiny(vocab_size=VOCAB), seed=0),
    "smallthinker": lambda: SmallThinkerForCausalLM.from_config(
        SmallThinkerConfig.tiny(vocab_size=VOCAB), seed=0),
}


@pytest.fixture(scope="module")
def models():
    """``models(family)``: the family's tiny model, made once a module."""
    return functools.cache(lambda family: FAMILIES[family]())


def _engine(model, **kw):
    geometry = dict(num_slots=SLOTS, max_seq_len=128, prefill_chunk=CHUNK, block_size=8,
                    logprobs_topn=2, decode_burst=2)
    geometry.update(kw)
    return InferenceEngine(model, EngineConfig(**geometry))


def _ask(engine, prompt, new_tokens=6, **sampling):
    return engine.add_request(
        list(prompt), new_tokens, sampling=SamplingParams(logprobs=2, **sampling))


# -- by compiled text ---------------------------------------------------------------


def _wide(text: str, *shapes) -> list:
    """The instructions of a compiled program - parameters aside, which are
    there whether read or not - that name an array of one of ``shapes``, or,
    with none given, any array whose last dimension is the vocabulary."""
    want = ([re.escape("[" + ",".join(map(str, s)) + "]") for s in shapes]
            or [rf"\[(?:\d+,)*{VOCAB}\]"])
    found = re.compile(r"[a-z]\w*(?:" + "|".join(want) + ")")
    return [line.strip()[:160] for line in text.splitlines()[1:]
            if " parameter(" not in line and found.search(line)]


@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_compiled_programs_hold_the_head_of_the_rows_that_are_read(models, family):
    model = models(family)
    engine = _engine(model, **({"denoise_steps": 2} if family == "sdar" else {}))
    _ask(engine, range(3, 40))
    engine.run_until_idle()
    chunk = engine.compiled_text("prefill")
    every_row = ((CHUNK, VOCAB), (1, CHUNK, VOCAB))
    assert _wide(chunk, *every_row) == []
    # the check sees the program this one replaces: the step called without
    # the argument and one row taken of what comes back
    jitted, (params, cache, table, start, ids, valid, last_idx, slot) = engine._dispatched[
        "prefill"]
    has_state = bool(engine._cache_spec.slot_state)

    def parents(params, cache, table, start, ids, valid, last_idx, slot):
        out = model.apply_fn(
            params, input_ids=ids, paged_kv=cache, block_tables=table, cache_positions=start,
            paged_write_mask=valid, **({"state_slots": slot} if has_state else {}))
        return out["paged_kv"], jnp.take(out["logits"][0], last_idx, axis=0)

    control = jax.jit(parents, donate_argnums=(1,)).lower(
        params, cache, table, start, ids, valid, last_idx, slot).compile().as_text()
    assert _wide(control, *every_row)
    s = engine.stats()
    if family != "sdar":
        # the one row is there: the head was not lost with the others
        assert _wide(chunk, (VOCAB,), (1, VOCAB), (1, 1, VOCAB))
        assert s["head_rows_per_chunk"] == 1 and "head_rows_per_pass" not in s
        return
    # a block model's chunk reads no row: no head, no array as wide as the vocabulary
    assert _wide(chunk) == []
    assert s["head_rows_per_chunk"] == 0
    # its round: two passes of B / T = 2 positions of every slot, and the
    # commit pass's head still falls out
    assert s["head_rows_per_pass"] == SLOTS * BLOCK // 2 == 8
    rounds = engine.compiled_text("decode")
    assert _wide(rounds, (SLOTS * BLOCK, VOCAB), (SLOTS, BLOCK, VOCAB)) == []
    assert _wide(rounds, (SLOTS * BLOCK // 2, VOCAB), (SLOTS, BLOCK // 2, VOCAB))
    assert s["decode_compiles"] == 1 and s["prefill_compiles"] == 1


# -- the numbers of a chunk ---------------------------------------------------------

# float32 against float32, the same weights: one row through the norm and a
# [1, h] x [h, vocab] product against row last_idx of the [chunk, h] one. On
# this CPU the untied heads read bit-equal and the tied ones (x @ embed.T,
# hybrid and LFM2) 1.2e-7 apart; the room is for another tiling of the product
ROW_TOLERANCE = 2e-6

#: prompt length, which of the prompt's chunks is held, and what it is handed
CHUNK_CASES = {
    "full": (32, 1, dict(last_idx=15, valid=16)),  # a final chunk, every row real
    "padded": (27, 1, dict(last_idx=10, valid=11)),  # a final chunk with a padded tail
    "first": (32, 0, dict(last_idx=0, valid=16)),  # not final: row 0, which the host ignores
}


@pytest.mark.parametrize("case", list(CHUNK_CASES))
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_chunks_program_returns_the_row_the_whole_head_gives(models, family, case):
    model = models(family)
    engine = _engine(model)
    has_state = bool(engine._cache_spec.slot_state)
    block_model = family == "sdar"
    prompt_len, held, handed = CHUNK_CASES[case]
    if block_model and case == "padded":
        handed = dict(last_idx=7, valid=8)  # whole blocks only: 24 of the 27 tokens
    seen = []

    def step(params, cache, table, start, ids, valid, slot, rows=None):
        return model.apply_fn(
            params, input_ids=ids, paged_kv=cache, block_tables=table, cache_positions=start,
            paged_write_mask=valid, logit_positions=rows,
            **({"state_slots": slot} if has_state else {}))

    program, step = engine._prefill_fn, jax.jit(step)

    def recorded(params, cache, table, start, ids, valid, last_idx, slot):
        # before the program runs: it takes the cache donated
        whole = step(params, cache, table, start, ids, valid, slot)
        one = step(params, cache, table, start, ids, valid, slot,
                   jnp.asarray(last_idx, jnp.int32).reshape(1, 1))
        got = program(params, cache, table, start, ids, valid, last_idx, slot)
        seen.append(dict(last_idx=int(last_idx), valid=int(np.sum(valid)),
                         whole=np.asarray(whole["logits"]), one=np.asarray(one["logits"]),
                         cache=jax.tree.map(np.asarray, whole["paged_kv"]),
                         got=jax.tree.map(np.asarray, got)))
        return got

    engine._prefill_fn = recorded
    rng = np.random.default_rng(1)
    request = _ask(engine, rng.integers(0, VOCAB - 1, size=prompt_len).tolist())
    engine.run_until_idle()
    assert len(request.output_tokens) == 6 and len(seen) == 2
    call = seen[held]
    assert {k: call[k] for k in handed} == handed
    want = call["whole"][0, call["last_idx"]]
    assert call["whole"].shape == (1, CHUNK, VOCAB) and call["one"].shape == (1, 1, VOCAB)
    assert np.abs(want).max() > 0.05  # (a row of zeros would agree with anything)
    # the step itself, every family through the one gather
    np.testing.assert_allclose(call["one"][0, 0], want, rtol=0, atol=ROW_TOLERANCE)
    cache, *rest = call["got"]
    counted = "moe_layers" in engine.stats()
    if block_model:
        # no token comes of a block model's prefill: the chunk hands back the
        # cache (and its counters), and the cache is the whole step's
        assert len(rest) == int(counted)
    else:
        assert len(rest) == 1 + int(counted) and rest[0].shape == (VOCAB,)
        np.testing.assert_allclose(rest[0], want, rtol=0, atol=ROW_TOLERANCE)
    for name, leaf in call["cache"].items():
        np.testing.assert_array_equal(cache[name], leaf, err_msg=name)


# -- the numbers of a round ---------------------------------------------------------


def _parents_rounds(engine, model):
    """The block rounds as the parent commit (``42d3b0d``) ran them: every
    denoise pass takes the head over all ``B`` positions of every slot, picks
    over ``slots * B`` rows and keeps its sub-block's."""
    cfg, blk = engine.config, engine._block
    b, t_steps, slots = blk.block_length, engine._denoise_steps, cfg.num_slots
    sub, n_top = b // t_steps, max(int(cfg.logprobs_topn), 1)
    col = jnp.arange(b, dtype=jnp.int32)

    def rounds(params, cache, block_tables, pos0, toks, known, active, lanes, gmask, base_key):
        rows = {name: jnp.repeat(lane, b, axis=0) for name, lane in lanes.items()}
        write = jnp.broadcast_to(active, (slots, b))

        def forward(cache, x, pos):
            return model.apply_fn(
                params, input_ids=x, paged_kv=cache, block_tables=block_tables,
                cache_positions=pos, paged_write_mask=write)

        def one_round(carry, n):
            cache, x, known, pos = carry
            row_lanes = dict(rows, pos=(
                (lanes["pos"] + n * b)[:, None] + col[None, :]).reshape(slots * b))
            logp = jnp.zeros((slots, b), jnp.float32)
            tvals = jnp.zeros((slots, b, n_top), jnp.float32)
            tids = jnp.zeros((slots, b, n_top), jnp.int32)
            for t in range(t_steps):
                out = forward(cache, x, pos)
                cache = out["paged_kv"]
                tok, lp, tv, ti = pick_tokens(
                    out["logits"].reshape(slots * b, -1), row_lanes, row_lanes["dfa_state"],
                    jnp.int32(0), gmask, base_key, eos_id=cfg.eos_token_id,
                    logprobs_topn=cfg.logprobs_topn)
                fix = ~known & (col >= t * sub) & (col < (t + 1) * sub)
                x = jnp.where(fix, tok.reshape(slots, b), x)
                logp = jnp.where(fix, lp.reshape(slots, b), logp)
                tvals = jnp.where(fix[..., None], tv.reshape(slots, b, n_top), tvals)
                tids = jnp.where(fix[..., None], ti.reshape(slots, b, n_top), tids)
                known = known | fix
            out = forward(cache, x, pos)
            nxt = (out["paged_kv"], jnp.full_like(x, blk.mask_token_id),
                   jnp.zeros_like(known), pos + b)
            return nxt, (x, logp, tvals, tids)

        (cache, _, _, _), ys = jax.lax.scan(
            one_round, (cache, toks, known, pos0), jnp.arange(cfg.decode_burst))
        return (cache, *ys)

    return jax.jit(rounds)


# a [slots * B / T, h] x [h, vocab] product against [slots * B, h]'s rows, in
# float32: bit-equal on this CPU; the room is for another tiling of the product
ROUND_TOLERANCE = 2e-6


@pytest.mark.parametrize("lanes", ["greedy", "one_sampled"])
@pytest.mark.parametrize("denoise_steps", [1, 2, 4])
def test_a_round_serves_what_the_parents_round_served(models, denoise_steps, lanes):
    """Prompts of 37, 50, 23 and 16 tokens: the first blocks open with one,
    two, three and no known positions. With one lane sampling the whole
    batch takes the sampler, and that lane's draws are keyed by its seed and
    its output position, not by its row among ``slots * B / T``."""
    model = models("sdar")
    engine = _engine(model, denoise_steps=denoise_steps)
    parents, program, seen = _parents_rounds(engine, model), engine._decode_fn, []

    def recorded(*operands):
        want = parents(*operands)  # before the program runs: it takes the cache donated
        got = program(*operands)
        seen.append((jax.tree.map(np.asarray, want[:5]), jax.tree.map(np.asarray, got[:5]),
                     np.asarray(operands[5]), np.asarray(operands[6]).reshape(-1)))
        return got

    engine._decode_fn = recorded
    rng = np.random.default_rng(2)
    requests = [
        _ask(engine, rng.integers(0, VOCAB - 1, size=n).tolist(), 11,
             **(dict(do_sample=True, temperature=1.3, seed=5)
                if lanes == "one_sampled" and n == 50 else {}))
        for n in (37, 50, 23, 16)]
    engine.run_until_idle()
    assert all(len(r.output_tokens) == 11 for r in requests)
    s = engine.stats()
    assert s["decode_compiles"] == 1
    assert (s["pick_draw_dispatches_total"] > 0) == (lanes == "one_sampled")
    assert s["head_rows_per_pass"] == SLOTS * BLOCK // denoise_steps
    opened_with = set()
    for want, got, known, active in seen:
        opened_with |= set(known[active].sum(axis=1).tolist())
        for w, g in zip(want[0].values(), got[0].values()):  # the cache
            np.testing.assert_allclose(g, w, rtol=0, atol=ROUND_TOLERANCE)
        toks, logp, tvals, tids = got[1:]
        assert toks.shape == (2, SLOTS, BLOCK) and tvals.shape == (2, SLOTS, BLOCK, 2)
        np.testing.assert_array_equal(toks[:, active], want[1][:, active])
        np.testing.assert_array_equal(tids[:, active], want[4][:, active])
        np.testing.assert_allclose(logp[:, active], want[2][:, active], rtol=0,
                                   atol=ROUND_TOLERANCE)
        np.testing.assert_allclose(tvals[:, active], want[3][:, active], rtol=0,
                                   atol=ROUND_TOLERANCE)
    assert opened_with == {0, 1, 2, 3}
