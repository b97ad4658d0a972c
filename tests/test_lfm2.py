"""LFM2-8B-A1B through the serving engine (ISSUE 36): gated short
convolutions whose whole per-slot state is a two-token tail, QK-normed
rotary GQA layers with paged K/V, and routed experts under a sigmoid router
with a selection bias, held against the plain reference of
``perfbench/reference/lfm2.py`` — float32 at ``highest``, every expert over
every token, no cache, nothing shared with the program.

All on the CPU at a small size with seeded weights (``perfbench.weights``,
the recipe the benchmark's check uses). Tolerances, each with its reason,
are beside the comparison they belong to.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import accelerate_tpu.models.lfm2 as lfm2  # noqa: E402
from accelerate_tpu import Accelerator  # noqa: E402
from accelerate_tpu.big_modeling import init_empty_weights  # noqa: E402
from accelerate_tpu.models import (  # noqa: E402
    KNOWN_MODEL_TYPES,
    config_from_hf_json,
    model_factory_for_config,
)
from accelerate_tpu.serving import EngineConfig, InferenceEngine  # noqa: E402
from accelerate_tpu.serving.sampling import SamplingParams  # noqa: E402
from accelerate_tpu.state import AcceleratorState, GradientState  # noqa: E402
from perfbench import common, probe, weights  # noqa: E402
from perfbench.reference import lfm2 as reference  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(HERE, "perfbench", "configs", "lfm2-8b-a1b-serve-v5e1.json")
CELL = "lfm2-8b-a1b-chat-steady"
SEED = 7
#: a quiet embedding under a tied head and logits with a spread; attention
#: scores with a spread (q is normed, so its norm's weight scales them); a
#: bias large enough to move the router's choice
SCALES = {"embed_tokens": 0.05, "embedding_norm": 6.0, "layers.attention.q_norm": 3.0,
          "layers.moe.expert_bias": 2.0}
PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_dense_layers", "intermediate_size",
    "num_experts", "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob",
    "routed_scaling_factor", "num_attention_heads", "num_key_value_heads", "head_dim",
    "rope_theta", "conv_L_cache", "norm_eps",
)


def _reference_config(c) -> dict:
    cfg = {k: getattr(c, k) for k in PUBLISHED_KEYS}
    return {**cfg, "layer_types": list(c.layer_types), "weight_scales": SCALES}


@pytest.fixture(scope="module")
def tiny():
    """(model with seeded float32 weights, the reference's configuration)."""
    c = lfm2.Lfm2MoeConfig.tiny()
    with init_empty_weights():
        model = lfm2.Lfm2MoeForCausalLM.from_config(c)
    model.params = weights.make_tree(SEED, model.params, dtype=jnp.float32, scales=SCALES)
    return model, _reference_config(c)


def _engine(model, **kw):
    geometry = dict(num_slots=4, max_seq_len=128, prefill_chunk=16, block_size=8,
                    logprobs_topn=1, decode_burst=4)
    geometry.update(kw)
    return InferenceEngine(model, EngineConfig(**geometry))


def _ask(engine, prompt, new_tokens=12):
    return engine.add_request(list(prompt), new_tokens, sampling=SamplingParams(logprobs=1))


def _reference_logits(cfg, ids, rows):
    padded = np.zeros((128,), np.int32)
    padded[: len(ids)] = ids
    return np.asarray(reference.logits_at(cfg, SEED, padded, len(ids), rows, "float32"), np.float64)


def _reference_logprobs(cfg, request):
    """The reference's full forward over prompt + served tokens: the
    log-probability of every served token, and whether it was the best."""
    ids = np.asarray(request.prompt + request.output_tokens[:-1], np.int32)
    rows = np.arange(len(request.prompt) - 1, len(ids))
    logits = _reference_logits(cfg, ids, rows)
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
    requests = {n: _ask(engine, rng.integers(0, 256, size=n).tolist())
                for n in (37, 16, 5, 50, 33, 20)}
    engine.run_until_idle()
    return engine, requests, cfg


# float32 against float32: what is left is the order of summation (the
# grouped product and the paged kernel's walk against plain einsums). Over
# these sequences it reads 2e-6; a tail dropped at a chunk's edge reads 3e-1,
# a router whose bias leaks into the weights 1e-1, an fp8 KV pool 1e-2.
LOGPROB_TOLERANCE = 3e-5


@pytest.mark.parametrize("prompt_len", [5, 16, 20, 33, 37, 50])
def test_prefill_in_chunks_then_decode_agrees_with_the_full_forward_pass(served, prompt_len):
    _, requests, cfg = served
    request = requests[prompt_len]
    want, is_best = _reference_logprobs(cfg, request)
    assert len(request.output_tokens) == 12 and is_best.all()
    assert np.abs(_reported(request) - want).max() < LOGPROB_TOLERANCE


def test_the_whole_sequence_forward_agrees_with_the_reference_on_logits(tiny):
    model, cfg = tiny
    ids = np.random.default_rng(2).integers(0, 256, size=(2, 40)).astype(np.int32)
    out = model.apply_fn(model.params, input_ids=ids, labels=ids)
    for row in range(2):
        np.testing.assert_allclose(
            out["logits"][row], _reference_logits(cfg, ids[row], np.arange(40)), atol=3e-5)
    # right padding routes nowhere and moves no valid row
    mask = np.ones((2, 40), np.int32)
    mask[1, 25:] = 0
    masked = model.apply_fn(model.params, input_ids=ids, attention_mask=mask)["logits"]
    np.testing.assert_allclose(masked[1, :25], out["logits"][1, :25], atol=3e-5)


def test_it_trains_through_the_accelerator(tiny):
    """The five-line loop on the tiny model: experts, router, bias-free
    convolutions and head norms all get a gradient, and the loss falls."""
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    accelerator = Accelerator()
    model = lfm2.Lfm2MoeForCausalLM.from_config(lfm2.Lfm2MoeConfig.tiny(), seed=3)
    grads = jax.grad(lambda p: model.apply_fn(
        p, input_ids=np.arange(24, dtype=np.int32).reshape(2, 12),
        labels=np.arange(24, dtype=np.int32).reshape(2, 12))["loss"])(model.params)
    flat = weights.flat_names(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat.values())
    for leaf in ("layers.moe.w_in", "layers.moe.w_out", "layers.moe.gate", "layers.conv.conv_w",
                 "layers.attention.q_norm", "layers.dense.w_in"):
        assert float(jnp.abs(flat[leaf]).max()) > 0, leaf
    # the bias picks and is not differentiated through
    assert not np.asarray(flat["layers.moe.expert_bias"]).any()
    ids = np.random.default_rng(5).integers(0, 256, size=(4, 24)).astype(np.int32)
    prepared, optimizer = accelerator.prepare(model, optax.adamw(3e-3))
    losses = []
    for _ in range(8):
        out = prepared(input_ids=ids, labels=ids)
        accelerator.backward(out.loss)
        optimizer.step()
        optimizer.zero_grad()
        losses.append(out.loss.item())
    assert losses[-1] < losses[0] - 0.1, losses
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()


def test_one_decode_and_one_prefill_executable_and_what_stats_says(served):
    engine, requests, _ = served
    s = engine.stats()
    assert s["decode_compiles"] == 1 and s["prefill_compiles"] == 1
    assert (s["kv_layers"], s["state_layers"], s["state_dtype"]) == (2, 3, None)
    # the whole per-slot state is three layers' two-row tails, float32 here
    assert s["state_bytes_per_slot"] == 3 * 2 * 64 * 4
    assert s["prefix_cache"] is False and "per-slot state (conv)" in s["prefix_cache_off_reason"]
    assert (s["moe_layers"], s["moe_experts"], s["moe_top_k"]) == (4, 8, 2)
    pairs = np.asarray(s["moe_expert_pairs"])
    assert pairs.shape == (4, 8) and pairs.sum() == s["moe_pairs_routed_total"]
    # every routed layer saw the same tokens, two pairs each
    assert len(set(pairs.sum(axis=1))) == 1
    assert s["moe_dispatches_total"] * 4 <= s["moe_experts_touched_total"] \
        <= s["moe_dispatches_total"] * 4 * 8
    assert s["moe_load_max_total"] * 8 >= s["moe_pairs_routed_total"]
    engine.reset_stats()
    z = engine.stats()
    assert z["moe_pairs_routed_total"] == z["moe_dispatches_total"] == 0
    assert not np.asarray(z["moe_expert_pairs"]).any() and z["moe_layers"] == 4


def test_dead_lanes_add_no_pairs_the_counters_equal_the_hosts_live_rows(tiny):
    """``moe_pairs_routed_total`` = live tokens x top-k x routed layers,
    counted by the host: every prompt token once, and one row for each
    decode step a request was live in (``decode_burst`` 1, so a dispatch is
    a step); free slots and a chunk's padded tail route nowhere."""
    model, _ = tiny
    engine = _engine(model, decode_burst=1)
    rng = np.random.default_rng(4)
    lens, new = (21, 9, 40), (7, 3, 5)
    requests = [_ask(engine, rng.integers(0, 256, size=n).tolist(), t) for n, t in zip(lens, new)]
    engine.run_until_idle()
    assert [len(r.output_tokens) for r in requests] == list(new)
    s = engine.stats()
    # a request of t tokens feeds t - 1 of them back through a decode step
    live_rows = sum(lens) + sum(t - 1 for t in new)
    assert s["moe_pairs_routed_total"] == live_rows * 2 * 4
    chunks = sum(-(-n // 16) for n in lens)
    # the three decode together for some steps and alone for others
    assert chunks + max(new) - 1 <= s["moe_dispatches_total"] <= chunks + sum(t - 1 for t in new)


def test_a_flight_entry_carries_the_scalar_counters_as_of_its_harvest(tiny):
    """What a reader lays over a span of device trace: the running sums on
    every iteration's flight entry, the per-expert grid left to ``stats()``;
    ``stats()`` itself only reads (no fetch, no sum: other threads call it)."""
    model, _ = tiny
    engine = _engine(model)
    _ask(engine, range(40), 9)
    engine.run_until_idle()
    entries = engine._flight.tail(1000)
    pairs = [e["counters"]["moe_pairs_routed_total"] for e in entries]
    assert pairs == sorted(pairs) and pairs[0] < pairs[-1]
    s = engine.stats()
    assert entries[-1]["counters"] == {k: s[k] for k in (
        "moe_dispatches_total", "moe_pairs_routed_total", "moe_experts_touched_total",
        "moe_load_max_total")}
    engine._pending_counters.append(None)  # a reading leaves what is pending alone
    assert engine.stats()["moe_pairs_routed_total"] == s["moe_pairs_routed_total"]
    assert engine._pending_counters == [None]


def test_a_masked_lane_of_the_decode_step_leaves_tail_and_kv_bit_identical(tiny):
    model, _ = tiny
    spec, slots = model.cache_spec, 4
    rng = np.random.default_rng(3)
    cache = {"k": jnp.asarray(rng.normal(size=(2, 40, 8, 32)), jnp.float32),
             "v": jnp.asarray(rng.normal(size=(2, 40, 8, 32)), jnp.float32)}
    for name, leaf in spec.slot_state.items():
        cache[name] = jnp.asarray(rng.normal(size=leaf.array_shape(slots)), jnp.float32)
    before = {k: np.asarray(v) for k, v in cache.items()}
    active = np.asarray([[True], [False], [False], [True]])
    tables = np.zeros((slots, 16), np.int32)
    tables[0, 0], tables[3, 0], tables[1, 0], tables[2, 0] = 1, 2, 3, 4
    out = model.apply_fn(
        model.params, input_ids=rng.integers(0, 256, size=(slots, 1)).astype(np.int32),
        paged_kv=cache, block_tables=tables, cache_positions=np.zeros((slots,), np.int32),
        paged_write_mask=active,
    )
    after = {k: np.asarray(v) for k, v in out["paged_kv"].items()}
    assert np.array_equal(after["conv"][:, [1, 2]], before["conv"][:, [1, 2]])
    assert not np.array_equal(after["conv"][:, [0, 3]], before["conv"][:, [0, 3]])
    for pool in ("k", "v"):
        # the masked lanes' blocks (3, 4) and every block nobody owns
        untouched = [b for b in range(40) if b not in (1, 2)]
        assert np.array_equal(after[pool][:, untouched], before[pool][:, untouched])
        assert not np.array_equal(after[pool][:, [1, 2]], before[pool][:, [1, 2]])
    # two live rows, two pairs each, in each of four routed layers
    assert int(out["step_counters"]["moe_pairs_routed_total"]) == 2 * 2 * 4
    assert np.asarray(out["step_counters"]["moe_expert_pairs"]).sum(axis=1).tolist() == [4] * 4


def test_a_reused_slot_starts_from_a_zeroed_tail(tiny):
    """One slot, two requests one after the other: the second's
    log-probabilities are those it gets from an engine nobody used."""
    model, cfg = tiny
    rng = np.random.default_rng(7)
    first, second = (rng.integers(0, 256, size=n).tolist() for n in (41, 23))
    engine = _engine(model, num_slots=1)
    _ask(engine, first)
    engine.run_until_idle()
    assert np.asarray(engine._cache["conv"]).any()
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
    back, re-queues, has its slot's tail zeroed and is prefilled again over
    prompt and emitted tokens; what it reports agrees with the reference as
    if nothing had happened."""
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
        assert is_best.all() and np.abs(_reported(r) - want).max() < LOGPROB_TOLERANCE


@pytest.mark.parametrize("armed, why", [
    (dict(swap_gb=0.01), "swap_gb"),
    (dict(spec_k=2, logprobs_topn=0), "spec_k"),
])
def test_what_a_tail_only_state_still_switches_off(tiny, armed, why):
    model, _ = tiny
    config = EngineConfig(num_slots=2, max_seq_len=64, prefill_chunk=16, block_size=8, **armed)
    with pytest.raises(ValueError, match="per-slot state") as e:
        InferenceEngine(model, config)
    assert why in str(e.value)


def test_state_dtype_bf16_is_refused_there_is_no_leaf_at_a_precision_of_its_own(tiny):
    """The whole per-slot state is tails in the served dtype: nothing for
    ``state_dtype`` to narrow, and the engine says so with the reason it
    gives an attention-only model."""
    with pytest.raises(ValueError, match="keeps no per-slot state at a precision of its own"):
        _engine(tiny[0], state_dtype="bf16")
    assert _engine(tiny[0], state_dtype="auto").stats()["state_dtype"] is None


# -- a model without step counters compiles the parent's programs -----------------

#: sha256 of the StableHLO text (``jitted.lower(...).as_text()``, no
#: locations) of the decode and prefill programs of a tiny llama and a tiny
#: hybrid engine, taken on the parent commit (dee9f3b): handing counters back
#: is a static branch, and a model that declares none traces what it traced
#: before. A PR that changes these programs on purpose takes the new digests.
PARENT_PROGRAMS = {
    "llama": {"decode": "a75288acbd793f5b9ccf1f009d154c0f8019fc3d1df65876c1ebe506ee837729",
              "prefill": "e594c3b623ec5bf1e9a060bc05fa603db2a18378056928ce059edf1767779368"},
    "hybrid": {"decode": "65630f0e129fc5e7a5dc1faba0de6280350efc41bb2a1d29cc2283efae433fae",
               "prefill": "0c510225ac2b234194e42ac32b93c15f4420d77415366687abb66744dabdc240"},
    # taken on 2a01d1b (the parent of PR 38, which gave the paged kernel its
    # ``block_len`` and the router its ``scoring``): at ``block_len`` 1 and
    # ``scoring="sigmoid"`` this model's programs, counters and all, are that commit's
    "lfm2": {"decode": "a66962a90db2b7a2b3b5764c3f9e93417827405a1aa95e95574284d18ad839c3",
             "prefill": "315cfca96229288ec1db9a01e713cb174fb85815e01b0de1cab1c6cde6384bfd"},
    # taken on 6756b7a (the parent of PR 42, which gave the cache spec a latent
    # kind, the router its groups and the expert product its held range): the
    # block round and the chunk of the fourth served family; the llama digests
    # above are the Mistral cell's programs (one model class)
    "sdar": {"decode": "86dec0d7ac67dffd6771d2ac0f08953ff38f85107a49b72e544cf087d9177402",
             "prefill": "195ee03170d3dc92ca790b54049075c6a5c9e90173e348d3df988a28a9bee818"},
}
# PR 46 took five of these on purpose, on the tree it built on 42d3b0d: every
# ``prefill`` (a chunk's step is asked for the one row the first token is picked
# from, ``logit_positions``; SDAR's chunk for none and hands back no logits) and
# SDAR's ``decode`` (a denoise pass asks for its sub-block's rows and picks over
# them). The ``decode`` digests of llama, hybrid and lfm2 are the commits' named
# above, untouched: a step that is not handed the argument is the parent's program


_DIGEST_SCRIPT = """
import hashlib, json, sys
from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
from accelerate_tpu.models.granite_hybrid import GraniteHybridConfig, GraniteHybridForCausalLM
from accelerate_tpu.models.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM
from accelerate_tpu.models.sdar_moe import SdarMoeConfig, SdarMoeForCausalLM
from accelerate_tpu.serving import EngineConfig, InferenceEngine
from accelerate_tpu.serving.sampling import SamplingParams
model = {"llama": lambda: LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0),
         "hybrid": lambda: GraniteHybridForCausalLM.from_config(GraniteHybridConfig.tiny(), seed=0),
         "lfm2": lambda: Lfm2MoeForCausalLM.from_config(Lfm2MoeConfig.tiny(), seed=0),
         "sdar": lambda: SdarMoeForCausalLM.from_config(SdarMoeConfig.tiny(), seed=0)}[sys.argv[1]]()
engine = InferenceEngine(model, EngineConfig(
    num_slots=4, max_seq_len=128, prefill_chunk=16, block_size=8, logprobs_topn=1, decode_burst=4))
engine.add_request(list(range(3, 40)), 6, sampling=SamplingParams(logprobs=1))
engine.run_until_idle()
out = {"counters": "moe_layers" in engine.stats()}
for program in ("decode", "prefill"):
    jitted, operands = engine._dispatched[program]
    out[program] = hashlib.sha256(jitted.lower(*operands).as_text().encode()).hexdigest()
print("DIGESTS " + json.dumps(out))
"""


@pytest.mark.parametrize("family", ["llama", "hybrid", "lfm2", "sdar"])
def test_a_model_without_counters_compiles_the_parents_programs(family):
    """In a process of its own: what a program lowers to also depends on
    process-wide settings other tests change (the attention context, the
    default matmul precision), and the digests were taken in a fresh one."""
    import subprocess

    done = subprocess.run(
        [sys.executable, "-c", _DIGEST_SCRIPT, family], cwd=HERE, capture_output=True, text=True,
        timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    assert done.returncode == 0, done.stderr[-2000:]
    got = json.loads(next(l for l in done.stdout.splitlines() if l.startswith("DIGESTS "))[8:])
    assert got == {"counters": family in ("lfm2", "sdar"), **PARENT_PROGRAMS[family]}


# -- the published file -> the model ------------------------------------------------


def _published(tmp_path, **changes):
    with open(CONFIG_FILE) as f:
        d = json.load(f)
    d.update(changes)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(d))
    return str(path)


def test_the_published_config_builds_the_published_model(tmp_path):
    config = config_from_hf_json(_published(tmp_path))
    assert type(config).__name__ == "Lfm2MoeConfig"
    assert (config.n_conv, config.n_attention, config.num_dense_layers, config.n_moe) == (10, 3, 1, 12)
    assert [i for i, k in enumerate(config.layer_types) if k == "full_attention"] == [1, 5, 9]
    assert (config.head_dim, config.num_experts, config.num_experts_per_tok) == (64, 32, 4)
    with init_empty_weights():
        model = model_factory_for_config(config)(config)
    flat = weights.flat_names(model.params)
    assert "lm_head" not in flat  # the head is the embedding
    # 3 x 362.9 M + 9 x 369.2 M routed layers, a 60.8 M dense one, 134.2 M embedding
    assert sum(int(np.prod(a.shape)) for a in flat.values()) == 4_606_249_728
    assert {k: tuple(a.shape) for k, a in flat.items()} == reference.leaf_shapes(
        {**dataclasses.asdict(config), "layer_types": list(config.layer_types)})
    spec = model.cache_spec
    assert spec.paged_layers == 3 and spec.kv_heads * spec.head_dim == 512
    assert list(spec.slot_state) == ["conv"]
    assert spec.slot_state["conv"].array_shape(64) == (10, 64, 2, 2048)
    assert spec.state_bytes_per_slot("bfloat16") == 10 * 2 * 2048 * 2 == 81_920
    assert model.step_counter_shapes["moe_expert_pairs"] == (12, 32)
    # as published: 24 layers, attention at 2, 6, 10, 14, 18, 21; 8.34 B tied
    with open(CONFIG_FILE) as f:
        reduced = json.load(f)["reduced"]
    whole = config_from_hf_json(_published(
        tmp_path, **{key: published for key, (published, _) in reduced.items()}))
    assert [i for i, k in enumerate(whole.layer_types) if k == "full_attention"] == [
        2, 6, 10, 14, 18, 21]
    assert whole.layer_types[1:14] == config.layer_types
    with init_empty_weights():
        params = model_factory_for_config(whole)(whole).params
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == 8_339_930_560


@pytest.mark.parametrize("changes, said", [
    (dict(layer_types=["conv", "mamba"] + ["conv"] * 11), r"holds \['mamba'\]"),
    (dict(num_hidden_layers=12), "layer_types names 13 layers, num_hidden_layers is 12"),
    (dict(num_dense_layers=14), "num_dense_layers 14 of num_hidden_layers 13"),
    (dict(num_experts_per_tok=33), "num_experts_per_tok 33 of num_experts 32"),
    (dict(conv_bias=True), "conv_bias false"),
    (dict(model_type="lfm3"), r"unsupported model_type 'lfm3' \(known: .*lfm2_moe.*\)"),
])
def test_what_cannot_be_built_as_published_is_refused_not_guessed_at(tmp_path, changes, said):
    with pytest.raises(ValueError, match=said):
        config_from_hf_json(_published(tmp_path, **changes))
    assert "lfm2_moe" in KNOWN_MODEL_TYPES


# -- serve --model-config ---------------------------------------------------------


def _serve_args(*flags):
    import argparse

    from accelerate_tpu.commands import serve

    cli = argparse.ArgumentParser()
    serve.add_parser(cli.add_subparsers())
    return serve, cli.parse_args(["serve", *flags])


def _published_small(tmp_path) -> str:
    """The published file at its rehearsal's widths."""
    with open(CONFIG_FILE) as f:
        d = json.load(f)
    small = {k: v for k, v in d["rehearsal"].items() if k not in ("serve_flags", "check")}
    return _published(tmp_path, **small)


def test_serve_builds_the_engine_of_a_published_config(tmp_path):
    serve, args = _serve_args("--model-config", _published_small(tmp_path), "--num-slots", "2",
                              "--max-seq-len", "64", "--prefill-chunk", "16")
    engine = serve._make_engine(args)
    s = engine.stats()
    assert (s["state_layers"], s["kv_layers"], s["moe_layers"]) == (3, 2, 4)
    assert s["prefix_cache"] is False
    request = engine.add_request(list(range(20)), 5)
    engine.run_until_idle()
    assert len(request.output_tokens) == 5
    # 20 prompt tokens and whole decode bursts, two pairs a token in four layers
    rows, rest = divmod(engine.stats()["moe_pairs_routed_total"], 2 * 4)
    assert rest == 0 and rows >= 20 + 4 and (rows - 20) % args.decode_burst == 0


def test_serve_refuses_state_dtype_bf16_for_a_tail_only_state(tmp_path):
    serve, args = _serve_args("--model-config", _published_small(tmp_path), "--state-dtype", "bf16")
    with pytest.raises(ValueError, match="keeps no per-slot state at a precision of its own"):
        serve._make_engine(args)


def test_auto_blocks_and_the_preflight_price_the_experts_unasked(tmp_path, capsys):
    """Weights are read from the parameter tree: the experts' bytes are in
    what ``--auto-blocks`` leaves for the pool and in the preflight's sum,
    with no tier of their own."""
    serve, args = _serve_args(
        "--model-config", _published_small(tmp_path), "--num-slots", "8", "--max-seq-len", "512",
        "--prefill-chunk", "16", "--auto-blocks", "--hbm-gb", "0.004")
    engine = serve._make_engine(args)
    capsys.readouterr()
    flat = weights.flat_names(engine._params)
    experts = sum(int(a.nbytes) for k, a in flat.items()
                  if k in ("layers.moe.w_in", "layers.moe.w_out"))
    total = sum(int(a.nbytes) for a in flat.values())
    assert experts > 0.5 * total
    pre = engine.hbm_preflight
    assert pre["params_bytes"] == total
    assert pre["state_bytes"] == engine.stats()["state_bytes_total"] == 8 * 3 * 2 * 64 * 4


def test_the_shard_plan_prices_the_published_cut_as_the_file_says():
    """At the published widths, shapes only: 9.21 GB of bfloat16 weights of
    which 8.46 GB are experts, 80 KB of tails a slot, 6,144 B of KV a token."""
    from accelerate_tpu.analysis.shardplan import plan_params

    config = config_from_hf_json(CONFIG_FILE)
    with init_empty_weights():
        model = model_factory_for_config(config)(config, dtype=jnp.bfloat16)
    sizes = {ax: 1 for ax in ("dp", "pp", "fsdp", "ep", "cp", "tp")}
    plan = plan_params(model.params, sizes, rules=model.partition_rules)
    assert sum(p.bytes_per_device for p in plan) == 2 * 4_606_249_728
    experts = 12 * 32 * 3 * 2048 * 1792 * 2
    assert experts == 8_455_716_864 and experts / (2 * 4_606_249_728) > 0.91
    spec = model.cache_spec
    assert 2 * spec.paged_layers * spec.kv_heads * spec.head_dim * 2 == 6_144
    assert 64 * spec.state_bytes_per_slot("bfloat16") == 5_242_880


# -- the benchmark's check at the rehearsal size: it has teeth --------------------


def _rehearse(monkeypatch, how):
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, CELL)
    config, traffic = common.apply_rehearsal(config, traffic)
    extra = {}
    if how == "fp8 KV pool":
        # the file's control: the program's own --kv-dtype fp8
        extra["serve_flags"] = probe.control_flags(config)
        assert extra["serve_flags"][-2:] == ["--kv-dtype", "fp8"]
    if how == "tail dropped at a prefill chunk's edge":
        conv = lfm2.conv_with_tail
        monkeypatch.setattr(lfm2, "conv_with_tail", lambda g, tail, *a, **kw: conv(
            g, jnp.zeros_like(tail) if g.shape[1] > 1 else tail, *a, **kw))
    if how == "the bias leaks into the weights":
        def leaky(x, w_gate, bias, top_k, norm=True, scale=1.0):
            scores = jax.nn.sigmoid(x.astype(jnp.float32) @ w_gate.astype(jnp.float32)) + bias
            top, experts = jax.lax.top_k(scores, top_k)
            return experts, top / (top.sum(-1, keepdims=True) + 1e-6) * scale
        monkeypatch.setattr(lfm2.moe, "route", leaky)  # where the layer looks it up
    ctx = common.Ctx(cell=cell, config=config, traffic=traffic, seed=3_600_000_021,
                     seconds=2.0, trace=False, rehearse=True, **extra)
    return common.load_driver("serve_engine").run(ctx)


def test_the_rehearsal_of_the_new_cell_is_correct(monkeypatch):
    out = _rehearse(monkeypatch, "sound")
    assert out["correct"] and out["failed"] == 0 and out["observed"]["compiles_in_window"] == 0
    limits = out["check"]["limits"]
    # a tenth of the limit: the sound float32 run reads 4e-7
    assert out["check"]["numbers"]["logprob_err_mean"] < limits["logprob_err_mean"] / 10


@pytest.mark.parametrize("how", [
    "tail dropped at a prefill chunk's edge", "the bias leaks into the weights", "fp8 KV pool"])
def test_a_run_that_loses_state_routing_or_precision_fails_the_rehearsal_limits(monkeypatch, how):
    out = _rehearse(monkeypatch, how)
    numbers, limits = out["check"]["numbers"], out["check"]["limits"]
    assert out["failed"] == 0 and not out["correct"] and not out["check"]["ok"]
    assert numbers["logprob_err_mean"] > 2 * limits["logprob_err_mean"], numbers
