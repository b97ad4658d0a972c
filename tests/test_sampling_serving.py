"""Per-slot sampling, constrained decoding, and the OpenAI front door.

The contract under test: per-request sampling knobs and grammar DFA
states ride the ONE compiled decode executable as fixed-shape lane
inputs — ``decode_compiles == 1`` with the lanes armed, including with
speculation and on a 4-device mesh — while greedy requests are served
the tokens the engine without lanes served them (recorded, at every
``kv_dtype``), and a fixed seed reproduces the exact same tokens
regardless of admission order or preempt/swap/resume.

Tier-1 (pure host / no compiles): params validation + resolution, stop
matching, the regex→DFA compiler and JSON-schema subset, the OpenAI
request/response translation (golden payloads, SSE framing, error
objects) against a fake submit fn, the metrics/monitor plumbing, the
pick's stages against their unstaged composition (bit for bit) and its
counters, the recorded greedy tokens and the prefill program's operands. The other
engine end-to-end legs and the real ``serve --http`` / ``route --http``
subprocess tests ride the slow lane.
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from accelerate_tpu.serving import (
    ITERATION_PHASES,
    EngineConfig,
    GrammarError,
    InferenceEngine,
    SamplingParams,
    compile_grammar,
    resolve_sampling,
    validate_instance,
)
from accelerate_tpu.serving.grammar import compile_regex, schema_to_regex
from accelerate_tpu.serving.sampling import match_stop

# ---------------------------------------------------------------------------
# sampling params: validation + resolution (tier-1)
# ---------------------------------------------------------------------------


def test_flight_phase_vocabulary_unchanged():
    """Sampling/grammar work lands inside the existing phases (the pick is
    part of dispatch, stop bookkeeping is harvest) — the flight recorder's
    phase vocabulary must NOT grow."""
    assert ITERATION_PHASES == (
        "schedule", "prefill", "dispatch", "device_wait", "harvest"
    )


@pytest.mark.parametrize(
    "kw, match",
    [
        (dict(temperature=-0.1), "temperature"),
        (dict(top_p=0.0), "top_p"),
        (dict(top_p=1.5), "top_p"),
        (dict(top_k=-1), "top_k"),
        (dict(repetition_penalty=0.0), "repetition_penalty"),
        (dict(min_tokens=-1), "min_tokens"),
        (dict(logprobs=-1), "logprobs"),
    ],
)
def test_sampling_params_refusals(kw, match):
    with pytest.raises(ValueError, match=match):
        SamplingParams(**kw).validate()


def test_resolve_sampling_coercions():
    # None inherits the engine default
    default = SamplingParams(do_sample=True, temperature=0.7)
    assert resolve_sampling(None, default) is default
    assert resolve_sampling(None) == SamplingParams()
    # dicts validate; a bare token-id sequence becomes one stop sequence
    p = resolve_sampling({"do_sample": True, "seed": 7, "stop": [3, 4]})
    assert p.seed == 7 and p.stop == ((3, 4),)
    p = resolve_sampling({"stop": [[3], [4, 5]]})
    assert p.stop == ((3,), (4, 5))
    with pytest.raises(ValueError, match="unknown sampling params"):
        resolve_sampling({"temprature": 1.0})  # typo'd key names itself
    with pytest.raises(ValueError, match="dict or SamplingParams"):
        resolve_sampling("greedy")
    # inert == indistinguishable from bare greedy (argmax fast path)
    assert SamplingParams().inert
    assert not SamplingParams(do_sample=True).inert
    assert not SamplingParams(repetition_penalty=1.2).inert
    assert not SamplingParams(logprobs=2).inert


def test_match_stop_suffix_semantics():
    # returns the matched length (the caller trims that many tokens)
    assert match_stop([1, 2, 3], ((2, 3),)) == 2
    assert match_stop([1, 2, 3], ((9,), (3,))) == 1
    assert match_stop([1, 2, 3], ((1, 2),)) == 0  # suffix only
    assert match_stop([1], ((1, 1),)) == 0  # longer than output
    assert match_stop([1, 2, 3], ()) == 0


# ---------------------------------------------------------------------------
# grammar: regex → DFA, schema subset, cache (tier-1)
# ---------------------------------------------------------------------------


def test_regex_dfa_walk_and_final_states():
    g = compile_regex("ab+c", 256, eos_id=0)
    s = g.start
    assert g.allows(s, ord("a")) and not g.allows(s, ord("b"))
    s = g.advance(s, ord("a"))
    s = g.advance(s, ord("b"))
    assert g.allows(s, ord("b")) and g.allows(s, ord("c"))
    s = g.advance(s, ord("c"))
    assert g.accepting[s]
    # 'c' is terminal for this pattern: accepting with no way forward
    assert g.final[s]
    # eos is only allowed from accepting states
    assert g.allows(s, 0)
    assert not g.allows(g.start, 0)


def test_regex_open_ended_accepting_is_not_final():
    g = compile_regex("[0-9]+", 256)
    s = g.advance(g.start, ord("7"))
    assert g.accepting[s] and not g.final[s]  # more digits always legal


def test_padded_tables_shapes():
    g = compile_regex("ab", 256)
    allow, trans = g.padded_tables(16)
    assert allow.shape == (16, 256) and trans.shape == (16, 256)
    # padding rows are inert (all-allow) — a stale lane value can never
    # produce an all-masked distribution
    assert allow[g.num_states:].all()
    assert (trans[g.num_states:] == 0).all()
    with pytest.raises(GrammarError, match="grammar_states"):
        g.padded_tables(g.num_states - 1)


def test_schema_subset_to_regex_and_validate():
    assert json.loads("42") == 42  # sanity on the target encoding
    for schema, good, bad in [
        ({"type": "integer"}, 42, 4.5),
        ({"type": "boolean"}, True, "true"),
        ({"type": "number"}, -3.5, "x"),
        ({"enum": ["a", "b"]}, "a", "c"),
        ({"type": "string"}, "hi", 7),
        ({"type": "null"}, None, 0),
    ]:
        pattern = schema_to_regex(schema)
        assert isinstance(pattern, str) and pattern
        assert validate_instance(schema, good) is None
        with pytest.raises(GrammarError):
            validate_instance(schema, bad)
    obj_schema = {
        "type": "object",
        "properties": {"n": {"type": "integer"}},
        "required": ["n"],
    }
    assert validate_instance(obj_schema, {"n": 1}) is None
    with pytest.raises(GrammarError, match="missing property"):
        validate_instance(obj_schema, {})
    arr = {"type": "array", "items": {"type": "integer"}}
    assert validate_instance(arr, [1, 2]) is None
    with pytest.raises(GrammarError):
        validate_instance(arr, [1, "x"])


@pytest.mark.parametrize(
    "spec, match",
    [
        ({"type": "regex", "pattern": ""}, "pattern"),
        ({"type": "json_schema"}, "schema"),
        ({"type": "bnf", "rules": "x"}, "unknown grammar type"),
        # lowercase letters are bytes >= 97: 'true|false' cannot be spelt
        # over the 64-token byte vocab — refused at compile, not at decode
        ({"type": "json_schema", "schema": {"type": "boolean"}},
         "matches nothing over this vocabulary"),
    ],
)
def test_grammar_compile_refusals(spec, match):
    with pytest.raises(GrammarError, match=match):
        compile_grammar(spec, 64, eos_id=0)


def test_grammar_cache_memoises_by_spec_and_vocab():
    spec = {"type": "regex", "pattern": "[0-9]{1,4}"}
    a = compile_grammar(spec, 256, eos_id=0, max_states=64)
    b = compile_grammar(dict(spec), 256, eos_id=0, max_states=64)
    assert a is b  # hash of the spec, not object identity
    c = compile_grammar(spec, 128, eos_id=0, max_states=64)
    assert c is not a  # vocab is part of the key
    assert a.hash == c.hash  # ... but the spec hash matches


# ---------------------------------------------------------------------------
# OpenAI front end: translation + framing against a fake submit (tier-1)
# ---------------------------------------------------------------------------


def _fake_submit(result_fn, capture):
    """A submit fn that answers synchronously: records the payload, echoes
    a result row derived from it."""

    def submit(payload, cb):
        capture.append(payload)
        cb(result_fn(payload))

    return submit


def _ok_result(payload, tokens=(104, 105)):
    out = {
        "tokens": list(tokens),
        "finish_reason": "length",
        "prompt_tokens": len(payload["prompt"]),
    }
    if "trace_id" in payload:  # the serve loop echoes it back like this
        out["trace_id"] = payload["trace_id"]
    return out


def test_openai_completion_payload_and_body_golden():
    from accelerate_tpu.serving.openai_api import OpenAIFrontend

    sent = []
    fe = OpenAIFrontend(_fake_submit(_ok_result, sent))
    kind, status, body = fe.handle("/v1/completions", {
        "prompt": "hi", "temperature": 0, "max_tokens": 4, "stop": "X",
        "seed": 3, "x_accelerate_priority": "batch",
        "x_accelerate_trace_id": "0af7651916cd43dd8448eb211c80319c",
    })
    assert (kind, status) == ("json", 200)
    payload = sent[0]
    assert payload["prompt"] == [104, 105]  # UTF-8 bytes of "hi"
    assert payload["sampling"]["do_sample"] is False  # temperature 0 == greedy
    assert payload["sampling"]["seed"] == 3
    assert payload["sampling"]["stop"] == [[88]]
    assert payload["max_new_tokens"] == 4
    assert payload["priority"] == "batch"
    assert payload["trace_id"] == "0af7651916cd43dd8448eb211c80319c"
    assert body["object"] == "text_completion"
    assert body["id"].startswith("cmpl-")
    assert body["choices"][0]["text"] == "hi"
    assert body["choices"][0]["finish_reason"] == "length"
    assert body["usage"] == {
        "prompt_tokens": 2, "completion_tokens": 2, "total_tokens": 4,
    }
    assert body["x_accelerate"]["trace_id"] == "0af7651916cd43dd8448eb211c80319c"


def test_openai_chat_payload_defaults_to_sampling():
    from accelerate_tpu.serving.openai_api import OpenAIFrontend

    sent = []
    fe = OpenAIFrontend(_fake_submit(_ok_result, sent))
    kind, status, body = fe.handle("/v1/chat/completions", {
        "messages": [{"role": "user", "content": "hello"}],
        "top_p": 0.9, "response_format": {
            "type": "json_schema",
            "json_schema": {"name": "t", "schema": {"type": "integer"}},
        },
    })
    assert status == 200
    payload = sent[0]
    # OpenAI default temperature 1.0 → sampled lanes, top_p forwarded
    assert payload["sampling"]["do_sample"] is True
    assert payload["sampling"]["top_p"] == 0.9
    assert payload["grammar"] == {"type": "json_schema",
                                  "schema": {"type": "integer"}}
    assert body["object"] == "chat.completion"
    assert body["id"].startswith("chatcmpl-")
    assert body["choices"][0]["message"]["role"] == "assistant"
    assert body["choices"][0]["message"]["content"] == "hi"


def test_openai_sse_framing_delta_mode():
    """Streaming contract: a role-bearing first chunk, content deltas,
    exactly one finish chunk carrying usage, then ``data: [DONE]``."""
    from accelerate_tpu.serving.openai_api import OpenAIFrontend

    def submit(payload, cb):
        stream = payload["_stream"]
        stream([104])
        stream([105, 33])
        cb(_ok_result(payload, tokens=(104, 105, 33)))

    fe = OpenAIFrontend(submit, streaming="delta")
    kind, events = fe.handle("/v1/chat/completions", {
        "messages": [{"role": "user", "content": "go"}], "stream": True,
    })
    assert kind == "sse"
    frames = list(events)
    assert all(f.startswith("data: ") and f.endswith("\n\n") for f in frames)
    assert frames[-1] == "data: [DONE]\n\n"
    chunks = [json.loads(f[6:]) for f in frames[:-1]]
    assert all(c["object"] == "chat.completion.chunk" for c in chunks)
    assert chunks[0]["choices"][0]["delta"]["role"] == "assistant"
    text = "".join(c["choices"][0]["delta"].get("content", "") for c in chunks)
    assert text == "hi!"
    finals = [c for c in chunks if c["choices"][0]["finish_reason"]]
    assert len(finals) == 1
    assert finals[0]["usage"]["completion_tokens"] == 3


def test_openai_sse_at_completion_replays_whole_answer():
    """Route mode: replicas answer whole completions, the front end still
    speaks SSE — one content chunk, one finish chunk, [DONE]."""
    from accelerate_tpu.serving.openai_api import OpenAIFrontend

    fe = OpenAIFrontend(_fake_submit(_ok_result, []), streaming="at_completion")
    kind, events = fe.handle("/v1/completions", {"prompt": "x", "stream": True})
    frames = list(events)
    assert frames[-1] == "data: [DONE]\n\n"
    chunks = [json.loads(f[6:]) for f in frames[:-1]]
    assert "".join(c["choices"][0].get("text") or "" for c in chunks) == "hi"
    assert sum(1 for c in chunks if c["choices"][0]["finish_reason"]) == 1


@pytest.mark.parametrize(
    "path, body, param",
    [
        ("/v1/completions", {"prompt": "x", "n": 3}, "n"),
        ("/v1/completions", {"prompt": 42}, "prompt"),
        ("/v1/completions", {"prompt": "x", "temperature": 3.0}, "temperature"),
        ("/v1/completions", {"prompt": "x", "seed": "lucky"}, "seed"),
        ("/v1/completions", {"prompt": "x", "max_tokens": 0}, "max_tokens"),
        ("/v1/completions",
         {"prompt": "x", "response_format": {"type": "json_object"}},
         "response_format"),
        ("/v1/chat/completions", {"messages": []}, "messages"),
        ("/v1/chat/completions",
         {"messages": [{"role": "user", "content": "x"}], "tools": [{}]},
         "tools"),
    ],
)
def test_openai_error_objects(path, body, param):
    from accelerate_tpu.serving.openai_api import OpenAIFrontend

    fe = OpenAIFrontend(_fake_submit(_ok_result, []))
    kind, status, out = fe.handle(path, body)
    assert (kind, status) == ("json", 400)
    err = out["error"]
    assert err["type"] == "invalid_request_error"
    assert err["param"] == param
    assert isinstance(err["message"], str) and err["message"]


def test_openai_engine_error_rows_become_error_objects():
    from accelerate_tpu.serving.openai_api import OpenAIFrontend

    fe = OpenAIFrontend(_fake_submit(lambda p: {"error": "queue full"}, []))
    kind, status, out = fe.handle("/v1/completions", {"prompt": "x"})
    assert status == 400 and "queue full" in out["error"]["message"]


# ---------------------------------------------------------------------------
# metrics + monitor plumbing (tier-1: synthetic rows)
# ---------------------------------------------------------------------------


def test_sampling_metrics_round_trip_both_surfaces():
    """The new counters/gauges flow through BOTH ingest surfaces — the
    telemetry step-row path and the live stats()-dict path — into the
    documented serving_* names with the mode label split."""
    from accelerate_tpu.metrics.ingest import observe_engine_stats, observe_record
    from accelerate_tpu.metrics.openmetrics import (
        parse_openmetrics,
        render_openmetrics,
        sample_value,
    )
    from accelerate_tpu.metrics.registry import MetricsRegistry

    reg = MetricsRegistry(gate_main_process=False)
    observe_record(reg, {
        "type": "serving", "kind": "step",
        "sampled_tokens_greedy": 40, "sampled_tokens_sample": 10,
        "grammar_masked_steps": 6,
        "rejection_drafted_tokens": 20, "rejection_accepted_tokens": 15,
        "rejection_accept_rate": 0.75,
    })
    families = parse_openmetrics(render_openmetrics(reg))
    assert families["accelerate_serving_sampled_tokens"]["type"] == "counter"
    assert sample_value(
        families, "accelerate_serving_sampled_tokens", mode="greedy") == 40
    assert sample_value(
        families, "accelerate_serving_sampled_tokens", mode="sample") == 10
    assert sample_value(families, "accelerate_serving_grammar_masked_steps") == 6
    assert sample_value(families, "accelerate_serving_rejection_accept_rate") == 0.75

    # the stats() path ratchets the same counters (set_total semantics)
    observe_engine_stats(reg, {
        "sampled_tokens_greedy": 100, "sampled_tokens_sample": 30,
        "grammar_masked_steps": 9, "rejection_accept_rate": 0.8,
    })
    families = parse_openmetrics(render_openmetrics(reg))
    assert sample_value(
        families, "accelerate_serving_sampled_tokens", mode="greedy") == 100
    assert sample_value(
        families, "accelerate_serving_sampled_tokens", mode="sample") == 30
    assert sample_value(families, "accelerate_serving_grammar_masked_steps") == 9
    assert sample_value(families, "accelerate_serving_rejection_accept_rate") == 0.8


# ---------------------------------------------------------------------------
# the pick's stages (tier-1; pick_tokens alone, no model)
# ---------------------------------------------------------------------------

PICK_SLOTS, PICK_VOCAB, PICK_EOS, PICK_STEP = 6, 1003, 2, 1


def _unstaged_pick(logits, lanes, dfa_state, step, gmask, base_key, *, eos_id, logprobs_topn):
    """The body of ``fancy`` at commit 5de64b7, frozen: every stage for
    every slot on every call. The reference ``pick_tokens`` must equal bit
    for bit, whatever its lanes ask."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.serving import sampling as S

    n = max(int(logprobs_topn), 1)
    pos = lanes["pos"] + step
    filtered = S.apply_filters(logits, lanes, dfa_state, pos, gmask, eos_id)
    greedy = jnp.argmax(filtered, axis=-1).astype(jnp.int32)
    logp_dist = S.dist_logprobs(filtered, lanes)
    keys = S.slot_keys(base_key, lanes["seed"], pos, S.TAG_SAMPLE)
    sampled = S.categorical_per_slot(keys, logp_dist)
    tok = jnp.where(lanes["sample"], sampled, greedy).astype(jnp.int32)
    lp = jax.nn.log_softmax(jnp.asarray(filtered, jnp.float32), axis=-1)
    logp_tok = jnp.take_along_axis(lp, tok[:, None], axis=1)[:, 0]
    top_vals, top_ids = jax.lax.top_k(lp, n)
    return tok, logp_tok, top_vals, top_ids.astype(jnp.int32)


def _pick_operands(dtype, mix):
    """Random logits with ties planted at every row's maximum (two more
    columns hold it, one of them ``eos``), all lanes live and greedy but
    for slot 3, which ``mix`` arms."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.serving.sampling import blank_lanes, set_slot_lane

    rng = np.random.default_rng(31)
    logits = (rng.standard_normal((PICK_SLOTS, PICK_VOCAB)) * 4).astype(np.float32)
    top = logits.max(axis=1) + 0.5
    for col in (PICK_EOS, 517, 998):
        logits[:, col] = top
    lanes = blank_lanes(PICK_SLOTS, 8)
    for slot in range(PICK_SLOTS):
        set_slot_lane(lanes, slot, SamplingParams(seed=slot, logprobs=1), pos=slot)
    params = {
        "greedy": SamplingParams(logprobs=1),
        "sample": SamplingParams(do_sample=True, temperature=0.7, top_p=0.9, seed=9),
        "repetition": SamplingParams(repetition_penalty=1.7),
        "min_tokens": SamplingParams(min_tokens=7),
        "grammar": SamplingParams(),
    }[mix]
    set_slot_lane(
        lanes, 3, params, pos=3, grammar_row=int(mix == "grammar"), dfa_state=2,
        recent=(PICK_EOS, 517, 40) if mix == "repetition" else (),
    )
    gmask = np.ones((2, 4, PICK_VOCAB), bool)
    gmask[1, 2, : PICK_VOCAB // 2] = False  # row 1, state 2: the upper half only
    return (
        jnp.asarray(logits, dtype), {k: jnp.asarray(v) for k, v in lanes.items()},
        jnp.asarray(lanes["dfa_state"]), jnp.int32(PICK_STEP), jnp.asarray(gmask),
        jax.random.PRNGKey(4),
    )


@pytest.mark.parametrize("mix", ["greedy", "sample", "repetition", "min_tokens", "grammar"])
@pytest.mark.parametrize("topn", [1, 3])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_pick_runs_only_the_stages_its_lanes_ask_for(dtype, topn, mix):
    """Token, its log-probability and the top-N are those of the unstaged
    composition to the bit: staging may skip work nobody asked for and may
    change no value. bfloat16 is what the engine hands the pick (a
    log-probability taken in bfloat16 fails here, by dtype and by bits);
    the vocabulary is no multiple of 128."""
    import functools

    import jax

    from accelerate_tpu.serving.sampling import pick_tokens

    operands = _pick_operands(dtype, mix)
    kw = dict(eos_id=PICK_EOS, logprobs_topn=topn)
    want = jax.jit(functools.partial(_unstaged_pick, **kw))(*operands)
    got = jax.jit(functools.partial(pick_tokens, **kw))(*operands)
    for name, a, b in zip(("tok", "logp_tok", "top_vals", "top_ids"), got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert a.tobytes() == b.tobytes(), (name, a, b)
    tok = np.asarray(got[0])
    # the planted ties decide the greedy slots: the first column that holds
    # the maximum, which is eos
    assert (np.delete(tok, 3) == PICK_EOS).all()
    if mix in ("repetition", "min_tokens"):
        assert tok[3] == {"repetition": 998, "min_tokens": 517}[mix]
    if mix == "grammar":
        assert tok[3] == 517  # the first maximum the mask allows


def _vocabulary_wide_ops(jaxpr, vocab, in_cond=False):
    """(primitive, inside a cond branch) of every equation that sorts,
    scans, scatters or draws over the vocabulary, through every nested
    jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        wide = (
            name in ("sort", "cumsum", "random_bits", "threefry2x32")
            or (name == "top_k" and eqn.params["k"] == vocab)
            or (name.startswith("scatter") and vocab in eqn.invars[2].aval.shape)
        )
        if wide:
            found.append((name, in_cond))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr's own
                if hasattr(sub, "eqns"):
                    found += _vocabulary_wide_ops(sub, vocab, in_cond or name == "cond")
    return found


def test_pick_keeps_the_sampler_inside_a_cond():
    """At ``logprobs_topn=1`` no sort, cumulative sum, random draw or
    vocabulary-wide scatter sits outside a ``cond`` branch: a later edit
    that hoists the draw back out fails here, on the CPU."""
    import functools

    import jax

    from accelerate_tpu.serving.sampling import pick_tokens

    operands = _pick_operands("bfloat16", "greedy")
    jaxpr = jax.make_jaxpr(
        functools.partial(pick_tokens, eos_id=PICK_EOS, logprobs_topn=1)
    )(*operands).jaxpr
    found = _vocabulary_wide_ops(jaxpr, PICK_VOCAB)
    assert [name for name, in_cond in found if not in_cond] == []
    inside = {name for name, in_cond in found if in_cond}
    assert {"top_k", "cumsum", "scatter"} <= inside
    assert inside & {"random_bits", "threefry2x32"}
    # and the reference does hold them at the top level: the walk sees them
    unstaged = jax.make_jaxpr(
        functools.partial(_unstaged_pick, eos_id=PICK_EOS, logprobs_topn=1)
    )(*operands).jaxpr
    assert {name for name, in_cond in _vocabulary_wide_ops(unstaged, PICK_VOCAB)
            if not in_cond} >= {"top_k", "cumsum", "scatter"}


# ---------------------------------------------------------------------------
# engine end-to-end (compiles the tiny model; slow lane but for the first three)
# ---------------------------------------------------------------------------

KV_DTYPES = ("bf16", "int8", "fp8")


@pytest.fixture(scope="module")
def tiny_model():
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig.tiny(vocab_size=64, hidden_size=32, layers=2, heads=4, seq=96)
    return LlamaForCausalLM.from_config(config, seed=0)


def _cfg(**kw):
    base = dict(num_slots=3, block_size=8, max_seq_len=64, prefill_chunk=8)
    base.update(kw)
    return EngineConfig(**base)


def _prompts(seed, sizes=(5, 11, 17, 3, 9)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, size=n).astype(np.int32) for n in sizes]


#: what the parent commit (8754e62) served this traffic on the CPU, by pool
#: dtype, from the executables it still kept from before the lanes: no lane
#: operand, every token a bare argmax
RECORDED_TOKENS = {
    "bf16": [
        [30, 6, 27], [8, 8, 8, 8, 8, 55, 27],
        [14, 9, 11, 20, 38, 20, 9, 30, 9, 30, 56],
        [27, 17, 36, 36, 57, 17, 5, 37, 37, 17, 36, 17, 5, 63, 37],
        [27, 17, 48, 11, 14, 14, 14, 14, 11, 7, 9, 11, 11, 11, 14, 11, 11, 11, 11],
    ],
    "int8": [
        [30, 6, 27], [8, 8, 8, 8, 8, 55, 27],
        [14, 9, 11, 20, 38, 20, 9, 30, 9, 30, 38],
        [27, 17, 36, 36, 57, 17, 5, 37, 37, 17, 36, 17, 5, 63, 37],
        [27, 17, 48, 11, 14, 14, 14, 14, 11, 7, 9, 11, 11, 11, 14, 11, 11, 11, 11],
    ],
    "fp8": [
        [30, 54, 48], [8, 8, 8, 8, 8, 55, 27],
        [14, 9, 11, 20, 38, 20, 9, 30, 9, 11, 7],
        [27, 17, 36, 24, 17, 37, 17, 63, 37, 17, 37, 17, 17, 59, 63],
        [27, 17, 48, 11, 14, 14, 14, 14, 11, 7, 9, 11, 11, 11, 14, 11, 11, 11, 11],
    ],
}


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_greedy_traffic_is_served_the_recorded_tokens(tiny_model, kv_dtype):
    """The lanes change NOTHING for greedy traffic: the tokens are those
    of the bare-argmax executables at every kv_dtype, from one decode and
    one prefill executable."""
    budgets = [3 + 4 * i for i in range(5)]
    eng = InferenceEngine(tiny_model, _cfg(kv_dtype=kv_dtype))
    reqs = [eng.add_request(p, b) for p, b in zip(_prompts(0), budgets)]
    eng.run_until_idle(max_iterations=5000)
    assert [list(r.output_tokens) for r in reqs] == RECORDED_TOKENS[kv_dtype]
    st = eng.stats()
    assert st["decode_compiles"] == 1 and st["prefill_compiles"] == 1
    assert st["sampled_tokens_greedy"] == sum(budgets)
    assert st["sampled_tokens_sample"] == 0


def test_pick_counters_follow_the_lanes(tiny_model):
    """``pick_draw_dispatches_total`` counts the runs of the pick in which
    some lane samples: none for greedy traffic, with or without a
    log-probability asked for; ``reset_stats()`` zeroes both counters."""
    eng = InferenceEngine(tiny_model, _cfg(logprobs_topn=1))
    prompts = _prompts(4, sizes=(5, 9, 7))
    eng.add_request(prompts[0], 6)
    eng.add_request(prompts[1], 6, sampling={"logprobs": 1})
    eng.run_until_idle(max_iterations=5000)
    st = eng.stats()
    # two first picks, and at least one burst of decode
    assert st["pick_dispatches_total"] >= 3
    assert st["pick_draw_dispatches_total"] == 0
    eng.reset_stats()
    st = eng.stats()
    assert st["pick_dispatches_total"] == st["pick_draw_dispatches_total"] == 0
    eng.add_request(
        prompts[2], 6, sampling={"do_sample": True, "temperature": 0.8, "seed": 3}
    )
    eng.run_until_idle(max_iterations=5000)
    st = eng.stats()
    assert st["pick_draw_dispatches_total"] == st["pick_dispatches_total"] >= 2
    assert st["decode_compiles"] == 1


def test_prefill_program_takes_no_key_and_picks_no_token(tiny_model):
    """The first token is picked once, by ``_first_token_pick``, from the
    logits the prefill program hands back: the program itself takes the
    eight operands below and returns the cache and one row of logits."""
    import inspect

    import jax

    eng = InferenceEngine(tiny_model, _cfg())
    eng.add_request(_prompts(0)[0], 2)
    eng.run_until_idle(max_iterations=100)
    jitted, operands = eng._dispatched["prefill"]
    assert list(inspect.signature(jitted).parameters) == [
        "params", "cache", "block_table", "start", "chunk", "valid", "last_idx", "slot",
    ]
    cache, logits = jax.eval_shape(jitted, *operands)
    shapes = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)  # noqa: E731
    assert shapes(cache) == shapes(operands[1]) and logits.shape == (64,)


@pytest.mark.slow
def test_fixed_seed_reproduces_across_admission_order(tiny_model):
    """A request's sampled tokens are a function of (prompt, seed, step) —
    never of which slot it landed in or who was admitted first."""
    prompts = _prompts(1, sizes=(6, 9, 12))
    payloads = [
        {"do_sample": True, "temperature": 0.9, "seed": 100 + i,
         "top_k": 40, "top_p": 0.95}
        for i in range(3)
    ]

    def run(order):
        eng = InferenceEngine(tiny_model, _cfg())
        reqs = {}
        for i in order:
            reqs[i] = eng.add_request(prompts[i], 8, sampling=payloads[i])
        eng.run_until_idle(max_iterations=5000)
        return {i: list(r.output_tokens) for i, r in reqs.items()}

    a = run([0, 1, 2])
    b = run([2, 0, 1])
    assert a == b
    assert any(a[i] for i in a)  # the trace actually decoded tokens


@pytest.mark.slow
def test_fixed_seed_reproduces_across_swap_preemption(tiny_model):
    """Preempt → swap out → restore mid-request replays nothing: the
    per-slot key is derived from (seed, position), so a sampled request
    resumes exactly where it left off, token-identical to the
    uncontended run."""
    prompts = [np.arange(8, dtype=np.int32), np.arange(8, dtype=np.int32) + 1]
    sampling = [
        {"do_sample": True, "temperature": 1.1, "seed": 7},
        {"do_sample": True, "temperature": 0.8, "seed": 8, "top_k": 20},
    ]

    def run(**pressure):
        eng = InferenceEngine(
            tiny_model,
            _cfg(num_slots=2, prefix_cache=False, **pressure),
        )
        reqs = [
            eng.add_request(p, max_new_tokens=30, sampling=s)
            for p, s in zip(prompts, sampling)
        ]
        eng.run_until_idle(max_iterations=5000)
        return eng, [list(r.output_tokens) for r in reqs]

    squeezed_eng, squeezed = run(num_blocks=6, swap_gb=0.01)
    _, roomy = run()
    assert squeezed == roomy
    st = squeezed_eng.stats()
    assert st["preemptions"] >= 1
    assert st["swapped_out_blocks"] == st["swapped_in_blocks"] > 0
    assert st["decode_compiles"] == 1


@pytest.mark.slow
def test_mixed_batch_one_executable_with_logprobs(tiny_model):
    """Greedy + sampled + grammar-constrained slots decode side by side in
    the SAME compiled executable; logprobs ride the existing harvest."""
    eng = InferenceEngine(tiny_model, _cfg(logprobs_topn=3))
    greedy = eng.add_request(_prompts(2)[0], 6)
    sampled = eng.add_request(
        _prompts(2)[1], 6,
        sampling={"do_sample": True, "temperature": 0.8, "seed": 5, "logprobs": 2},
    )
    digits = eng.add_request(
        _prompts(2)[3], 6,
        sampling={"do_sample": True, "temperature": 0.9, "seed": 6},
        grammar={"type": "regex", "pattern": "[0-9]+"},
    )
    eng.run_until_idle(max_iterations=5000)
    st = eng.stats()
    assert st["decode_compiles"] == 1 and st["prefill_compiles"] == 1
    assert st["sampled_tokens_greedy"] > 0 and st["sampled_tokens_sample"] > 0
    assert st["grammar_masked_steps"] == len(digits.output_tokens)
    assert greedy.finish_reason == "length"
    # the constrained slot only ever emitted digit bytes
    assert all(48 <= t <= 57 for t in digits.output_tokens)
    # logprobs: one entry per emitted token — the picked token's logprob
    # plus a descending top-2, all in the log domain
    assert sampled.logprobs is not None
    assert len(sampled.logprobs) == len(sampled.output_tokens)
    for entry, tok in zip(sampled.logprobs, sampled.output_tokens):
        assert entry["token"] == tok
        assert entry["logprob"] <= 0.0
        assert len(entry["top"]) == 2
        assert entry["top"][0][1] >= entry["top"][1][1]
    assert greedy.logprobs is None  # opt-in per request
    # grammar rows recycle once the holder finishes
    assert st["grammar_rows_live"] == 0


@pytest.mark.slow
def test_mixed_batch_one_executable_on_mesh4(tiny_model):
    """The same mixed batch over fsdp=2 x tp=2: lanes + grammar tables are
    replicated GSPMD inputs, decode_compiles == 1 on the mesh, and the
    sampled output is identical to the single-device engine."""
    import jax

    from accelerate_tpu.mesh import build_mesh
    from accelerate_tpu.utils.dataclasses import MeshPlugin

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs a >= 4-device (virtual) mesh")
    mesh = build_mesh(MeshPlugin(dp=1, fsdp=2, tp=2), devices=devices[:4])
    prompts = _prompts(3, sizes=(5, 12, 9))

    def run(mesh_arg):
        eng = InferenceEngine(tiny_model, _cfg(decode_burst=2), mesh=mesh_arg)
        reqs = [
            eng.add_request(prompts[0], 5),
            eng.add_request(
                prompts[1], 5,
                sampling={"do_sample": True, "temperature": 0.9, "seed": 11},
            ),
            eng.add_request(
                prompts[2], 5,
                sampling={"do_sample": True, "temperature": 0.7, "seed": 12},
                grammar={"type": "regex", "pattern": "[0-9]+"},
            ),
        ]
        eng.run_until_idle(max_iterations=5000)
        return eng, [list(r.output_tokens) for r in reqs]

    _, single = run(None)
    sharded_eng, sharded = run(mesh)
    assert sharded == single
    st = sharded_eng.stats()
    assert st["decode_compiles"] == 1
    assert st["mesh"] == {"fsdp": 2, "tp": 2}


@pytest.mark.slow
def test_rejection_sampling_goes_greedy_at_low_temperature(tiny_model):
    """temperature → 0 is the analytic sanity check for the rejection
    path: target and draft both collapse to argmax, so a draft token is
    accepted exactly when the two argmaxes agree — the sampled output
    equals the greedy spec output token for token and the rejection
    accept rate lands on the greedy agreement rate."""

    def run(sampling):
        eng = InferenceEngine(
            tiny_model, _cfg(spec_k=3, draft="early_exit:1")
        )
        reqs = [
            eng.add_request(p, 8, sampling=sampling)
            for p in _prompts(4, sizes=(6, 13))
        ]
        eng.run_until_idle(max_iterations=5000)
        return eng, [list(r.output_tokens) for r in reqs]

    greedy_eng, greedy_toks = run(None)
    eng, cold_toks = run({"do_sample": True, "temperature": 1e-6, "seed": 1})
    assert cold_toks == greedy_toks
    st = eng.stats()
    assert st["decode_compiles"] == 1
    assert st["rejection_drafted_tokens"] > 0
    # identical tokens → identical rounds: the rejection rate reproduces
    # the greedy longest-prefix agreement rate, not some sampled blur
    assert st["rejection_accept_rate"] == pytest.approx(
        greedy_eng.stats()["spec_accept_rate"], abs=0.1
    )
    # hot sampling still makes progress and keeps the rate in range
    hot_eng, hot_toks = run({"do_sample": True, "temperature": 2.0, "seed": 2})
    assert all(toks for toks in hot_toks)
    assert 0.0 < hot_eng.stats()["rejection_accept_rate"] <= 1.0


@pytest.mark.slow
def test_constrained_output_parses_and_validates(tiny_model):
    """Every grammar-constrained completion is valid JSON for its schema —
    including under sampling and composed with speculation. (Only scalar
    schemas fit the 64-token test vocab: object braces are bytes >= 123.)"""
    schema = {"type": "integer"}

    def run(spec_k):
        eng = InferenceEngine(
            tiny_model,
            _cfg(spec_k=spec_k,
                 draft="early_exit:1" if spec_k else "early_exit:2"),
        )
        reqs = [
            eng.add_request(
                p, 8,
                sampling={"do_sample": True, "temperature": 1.2, "seed": 20 + i},
                grammar={"type": "json_schema", "schema": schema},
            )
            for i, p in enumerate(_prompts(5, sizes=(4, 7, 10)))
        ]
        eng.run_until_idle(max_iterations=5000)
        return eng, reqs

    for spec_k in (0, 3):
        eng, reqs = run(spec_k)
        assert eng.stats()["decode_compiles"] == 1
        for req in reqs:
            text = bytes(req.output_tokens).decode()
            value = json.loads(text)  # digits (int mask) always parse
            assert validate_instance(schema, value) is None
            # a DFA-final state finishes the request as a natural stop
            assert req.finish_reason in ("stop", "length")


@pytest.mark.slow
def test_stop_sequences_and_min_tokens(tiny_model):
    eng = InferenceEngine(tiny_model, _cfg())
    probe = eng.add_request(_prompts(6)[0], 10)
    eng.run_until_idle(max_iterations=5000)
    toks = list(probe.output_tokens)
    assert len(toks) == 10
    stop_tok = toks[2]
    first = toks.index(stop_tok)

    # stop sequences: matched at the tail, trimmed from the answer
    eng = InferenceEngine(tiny_model, _cfg())
    stopped = eng.add_request(
        _prompts(6)[0], 10, sampling={"stop": [[stop_tok]]}
    )
    eng.run_until_idle(max_iterations=5000)
    assert list(stopped.output_tokens) == toks[:first]
    assert stopped.finish_reason == "stop"

    # min_tokens: the in-trace lane masks eos until the floor is reached
    eos = toks[2]
    eng = InferenceEngine(tiny_model, _cfg(eos_token_id=eos))
    early = eng.add_request(_prompts(6)[0], 10)
    floored = eng.add_request(_prompts(6)[0], 10, sampling={"min_tokens": 6})
    eng.run_until_idle(max_iterations=5000)
    assert early.finish_reason == "eos" and len(early.output_tokens) == first + 1
    assert len(floored.output_tokens) >= 6
    assert eng.stats()["decode_compiles"] == 1


@pytest.mark.slow
def test_sampling_telemetry_rows_and_monitor_line(tiny_model, tmp_path):
    from accelerate_tpu.diagnostics.monitor import collect_status, render_status
    from accelerate_tpu.telemetry import TelemetryRecorder, set_active_recorder

    recorder = TelemetryRecorder(logging_dir=str(tmp_path))
    set_active_recorder(recorder)
    try:
        eng = InferenceEngine(tiny_model, _cfg(num_slots=2, stats_interval=2))
        eng.add_request(_prompts(7)[0], 6)
        eng.add_request(
            _prompts(7)[1], 6,
            sampling={"do_sample": True, "temperature": 0.9, "seed": 3},
        )
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
    assert last["sampled_tokens_greedy"] > 0
    assert last["sampled_tokens_sample"] > 0
    assert last["grammar_masked_steps"] == 0

    status = collect_status(str(tmp_path))
    srv = status["serving"]
    assert srv["sampled_tokens_sample"] > 0
    rendered = render_status(status)
    assert "sampling: greedy" in rendered and "grammar-masked" in rendered


# ---------------------------------------------------------------------------
# the OpenAI door on the real CLIs (slow lane: subprocesses)
# ---------------------------------------------------------------------------

_TINY_ARGS = [
    "--preset", "tiny", "--num-slots", "2", "--block-size", "8",
    "--max-seq-len", "96", "--prefill-chunk", "8", "--decode-burst", "2",
]


def _cli_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    env.pop("ACCELERATE_TELEMETRY", None)
    return env


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_ready(port, proc, timeout=240):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"server exited rc={proc.returncode}")
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=2
            ) as r:
                if json.loads(r.read()).get("state") == "ready":
                    return
        except (OSError, ValueError):
            pass
        time.sleep(0.25)
    raise RuntimeError("server never became ready")


def _post(port, path, body, stream=False):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    try:
        resp = urllib.request.urlopen(req, timeout=180)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())
    with resp:
        raw = resp.read().decode()
    return resp.status, raw if stream else json.loads(raw)


def _sse_chunks(raw):
    events = [line[6:] for line in raw.split("\n\n") if line.startswith("data: ")]
    assert events and events[-1] == "[DONE]"
    return [json.loads(e) for e in events[:-1]]


@pytest.mark.slow
def test_openai_endpoints_on_live_serve(tmp_path):
    """Golden requests through a REAL ``serve --http`` subprocess: both
    endpoints, SSE framing on the wire (chunked HTTP/1.1), schema-valid
    constrained output, error objects, and decode_compiles == 1 after the
    whole mixed trace."""
    port = _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
         "serve", *_TINY_ARGS, "--max-new-tokens", "16",
         "--logprobs-topn", "2", "--http", str(port)],
        env=_cli_env(), stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        _wait_ready(port, proc)

        # greedy completion: deterministic, usage adds up
        st, body = _post(port, "/v1/completions", {
            "prompt": "hello", "temperature": 0, "max_tokens": 8,
        })
        assert st == 200 and body["object"] == "text_completion"
        assert body["usage"]["prompt_tokens"] == 5
        assert body["usage"]["total_tokens"] == (
            body["usage"]["prompt_tokens"] + body["usage"]["completion_tokens"]
        )
        _, again = _post(port, "/v1/completions", {
            "prompt": "hello", "temperature": 0, "max_tokens": 8,
        })
        assert again["choices"][0]["text"] == body["choices"][0]["text"]

        # seeded sampling reproduces; logprobs ride along
        req = {"prompt": "abc", "temperature": 0.8, "seed": 42,
               "max_tokens": 6, "logprobs": 2}
        st, one = _post(port, "/v1/completions", req)
        _, two = _post(port, "/v1/completions", req)
        assert st == 200
        assert one["choices"][0]["text"] == two["choices"][0]["text"]
        lp = one["choices"][0]["logprobs"]
        assert len(lp["token_logprobs"]) == one["usage"]["completion_tokens"]

        # constrained chat answers valid JSON for the schema
        schema = {"type": "object",
                  "properties": {"name": {"enum": ["alpha", "beta", "gamma"]},
                                 "n": {"type": "integer"}},
                  "required": ["name", "n"]}
        st, body = _post(port, "/v1/chat/completions", {
            "messages": [{"role": "user", "content": "give me json"}],
            "temperature": 0.7, "seed": 1, "max_tokens": 48,
            "response_format": {"type": "json_schema",
                                "json_schema": {"name": "t", "schema": schema}},
        })
        assert st == 200
        value = json.loads(body["choices"][0]["message"]["content"])
        assert validate_instance(schema, value) is None
        assert body["choices"][0]["finish_reason"] == "stop"

        # SSE chat over the wire: role delta, one finish chunk w/ usage
        st, raw = _post(port, "/v1/chat/completions", {
            "messages": [{"role": "user", "content": "hi"}],
            "temperature": 0, "max_tokens": 6, "stream": True,
        }, stream=True)
        assert st == 200
        chunks = _sse_chunks(raw)
        assert chunks[0]["choices"][0]["delta"]["role"] == "assistant"
        finals = [c for c in chunks if c["choices"][0]["finish_reason"]]
        assert len(finals) == 1 and "usage" in finals[0]

        # streamed deltas never over-send past a later stop truncation
        st, raw = _post(port, "/v1/completions", {
            "prompt": "hello", "temperature": 0, "max_tokens": 12,
            "stop": ["X"], "stream": True,
        }, stream=True)
        chunks = _sse_chunks(raw)
        streamed = "".join(c["choices"][0].get("text") or "" for c in chunks)
        finals = [c for c in chunks if c["choices"][0]["finish_reason"]]
        assert len(streamed) == finals[0]["usage"]["completion_tokens"]

        # OpenAI error objects over the wire
        st, body = _post(port, "/v1/completions", {"prompt": "x", "n": 3})
        assert st == 400 and body["error"]["param"] == "n"
        st, body = _post(port, "/v1/completions",
                         {"prompt": "x", "logprobs": 9})  # over the cap
        assert st == 400 and body["error"]["type"] == "invalid_request_error"

        # one executable after the whole mixed trace
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/stats", timeout=10
        ) as r:
            stats = json.loads(r.read())
        assert stats["decode_compiles"] == 1
        assert stats["sampled_tokens_sample"] > 0
        assert stats["grammar_masked_steps"] > 0
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)


@pytest.mark.slow
def test_openai_endpoints_on_route_fleet(tmp_path):
    """The same front door mounted on the router: an unmodified OpenAI
    HTTP client (stdlib here) completes a streaming chat against
    ``accelerate-tpu route --http`` — sampling/grammar payloads forward
    verbatim to the replica."""
    port = _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
         "route", "--replicas", "1", "--logging-dir", str(tmp_path),
         "--http", str(port), *_TINY_ARGS],
        env=_cli_env(), stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True,
    )
    try:
        _wait_ready(port, proc)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5
        ) as r:
            health = json.loads(r.read())
        assert health["replicas"] >= 1

        st, body = _post(port, "/v1/chat/completions", {
            "messages": [{"role": "user", "content": "hello"}],
            "temperature": 0.7, "seed": 9, "max_tokens": 6,
        })
        assert st == 200 and body["object"] == "chat.completion"
        assert body["usage"]["completion_tokens"] >= 1

        # streaming (at_completion mode): SSE framing intact end to end
        st, raw = _post(port, "/v1/chat/completions", {
            "messages": [{"role": "user", "content": "stream please"}],
            "temperature": 0, "max_tokens": 6, "stream": True,
        }, stream=True)
        assert st == 200
        chunks = _sse_chunks(raw)
        text = "".join(
            c["choices"][0]["delta"].get("content", "") for c in chunks
        )
        assert len(text) >= 1
        assert sum(1 for c in chunks if c["choices"][0]["finish_reason"]) == 1

        # error objects answer from the router too
        st, body = _post(port, "/v1/completions", {"prompt": 42})
        assert st == 400 and body["error"]["param"] == "prompt"
    finally:
        if proc.stdin:
            proc.stdin.close()
        try:
            proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            proc.terminate()
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
