"""One span stream inside the program (ISSUE 24): the engine's phases, the
request waits and the model-part scopes, as a ``jax.profiler`` capture, the
flight recorder, ``stats()`` and the lowered programs show them. CPU, tiny
engine and tiny ``Accelerator`` loop."""

import glob
import os
import queue
import re
import threading
import time

import jax
import numpy as np
import optax
import pytest

from accelerate_tpu import Accelerator
from accelerate_tpu.diagnostics.tracing import get_tracer, NULL_TRACER
from accelerate_tpu.serving import EngineConfig, InferenceEngine
from accelerate_tpu.serving.flight import (
    ITERATION_PARTS,
    ITERATION_PHASES,
    PART_NAMES,
    FlightRecorder,
)
from accelerate_tpu.test_utils import RegressionDataset, RegressionModel, SimpleLoader

PHASE_SPANS = {f"serve/{phase}" for phase in ITERATION_PHASES}  # (a part is a grandchild)
SCOPES = ("embed", "layers", "attn_proj", "kv_write", "attn_kernel", "mlp", "head",
          "sample", "loss", "optimizer")


@pytest.fixture(scope="module")
def tiny_model():
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig.tiny(vocab_size=64, hidden_size=32, layers=2, heads=4, seq=96)
    return LlamaForCausalLM.from_config(config, seed=0)


def _engine(tiny_model, **overrides):
    kw = dict(num_slots=2, block_size=8, max_seq_len=96, prefill_chunk=8,
              decode_burst=2, stats_interval=0, prefix_cache=False)
    kw.update(overrides)
    return InferenceEngine(tiny_model, EngineConfig(**kw))


@pytest.fixture(scope="module")
def engine(tiny_model):
    """Warm: both programs compiled, stats reset."""
    eng = _engine(tiny_model, flight_history=64)
    eng.add_request(np.arange(12, dtype=np.int32), max_new_tokens=4)
    eng.run_until_idle(max_iterations=200)
    eng.reset_stats()
    return eng


# -- a profiler capture holds the program's spans ----------------------------


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    data = ProfileData.from_file(path)
    start = next(int(v) for p in data.planes if p.name == "Task Environment"
                 for k, v in p.stats if k == "profile_start_time")
    events = {}  # thread line -> [(name, start_ns, end_ns, stats)]
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serve/", "step/")) or ev.name == "train":
                    events.setdefault(line.name, []).append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return start, events


@pytest.fixture(scope="module")
def capture(engine, tmp_path_factory):
    """One real ``jax.profiler`` session over a few engine iterations and a
    few steps of the five-line training loop."""
    from accelerate_tpu.state import AcceleratorState, GradientState

    tmp = tmp_path_factory.mktemp("capture")
    acc = Accelerator(project_dir=str(tmp))
    model, opt, dl = acc.prepare(RegressionModel(a=0.0, b=0.0), optax.sgd(0.1),
                                 SimpleLoader(RegressionDataset(length=64), batch_size=16))
    batches = list(dl)
    assert get_tracer() is NULL_TRACER  # no Tracer: the profiler alone sees the spans
    engine.reset_stats()
    jax.profiler.start_trace(str(tmp / "xplane"))
    try:
        wall0 = time.time_ns()
        engine.add_request(np.arange(20, dtype=np.int32), max_new_tokens=6)
        engine.run_until_idle(max_iterations=200)
        for batch in batches[:3]:
            out = model(**batch)
            acc.backward(out.loss)
            opt.step()
            opt.zero_grad()
        jax.block_until_ready(model.params)
    finally:
        jax.profiler.stop_trace()
    flights = engine._flight.tail(64)
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    start, events = _host_events(str(tmp / "xplane"))
    (line,) = [evs for evs in events.values() if any(n == "serve/iteration" for n, *_ in evs)]
    return {"start": start, "line": line, "flights": flights, "wall0": wall0}


@pytest.mark.parametrize("phase", ITERATION_PHASES)
def test_capture_holds_each_phase_nested_in_its_iteration(capture, phase):
    iterations = [e for e in capture["line"] if e[0] == "serve/iteration"]
    spans = [e for e in capture["line"] if e[0] == f"serve/{phase}"]
    assert len(iterations) == len(capture["flights"]) and spans
    for _, lo, hi, _ in spans:
        assert sum(1 for _, a, b, _ in iterations if a <= lo and hi <= b) == 1


def test_capture_iterations_are_numbered_and_phases_tile_them(capture):
    iterations = [e for e in capture["line"] if e[0] == "serve/iteration"]
    assert [int(s["iteration"]) for *_, s in iterations] == \
        [f["iteration"] for f in capture["flights"]]
    assert all({"decoding", "prefill_chunks", "tokens"} <= set(s) for *_, s in iterations)
    assert sum(int(s["tokens"]) for *_, s in iterations) == 6
    for _, a, b, _ in iterations:
        inside = [(lo, hi) for n, lo, hi, _ in capture["line"]
                  if n in PHASE_SPANS and a <= lo and hi <= b]
        # children do not overlap, and cover the iteration but for the
        # microseconds each switch takes while a session records
        inside.sort()
        assert all(x[1] <= y[0] + 1 for x, y in zip(inside, inside[1:]))
        assert sum(hi - lo for lo, hi in inside) >= 0.5 * (b - a) - 50_000


def test_capture_and_flight_entries_share_the_wall_clock(capture):
    """``t_start_unix_ns`` less the xplane's ``profile_start_time`` is the
    iteration span's own start, to well under a millisecond; the phase
    intervals laid from there fall inside it."""
    assert capture["start"] <= capture["wall0"]
    iterations = [e for e in capture["line"] if e[0] == "serve/iteration"]
    apart = []
    for f, (_, a, b, _) in zip(capture["flights"], iterations):
        at = f["t_start_unix_ns"] - capture["start"]
        apart.append(abs(at - a))
        for _, lo, hi in f["intervals"]:  # (slack: a loaded test host may preempt a thread)
            assert a - 50e6 <= at + lo * 1e9 <= at + hi * 1e9 <= b + 50e6
    assert sorted(apart)[len(apart) // 2] < 200_000 and max(apart) < 50e6


@pytest.mark.parametrize("part", sorted(PART_NAMES))
def test_capture_holds_each_part_nested_in_its_phase_and_its_iteration(capture, part):
    """The prompt's three chunks (the last one picks the first token), the
    decode rounds and every iteration's tail: all ten parts are there, each
    a grandchild of exactly one iteration through exactly one span of its
    own phase."""
    iterations = [e for e in capture["line"] if e[0] == "serve/iteration"]
    phases = [e for e in capture["line"] if e[0] == "serve/" + part.split("/")[0]]
    spans = [e for e in capture["line"] if e[0] == "serve/" + part]
    assert spans
    for _, lo, hi, _ in spans:
        assert sum(1 for _, a, b, _ in phases if a <= lo and hi <= b) == 1
        assert sum(1 for _, a, b, _ in iterations if a <= lo and hi <= b) == 1
    rows = sum(1 for f in capture["flights"] for n, *_ in f["parts"] if n == part)
    assert len(spans) == rows  # one span a row of the flight entries


@pytest.mark.parametrize("name", ["train", "step/dispatch"])
def test_capture_holds_the_train_loop(capture, name):
    spans = [e for e in capture["line"] if e[0] == name]
    assert len(spans) == 3
    if name == "train":
        assert [int(s["step_num"]) for *_, s in spans] == [1, 2, 3]
        dispatch = [e for e in capture["line"] if e[0] == "step/dispatch"]
        for (_, lo, hi, _), (_, a, b, _) in zip(spans, dispatch):
            assert a <= lo and hi <= b  # the fused step's call, inside optimizer.step


# -- flight entries: intervals and buckets -----------------------------------


def test_every_flight_entry_tiles_its_wall_time(capture):
    assert len(capture["flights"]) >= 4
    for e in capture["flights"]:
        iv = e["intervals"]
        assert iv[0][1] == 0.0 and iv[-1][2] == pytest.approx(e["wall_s"], abs=1e-9)
        assert all(x[2] == y[1] for x, y in zip(iv, iv[1:]))  # shared boundary reads
        for p in ITERATION_PHASES:
            assert sum(b - a for q, a, b in iv if q == p) == pytest.approx(e[f"{p}_s"], abs=1e-9)
        assert {q for q, *_ in iv} <= set(ITERATION_PHASES)
        assert abs(e["t_start_unix_ns"] / 1e9 - time.time()) < 3600


def test_parts_are_ordered_disjoint_inside_their_phase_and_add_up_with_the_rest(capture):
    seen = set()
    for e in capture["flights"]:
        parts = e["parts"]
        assert all(x[1] <= x[2] <= y[1] for x, y in zip(parts, parts[1:]))
        for name, lo, hi in parts:
            phase, part = name.split("/")
            assert part in ITERATION_PARTS[phase]
            assert sum(1 for q, a, b in e["intervals"] if q == phase and a <= lo and hi <= b) == 1
        for phase in ITERATION_PHASES:
            under = sum(hi - lo for n, lo, hi in parts if n.startswith(phase + "/"))
            rest = e[f"{phase}_s"] - under  # what no part stamps
            assert -1e-9 <= rest <= e[f"{phase}_s"] + 1e-9
            assert under == 0.0 or phase in ITERATION_PARTS
        seen |= {n for n, *_ in parts}
        assert parts[-1][0] == "harvest/close"  # every iteration ends in its tail
        assert parts[-1][2] == pytest.approx(e["wall_s"], abs=1e-9)  # ... on the finish's read
    assert seen == PART_NAMES


def test_a_chunk_that_is_not_final_picks_and_fetches_nothing(capture):
    """20 prompt tokens in chunks of 8: three chunks, an iteration each; only
    the last runs the first token's pick and waits for it."""
    chunks = [[n.split("/")[1] for n, *_ in e["parts"] if n.startswith("prefill/")]
              for e in capture["flights"]]
    chunks = [c for c in chunks if c]
    assert chunks == [["operands", "call", "emit"]] * 2 + [list(ITERATION_PARTS["prefill"])]


@pytest.mark.parametrize("kind", ["spec", "block"])
def test_every_decode_program_stamps_its_call(tiny_model, kind):
    """A speculative round and a block model's rounds are dispatched from
    methods of their own: each stamps ``dispatch/call`` after the shared
    capacity and operand passes, and the harvest its emission."""
    if kind == "spec":
        eng = _engine(tiny_model, spec_k=2, draft="early_exit:1", flight_history=64)
    else:
        import accelerate_tpu.models.sdar_moe as sdar

        model = sdar.SdarMoeForCausalLM.from_config(sdar.SdarMoeConfig.tiny(), seed=0)
        eng = InferenceEngine(model, EngineConfig(
            num_slots=2, block_size=8, max_seq_len=64, prefill_chunk=8, decode_burst=2,
            denoise_steps=2, logprobs_topn=1, stats_interval=0, flight_history=64))
    eng.add_request(np.arange(11, dtype=np.int32), max_new_tokens=9)
    eng.run_until_idle(max_iterations=200)
    rounds = [[n for n, *_ in e["parts"] if n.startswith("dispatch/")]
              for e in eng._flight.tail(64)]
    rounds = [r for r in rounds if r]
    assert len(rounds) >= 2
    assert all(r == ["dispatch/capacity", "dispatch/operands", "dispatch/call"] for r in rounds)
    emits = sum(1 for e in eng._flight.tail(64) for n, *_ in e["parts"] if n == "harvest/emit")
    assert emits == len(rounds)  # every round dispatched was harvested, once
    if kind == "block":  # (its prefill yields no token: no pick, no fetch)
        assert not any(n.startswith("prefill/first_") for e in eng._flight.tail(64)
                       for n, *_ in e["parts"])


PHASES_1S = dict(schedule=0.1, prefill=0.2, dispatch=0.3, device_wait=0.3, harvest=0.1)


@pytest.mark.parametrize("intervals", [
    [("schedule", 0.0, 0.1), ("prefill", 0.1, 0.3), ("dispatch", 0.3, 0.6),
     ("device_wait", 0.65, 0.9), ("harvest", 0.9, 1.0)],                      # a hole
    [("schedule", 0.0, 0.1), ("prefill", 0.1, 0.3), ("dispatch", 0.3, 0.6),
     ("device_wait", 0.6, 0.9)],                                              # ends early
    [("schedule", 0.0, 0.2), ("prefill", 0.2, 0.3), ("dispatch", 0.3, 0.6),
     ("device_wait", 0.6, 0.9), ("harvest", 0.9, 1.0)],                       # disagrees with buckets
    [("schedule", 0.0, 0.1), ("prefill", 0.1, 0.3), ("dispatch", 0.3, 0.6),
     ("device_wait", 0.6, 0.9), ("sleep", 0.9, 1.0)],                         # unknown phase
])
def test_recorder_refuses_intervals_that_do_not_tile(intervals):
    with pytest.raises(AssertionError):
        FlightRecorder(4).record(1, 10.0, 1.0, intervals=intervals, **PHASES_1S)


TILING_1S = [("schedule", 0.0, 0.1), ("prefill", 0.1, 0.3), ("dispatch", 0.3, 0.45),
             ("device_wait", 0.45, 0.75), ("harvest", 0.75, 0.8), ("dispatch", 0.8, 0.95),
             ("harvest", 0.95, 1.0)]


@pytest.mark.parametrize("parts", [
    [("prefill/call", 0.2, 0.3), ("prefill/operands", 0.1, 0.2)],        # out of order
    [("prefill/operands", 0.1, 0.25), ("prefill/call", 0.2, 0.3)],       # overlap
    [("dispatch/call", 0.25, 0.4)],                                      # starts in another phase
    [("dispatch/capacity", 0.4, 0.85)],                                  # straddles two intervals
    [("harvest/emit", 0.45, 0.5)],                                       # inside another phase
    [("prefill/sleep", 0.1, 0.2)],                                       # unknown part
    [("device_wait/fetch", 0.5, 0.6)],                                   # a phase without parts
    [("prefill/call", 0.25, 0.2)],                                       # ends before it starts
])
def test_recorder_refuses_parts_that_do_not_fit_their_phase(parts):
    with pytest.raises(AssertionError):
        FlightRecorder(4).record(1, 10.0, 1.0, intervals=TILING_1S, parts=parts, **PHASES_1S)


def test_recorder_keeps_parts_that_fit_and_no_field_where_none_is_given():
    parts = [("prefill/operands", 0.1, 0.15), ("prefill/call", 0.15, 0.3),
             ("dispatch/capacity", 0.3, 0.45), ("harvest/emit", 0.75, 0.78),
             ("dispatch/capacity", 0.8, 0.8), ("dispatch/call", 0.9, 0.95),
             ("harvest/close", 0.95, 1.0)]
    entry = FlightRecorder(4).record(1, 10.0, 1.0, intervals=TILING_1S, parts=parts, **PHASES_1S)
    assert entry["parts"] == parts
    assert FlightRecorder(4).record(1, 10.0, 1.0, intervals=TILING_1S, parts=[],
                                    **PHASES_1S)["parts"] == []
    assert "parts" not in FlightRecorder(4).record(1, 10.0, 1.0, intervals=TILING_1S, **PHASES_1S)


def test_recorder_keeps_repeated_phases_and_the_wall_clock_anchor():
    iv = [("schedule", 0.0, 0.05), ("device_wait", 0.05, 0.35), ("harvest", 0.35, 0.4),
          ("schedule", 0.4, 0.45), ("prefill", 0.45, 0.65), ("dispatch", 0.65, 0.95),
          ("harvest", 0.95, 1.0)]
    entry = FlightRecorder(4).record(7, 10.0, 1.0, intervals=iv, t_start_unix_ns=123, **PHASES_1S)
    assert entry["intervals"] == iv and entry["t_start_unix_ns"] == 123
    plain = FlightRecorder(4).record(7, 10.0, 1.0, **PHASES_1S)
    assert "intervals" not in plain and "t_start_unix_ns" not in plain


# -- request waits -------------------------------------------------------------


def test_ttft_totals_split_into_queue_own_prefill_and_the_rest(tiny_model):
    """Two slots, a pool too small for both answers, the swap tier on: one
    request queues for a slot, one prompt takes three chunks, one request
    is preempted. The totals are the sums of the per-request stamps."""
    eng = _engine(tiny_model, num_blocks=6, swap_gb=0.01, max_seq_len=64)
    reqs = [eng.add_request(np.arange(8, dtype=np.int32), max_new_tokens=30),
            eng.add_request(np.arange(8, dtype=np.int32) + 1, max_new_tokens=30),
            eng.add_request(np.arange(20, dtype=np.int32) + 2, max_new_tokens=4)]
    eng.run_until_idle(max_iterations=5000)
    st = eng.stats()
    assert st["preemptions"] >= 1 and all(r.first_token_time is not None for r in reqs)
    assert st["first_tokens_total"] == 3
    assert st["ttft_sum_s"] == pytest.approx(sum(r.ttft_s for r in reqs))
    queue_s = [r.admit_time - r.arrival_time for r in reqs]
    assert st["ttft_queue_sum_s"] == pytest.approx(sum(queue_s))
    assert min(queue_s) >= 0 and queue_s[2] > 10 * max(queue_s[:2])  # the third waited for a slot
    assert st["ttft_own_prefill_sum_s"] == pytest.approx(sum(r.own_prefill_s for r in reqs))
    assert [r.prefill_iterations for r in reqs] == [1, 1, 3]
    assert st["ttft_prefill_iterations_sum"] == 5
    for r in reqs:  # each request's wait splits without a remainder below zero
        rest = r.ttft_s - (r.admit_time - r.arrival_time) - r.own_prefill_s
        assert rest >= -1e-9 and 0 < r.own_prefill_s <= r.ttft_s
    rest = st["ttft_sum_s"] - st["ttft_queue_sum_s"] - st["ttft_own_prefill_sum_s"]
    assert rest >= -1e-9
    eng.reset_stats()
    assert eng.stats()["first_tokens_total"] == 0 and eng.stats()["ttft_sum_s"] == 0.0


@pytest.mark.parametrize("stamped", [True, False])
def test_arrival_time_is_the_doors_stamp(engine, stamped):
    """A payload that went through ``serve``'s door starts its clock there,
    however long it then sat in the inbox; one put into the inbox directly
    starts it at ``add_request``."""
    from accelerate_tpu.commands import serve

    inbox, stop, results = queue.Queue(), threading.Event(), []
    payload = {"id": "a", "prompt": [1, 2, 3], "max_new_tokens": 2, "_arrival": -5.0}
    t_before = time.perf_counter()
    if stamped:
        serve._at_the_door(inbox, payload, results.append)
        assert t_before <= payload["_arrival"] <= time.perf_counter()  # a client's value is overwritten
    else:
        del payload["_arrival"]
        inbox.put((payload, results.append))
    time.sleep(0.05)  # the wait in the inbox
    t_loop = time.perf_counter()
    stop.set()
    seen = []
    add = engine.add_request
    engine.add_request = lambda *a, **kw: seen.append(add(*a, **kw)) or seen[-1]
    try:
        serve._engine_loop(engine, inbox, lambda r: None, stop)
    finally:
        del engine.add_request
    (req,) = seen
    assert results and results[0]["id"] == "a"
    if stamped:
        assert req.arrival_time == payload["_arrival"] < t_loop - 0.04
    else:
        assert req.arrival_time >= t_loop
    assert req.ttft_s == pytest.approx(req.first_token_time - req.arrival_time)


# -- scopes in the lowered programs --------------------------------------------


def _op_names(text):
    """``[(operation, scope stack)]`` of a lowering printed with
    ``debug_info=True``. Every operation ends in a reference to a location
    whose name is the stack it was traced under; a function traced once and
    called (a scan body, an ``einsum``) names its operations from its own
    root, so the stack of the call is put in front, as XLA does when it
    inlines the call."""
    defs = {ref: (name, child) for ref, name, child in re.findall(
        r'^(#loc\d+) = loc\("([^"]*)"(?:\((#loc\d+)\))?', text, flags=re.M)}

    def resolve(ref):
        name, child = defs.get(ref, ("", ""))
        # a label such as "closed_call:" wraps the location that has the stack
        return resolve(child) if name.endswith(":") and child else name

    locs = {ref: resolve(ref) for ref in defs}
    funcs, current = {}, None
    for line in text.splitlines():
        head = re.search(r"func\.func \w+ @(\w+)\(", line)
        if head:
            current = funcs.setdefault(head.group(1), [])
            continue
        m = re.search(r"(?:stablehlo\.(\w+)|call @(\w+))\b.*loc\((#loc\d+)\)\s*$", line)
        if m and current is not None:
            current.append((m.group(1), m.group(2), locs.get(m.group(3), "")))
    out = []

    def walk(fn, prefix):
        for op, callee, stack in funcs[fn]:
            full = f"{prefix}/{stack}" if prefix and stack else prefix or stack
            if callee:
                walk(callee, full)
            else:
                out.append((op, full))

    walk("main", "")
    return out


def _scopes_in(op_name):
    return {w for w in re.findall(r"[A-Za-z_]\w*", op_name) if w in SCOPES}


def _lowered_text(jitted, args):
    """The program as lowered, before XLA's passes (which drop the metadata
    of instructions they rewrite): every operation with its location."""
    return jitted.lower(*args).as_text(debug_info=True)


@pytest.fixture(scope="module")
def fused_step(tiny_model):
    """The fused train step of a tiny llama (remat on) as the five-line loop
    lowers it, taken where the compile callback's facts are made, and the
    program's own instruction-to-scope table of the executable."""
    from accelerate_tpu import lazy
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.state import AcceleratorState, GradientState

    cfg = LlamaConfig.tiny(vocab_size=64, hidden_size=32, layers=2, heads=4, seq=32)
    cfg.remat = True
    rows = [{"input_ids": np.arange(32, dtype=np.int32) % 64,
             "labels": np.arange(32, dtype=np.int32) % 64}] * 8
    texts, facts_of = [], lazy._compile_facts

    def spy(jitted, args, label):
        if label == "fused_step":
            texts.append(_lowered_text(jitted, args))
        return facts_of(jitted, args, label)

    lazy._compile_facts = spy
    lazy.clear_caches()
    try:
        acc = Accelerator()
        lazy.set_compile_callback(lambda facts: None)  # (an Accelerator sets its own, or none)
        model, opt, dl = acc.prepare(LlamaForCausalLM.from_config(cfg, seed=0),
                                     optax.adamw(1e-3), SimpleLoader(rows, batch_size=8))
        for batch in dl:
            out = model(**batch)
            acc.backward(out.loss)
            opt.step()
            opt.zero_grad()
    finally:
        lazy._compile_facts = facts_of
        lazy.set_compile_callback(None)
        AcceleratorState._reset_state(reset_partial_state=True)
        GradientState._reset_state()
    (text,) = texts
    table = lazy.scope_table("fused_step")  # (the executable outlives the loop)
    return text, table


PROGRAM_SCOPES = {
    "decode": {"embed", "layers", "attn_proj", "kv_write", "attn_kernel", "mlp", "head", "sample"},
    # no "sample": the first token is picked from the logits prefill returns
    "prefill": {"embed", "layers", "attn_proj", "kv_write", "attn_kernel", "mlp", "head"},
    "fused_step": {"embed", "layers", "attn_proj", "attn_kernel", "mlp", "head", "loss",
                   "optimizer"},
}


@pytest.mark.parametrize("program", sorted(PROGRAM_SCOPES))
def test_lowered_program_names_every_scope_and_every_matrix_product(
        program, engine, fused_step):
    text = fused_step[0] if program == "fused_step" else _lowered_text(
        *engine._dispatched[program])
    ops = _op_names(text)
    named = set().union(*(_scopes_in(n) for _, n in ops))
    assert PROGRAM_SCOPES[program] <= named, PROGRAM_SCOPES[program] - named
    assert program != "prefill" or "sample" not in named
    products = [(op, n) for op, n in ops if op in ("dot_general", "dot", "convolution")]
    assert products
    inner = {"attn_proj", "attn_kernel", "mlp", "head", "sample", "kv_write", "embed",
             "optimizer"}
    outside = [n for _, n in products if not _scopes_in(n) & inner]
    assert not outside, outside


def test_fused_step_stacks_spell_forward_backward_and_recomputation(fused_step):
    """What the pass readers rest on: ``jvp(`` alone is the forward pass,
    ``transpose(jvp(`` the backward, ``rematted_computation`` under it the
    recomputed forward of ``remat``."""
    names = [n for _, n in _op_names(fused_step[0]) if "mlp" in _scopes_in(n)]
    fwd = [n for n in names if "jvp(" in n and "transpose(" not in n]
    remat = [n for n in names if "rematted_computation" in n]
    bwd = [n for n in names if "transpose(" in n and "rematted_computation" not in n]
    assert fwd and remat and bwd
    assert all("transpose(jvp(" in n for n in remat)
    assert all(n.startswith("jit(") and "/loss/" in n for n in fwd + remat + bwd)


@pytest.mark.parametrize("program", ["decode", "prefill", "fused_step"])
def test_the_program_hands_out_its_instruction_to_scope_table(program, engine, fused_step):
    """A TPU trace names a device operation by its HLO instruction and
    nothing else; the table leads from there back to the scope."""
    if program == "fused_step":
        table, text = fused_step[1], None
    else:
        table, text = engine.scope_table(program), engine.compiled_text(program)
    assert table and all(isinstance(k, str) and len(v) == 2 for k, v in table.items())
    named = set().union(*(_scopes_in(stack) for _, stack in table.values()))
    assert {"attn_proj", "mlp", "head", "layers"} <= named
    if text is not None:  # every key is an instruction of the compiled text, with its shape
        for name, (shape, _) in list(table.items())[:50]:
            assert re.search(rf"%?{re.escape(name)} = \(*{re.escape(shape)}", text), name


def test_op_scopes_reads_names_shapes_and_stacks():
    from accelerate_tpu.utils.hlo import op_scopes

    text = (
        '  %fusion.3 = s32[1,8]{1,0:T(4,128)S(1)} fusion(%ids.1), kind=kLoop, calls=%f.2, '
        'metadata={op_name="jit(step)/loss/jvp(embed)/gather" source_file="x.py" source_line=3}\n'
        '  ROOT %tuple.1 = (bf16[2,3]{1,0}, s32[]) tuple(%a, %b), '
        'metadata={op_name="jit(step)/optimizer/add"}\n'
        '  %copy.4 = f32[8]{0} copy(%p)\n'
        '  %ag-start.2 = ((bf16[4,8]{1,0}), bf16[16,8]{1,0}) all-gather-start(%w), '
        'metadata={op_name="jit(step)/loss/jvp(layers)/while/body/mlp/dot_general"}\n')
    assert op_scopes(text) == {
        "fusion.3": ("s32[1,8]", "jit(step)/loss/jvp(embed)/gather"),
        "tuple.1": ("bf16[2,3]", "jit(step)/optimizer/add"),
        "ag-start.2": ("bf16[4,8]", "jit(step)/loss/jvp(layers)/while/body/mlp/dot_general"),
    }


# -- the cost with nothing listening ---------------------------------------------


class _CountingClock:
    """Stands in for the engine module's ``time``: counts the reads."""

    def __init__(self):
        self.reads = {"perf_counter": 0, "time_ns": 0}

    def __getattr__(self, name):
        if name in self.reads:
            self.reads[name] += 1
        return getattr(time, name)


@pytest.mark.parametrize("flight_on", [True, False])
def test_step_reads_the_clock_a_pinned_number_of_times_and_writes_no_file(
        tiny_model, monkeypatch, tmp_path, flight_on):
    """No profiler session, no Tracer: a phase or part boundary is ONE clock
    read (none with the recorder off), an iteration one wall-clock read, an
    emitted token one; nothing is opened for writing."""
    import builtins

    from accelerate_tpu.serving import engine as engine_mod

    monkeypatch.chdir(tmp_path)
    eng = _engine(tiny_model, flight_history=8 if flight_on else 0, async_dispatch=False)
    eng.add_request(np.arange(8, dtype=np.int32), max_new_tokens=5)
    eng.step()  # the one prefill chunk and the first decode round: compiles
    clock = _CountingClock()
    monkeypatch.setattr(engine_mod, "time", clock)
    opened = []
    real_open = builtins.open
    monkeypatch.setattr(builtins, "open", lambda *a, **kw: opened.append(a) or real_open(*a, **kw))
    tokens0 = eng._tokens_emitted
    eng.step()  # a pure decode iteration: schedule, prefill (empty), dispatch, wait, harvest
    tokens = eng._tokens_emitted - tokens0
    assert tokens == 2  # decode_burst
    # boundaries: begin, ->prefill, ->dispatch, ->device_wait, ->harvest, ->harvest, finish;
    # parts between them: dispatch/capacity, /operands, /call and its end, harvest/emit's
    # end (harvest/emit and harvest/close open on their phase's read, /close ends on finish's)
    # (recorder off: the usage ledger stamps the device wait itself, twice)
    assert clock.reads["perf_counter"] == (7 + 5 if flight_on else 2) + tokens
    assert clock.reads["time_ns"] == (1 if flight_on else 0)
    assert not opened and not os.listdir(tmp_path)


class _TickingClock:
    """Stands in for the engine module's ``time``: every ``perf_counter``
    read is the next whole second, so that sums and differences of stamps are
    exact and two observers agree only if they took the same read."""

    def __init__(self):
        self.now = 1000.0

    def perf_counter(self):
        self.now += 1.0
        return self.now

    def __getattr__(self, name):
        return getattr(time, name)


@pytest.mark.parametrize("flight_on", [True, False])
def test_a_chunks_observers_take_the_part_boundaries_reads(
        tiny_model, monkeypatch, tmp_path, flight_on):
    """``own_prefill_s``, the usage ledger's prefill seconds, the
    ``req/prefill_chunk`` event and ``first_token_time`` are the floats that
    opened the chunk's first part and its ``prefill/emit``: a chunk reads the
    clock once a part boundary (5 reads, 7 on the last chunk) and twice with
    the recorder off, where the two reads are those observers' own."""
    from accelerate_tpu.diagnostics.tracing import Tracer, parse_trace_file, set_active_tracer
    from accelerate_tpu.serving import engine as engine_mod

    eng = _engine(tiny_model, flight_history=8 if flight_on else 0, async_dispatch=False,
                  max_seq_len=32)
    eng.add_request(np.arange(12, dtype=np.int32), max_new_tokens=1)
    eng.run_until_idle(max_iterations=50)  # compiles
    accrued = []
    monkeypatch.setattr(eng.usage, "accrue_prefill", lambda req, dt: accrued.append(dt))
    clock = _TickingClock()
    monkeypatch.setattr(engine_mod, "time", clock)
    tracer = Tracer(logging_dir=str(tmp_path), host=0)
    set_active_tracer(tracer)
    try:
        req = eng.add_request(np.arange(12, dtype=np.int32) + 1, max_new_tokens=1)
        stamps, chunk = [], eng._prefill_one_chunk

        def timed_chunk(*args):
            before = clock.now
            chunk(*args)
            stamps.append((before, clock.now))

        monkeypatch.setattr(eng, "_prefill_one_chunk", timed_chunk)
        eng.step()  # a chunk of 8,
        eng.step()  # the final chunk of 4 and the one token
    finally:
        tracer.close()
        set_active_tracer(None)
    events = parse_trace_file(tracer.path)
    chunk_ts = [e["ts"] / 1e6 for e in events if e["name"] == "req/prefill_chunk"]
    assert len(chunk_ts) == 2 and req.first_token_time == chunk_ts[1]
    if not flight_on:
        # two reads a chunk, whatever it ran: its first part's and its emit's
        assert [hi - lo for lo, hi in stamps] == [2.0, 2.0]
        assert chunk_ts == [hi for _, hi in stamps]
        assert accrued == [1.0, 1.0] and req.own_prefill_s == 2.0
        return
    assert [hi - lo for lo, hi in stamps] == [5.0, 7.0]
    spans = {name: [(e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6) for e in events
                    if e.get("ph") == "X" and e["name"] == "serve/prefill/" + name]
             for name in ITERATION_PARTS["prefill"]}
    assert [len(spans[n]) for n in ITERATION_PARTS["prefill"]] == [2, 2, 1, 1, 2]
    opened = [lo for lo, _ in spans["operands"]]
    emit = [lo for lo, _ in spans["emit"]]
    assert chunk_ts == emit                                  # the event's ts IS emit's opening read
    assert accrued == [e - o for o, e in zip(opened, emit)] == [3.0, 5.0]
    assert req.own_prefill_s == sum(accrued)
    # one read a boundary: operands, call, its end, [first_pick, first_fetch,] emit, its end
    assert [(lo, hi) for (lo, _), (_, hi) in zip(spans["operands"], spans["emit"])] == \
        [(lo + 1.0, hi) for lo, hi in stamps]
    first = eng._flight.tail(8)[-2]  # the flight rows are the same reads, from the iteration's start
    assert [(n, first["t_start"] + a) for n, a, _ in first["parts"] if n.startswith("prefill/")] \
        == [("prefill/operands", opened[0]), ("prefill/call", opened[0] + 1.0),
            ("prefill/emit", emit[0])]
