"""Whole runs at the tiny sizes, on the CPU: the rehearsal of both cells,
the controls that must come out as not correct, the timed path broken
underneath, the result line's keys, and a cell added as files."""

import json
import os
import shutil

import numpy as np
import pytest

from perfbench import common, probe, rehearse, run


def _ctx(workload, seed=7, seconds=2.0, **extra):
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, workload)
    config, traffic = common.apply_rehearsal(config, traffic)
    return bench, common.Ctx(cell=cell, config=config, traffic=traffic, seed=seed,
                             seconds=seconds, trace=False, rehearse=True, **extra)


@pytest.fixture(scope="module")
def chat_run():
    bench, ctx = _ctx("mistral7b-chat-steady", seed=3_000_000_019)
    return bench, ctx, common.load_driver("serve_engine").run(ctx)


@pytest.fixture(autouse=True)
def _fresh_accelerator_state():
    yield
    from accelerate_tpu.state import AcceleratorState, GradientState

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()


def test_chat_rehearsal_is_correct_and_counts(chat_run):
    _, ctx, out = chat_run
    assert out["correct"] and out["failed"] == 0
    obs = out["observed"]
    assert obs["requests_due"] == round(ctx.traffic["rate_rps"] * ctx.seconds)
    assert obs["requests_finished"] == obs["requests_due"]
    assert obs["compiles_in_window"] == 0 and obs["iterations"] > 0
    assert set(out["values"]) == {"ttft_ms.tail10", "tpot_ms.p90", "setup_s"}
    numbers, limits = out["check"]["numbers"], out["check"]["limits"]
    assert set(numbers) == set(limits) == {"gap_max", "logprob_err_mean"}
    assert all(numbers[k] <= limits[k] for k in limits)
    assert all(len(lp) == len(t) for _, t, lp in out["sample"])
    longest = max(len(p) + len(t) for p, t, _ in out["sample"])
    assert len(out["sample"][0][0]) + len(out["sample"][0][1]) == longest


def test_chat_control_in_lower_precision_is_not_correct(chat_run):
    """The program itself with its KV pool in fp8 (``check.control`` of the
    configuration file): the log-probabilities it reports lie far from the
    reference's, and the run is not correct."""
    _, sound_ctx, sound = chat_run
    _, ctx = _ctx("mistral7b-chat-steady", seed=sound_ctx.seed,
                  serve_flags=probe.control_flags(sound_ctx.config))
    assert "fp8" in ctx.serve_flags
    out = common.load_driver("serve_engine").run(ctx)
    assert not out["correct"] and not out["check"]["ok"] and out["failed"] == 0
    limit = out["check"]["limits"]["logprob_err_mean"]
    assert out["check"]["numbers"]["logprob_err_mean"] > 3 * limit
    assert sound["check"]["numbers"]["logprob_err_mean"] < limit / 3


def test_chat_that_reports_no_logprobs_is_not_correct():
    """A limit with nothing to compare against is a failed comparison."""
    bench, ctx = _ctx("mistral7b-chat-steady", seed=5)
    flags = list(ctx.config["serve_flags"])
    i = flags.index("--logprobs-topn")
    del flags[i:i + 2]
    ctx.serve_flags = flags
    out = common.load_driver("serve_engine").run(ctx)
    assert not out["correct"] and "logprob_err_mean" not in out["check"]["numbers"]


def test_chat_with_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from accelerate_tpu.serving.engine import InferenceEngine

    emit = InferenceEngine._emit_token
    count = [0]

    def altered(self, req, tok, *a, **kw):
        count[0] += 1
        if count[0] % 5 == 0:
            tok = (int(tok) + 1) % self._vocab_size
        return emit(self, req, tok, *a, **kw)

    monkeypatch.setattr(InferenceEngine, "_emit_token", altered)
    _, ctx = _ctx("mistral7b-chat-steady", seed=11)
    out = common.load_driver("serve_engine").run(ctx)
    assert not out["correct"] and not out["check"]["ok"]


def test_result_line_keys(chat_run):
    bench, ctx, out = chat_run
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = run.result_line(bench, ctx.cell, out, device, trace=False)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["metrics"]) == {"ttft_ms.tail10", "tpot_ms.p90", "setup_s"}
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(line)
    traced = run.result_line(bench, ctx.cell, out, device, trace=True)
    per_layer = {m["name"] for m in bench["per_layer"]}
    assert set(traced["metrics"]) <= per_layer and "sched.host_share_pct" in traced["metrics"]
    assert "ttft_ms.tail10" not in traced["metrics"]
    assert {"ttft_ms.p90", "tpot_ms.mean", "kv.pool_used_pct"} <= set(traced["metrics"])
    assert 0 < traced["metrics"]["kv.pool_used_pct"]["value"] <= 100


def test_train_rehearsal_is_correct_and_one_compile():
    counts = rehearse.main(["mistral7b-train-fsdp4-seq4k", "--seconds", "1", "--seed", "9"])
    assert counts["correct"] and counts["counts"]["fused_step_compiles"] == 1
    assert counts["counts"]["compiles_in_window"] == 0
    assert counts["end_to_end_present"] == ["setup_s", "train_tok_s_chip"]
    assert not any("ms" in k or "tok_s" in k for k in counts["counts"])  # counts only


def test_train_control_in_lower_precision_is_not_correct():
    """float32 at the tiny size; the nearest precision below is bfloat16."""
    _, ctx = _ctx("mistral7b-train-fsdp4-seq4k", seconds=0.5,
                  accelerator_kwargs={"mixed_precision": "bf16"})
    out = common.load_driver("train_step").run(ctx)
    assert not out["correct"] and not out["check"]["ok"]
    assert out["check"]["numbers"]["grad_norm_gap"] > 3 * out["check"]["limits"]["grad_norm_gap"]


def test_train_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from accelerate_tpu.optimizer import AcceleratedOptimizer

    step = AcceleratedOptimizer.step
    calls = [0]

    def frozen(self, *a, **kw):
        import jax

        calls[0] += 1
        before = jax.tree.map(lambda x: x + 0, self.model.params)  # the step donates its own
        out = step(self, *a, **kw)
        if calls[0] > 1:  # the first step stands, so that Adam's moment exists
            self.model.params = before
        return out

    monkeypatch.setattr(AcceleratedOptimizer, "step", frozen)
    _, ctx = _ctx("mistral7b-train-fsdp4-seq4k", seconds=0.5)
    out = common.load_driver("train_step").run(ctx)
    assert not out["correct"]
    assert out["check"]["numbers"]["update_norm_gap"] > out["check"]["limits"]["update_norm_gap"]


def test_a_cell_arrives_as_files_and_one_entry(tmp_path):
    """``mistral7b-batch-saturated``: a traffic file, one entry of
    ``workloads`` and its end-to-end metric — no file that is there edited."""
    root = tmp_path / "checkout"
    shutil.copytree(common.HERE, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = common.benchmark()
    name = "mistral7b-batch-saturated"
    (root / "perfbench" / "traffic" / "batch-saturated.json").write_text(json.dumps({
        "kind": "closed_loop", "clients": 96, "ramp_s": 30.0, "drain_s": 0.0,
        "prompt_tokens": {"dist": "uniform", "min": 2048, "max": 3584},
        "output_tokens": {"dist": "uniform", "min": 32, "max": 128},
        "rehearsal": {"clients": 6, "ramp_s": 1.0,
                      "prompt_tokens": {"dist": "uniform", "min": 40, "max": 120},
                      "output_tokens": {"dist": "uniform", "min": 4, "max": 12}},
    }))
    bench["workloads"].append({"name": name, "config": "mistral-7b-serve-v5e1",
                               "traffic": "batch-saturated", "chips": 1,
                               "why": "closed loop, 96 clients, long prompts: prefill does the work"})
    bench["end_to_end"].append({"name": "serve_tok_s", "unit": "tokens/s", "better": "higher",
                                "bound": 0.03, "source": "host_clock", "workloads": [name]})
    for m in bench["per_layer"]:
        if m["moves"] == "tpot_ms.p90" and m["name"].startswith(("sched.", "kv.")):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    counts = rehearse.main([name, "--seconds", "2"], root=str(root))
    assert counts["correct"] and counts["failed"] == 0
    assert counts["end_to_end_present"] == ["serve_tok_s", "setup_s"]
    assert counts["counts"]["tokens_completed"] > 0
    assert "sched.slot_occupancy_pct" in counts["per_layer_readable"]


def test_the_real_command_refuses_a_host_without_a_tpu():
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "mistral7b-chat-steady", "--seed", "1", "--seconds", "1"])
    assert e.value.code not in (0, None)
