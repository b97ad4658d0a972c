"""The two-cache-kind configuration (SmallThinker-21BA3B-Instruct, two periods
of its layers): its cell as files and entries, the rehearsal of its cell, a
reference told the window, the rotation, the router's input or the experts'
activation otherwise reading the program as not ``correct``, the control, and
the ``window.*``, ``kv.pool_used_pct.<kind>`` and
``kernel.paged_attention.window_roofline_pct`` readers on fixtures."""

import json
from types import SimpleNamespace

import pytest

from perfbench import common, counts, rehearse, weights
from perfbench.layer_metrics import kernel_paged_attention_window_roofline_pct as roofline_reader
from perfbench.layer_metrics import kv_pool_used_pct_full as pool_reader
from perfbench.layer_metrics import window as window_reader
from perfbench.reference import smallthinker as reference

CELL = "smallthinker-21b-a3b-mixed-steady"
CONFIG = "perfbench/configs/smallthinker-21b-a3b-serve-v5e1.json"
PERIOD = [0, 1, 1, 1]
CATALOG = {  # the catalog's `config` of SmallThinker-21BA3B-Instruct, every key of it
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_layout": PERIOD * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": PERIOD * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936,
}
NEW_METRICS = ("scope.attn_kernel_full_pct.chat", "scope.attn_kernel_window_pct.chat",
               "window.walk_saved_pct", "window.freed_blocks_per_request",
               "kv.pool_used_pct.full", "kv.pool_used_pct.window",
               "kernel.paged_attention.window_roofline_pct")


def test_the_cell_arrived_as_files_and_entries():
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, CELL)
    assert cell["chips"] == 1 and config["reference"] == "smallthinker"
    assert config["program"] == "serve_engine" and config["kernels"] == ["paged_attention"]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == list(config["reduced"]) == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    assert entry["source"] == config["source"] and entry["file"] == CONFIG
    # every key of the catalog's entry under the same key, but the depth and the
    # two lists cut to it: two whole periods
    for key, value in CATALOG.items():
        if key == "num_hidden_layers":
            assert config["reduced"][key] == [value, config[key]] == [52, 8]
        elif key in config["reduced"]:
            assert config[key] == value[:8] == PERIOD * 2
        else:
            assert config[key] == value, key
    assert config["model_type"] == "smallthinker"
    assert config["moe_intermediate_size"] == config["moe_ffn_hidden_size"]  # moe.py reads it
    for key in ("model_type", "router input", "window edge", "rope pairing", "router precision",
                "secondary experts", "leaf storage", "weights"):
        assert any(key in name for name in config["assumed"]), key
    for said in ("four pipeline stages or more", "first stage's first 8 layers",
                 "every expert of each layer held"):
        assert said in config["deployment"], said
    flags = config["serve_flags"]
    assert flags[flags.index("--max-seq-len") + 1] == "16384"
    slots, blocks = (int(flags[flags.index(f) + 1]) for f in ("--num-slots", "--num-blocks"))
    assert slots % 8 == 0 and blocks == slots * 1024 + 1  # every slot's full kind resident
    assert config["check"]["control"] == {"serve_flags_replace": {"--kv-dtype": "fp8"}}
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert set(NEW_METRICS) <= set(listed)
    for name in NEW_METRICS:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
    scopes = {n.split(".")[1][:-4] for n in listed if n.startswith("scope.")}
    assert scopes == {"embed", "attn_proj", "kv_write", "attn_kernel_full", "attn_kernel_window",
                      "head", "sample", "layer_carry", "unscoped", "moe_router", "moe_experts"}
    assert {"moe.experts_touched_pct", "moe.load_max_over_mean", "moe.pairs_per_dispatch",
            "moe.experts_roofline_pct", "paged.table_live_pct", "paged.tile_fill_pct",
            "device.hbm_peak_pct.chat", "device.idle_pct.chat", "step.decode_ms",
            "kernel.paged_attention.busy_pct"} <= set(listed)
    # kernel.py counts every layer at the row's whole context: over 100 here
    assert "kernel.paged_attention.roofline_pct" not in listed
    assert not [n for n in listed if "latent" in n or n == "moe.pairs_held_pct"]
    for name in ("ttft_ms.tail10", "tpot_ms.p90"):
        assert CELL in next(m for m in bench["end_to_end"] if m["name"] == name)["workloads"]
    assert traffic["kind"] == "open_loop_lognormal"
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": traffic["prompt_tokens"][
        "median"], "sigma": 0.9, "min": 256, "max": 15872}
    assert traffic["prompt_tokens"]["median"] in (3072, 4096)
    assert traffic["output_tokens"] == {"dist": "lognormal", "median": 192, "sigma": 0.7,
                                        "min": 16, "max": 512}
    assert (traffic["schedule_seed"], traffic["block"], traffic["shuffle_group"],
            traffic["jitter_s"], traffic["drain_s"]) == (42, 10, 1, 0.02, 150.0)
    assert traffic["rate_rps"] * 50 >= 60  # at least 60 requests a 50 s window
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert len(bench["per_layer"]) <= 128


def test_the_reference_draws_the_programs_leaves():
    cfg = common.read_json(CONFIG)
    for sized in (cfg, {**cfg, **cfg["rehearsal"]}):
        flat = weights.flat_names(common.build_model(sized).params)
        assert {k: tuple(a.shape) for k, a in flat.items()} == reference.leaf_shapes(sized)
        assert all(k in flat for k in sized["weight_scales"])


@pytest.fixture(scope="module")
def rehearsed():
    return rehearse.main([CELL, "--seconds", "2", "--seed", "4400000041"])


def test_the_rehearsal_of_the_cell_runs_the_whole_command(rehearsed):
    out = rehearsed
    assert out["correct"] and out["failed"] == 0 and out["counts"]["compiles_in_window"] == 0
    assert set(out["end_to_end_present"]) == {"ttft_ms.tail10", "tpot_ms.p90", "setup_s"}
    assert out["check"]["numbers"]["logprob_err_mean"] < out["check"]["limits"]["logprob_err_mean"]
    assert {"moe.experts_touched_pct", "moe.load_max_over_mean", "moe.pairs_per_dispatch",
            "paged.table_live_pct", "paged.tile_fill_pct", "window.walk_saved_pct",
            "window.freed_blocks_per_request", "kv.pool_used_pct.full",
            "kv.pool_used_pct.window"} <= set(out["per_layer_readable"])


def _run_against(changes: dict):
    """The cell's rehearsal with the REFERENCE told ``changes``; the program
    is built from the configuration as committed."""
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, CELL)
    sound, traffic = common.apply_rehearsal(config, traffic)
    ctx = common.Ctx(cell=cell, config={**sound, **changes}, traffic=traffic, seed=4400000041,
                     seconds=2.0, trace=False, rehearse=True)
    from perfbench.drivers import serve_engine

    original = common.build_model
    try:
        common.build_model = lambda cfg, **kw: original(
            {k: v for k, v in {**cfg, **{k: sound.get(k) for k in changes}}.items()
             if v is not None}, **kw)
        return serve_engine.run(ctx)
    finally:
        common.build_model = original


@pytest.mark.parametrize("changes", [
    dict(sliding_window_size=25),                   # the window a position too long
    dict(sliding_window_size=512),                  # the window ignored (no context reaches it)
    dict(rope_layout=[1, 1, 1, 1, 1]),              # the full layers rotated
    dict(_fault="route_after_attention"),
    dict(_fault="silu"),
    dict(_fault="last_choice_dropped"),
], ids=["window_plus_one", "window_ignored", "full_rotated", "router_input", "silu", "top_k"])
def test_a_reference_told_otherwise_reads_the_program_as_not_correct(changes):
    out = _run_against(changes)
    assert not out["correct"] and out["check"]["numbers"]["logprob_err_mean"] > 1e-3


def test_the_control_reads_the_rehearsal_as_not_correct():
    from perfbench import probe

    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, CELL)
    config, traffic = common.apply_rehearsal(config, traffic)
    flags = probe.control_flags(config)
    assert flags[flags.index("--kv-dtype") + 1] == "fp8"
    ctx = common.Ctx(cell=cell, config=config, traffic=traffic, seed=4400000041, seconds=2.0,
                     trace=False, rehearse=True, serve_flags=flags)
    from perfbench.drivers import serve_engine

    out = serve_engine.run(ctx)
    assert not out["correct"] and out["check"]["numbers"]["logprob_err_mean"] > 1e-3


# -- the readers on fixtures ----------------------------------------------------------

CFG = {"num_attention_heads": 28, "num_key_value_heads": 4, "head_dim": 128, "hidden_size": 2560,
       "num_hidden_layers": 8, "sliding_window_layout": PERIOD * 2, "sliding_window_size": 4096}


def test_the_kernels_operations_and_bytes_by_kind():
    pairs = roofline_reader.visible_pairs
    assert pairs(11999, 1, 0) == 12000 and pairs(11999, 1, 4096) == 4096
    assert pairs(99, 1, 4096) == 100                      # inside the window: every key
    assert pairs(0, 1024, 0) == 1024 * 1025 / 2           # a first chunk: the triangle
    assert pairs(0, 1024, 4096) == 1024 * 1025 / 2
    # a chunk that crosses the window's edge: 96 queries still see all, 928 see 4,096
    assert pairs(4000, 1024, 4096) == 96 * 4000 + 96 * 97 / 2 + 928 * 4096
    assert pairs(12000, 1024, 4096) == 1024 * 4096 and pairs(12000, 1024, 0) == (
        1024 * 12000 + 1024 * 1025 / 2)
    full = roofline_reader.call_cost(CFG, [(11999, 1)], 0)
    window = roofline_reader.call_cost(CFG, [(11999, 1)], 4096)
    assert full["flops"] == 4 * 28 * 128 * 12000 and window["flops"] == 4 * 28 * 128 * 4096
    assert full["bytes"] == 2 * 12000 * 4 * 128 * 2 + 2 * 28 * 128 * 2
    assert window["bytes"] == 2 * 4096 * 4 * 128 * 2 + 2 * 28 * 128 * 2
    # a chunk at 12,000 reads the 4,095 before its first query and its own 1,024
    chunk = roofline_reader.call_cost(CFG, [(12000, 1024)], 4096)
    assert chunk["bytes"] == 2 * 5119 * 4 * 128 * 2 + 2 * 1024 * 28 * 128 * 2
    assert roofline_reader.layer_windows(CFG) == [0, 4096, 4096, 4096] * 2


def _lc(trace_ns: float, busy_ns: float = 4e9):
    """Two iterations inside the traced span: 10 rows decoding at 9,000 (a
    burst of 4) and one 1,024-token chunk from 8,192; and one outside it."""
    rec = SimpleNamespace(iter_t=[10.0, 10.5, 99.0],
                          decode_contexts=[[9000] * 10, [], [9000] * 10],
                          prefill_chunks=[[], [(8192, 1024)], []])
    trace = {"devices": {"0": {"busy_ns": busy_ns, "self_by_name": {"paged_attention": trace_ns}}}}
    return {"recorder": rec, "config": CFG, "trace_span": (9.0, 12.0), "trace": trace,
            "device_kind": "TPU v5 lite", "decode_burst": 4, "kv_itemsize": 2}


def test_the_roofline_reader_sums_the_spans_calls_one_by_one_and_kind_by_kind():
    peak = counts.peaks("TPU v5 lite")
    least = 0.0
    for window, layers in ((0, 2), (4096, 6)):
        for s in range(4):
            cost = roofline_reader.call_cost(CFG, [(8999 + s, 1)] * 10, window)
            least += layers * counts.roofline(cost, peak)["least_s"]
        cost = roofline_reader.call_cost(CFG, [(8192, 1024)], window)
        least += layers * counts.roofline(cost, peak)["least_s"]
    lc = _lc(trace_ns=4 * least * 1e9)
    assert abs(roofline_reader.least_s(lc) - least) < 1e-12
    name = "kernel.paged_attention.window_roofline_pct"
    assert abs(roofline_reader.read(name, lc) - 25.0) < 1e-9
    # what kernel.py's count would say of the same calls: every layer at the whole context
    whole = 0.0
    for s in range(4):
        whole += 8 * counts.roofline(counts.paged_attention_cost(
            CFG, [9000 + s] * 10, [1] * 10), peak)["least_s"]
    assert whole > 1.5 * sum(
        layers * counts.roofline(roofline_reader.call_cost(
            CFG, [(8999 + s, 1)] * 10, window), peak)["least_s"]
        for window, layers in ((0, 2), (4096, 6)) for s in range(4))


def test_the_readers_read_nothing_from_a_program_without_the_kinds():
    name = "kernel.paged_attention.window_roofline_pct"
    lc = _lc(trace_ns=1e6)
    assert roofline_reader.read(name, {**lc, "trace": None}) is None
    no_kernel = {**lc, "trace": {"devices": {"0": {"busy_ns": 1e9, "self_by_name": {}}}}}
    assert roofline_reader.read(name, no_kernel) is None
    mistral = {**lc, "config": {"num_attention_heads": 32, "num_key_value_heads": 8,
                                "hidden_size": 4096, "num_hidden_layers": 8}}
    assert roofline_reader.read(name, mistral) is None
    parent = {"stats0": {"completed": 0}, "stats1": {"completed": 70, "allocated_blocks": 9},
              "num_blocks": 100}
    for metric in ("window.walk_saved_pct", "window.freed_blocks_per_request"):
        assert window_reader.read(metric, parent) is None
        assert window_reader.read(metric, {}) is None
    for metric in ("kv.pool_used_pct.full", "kv.pool_used_pct.window"):
        assert pool_reader.read(metric, parent) is None
        assert common.metric_reader(metric)(metric, {}) is None


def test_the_counter_readers():
    s0 = {"paged_entries_behind_window_total": 1000, "paged_entries_walked_window_total": 3000,
          "window_blocks_freed_total": 100, "completed": 10}
    s1 = {"paged_entries_behind_window_total": 4000, "paged_entries_walked_window_total": 12000,
          "window_blocks_freed_total": 2900, "completed": 80, "allocated_blocks_full": 4096,
          "allocated_blocks_window": 1284, "window_num_blocks": 12841}
    lc = {"stats0": s0, "stats1": s1, "num_blocks": 40961}
    assert window_reader.read("window.walk_saved_pct", lc) == 25.0
    assert window_reader.read("window.freed_blocks_per_request", lc) == 40.0
    assert window_reader.read("window.walk_saved_pct", {"stats0": s1, "stats1": s1}) is None
    assert abs(pool_reader.read("kv.pool_used_pct.full", lc) - 100 * 4096 / 40961) < 1e-12
    assert abs(common.metric_reader("kv.pool_used_pct.window")("kv.pool_used_pct.window", lc)
               - 100 * 1284 / 12841) < 1e-12


def test_the_new_metrics_have_readers_and_the_benchmark_file_keeps_its_form():
    bench = common.benchmark()
    for name in NEW_METRICS:
        assert callable(common.metric_reader(name))
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert metric["layer"] in {m["layer"] for m in bench["per_layer"][:116]}
    with open(common.ROOT + "/BENCHMARK.json") as f:
        assert len(f.read()) < 64 * 1024
    assert json.dumps(bench["workloads"][-1]["name"]) == f'"{CELL}"'
