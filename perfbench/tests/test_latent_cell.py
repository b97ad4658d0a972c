"""The latent-cache configuration (DeepSeek-V3, rank 0's share of its
experts): its cell as files and entries, the rehearsal of its cell, a
reference told the latent rank or the held range wrong reading the program
as not ``correct``, and the ``kernel.latent_attention.*``,
``moe.pairs_held_pct`` and ``latent.bytes_per_token`` readers on fixtures."""

import json
from types import SimpleNamespace

import pytest

from perfbench import common, counts, rehearse, weights
from perfbench.layer_metrics import kernel_latent_attention_busy_pct as busy_reader
from perfbench.layer_metrics import kernel_latent_attention_roofline_pct as roofline_reader
from perfbench.layer_metrics import latent as latent_reader
from perfbench.layer_metrics import moe_pairs_held_pct as held_reader
from perfbench.reference import deepseek_v3 as reference

CELL = "deepseek-v3-doc-steady"
CONFIG = "perfbench/configs/deepseek-v3-serve-v5e1.json"
CATALOG = {  # the catalog's `config` of DeepSeek-V3, every key of it
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7168, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v3",
    "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280,
}
NEW_METRICS = ("kernel.latent_attention.busy_pct", "kernel.latent_attention.roofline_pct",
               "scope.mla_absorb_pct.chat", "scope.moe_shared_pct.chat", "moe.pairs_held_pct",
               "latent.bytes_per_token")


def test_the_cell_arrived_as_files_and_entries():
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, CELL)
    assert cell["chips"] == 1 and config["reference"] == "deepseek_v3"
    assert config["program"] == "serve_engine" and config["kernels"] == ["latent_attention"]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == list(config["reduced"]) == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
    assert entry["source"] == config["source"] and entry["file"] == CONFIG
    # every key of the catalog's entry under the same key, but the four cuts
    for key, value in CATALOG.items():
        if key in config["reduced"]:
            assert config["reduced"][key] == [value, config[key]], key
        else:
            assert config[key] == value, key
    # the floors: a leading dense layer and four behind it, 16 >= 8 experts, an eighth
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8 and config["vocab_size"] * 8 >= CATALOG["vocab_size"]
    assert (config["router_experts"], config["first_held_expert"]) == (256, 0)
    for key in ("rope pairing", "router precision", "num_nextn_predict_layers", "leaf storage",
                "weights", "router_experts"):
        assert any(key in name for name in config["assumed"]), key
    for said in ("16 chips share each layer", "rank 0", "a sixteenth"):
        assert said in config["deployment"], said
    flags = config["serve_flags"]
    assert flags[flags.index("--max-seq-len") + 1] == "16384"
    slots, blocks = (int(flags[flags.index(f) + 1]) for f in ("--num-slots", "--num-blocks"))
    assert slots % 8 == 0 and blocks == slots * 1024 + 1  # every slot resident
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert set(NEW_METRICS) <= set(listed)
    for name in NEW_METRICS:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
    scopes = {n.split(".")[1][:-4] for n in listed if n.startswith("scope.")}
    assert scopes == {"embed", "attn_proj", "kv_write", "attn_kernel", "mlp", "head", "sample",
                      "layer_carry", "unscoped", "moe_router", "moe_experts", "mla_absorb",
                      "moe_shared"}
    assert {"moe.experts_touched_pct", "moe.load_max_over_mean", "moe.pairs_per_dispatch",
            "moe.experts_roofline_pct", "paged.table_live_pct", "paged.tile_fill_pct",
            "device.hbm_peak_pct.chat", "device.idle_pct.chat", "step.decode_ms"} <= set(listed)
    # the accepted kernel shares count K and V per kv head: not what a latent pool holds
    assert not [n for n in listed if n.startswith("kernel.paged_attention")]
    for name in ("ttft_ms.tail10", "tpot_ms.p90"):
        assert CELL in next(m for m in bench["end_to_end"] if m["name"] == name)["workloads"]
    assert traffic["kind"] == "open_loop_lognormal"
    assert (traffic["prompt_tokens"]["sigma"], traffic["output_tokens"]["sigma"]) == (0.8, 0.7)
    assert (traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]) == (512, 15872)
    assert traffic["prompt_tokens"]["median"] in (2048, 4096)
    assert (traffic["output_tokens"]["median"], traffic["output_tokens"]["min"],
            traffic["output_tokens"]["max"]) == (128, 16, 512)
    assert (traffic["schedule_seed"], traffic["block"], traffic["shuffle_group"],
            traffic["jitter_s"], traffic["drain_s"]) == (42, 10, 1, 0.02, 150.0)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert len(bench["per_layer"]) <= 128


def test_the_reference_draws_the_programs_leaves():
    cfg = common.read_json(CONFIG)
    cfg = {**cfg, **cfg["rehearsal"]}
    flat = weights.flat_names(common.build_model(cfg).params)
    assert {k: tuple(a.shape) for k, a in flat.items()} == reference.leaf_shapes(cfg)
    assert all(k in flat for k in cfg["weight_scales"])
    assert (cfg["n_group"], cfg["router_experts"], cfg["n_routed_experts"]) == (2, 8, 4)


@pytest.fixture(scope="module")
def rehearsed():
    return rehearse.main([CELL, "--seconds", "2", "--seed", "3900000041"])


def test_the_rehearsal_of_the_cell_runs_the_whole_command(rehearsed):
    out = rehearsed
    assert out["correct"] and out["failed"] == 0 and out["counts"]["compiles_in_window"] == 0
    assert set(out["end_to_end_present"]) == {"ttft_ms.tail10", "tpot_ms.p90", "setup_s"}
    assert out["check"]["numbers"]["logprob_err_mean"] < out["check"]["limits"]["logprob_err_mean"]
    assert {"moe.experts_touched_pct", "moe.load_max_over_mean", "moe.pairs_per_dispatch",
            "moe.pairs_held_pct", "latent.bytes_per_token", "paged.table_live_pct",
            "paged.tile_fill_pct"} <= set(out["per_layer_readable"])


def _run_against(changes: dict):
    """The cell's rehearsal with the REFERENCE told ``changes``; the program
    is built from the configuration as committed."""
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, CELL)
    sound, traffic = common.apply_rehearsal(config, traffic)
    ctx = common.Ctx(cell=cell, config={**sound, **changes}, traffic=traffic, seed=3900000041,
                     seconds=2.0, trace=False, rehearse=True)
    from perfbench.drivers import serve_engine

    original = common.build_model
    try:
        common.build_model = lambda cfg, **kw: original({**cfg, **{k: sound[k] for k in changes}},
                                                        **kw)
        return serve_engine.run(ctx)
    finally:
        common.build_model = original


@pytest.mark.parametrize("changes", [
    # the held range: the reference computes experts 4-7 where the program holds 0-3
    dict(first_held_expert=4),
    # the groups ignored: top 2 of all 8 experts
    dict(n_group=1, topk_group=1),
    # the softmax scale without YaRN's m^2 (mscale_all_dim read as 0)
    dict(rope_scaling={"type": "yarn", "factor": 8, "original_max_position_embeddings": 64,
                       "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 0}),
], ids=["held_range", "groups", "scale"])
def test_a_reference_told_otherwise_reads_the_program_as_not_correct(changes):
    out = _run_against(changes)
    assert not out["correct"] and out["check"]["numbers"]["logprob_err_mean"] > 0.01


def test_a_reference_told_another_latent_rank_cannot_draw_the_programs_weights():
    """``kv_lora_rank`` is a shape of ``wkv_a``, ``kv_norm`` and ``wkv_b``: told
    24 where the program compresses to 32, the reference draws other leaves
    and the served tokens are not its own."""
    out = _run_against(dict(kv_lora_rank=24))
    assert not out["correct"] and out["check"]["numbers"]["logprob_err_mean"] > 0.01


def test_the_control_reads_the_rehearsal_as_not_correct():
    from perfbench import probe

    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, CELL)
    config, traffic = common.apply_rehearsal(config, traffic)
    flags = probe.control_flags(config)
    assert flags[flags.index("--kv-dtype") + 1] == "fp8"
    ctx = common.Ctx(cell=cell, config=config, traffic=traffic, seed=3900000041, seconds=2.0,
                     trace=False, rehearse=True, serve_flags=flags)
    from perfbench.drivers import serve_engine

    out = serve_engine.run(ctx)
    assert not out["correct"] and out["check"]["numbers"]["logprob_err_mean"] > 3e-4


# -- the readers on fixtures ----------------------------------------------------------

CFG = {"kv_lora_rank": 512, "qk_rope_head_dim": 64, "num_attention_heads": 128,
       "num_hidden_layers": 5}


def test_the_latent_kernels_operations_and_bytes():
    one = roofline_reader.latent_attention_cost(CFG, [4096], [1])
    assert one["flops"] == 2 * 128 * 1 * 4096 * (576 + 512)
    assert one["bytes"] == 4096 * 576 * 2 + 128 * (576 + 512) * 2
    # 242 operations a byte of cache: the v5e's own ridge (197e12 / 819e9 = 240.5)
    assert abs(one["flops"] / (4096 * 576 * 2) - 241.8) < 0.1
    # so a decode row is bound by neither plainly: the two bounds lie within a tenth
    assert 0.9 < (one["flops"] / 197e12) / (one["bytes"] / 819e9) < 1.1
    rows = roofline_reader.latent_attention_cost(CFG, [100, 900], [1, 1])
    assert rows["flops"] == 2 * 128 * 1000 * 1088
    chunk = roofline_reader.latent_attention_cost(CFG, [1024], [512])
    assert chunk["flops"] == 2 * 128 * 512 * 1024 * 1088


def _lc(trace_ns: float, busy_ns: float = 4e9):
    """Two iterations inside the traced span: 24 rows decoding at 6,000 (a
    burst of 4) and one 512-token chunk from 2,048; and one outside it."""
    rec = SimpleNamespace(iter_t=[10.0, 10.5, 99.0],
                          decode_contexts=[[6000] * 24, [], [6000] * 24],
                          prefill_chunks=[[], [(2048, 512)], []])
    trace = {"devices": {"0": {"busy_ns": busy_ns, "self_by_name": {"latent_attention": trace_ns}}}}
    return {"recorder": rec, "config": CFG, "trace_span": (9.0, 12.0), "trace": trace,
            "device_kind": "TPU v5 lite", "decode_burst": 4, "kv_itemsize": 2}


def test_the_roofline_reader_sums_the_spans_calls_one_by_one():
    peak = counts.peaks("TPU v5 lite")
    decode = sum(counts.roofline(roofline_reader.latent_attention_cost(
        CFG, [6000 + s] * 24, [1] * 24), peak)["least_s"] for s in range(4))
    chunk = roofline_reader.latent_attention_cost(CFG, [2560], [512])
    chunk["flops"] *= (2048 + 513 / 2.0) / 2560
    least = 5 * (decode + counts.roofline(chunk, peak)["least_s"])
    lc = _lc(trace_ns=2 * least * 1e9)
    assert abs(roofline_reader.least_s(lc) - least) < 1e-12
    assert abs(roofline_reader.read("kernel.latent_attention.roofline_pct", lc) - 50.0) < 1e-9
    assert abs(busy_reader.read("kernel.latent_attention.busy_pct", lc)
               - 100 * 2 * least / 4.0) < 1e-9


def test_the_readers_read_nothing_from_a_program_without_the_kernel_or_the_counters():
    lc = _lc(trace_ns=1e6)
    assert roofline_reader.read("kernel.latent_attention.roofline_pct", {**lc, "trace": None}) is None
    no_kernel = {**lc, "trace": {"devices": {"0": {"busy_ns": 1e9, "self_by_name": {}}}}}
    assert roofline_reader.read("kernel.latent_attention.roofline_pct", no_kernel) is None
    assert busy_reader.read("kernel.latent_attention.busy_pct", no_kernel) is None
    mistral = {**lc, "config": {"num_attention_heads": 32, "num_hidden_layers": 8}}
    assert roofline_reader.read("kernel.latent_attention.roofline_pct", mistral) is None
    assert held_reader.read("moe.pairs_held_pct", {"stats0": {}, "stats1": {}}) is None
    routed = {"moe_pairs_routed_total": 10}  # every expert held: no second counter
    assert held_reader.read("moe.pairs_held_pct", {"stats0": routed, "stats1": routed}) is None
    assert latent_reader.read("latent.bytes_per_token", {"stats1": {}}) is None
    assert latent_reader.read("latent.bytes_per_token",
                              {"stats1": {"latent_bytes_per_token": 0}}) is None


def test_the_counter_readers():
    s0 = {"moe_pairs_routed_total": 100, "moe_pairs_elsewhere_total": 1500}
    s1 = {"moe_pairs_routed_total": 725, "moe_pairs_elsewhere_total": 10875,
          "latent_bytes_per_token": 6400}
    assert held_reader.read("moe.pairs_held_pct", {"stats0": s0, "stats1": s1}) == 6.25
    assert held_reader.read("moe.pairs_held_pct", {"stats0": s1, "stats1": s1}) is None
    assert latent_reader.read("latent.bytes_per_token", {"stats1": s1}) == 6400.0


def test_the_new_metrics_have_readers_and_the_benchmark_file_keeps_its_form():
    bench = common.benchmark()
    for name in NEW_METRICS:
        assert callable(common.metric_reader(name))
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert metric["layer"] in {m["layer"] for m in bench["per_layer"][:110]}
    with open(common.ROOT + "/BENCHMARK.json") as f:
        assert len(f.read()) < 64 * 1024
    assert json.dumps(bench["workloads"][-1]["name"]) == f'"{CELL}"'
