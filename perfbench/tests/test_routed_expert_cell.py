"""The routed-expert configuration (LFM2-8B-A1B): its cell as files and
entries, the rehearsal of its cell (correct against its own reference, not
correct against another architecture's), the reference's routed layer
against a token loop, and the ``moe.*`` readers on a fixture."""

from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import common, counts, rehearse, weights
from perfbench.layer_metrics import moe as reader
from perfbench.reference import lfm2 as reference

CELL = "lfm2-8b-a1b-chat-steady"
CONFIG = "perfbench/configs/lfm2-8b-a1b-serve-v5e1.json"
CATALOG = {  # the catalog's `config` of LFM2-8B-A1B, every number of it
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
}


def test_the_cell_arrived_as_files_and_entries():
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, CELL)
    assert cell["chips"] == 1 and config["reference"] == "lfm2" and config["program"] == "serve_engine"
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    # every number of the catalog's entry under the same key, but the three cut
    for key, value in CATALOG.items():
        if key in config["reduced"]:
            assert config["reduced"][key] == [value, config[key]], key
        else:
            assert config[key] == value, key
    published, cut = config["reduced"]["layer_types"]
    assert cut == published[1:14] == config["layer_types"] and len(published) == 24
    assert counts.kv_layers(config) == 3 and config["layer_types"].count("conv") == 10
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    scopes = {n.split(".")[1][:-4] for n in listed if n.startswith("scope.")}
    assert scopes == {"embed", "attn_proj", "kv_write", "attn_kernel", "mlp", "head", "sample",
                      "layer_carry", "unscoped", "conv_proj", "conv_mix", "moe_router",
                      "moe_experts"}
    assert {"moe.experts_touched_pct", "moe.load_max_over_mean", "moe.pairs_per_dispatch",
            "moe.experts_roofline_pct", "kernel.paged_attention.roofline_pct",
            "device.hbm_peak_pct.chat", "device.idle_pct.chat"} <= set(listed)
    for name in ("ttft_ms.tail10", "tpot_ms.p90"):
        assert CELL in next(m for m in bench["end_to_end"] if m["name"] == name)["workloads"]
    assert traffic["kind"] == "open_loop_lognormal"
    assert (traffic["prompt_tokens"]["median"], traffic["output_tokens"]["median"]) == (384, 192)
    assert (traffic["block"], traffic["shuffle_group"], traffic["jitter_s"]) == (10, 1, 0.02)


def test_the_rehearsal_of_the_cell_runs_the_whole_command():
    out = rehearse.main([CELL, "--seconds", "2", "--seed", "3600000041"])
    assert out["correct"] and out["failed"] == 0 and out["counts"]["compiles_in_window"] == 0
    assert set(out["end_to_end_present"]) == {"ttft_ms.tail10", "tpot_ms.p90", "setup_s"}
    assert out["check"]["numbers"]["logprob_err_mean"] < out["check"]["limits"]["logprob_err_mean"]
    assert {"moe.experts_touched_pct", "moe.load_max_over_mean",
            "moe.pairs_per_dispatch"} <= set(out["per_layer_readable"])


def test_under_another_architectures_reference_the_cell_is_not_correct(tmp_path):
    """The same program held against ``reference: mistral``: the reference
    cannot even name the program's leaves, and the run does not come out
    as correct."""
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, CELL)
    config, traffic = common.apply_rehearsal({**config, "reference": "mistral"}, traffic)
    ctx = common.Ctx(cell=cell, config=config, traffic=traffic, seed=3600000041, seconds=2.0,
                     trace=False, rehearse=True)
    try:
        out = common.load_driver("serve_engine").run(ctx)
    except (KeyError, ValueError, TypeError):
        return  # it has no equations for this model: no result, not a correct one
    assert not out["correct"]


def test_the_reference_draws_the_programs_leaves():
    cfg = common.read_json(CONFIG)
    cfg = {**cfg, **cfg["rehearsal"]}
    flat = weights.flat_names(common.build_model(cfg).params)
    assert {k: tuple(a.shape) for k, a in flat.items()} == reference.leaf_shapes(cfg)
    assert all(k in flat for k in cfg["weight_scales"])


def test_the_references_routed_layer_against_a_token_loop():
    cfg = {"hidden_size": 16, "num_experts": 6, "num_experts_per_tok": 2,
           "moe_intermediate_size": 8, "intermediate_size": 8, "vocab_size": 8,
           "num_attention_heads": 2, "num_key_value_heads": 1, "routed_scaling_factor": 1.5}
    rng = np.random.default_rng(0)
    w = {"gate": rng.normal(size=(16, 6)), "expert_bias": rng.normal(size=(6,)) * 0.3,
         "w_in": rng.normal(size=(6, 16, 16)) / 4, "w_out": rng.normal(size=(6, 8, 16)) / 3}
    y = rng.normal(size=(5, 16))
    got = np.asarray(reference.routed_ff(cfg, {k: jnp.asarray(v, jnp.float32) for k, v in w.items()},
                                         jnp.asarray(y, jnp.float32)))
    want = np.zeros_like(y)
    for t in range(5):
        s = 1 / (1 + np.exp(-(y[t] @ w["gate"])))
        chosen = np.argsort(-(s + w["expert_bias"]))[:2]
        for e in chosen:
            g, u = np.split(y[t] @ w["w_in"][e], 2)
            want[t] += s[e] / (s[chosen].sum() + 1e-6) * 1.5 * ((g / (1 + np.exp(-g)) * u) @ w["w_out"][e])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


def _flight_counters(dispatches):
    return {"moe_dispatches_total": dispatches, "moe_pairs_routed_total": 12 * dispatches * 96,
            "moe_experts_touched_total": 12 * dispatches * 28,
            "moe_load_max_total": 12 * dispatches * 9}


def _lc(stats0=None, stats1=None):
    # four iterations of the window as the engine's flight entries stamp them:
    # ended at 9.5 (55 dispatches by then), 10.5 (70), 11.5 (90) and 20.5 (150)
    rec = SimpleNamespace(flight=[
        {"t_start": t, "wall_s": 0.5, "counters": _flight_counters(n)}
        for t, n in ((9.0, 55), (10.0, 70), (11.0, 90), (20.0, 150))])
    dev = {"busy_ns": 2e9, "ops": []}
    # a scope table is what the engine hands out; here the sums are given
    dev["self_by_stack"] = [("jit(decode)/layers/moe_experts/gmm", "custom-call", 0.8e9),
                            ("jit(decode)/layers/moe_router/dot", "fusion", 0.2e9),
                            ("jit(decode)/head/dot", "fusion", 1.0e9)]
    return {"trace": {"devices": {"/device:TPU:0": dev}}, "recorder": rec,
            "config": common.read_json(CONFIG), "trace_span": (9.0, 12.0), "decode_burst": 1,
            "device_kind": "TPU v5 lite", "scope_tables": [{"x": ("", "")}],
            "stats0": stats0, "stats1": stats1}


def _stats(dispatches, pairs, touched, load_max):
    grid = [[pairs // (12 * 32)] * 32 for _ in range(12)]
    return {"moe_layers": 12, "moe_experts": 32, "moe_top_k": 4,
            "moe_dispatches_total": dispatches, "moe_pairs_routed_total": pairs,
            "moe_experts_touched_total": touched, "moe_load_max_total": load_max,
            "moe_expert_pairs": grid}


def test_the_moe_readers_on_a_fixture():
    """A window of 100 dispatches over 12 layers: 28 of 32 experts touched
    and 96 pairs a layer and dispatch, the busiest expert given 9. The
    traced span opens the window (no entry ended before it: ``stats0`` is
    its start) and the newest entry ended inside it stands at 90
    dispatches: 40 dispatches' exact counts over the scope's 0.8 s."""
    lc = _lc(_stats(50, 12 * 50 * 96, 12 * 50 * 28, 12 * 50 * 9),
             _stats(150, 12 * 150 * 96, 12 * 150 * 28, 12 * 150 * 9))
    assert reader.read("moe.experts_touched_pct", lc) == pytest.approx(100 * 28 / 32)
    assert reader.read("moe.pairs_per_dispatch", lc) == pytest.approx(96.0)
    assert reader.read("moe.load_max_over_mean", lc) == pytest.approx(9 / 3.0)
    assert reader.span_growth(lc, (9.0, 12.0))["moe_dispatches_total"] == 40
    assert reader.span_growth(lc, (10.0, 12.0))["moe_dispatches_total"] == 35  # 90 - 55
    assert reader.span_growth(lc, (8.0, 9.2)) is None
    cost = reader.expert_product_cost(lc["config"], 28, 96)
    assert cost["bytes"] == (3 * 2048 * 1792 * 28 + 2 * 2048 * 96) * 2
    assert cost["flops"] == 2 * 3 * 2048 * 1792 * 96
    assert counts.roofline(cost, counts.peaks("TPU v5 lite"))["bound"] == "memory"
    least = 12 * 40 * cost["bytes"] / 819e9
    share = reader.read("moe.experts_roofline_pct", lc)
    assert share == pytest.approx(100 * least / 0.8) and 0 < share < 100
    # a program whose flight entries carry no counters: nothing to read
    for entry in lc["recorder"].flight:
        del entry["counters"]
    assert reader.read("moe.experts_roofline_pct", lc) is None


def test_the_moe_readers_find_nothing_where_the_program_counts_no_experts():
    """The parent of the PR that brought the counters, or a model with no
    routed layer: every reader returns ``None`` and raises nothing."""
    for stats in ({}, {"iterations": 5}):
        lc = _lc(stats, stats)
        for name in ("moe.experts_touched_pct", "moe.load_max_over_mean",
                     "moe.pairs_per_dispatch", "moe.experts_roofline_pct"):
            assert reader.read(name, lc) is None
    quiet = _stats(50, 0, 0, 0)
    assert reader.read("moe.pairs_per_dispatch", _lc(quiet, quiet)) is None
    untraced = _lc(_stats(0, 0, 0, 0), _stats(10, 960, 280, 90))
    untraced["trace"] = None
    assert reader.read("moe.experts_roofline_pct", untraced) is None
    assert reader.read("moe.experts_touched_pct", untraced) is not None


@pytest.mark.parametrize("arrived, completed, occupancy, sustained", [
    (164, 168, [0.5, 0.625], True),     # kept up, slots to spare
    (186, 174, [0.64, 0.84], False),    # fell behind: 93.5 % of what arrived
    (212, 210, [0.9, 1.0], False),      # kept up, but an iteration ended with every slot taken
    (0, 0, [], False),
])
def test_the_knee_rule_asks_for_the_window_kept_up_and_a_free_slot(arrived, completed, occupancy,
                                                                    sustained):
    from perfbench import knee

    observed = {"arrivals_in_window": arrived, "completed_in_window": completed}
    assert knee.verdict(observed, occupancy) is sustained
