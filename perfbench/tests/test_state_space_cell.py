"""The state-space configuration: the rehearsal of its cell, the reference
against an independent check of its own equations, and the reader of the
new kernel's metrics on a fixture."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import common, counts, rehearse, weights
from perfbench.layer_metrics import kernel_ssm_state_update_roofline_pct as reader
from perfbench.reference import granite_hybrid as reference

CELL = "granite4h-micro-chat-short"


def test_the_cell_arrived_as_files_and_entries():
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, CELL)
    assert cell["chips"] == 1 and config["reference"] == "granite_hybrid"
    assert config["kernels"] == ["paged_attention", "ssm_state_update"]
    assert config["reduced"] == {} and config["num_hidden_layers"] == 40
    assert counts.kv_layers(config) == 4 and reader.mamba_layers(config) == 36
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == []
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    scopes = {n.split(".")[1][:-4] for n in listed if n.startswith("scope.")}
    assert scopes == {"embed", "attn_proj", "kv_write", "attn_kernel", "mlp", "head", "sample",
                      "layer_carry", "unscoped", "ssm_proj", "ssm_conv", "ssm_scan"}
    assert {"kernel.ssm_state_update.busy_pct", "kernel.ssm_state_update.roofline_pct",
            "kernel.paged_attention.roofline_pct", "device.hbm_peak_pct.chat"} <= set(listed)
    assert traffic["kind"] == "open_loop_lognormal" and traffic["prompt_tokens"]["median"] == 256
    # every published number of the catalog's entry is in the file as published
    for key, value in {"hidden_size": 2048, "vocab_size": 100352, "mamba_d_state": 128,
                       "attention_multiplier": 0.015625, "shared_intermediate_size": 8192,
                       "max_position_embeddings": 131072, "num_local_experts": 0}.items():
        assert config[key] == value


def test_the_rehearsal_of_the_cell_runs_the_whole_command():
    out = rehearse.main([CELL, "--seconds", "2", "--seed", "2700000041"])
    assert out["correct"] and out["failed"] == 0 and out["counts"]["compiles_in_window"] == 0
    assert set(out["end_to_end_present"]) == {"ttft_ms.tail10", "tpot_ms.p90", "setup_s"}
    assert out["check"]["numbers"]["logprob_err_mean"] < out["check"]["limits"]["logprob_err_mean"]


def test_the_reference_mixer_against_a_geometric_series():
    """Weights chosen by hand so that the whole mixer has a closed form: the
    convolution is its current tap, ``B = C = 1`` (a bias under ``silu``),
    ``dt`` constant, the gate ``z`` constant, the gated norm and the output
    projection the identity on the first head's channels. Then ``y_t = N *
    sum_{u<=t} exp(dt A (t-u)) dt x_u + D x_t``, a geometric series."""
    cfg = {"hidden_size": 8, "mamba_expand": 2, "mamba_n_heads": 4, "mamba_d_head": 4,
           "mamba_d_state": 6, "mamba_n_groups": 1, "mamba_d_conv": 4, "rms_norm_eps": 0.0,
           "shared_intermediate_size": 16, "vocab_size": 32, "num_attention_heads": 2,
           "num_key_value_heads": 1}
    t, z = 9, reference._sizes(cfg)
    d, conv = z["d_inner"], z["conv"]
    w = {"in_proj": np.zeros((8, d + conv), np.float32), "dt_proj": np.zeros((8, 4), np.float32),
         "conv_w": np.zeros((4, conv), np.float32), "conv_b": np.zeros((conv,), np.float32),
         "dt_bias": np.full((4,), -1.0, np.float32),
         "A_log": np.log(np.asarray([0.5, 1, 2, 4], np.float32)),
         "D": np.asarray([1, 2, 3, 4], np.float32), "gate_norm": np.ones((d,), np.float32),
         "out_proj": np.eye(d, 8, dtype=np.float32)}
    w["in_proj"][:, d: d + 8] = np.eye(8)       # x's first 8 channels (heads 0 and 1) = y
    w["conv_w"][3, :d] = 1.0                    # the current tap only
    w["conv_b"][d:] = 1.2784645                 # silu(1.2784645) = 1: B = C = 1
    y = np.random.default_rng(0).normal(size=(t, 8)).astype(np.float32)
    dt, a = float(jax.nn.softplus(-1.0)), -np.exp(w["A_log"])
    # the gate: z = 1 on every channel, from a first input channel held at 1
    y1 = np.concatenate([np.ones((t, 1), np.float32), y[:, 1:]], axis=1)
    w["in_proj"][0, :d] = 1.0                   # z = 1 on every channel
    out = np.asarray(reference.mamba_mixer(cfg, {k: jnp.asarray(v) for k, v in w.items()},
                                           jnp.asarray(y1)))
    x1 = np.asarray(jax.nn.silu(jnp.asarray(y1))).reshape(t, 2, 4)
    want = np.zeros((t, 4, 4))
    for tt in range(t):
        for u in range(tt + 1):
            want[tt, :2] += z["n"] * np.exp(dt * a[:2] * (tt - u))[:, None] * dt * x1[u]
        want[tt, :2] += w["D"][:2, None] * x1[tt]
    gated = want.reshape(t, d) * float(jax.nn.silu(1.0))
    normed = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True))
    np.testing.assert_allclose(out, normed[:, :8], rtol=2e-5, atol=1e-6)


def test_the_reference_draws_the_programs_leaves():
    cfg = common.read_json("perfbench/configs/granite-4.0-h-micro-serve-v5e1.json")
    cfg = {**cfg, **cfg["rehearsal"]}
    flat = weights.flat_names(common.build_model(cfg).params)
    assert {k: tuple(a.shape) for k, a in flat.items()} == reference.leaf_shapes(cfg)
    assert all(k in flat for k in cfg["weight_scales"])


def _lc(decode_rows, kernel_s, busy_s=2.0):
    rec = SimpleNamespace(iter_t=[10.0, 10.5, 11.0, 20.0],
                          decode_contexts=[decode_rows, decode_rows, [], decode_rows],
                          prefill_chunks=[[], [], [], []])
    dev = {"busy_ns": busy_s * 1e9, "self_by_name": {"ssm_state_update": kernel_s * 1e9, "fusion": 1e9}}
    config = common.read_json("perfbench/configs/granite-4.0-h-micro-serve-v5e1.json")
    return {"trace": {"devices": {"/device:TPU:0": dev}}, "recorder": rec, "config": config,
            "trace_span": (9.0, 12.0), "decode_burst": 8, "device_kind": "TPU v5 lite"}


def test_the_state_update_readers_on_a_fixture():
    """Two traced decode rounds of 48 live rows (a third round is outside
    the span, a fourth has no decoding row): the least time is 2 rounds x 8
    steps x 36 layers x 48 rows x (2 x 2.1 MB of state + operands) / 819
    GB/s, checked here by hand."""
    lc = _lc([100] * 48, kernel_s=0.5)
    cost = reader.state_update_cost(lc["config"], 48)
    row = 2 * 64 * 64 * 128 * 4 + (64 * 64 + 2 * 128) * 2 + 64 * 4 + 64 * 64 * 4
    assert cost["bytes"] == 48 * row and cost["flops"] == 5.0 * 64 * 64 * 128 * 48
    least = 2 * 8 * 36 * 48 * row / 819e9
    assert reader.least_s(lc) == pytest.approx(least, rel=1e-9)
    assert reader.read("kernel.ssm_state_update.roofline_pct", lc) == pytest.approx(100 * least / 0.5)
    assert reader.read("kernel.ssm_state_update.busy_pct", lc) == pytest.approx(25.0)
    assert 0 < reader.read("kernel.ssm_state_update.roofline_pct", lc) <= 100


def test_the_readers_find_nothing_where_the_program_has_no_such_kernel():
    lc = _lc([100] * 48, kernel_s=0.0)
    lc["trace"]["devices"]["/device:TPU:0"]["self_by_name"].pop("ssm_state_update")
    assert reader.read("kernel.ssm_state_update.roofline_pct", lc) is None
    assert reader.read("kernel.ssm_state_update.busy_pct", lc) is None
    assert reader.read("kernel.ssm_state_update.busy_pct", {**lc, "trace": None}) is None
    mistral = common.read_json("perfbench/configs/mistral-7b-serve-v5e1.json")
    assert reader.least_s({**lc, "config": mistral}) is None
