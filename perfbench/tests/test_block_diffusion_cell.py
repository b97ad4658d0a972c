"""The block-diffusion configuration (SDAR-30B-A3B-Chat): its cell as files
and entries and nothing that was there edited, the rehearsal of its cell,
the reference's conditioning against a brute-force forward a token, and the
``round.*``, ``block.*`` and block-roofline readers on fixtures."""

import subprocess
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import common, counts, rehearse, weights
from perfbench.layer_metrics import block as block_reader
from perfbench.layer_metrics import kernel_paged_attention_block_roofline_pct as roofline_reader
from perfbench.layer_metrics import round as round_reader
from perfbench.reference import sdar_moe as reference

CELL = "sdar-30b-a3b-chat-even"
CONFIG = "perfbench/configs/sdar-30b-a3b-serve-v5e1.json"
CATALOG = {  # the catalog's `config` of SDAR-30B-A3B-Chat, every key of it
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
NEW_METRICS = ("round.denoise_pct.chat", "round.commit_pct.chat", "block.tokens_per_forward",
               "block.waste_pct", "kernel.paged_attention.block_roofline_pct")
NEW_FILES = {
    CONFIG, "perfbench/reference/sdar_moe.py", "perfbench/traffic/chat-even-steady.json",
    "perfbench/layer_metrics/round.py", "perfbench/layer_metrics/block.py",
    "perfbench/layer_metrics/kernel_paged_attention_block_roofline_pct.py",
    "perfbench/tests/test_block_diffusion_cell.py",
}
#: the commit this cell was added on top of
PARENT = "2a01d1b098d6f1df7c636bb2b5ff4140c474577b"


def test_the_cell_arrived_as_files_and_entries():
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, CELL)
    assert cell["chips"] == 1 and config["reference"] == "sdar_moe"
    assert config["program"] == "serve_engine" and config["kernels"] == ["paged_attention"]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers"] == list(config["reduced"])
    assert entry["source"] == config["source"]
    # every key of the catalog's entry under the same key, but the depth
    for key, value in CATALOG.items():
        if key in config["reduced"]:
            assert config["reduced"][key] == [value, config[key]], key
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] >= 4 and counts.kv_layers(config) == config["num_hidden_layers"]
    for key in ("block_length", "mask_token_id", "aligned prediction", "schedule",
                "denoise_steps", "router precision", "leaf storage", "weights"):
        assert any(key in name for name in config["assumed"]), key
    flags = config["serve_flags"]
    assert flags[flags.index("--denoise-steps") + 1] == str(config["denoise_steps"]) == "2"
    assert flags[flags.index("--decode-burst") + 1] == "3"
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert set(NEW_METRICS) <= set(listed)
    for name in NEW_METRICS:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "tpot_ms.p90"
    scopes = {n.split(".")[1][:-4] for n in listed if n.startswith("scope.")}
    assert scopes == {"embed", "attn_proj", "kv_write", "attn_kernel", "mlp", "head", "sample",
                      "layer_carry", "unscoped", "moe_router", "moe_experts"}
    assert {"moe.experts_touched_pct", "moe.load_max_over_mean", "moe.pairs_per_dispatch",
            "moe.experts_roofline_pct", "kernel.paged_attention.busy_pct",
            "device.hbm_peak_pct.chat", "device.idle_pct.chat", "step.decode_ms"} <= set(listed)
    # the accepted kernel share counts one query a row a step: not what a round runs
    assert "kernel.paged_attention.roofline_pct" not in listed
    for name in ("ttft_ms.tail10", "tpot_ms.p90"):
        assert CELL in next(m for m in bench["end_to_end"] if m["name"] == name)["workloads"]
    assert traffic["kind"] == "open_loop_lognormal"
    assert (traffic["prompt_tokens"]["median"], traffic["output_tokens"]["median"]) == (256, 256)
    assert (traffic["prompt_tokens"]["sigma"], traffic["output_tokens"]["sigma"]) == (0.9, 0.6)
    assert (traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]) == (16, 2048)
    assert (traffic["output_tokens"]["min"], traffic["output_tokens"]["max"]) == (16, 512)
    assert (traffic["block"], traffic["shuffle_group"], traffic["jitter_s"]) == (10, 1, 0.02)
    assert len(cell["why"]) <= 200


def _git(*args) -> str:
    done = subprocess.run(["git", *args], cwd=common.ROOT, capture_output=True, text=True)
    if done.returncode:
        pytest.skip(f"no git history to compare with here: {done.stderr.strip()[:200]}")
    return done.stdout


def test_nothing_that_was_there_is_edited():
    """Against the parent commit: under ``perfbench/`` only new files, and in
    ``BENCHMARK.json`` only new entries at the end of their lists and the
    cell's name at the end of ``workloads`` lists."""
    changed = _git("diff", "--name-status", PARENT, "--", "perfbench").split("\n")
    changed = [line.split("\t") for line in changed if line]
    assert all(status == "A" for status, _ in changed), changed
    assert {path for _, path in changed} <= NEW_FILES
    import json

    old, new = json.loads(_git("show", f"{PARENT}:BENCHMARK.json")), common.benchmark()
    assert {k: v for k, v in new.items() if not isinstance(v, list) or k in ("command", "paths")} \
        == {k: v for k, v in old.items() if not isinstance(v, list) or k in ("command", "paths")}
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        was, now = old[kind], new[kind][: len(old[kind])]
        for a, b in zip(was, now):
            if a != b:  # the same entry with the cell appended to its list
                assert {**b, "workloads": b["workloads"][:-1]} == a and b["workloads"][-1] == CELL
    assert len(new["configs"]) == len(old["configs"]) + 1
    assert len(new["workloads"]) == len(old["workloads"]) + 1
    assert [m["name"] for m in new["per_layer"][len(old["per_layer"]):]] == list(NEW_METRICS)
    assert new["end_to_end"][len(old["end_to_end"]):] == []


def test_the_rehearsal_of_the_cell_runs_the_whole_command():
    out = rehearse.main([CELL, "--seconds", "2", "--seed", "3800000041"])
    assert out["correct"] and out["failed"] == 0 and out["counts"]["compiles_in_window"] == 0
    assert set(out["end_to_end_present"]) == {"ttft_ms.tail10", "tpot_ms.p90", "setup_s"}
    assert out["check"]["numbers"]["logprob_err_mean"] < out["check"]["limits"]["logprob_err_mean"]
    assert {"moe.experts_touched_pct", "moe.load_max_over_mean", "moe.pairs_per_dispatch",
            "block.tokens_per_forward", "block.waste_pct"} <= set(out["per_layer_readable"])


def test_under_a_causal_reference_the_cell_is_not_correct():
    """The same program held against the conditioning of a causal model (a
    token a pass of a block of one): what it serves is not what that
    reference expects."""
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, CELL)
    config, traffic = common.apply_rehearsal({**config, "block_length": 1, "denoise_steps": 1}, traffic)
    # the program keeps its blocks of four: only the reference is told otherwise
    ctx = common.Ctx(cell=cell, config=config, traffic=traffic, seed=3800000041, seconds=2.0,
                     trace=False, rehearse=True)
    from perfbench.drivers import serve_engine

    original = common.build_model
    try:
        common.build_model = lambda cfg, **kw: original({**cfg, "block_length": 4}, **kw)
        out = serve_engine.run(ctx)
    finally:
        common.build_model = original
    assert not out["correct"] and out["check"]["numbers"]["logprob_err_mean"] > 0.01


def test_the_reference_draws_the_programs_leaves():
    cfg = common.read_json(CONFIG)
    cfg = {**cfg, **cfg["rehearsal"]}
    flat = weights.flat_names(common.build_model(cfg).params)
    assert {k: tuple(a.shape) for k, a in flat.items()} == reference.leaf_shapes(cfg)
    assert all(k in flat for k in cfg["weight_scales"])


# -- the reference's conditioning against a forward a token ---------------------

TINY = {"hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "num_experts": 6, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
        "num_hidden_layers": 2, "vocab_size": 64, "mask_token_id": 63, "block_length": 4,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "norm_topk_prob": True}


def _brute_force_logits(cfg, seed, seq, p):
    """One plain forward over ``seq`` (its last block noised by the caller),
    a token at a time and a key at a time in numpy float64: position ``q``
    attends ``j < (q // B + 1) * B``. Returns the logits at ``p``."""
    shapes = reference.leaf_shapes(cfg)
    key = weights.root_key(seed)

    def get(name, layer=None):
        return np.asarray(weights.leaf(key, name, shapes[name], jnp.float32, layer=layer), np.float64)

    def norm(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + cfg["rms_norm_eps"]) * w

    def rope(x, pos):  # [heads, hd] at one position
        hd = x.shape[-1]
        ang = pos / cfg["rope_theta"] ** (np.arange(0, hd, 2) / hd)
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x2 * np.cos(ang) + x1 * np.sin(ang)], -1)

    b, nh, nkv, hd = cfg["block_length"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    x = get("embed_tokens")[seq]
    n = len(seq)
    for l in range(cfg["num_hidden_layers"]):
        w = {name: get(f"layers.{name}", l) for name in reference.LAYER_LEAVES}
        y = norm(x, w["attn_norm"])
        q = np.stack([rope(norm((y[t] @ w["wq"]).reshape(nh, hd), w["q_norm"]), t) for t in range(n)])
        k = np.stack([rope(norm((y[t] @ w["wk"]).reshape(nkv, hd), w["k_norm"]), t) for t in range(n)])
        v = (y @ w["wv"]).reshape(n, nkv, hd)
        a = np.zeros((n, nh, hd))
        for t in range(n):
            end = min((t // b + 1) * b, n)
            for h in range(nh):
                sc = k[:end, h // (nh // nkv)] @ q[t, h] / np.sqrt(hd)
                pr = np.exp(sc - sc.max())
                a[t, h] = (pr / pr.sum()) @ v[:end, h // (nh // nkv)]
        x = x + a.reshape(n, -1) @ w["wo"]
        y = norm(x, w["ffn_norm"])
        for t in range(n):
            logits = y[t] @ w["gate"]
            s = np.exp(logits - logits.max())
            s /= s.sum()
            chosen = np.argsort(-s)[: cfg["num_experts_per_tok"]]
            for e in chosen:
                g, u = np.split(y[t] @ w["w_in"][e], 2)
                x[t] = x[t] + s[e] / (s[chosen].sum() + 1e-6) * ((g / (1 + np.exp(-g)) * u) @ w["w_out"][e])
    return norm(x[p], get("norm")) @ get("lm_head")


@pytest.mark.parametrize("denoise_steps", [1, 2, 4])
@pytest.mark.parametrize("prompt_len", [8, 9, 11])
def test_the_references_conditioning_against_a_forward_a_token(denoise_steps, prompt_len):
    """For every served position ``p``: clean before ``p``'s sub-block (and
    the prompt, which here ends on a block's edge, one and three positions
    into a block), the mask token from there to the block's end, nothing
    behind the block; one brute-force forward each."""
    cfg = {**TINY, "denoise_steps": denoise_steps}
    rng = np.random.default_rng(prompt_len)
    n_new = 7
    ids = rng.integers(0, 63, size=prompt_len + n_new).astype(np.int32)
    valid = len(ids) - 1
    padded = np.zeros((32,), np.int32)
    padded[:valid] = ids[:-1]
    rows = np.arange(prompt_len - 1, valid)
    got = np.asarray(reference.logits_at(cfg, 5, padded, valid, rows, "float32"))
    b, sub = cfg["block_length"], cfg["block_length"] // denoise_steps
    for row, p in enumerate(rows + 1):
        first = p // b * b
        clean_until = max(first + (p - first) // sub * sub, prompt_len)
        seq = np.full((first + b,), cfg["mask_token_id"], np.int64)
        seq[:clean_until] = ids[:clean_until]
        np.testing.assert_allclose(got[row], _brute_force_logits(cfg, 5, seq, p), rtol=0, atol=2e-5)


def test_the_references_noisy_copies_by_hand():
    cfg = {**TINY, "denoise_steps": 2}
    ids = jnp.arange(100, 132, dtype=jnp.int32)
    # a prompt of 9: the first served token is at 9, one position into its block
    toks, first, place = reference.noisy_copies(cfg, ids, jnp.asarray([8, 9, 10, 11, 12]), 9)
    m = cfg["mask_token_id"]
    assert np.asarray(first).tolist() == [8, 8, 8, 12, 12]
    assert np.asarray(place).tolist() == [1, 2, 3, 0, 1]
    assert np.asarray(toks).tolist() == [
        [108, m, m, m],       # p = 9: the prompt's tail clean, its own sub-block masked
        [108, 109, m, m],     # p = 10: the first sub-block clean
        [108, 109, m, m],     # p = 11: the same pass as p = 10
        [m, m, m, m],         # p = 12 opens a block
        [m, m, m, m],         # p = 13: the same pass
    ]


# -- the new readers ---------------------------------------------------------


def _stats(rounds, live=10, emitted_share=0.9, t=2, b=4):
    forwards = rounds * (t + 1)
    committed = rounds * live * b
    return {"block_rounds_total": rounds, "block_forwards_total": forwards,
            "block_slot_forwards_total": forwards * live,
            "block_positions_committed_total": committed,
            "block_tokens_emitted_total": int(committed * emitted_share)}


def test_the_block_readers_on_a_fixture():
    lc = {"stats0": _stats(30), "stats1": _stats(130)}
    # 100 rounds of 10 lanes: 3000 lane-forwards, 4000 positions, 3600 emitted
    assert block_reader.read("block.tokens_per_forward", lc) == pytest.approx(3600 / 3000)
    assert block_reader.read("block.waste_pct", lc) == pytest.approx(10.0)
    assert block_reader.read("block.tokens_per_forward", lc) < 4 / 3
    for stats in ({}, {"iterations": 5}):  # the parent, or a model that decodes a token a step
        for name in ("block.tokens_per_forward", "block.waste_pct"):
            assert block_reader.read(name, {"stats0": stats, "stats1": stats}) is None
    quiet = {"stats0": _stats(30), "stats1": _stats(30)}
    assert block_reader.read("block.tokens_per_forward", quiet) is None
    assert block_reader.read("block.waste_pct", quiet) is None


def _traced(stacks):
    dev = {"busy_ns": 2e9, "ops": [], "self_by_name": {"paged_attention": 0.25e9},
           "self_by_stack": [(stack, "fusion", ns) for stack, ns in stacks]}
    return {"devices": {"/device:TPU:0": dev}}


def test_the_round_readers_on_a_fixture():
    lc = {"trace": _traced([
        ("jit(decode_block_rounds)/while/body/denoise_pass/layers/moe_experts/gmm", 0.9e9),
        ("jit(decode_block_rounds)/while/body/denoise_pass/head/dot_general", 0.1e9),
        ("jit(decode_block_rounds)/while/body/commit_pass/layers/attn_kernel/paged_attention", 0.5e9),
        ("jit(decode_block_rounds)/while/body/sample/argmax", 0.2e9),
        ("jit(prefill)/layers/moe_experts/gmm", 0.3e9),
    ]), "scope_tables": [{"x": ("", "")}]}
    assert round_reader.read("round.denoise_pct.chat", lc) == pytest.approx(50.0)
    assert round_reader.read("round.commit_pct.chat", lc) == pytest.approx(25.0)
    assert round_reader.pass_of("jit(decode)/while/body/layers/mlp/dot") == ""
    # a program with no such scope (the parent; a one-token decode step): nothing
    causal = {"trace": _traced([("jit(decode)/while/body/layers/mlp/dot", 1e9)]),
              "scope_tables": [{"x": ("", "")}]}
    assert round_reader.read("round.denoise_pct.chat", causal) is None
    assert round_reader.read("round.commit_pct.chat", {**lc, "trace": None}) is None
    assert round_reader.read("round.commit_pct.chat", {**lc, "scope_tables": []}) is None


def test_the_block_roofline_count_against_a_hand_count_for_one_round():
    """One dispatch of one round (``decode_burst`` 1) over two rows whose
    known tokens end at 37 and 128, ``B = 4``, ``T = 2``, 7 layers of 4 KV
    heads of 128 in bfloat16: the blocks stand at [36, 40) and [128, 132);
    each of the 3 forwards calls the kernel once a layer with 4 queries a
    row against 40 and 132 positions, keys and values read once a call."""
    cfg = common.read_json(CONFIG)
    contexts, queries = roofline_reader.round_calls(cfg, [37, 128], 0)
    assert (contexts, queries) == ([40, 132], [4, 4])
    assert roofline_reader.round_calls(cfg, [37, 128], 2)[0] == [48, 140]
    call_bytes = sum(2 * c * 4 * 128 * 2 + 2 * 4 * 32 * 128 * 2 for c in (40, 132))
    call_flops = sum(2 * 2 * 4 * c * 128 * 32 for c in (40, 132))
    cost = roofline_reader.dispatch_cost(cfg, [37, 128], 1)
    assert cost == {"flops": 3.0 * call_flops, "bytes": 3.0 * call_bytes}
    peak = counts.peaks("TPU v5 lite")
    assert counts.roofline({"flops": call_flops, "bytes": call_bytes}, peak)["bound"] == "memory"
    rec = SimpleNamespace(iter_t=[9.5, 10.5, 20.0], decode_contexts=[[5], [37, 128], [64]],
                          prefill_chunks=[[], [], []])
    lc = {"recorder": rec, "config": cfg, "trace_span": (10.0, 12.0), "decode_burst": 1,
          "kv_itemsize": 2, "device_kind": "TPU v5 lite", "trace": _traced([]),
          "stats1": _stats(5)}
    least = 7 * 3 * call_bytes / peak["hbm_bytes_per_s"]
    assert roofline_reader.least_s(lc) == pytest.approx(least)
    share = roofline_reader.read("kernel.paged_attention.block_roofline_pct", lc)
    assert share == pytest.approx(100 * least / 0.25) and 0 < share < 100
    # a chunk of 256 from 512 beside it: 256 queries against 768 positions
    rec.prefill_chunks[1] = [(512, 258)]
    chunk = roofline_reader.chunk_cost(cfg, 512, 258)
    assert chunk["bytes"] == 2 * 768 * 4 * 128 * 2 + 2 * 256 * 32 * 128 * 2
    assert chunk["flops"] == pytest.approx(2 * 2 * 256 * 768 * 128 * 32 * (512 + 130) / 768)
    assert roofline_reader.chunk_cost(cfg, 0, 3) is None
    assert roofline_reader.least_s(lc) > least
    # no trace, or a program that runs no block round: nothing, and no error
    assert roofline_reader.read("kernel.paged_attention.block_roofline_pct",
                                {**lc, "trace": None}) is None
    assert roofline_reader.read("kernel.paged_attention.block_roofline_pct",
                                {**lc, "stats1": {"iterations": 3}}) is None
