"""The static sharding-plan analyzer (``accelerate_tpu/analysis/shardplan.py``)
and its runtime seams.

The acceptance bar: on ``LlamaConfig.flagship_700m()`` over a virtual
``(dp=1, fsdp=2, tp=2)`` mesh, predicted per-device param+optimizer bytes
match the LIVE sharded ``jax.Array`` footprint exactly (leaf by leaf —
arrays are materialized one at a time so the test never holds the whole
~8 GiB model), the clean plan exits 0 through the real CLI, and each
seeded misconfiguration exits 2 naming its SP rule ID.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MESH_SIZES = {"dp": 1, "pp": 1, "fsdp": 2, "ep": 1, "cp": 1, "tp": 2}


def _flagship_abstract(dtype="float32"):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.llama import (
        LLAMA_PARTITION_RULES,
        LlamaConfig,
        init_llama_params,
    )

    config = LlamaConfig.flagship_700m()
    params = jax.eval_shape(
        lambda key: init_llama_params(key, config, dtype=jnp.dtype(dtype)),
        jax.random.PRNGKey(0),
    )
    return params, config, list(LLAMA_PARTITION_RULES)


def _mesh4():
    import jax

    from accelerate_tpu.mesh import build_mesh
    from accelerate_tpu.utils.dataclasses import MeshPlugin

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs a >= 4-device (virtual) mesh")
    return build_mesh(MeshPlugin(dp=1, fsdp=2, tp=2), devices=devices[:4])


# ---------------------------------------------------------------------------
# the analyzer proper (virtual mesh: no devices touched)
# ---------------------------------------------------------------------------


class TestAnalyzer:
    def test_clean_flagship_plan_has_no_findings(self):
        from accelerate_tpu.analysis.shardplan import analyze_plan

        params, config, rules = _flagship_abstract()
        report = analyze_plan(params, MESH_SIZES, rules=rules, optimizer="adam")
        assert report.findings == [], [f.to_dict() for f in report.findings]
        tiers = report.tiers
        assert set(tiers) == {"params", "opt_state"}
        # the sharded tiers really shrink per device (norms replicate, so
        # strictly between global/4 and global)
        for tier in tiers.values():
            assert tier["bytes_global"] / 4 < tier["bytes_per_device"] < tier["bytes_global"]
        # adam: mu + nu mirror the params byte-for-byte, count is noise
        assert tiers["opt_state"]["bytes_global"] >= 2 * tiers["params"]["bytes_global"]

    def test_dead_rule_sp001(self):
        from accelerate_tpu.analysis.shardplan import analyze_plan

        params, config, rules = _flagship_abstract()
        from jax.sharding import PartitionSpec as P

        report = analyze_plan(
            params, MESH_SIZES, rules=[("no_such_param", P("tp"))] + rules,
            optimizer="none",
        )
        assert [f.rule for f in report.findings] == ["SP001"]
        assert "no_such_param" in report.findings[0].subject

    def test_forced_replicated_sp002(self):
        from accelerate_tpu.analysis.shardplan import analyze_plan

        params, config, rules = _flagship_abstract()
        from jax.sharding import PartitionSpec as P

        report = analyze_plan(
            params, MESH_SIZES, rules=[("embed_tokens", P())] + rules,
            optimizer="none",
        )
        rules_fired = {f.rule for f in report.findings}
        # the shadowed original embed rule is now dead too — both findings
        # describe the same seeded bug
        assert rules_fired == {"SP001", "SP002"}
        sp002 = [f for f in report.findings if f.rule == "SP002"]
        assert sp002[0].subject == "embed_tokens"

    def test_non_divisible_axis_sp003(self):
        from accelerate_tpu.analysis.shardplan import analyze_plan

        params, config, rules = _flagship_abstract()
        from jax.sharding import PartitionSpec as P

        sizes = dict(MESH_SIZES, tp=7, fsdp=1)  # 1536 % 7 != 0
        report = analyze_plan(
            params, sizes, rules=[("embed_tokens", P(None, "tp"))] + rules,
            optimizer="none",
        )
        sp003 = [f for f in report.findings if f.rule == "SP003"]
        assert sp003 and sp003[0].subject == "embed_tokens"
        assert sp003[0].detail["extent"] == 7

    def test_unknown_axis_is_sp003_with_extent_zero(self):
        from accelerate_tpu.analysis.shardplan import analyze_plan

        params, config, rules = _flagship_abstract()
        from jax.sharding import PartitionSpec as P

        report = analyze_plan(
            params, MESH_SIZES, rules=[("embed_tokens", P("model"))] + rules,
            optimizer="none",
        )
        sp003 = [f for f in report.findings if f.rule == "SP003"]
        assert sp003 and sp003[0].detail["extent"] == 0

    def test_over_budget_sp004_breakdown(self):
        from accelerate_tpu.analysis.shardplan import analyze_plan

        params, config, rules = _flagship_abstract()
        report = analyze_plan(
            params, MESH_SIZES, rules=rules, optimizer="adam", hbm_gb=0.5,
        )
        sp004 = [f for f in report.findings if f.rule == "SP004"]
        assert len(sp004) == 1
        assert sp004[0].severity == "error"
        tiers = sp004[0].detail["tiers"]
        assert tiers["opt_state"] > tiers["params"] > 0
        assert sp004[0].detail["bytes_per_device"] == report.bytes_per_device

    def test_kv_pool_tier_tp_sharding(self):
        from accelerate_tpu.analysis.shardplan import plan_kv_pool

        # 12 kv heads over tp=2: sharded; over tp=5: replicated fallback
        sharded = plan_kv_pool(16, 12, 128, 8, 16, 512, dict(MESH_SIZES))
        assert all(l.bytes_per_device * 2 == l.bytes_global for l in sharded)
        repl = plan_kv_pool(16, 12, 128, 8, 16, 512, dict(MESH_SIZES, tp=5))
        assert all(l.bytes_per_device == l.bytes_global for l in repl)
        # default pool = full residency: slots * ceil(seq/block) + null
        assert sharded[0].shape[1] == 8 * 32 + 1
        # stored lane-folded, as the engine allocates it; tp on the lanes
        assert sharded[0].shape == (16, 8 * 32 + 1, 16, 12 * 128)
        assert sharded[0].spec == "PartitionSpec(None, None, None, 'tp')"

    def test_mesh_spec_parsing(self):
        from accelerate_tpu.analysis.shardplan import parse_mesh_spec

        assert parse_mesh_spec("1,2,2")["fsdp"] == 2
        assert parse_mesh_spec("1,2,2")["tp"] == 2
        named = parse_mesh_spec("dp=2, tp=4, cp=2")
        assert (named["dp"], named["tp"], named["cp"]) == (2, 4, 2)
        with pytest.raises(ValueError):
            parse_mesh_spec("bogus=2")
        with pytest.raises(ValueError):
            parse_mesh_spec("1,2,3,4")


# ---------------------------------------------------------------------------
# the acceptance bar: predicted == live jax.Array footprint, exactly
# ---------------------------------------------------------------------------


class TestLiveParity:
    def test_flagship_predicted_matches_live_footprint_exactly(self):
        """Every param+opt leaf of the sharded flagship plan, placed for
        real on the 4-device virtual CPU mesh one leaf at a time: the
        bytes each device holds must equal the prediction EXACTLY."""
        import jax
        from jax.sharding import NamedSharding

        from accelerate_tpu.analysis.shardplan import (
            analyze_plan,
            mesh_sizes_of,
        )
        from accelerate_tpu.parallel.sharding import explain_partition_spec
        from accelerate_tpu.utils.dataclasses import FullyShardedDataParallelPlugin

        mesh = _mesh4()
        params, config, rules = _flagship_abstract()
        report = analyze_plan(
            params, mesh_sizes_of(mesh), rules=rules, optimizer="adam"
        )
        assert report.findings == []

        plugin = FullyShardedDataParallelPlugin()
        devices = list(mesh.devices.flat)
        checked = 0
        for leaf in report.leaves:
            assert leaf.tier in ("params", "opt_state")
            # the analyzer's spec string round-trips through the REAL
            # placement decision for params; opt leaves inherit it
            if leaf.tier == "params":
                decision = explain_partition_spec(
                    leaf.path, leaf.shape, mesh, plugin, rules
                )
                assert str(decision.spec) == leaf.spec, leaf.path
                sharding = NamedSharding(mesh, decision.spec)
            else:
                # reconstruct the opt leaf's sharding from the param twin
                twin = next(
                    (
                        p
                        for p in report.leaves
                        if p.tier == "params" and p.shape == leaf.shape
                        and p.spec == leaf.spec
                    ),
                    None,
                )
                if twin is None:  # replicated scalar (adam count)
                    from jax.sharding import PartitionSpec

                    sharding = NamedSharding(mesh, PartitionSpec())
                else:
                    sharding = NamedSharding(
                        mesh,
                        explain_partition_spec(
                            twin.path, twin.shape, mesh, plugin, rules
                        ).spec,
                    )
            arr = jax.device_put(np.zeros(leaf.shape, leaf.dtype), sharding)
            for dev in devices:
                live = sum(
                    int(s.data.nbytes)
                    for s in arr.addressable_shards
                    if s.device == dev
                )
                assert live == leaf.bytes_per_device, (
                    f"{leaf.tier}/{leaf.path} on {dev}: "
                    f"live {live} != predicted {leaf.bytes_per_device}"
                )
            del arr
            checked += 1
        assert checked == len(report.leaves) > 20

    def test_kv_pool_prediction_matches_live_engine_pool(self, tiny_paged_model):
        """The kv-pool tier's per-device bytes equal the real sharded
        engine pool's shard bytes (the PR 7 sharded engine as ground
        truth)."""
        from accelerate_tpu.analysis.shardplan import mesh_sizes_of, plan_kv_pool
        from accelerate_tpu.serving import EngineConfig, InferenceEngine

        mesh = _mesh4()
        cfg = tiny_paged_model.config
        geometry = dict(num_slots=2, block_size=8, max_seq_len=64)
        engine = InferenceEngine(
            tiny_paged_model, EngineConfig(**geometry), mesh=mesh
        )
        plan = plan_kv_pool(
            num_layers=cfg.num_hidden_layers,
            num_kv_heads=cfg.num_key_value_heads,
            head_dim=cfg.head_dim,
            mesh_sizes=mesh_sizes_of(mesh),
            dtype=str(engine._kp.dtype),
            **geometry,
        )
        k_plan = next(p for p in plan if p.path.endswith(".k"))
        dev0 = engine._kp.addressable_shards[0].device
        live = sum(
            int(s.data.nbytes)
            for s in engine._kp.addressable_shards
            if s.device == dev0
        )
        assert live == k_plan.bytes_per_device
        assert tuple(engine._kp.shape) == k_plan.shape


# ---------------------------------------------------------------------------
# SP005: resharding report from HLO text
# ---------------------------------------------------------------------------


HLO_FIXTURE = """
  %ag = f32[8,4096,4096] all-gather(f32[8,2048,4096] %p0), dimensions={1}
  %aa = f32[1024,1024] all-to-all(f32[1024,1024] %p1), dimensions={0}
  %ar = f32[4096] all-reduce(f32[4096] %p2), replica_groups={}
  %small = f32[16] all-gather(f32[8] %p3), dimensions={0}
  %ags = (f32[8,65536], f32[8,131072]) all-gather-start(f32[8,65536] %p4), dimensions={1}
"""


class TestReshardingReport:
    def test_ranks_top_offenders_and_skips_small(self):
        from accelerate_tpu.analysis.shardplan import resharding_report

        entries = resharding_report(HLO_FIXTURE, min_bytes=1 << 20)
        ops = [e["op"] for e in entries]
        # biggest first; the all-reduce (not a reshard) and the tiny
        # all-gather are absent; the async -start counts its result only
        assert ops[0] == "all-gather"
        assert entries[0]["bytes"] == 8 * 4096 * 4096 * 4
        assert "all-reduce" not in ops
        assert all(e["bytes"] >= 1 << 20 for e in entries)
        assert "all-gather-start" in ops
        start = next(e for e in entries if e["op"] == "all-gather-start")
        assert start["bytes"] == 8 * 131072 * 4

    def test_findings_are_sp005_warnings(self):
        from accelerate_tpu.analysis.shardplan import resharding_findings

        findings = resharding_findings(HLO_FIXTURE, label="step")
        assert findings and all(f.rule == "SP005" for f in findings)
        assert all(f.severity == "warning" for f in findings)
        assert "MB/step" in findings[0].message


# ---------------------------------------------------------------------------
# SP006: manifest piece table vs the plan
# ---------------------------------------------------------------------------


class TestManifestDiff:
    def _plans(self):
        from accelerate_tpu.analysis.shardplan import plan_params

        params, config, rules = _flagship_abstract()
        return plan_params(params, MESH_SIZES, rules=rules)

    def test_sharded_vs_replicated_mismatch_flagged(self):
        from accelerate_tpu.analysis.shardplan import manifest_findings

        manifest = {
            "arrays": {
                "model_0": {
                    # saved replicated, plan shards it -> SP006
                    "embed_tokens": {"spec": "PartitionSpec()"},
                    # saved sharded, plan shards it -> clean
                    "layers.wq": {"spec": "PartitionSpec(None, 'fsdp', 'tp')"},
                    # unrecorded spec -> skipped
                    "norm": {"spec": None},
                    # unknown key -> skipped
                    "not_a_param": {"spec": "PartitionSpec('fsdp',)"},
                }
            }
        }
        findings = manifest_findings(manifest, self._plans())
        assert [f.rule for f in findings] == ["SP006"]
        assert "embed_tokens" in findings[0].subject

    def test_matching_manifest_clean(self):
        from accelerate_tpu.analysis.shardplan import manifest_findings

        manifest = {
            "arrays": {
                "model_0": {
                    "layers.wq": {"spec": "PartitionSpec(None, 'fsdp', 'tp')"},
                    "norm": {"spec": "PartitionSpec()"},
                }
            }
        }
        assert manifest_findings(manifest, self._plans()) == []


# ---------------------------------------------------------------------------
# runtime seams
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_paged_model():
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig.tiny(vocab_size=64, hidden_size=32, layers=2, heads=4, seq=96)
    return LlamaForCausalLM.from_config(config, seed=0)


class TestEnginePreflight:
    def test_engine_refuses_over_budget(self, tiny_paged_model):
        from accelerate_tpu.serving import EngineConfig, InferenceEngine

        with pytest.raises(ValueError, match="SP004"):
            InferenceEngine(
                tiny_paged_model,
                EngineConfig(num_slots=2, block_size=8, max_seq_len=64,
                             hbm_budget_gb=1e-6),
            )

    def test_engine_starts_under_budget_and_reports(self, tiny_paged_model):
        from accelerate_tpu.serving import EngineConfig, InferenceEngine

        engine = InferenceEngine(
            tiny_paged_model,
            EngineConfig(num_slots=2, block_size=8, max_seq_len=64,
                         hbm_budget_gb=1.0),
        )
        report = engine.hbm_preflight
        assert report is not None and not report["over"]
        assert report["headroom_bytes"] > 0
        assert report["total_bytes"] == report["params_bytes"] + report["pool_bytes"]
        assert engine.stats()["hbm_preflight"]["over"] is False

    def test_swap_pool_host_bytes_reported_not_budgeted(self, tiny_paged_model):
        """With swap_gb set, the preflight reports the host-DRAM swap tier
        alongside the HBM tiers but never counts it against the budget —
        swapped blocks live on the host (the tier's whole point)."""
        from accelerate_tpu.serving import EngineConfig, InferenceEngine

        engine = InferenceEngine(
            tiny_paged_model,
            EngineConfig(num_slots=2, block_size=8, max_seq_len=64,
                         hbm_budget_gb=1.0, swap_gb=0.25),
        )
        report = engine.hbm_preflight
        assert report["swap_pool_host_bytes"] > 0
        assert report["total_bytes"] == report["params_bytes"] + report["pool_bytes"]

    def test_plan_swap_pool_and_analyze_plan_host_tier(self):
        import jax.numpy as jnp

        from accelerate_tpu.analysis.shardplan import analyze_plan, plan_swap_pool

        swap = plan_swap_pool(num_layers=2, num_kv_heads=4, head_dim=16,
                              block_size=8, swap_gb=0.5, dtype="float32")
        per_block = 2 * 4 * 2 * 8 * 4 * 16
        assert swap["bytes_per_block"] == per_block
        assert swap["swap_blocks"] == int(0.5 * (1 << 30)) // per_block
        assert swap["swap_pool_host_bytes"] == swap["swap_blocks"] * per_block

        params = {"w": jnp.zeros((8, 8))}
        kv_pool = dict(num_layers=2, num_kv_heads=4, head_dim=16, num_slots=2,
                       block_size=8, max_seq_len=64, dtype="float32")
        report = analyze_plan(
            params, {"dp": 1}, optimizer="none", kv_pool=kv_pool, swap_gb=0.5
        )
        assert report.host["swap_pool_host_bytes"] == swap["swap_pool_host_bytes"]
        assert report.to_dict()["host"] == report.host
        # host bytes never leak into the per-device HBM sum
        assert report.bytes_per_device == sum(
            l.bytes_per_device for l in report.leaves
        )
        no_swap = analyze_plan(params, {"dp": 1}, optimizer="none", kv_pool=kv_pool)
        assert no_swap.host is None

    def test_auto_num_blocks_math(self):
        from accelerate_tpu.analysis.shardplan import auto_num_blocks

        # 100 MB budget, 40 MB params, 1 MB/block, 5% reserve -> 55 fit
        n, headroom = auto_num_blocks(
            100 << 20, 40 << 20, 1 << 20, full_residency_blocks=1000, min_blocks=4
        )
        assert n == 55
        assert headroom == (100 << 20) - (40 << 20) - n * (1 << 20)
        # full residency caps it
        n2, _ = auto_num_blocks(
            100 << 20, 40 << 20, 1 << 20, full_residency_blocks=10, min_blocks=4
        )
        assert n2 == 10
        with pytest.raises(ValueError, match="SP004"):
            auto_num_blocks(
                42 << 20, 40 << 20, 1 << 20, full_residency_blocks=10, min_blocks=4
            )

    def test_arg_bytes_report_replicated_and_sharded(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from accelerate_tpu.analysis.shardplan import arg_bytes_report

        mesh = _mesh4()
        x = jax.device_put(jnp.zeros((64, 64), jnp.float32), NamedSharding(mesh, P("fsdp", "tp")))
        r = jax.device_put(jnp.zeros((16,), jnp.float32), NamedSharding(mesh, P()))
        host = np.zeros((8,), np.float32)
        predicted, actual = arg_bytes_report(((x, r), host))
        expect = (64 * 64 * 4) // 4 + 16 * 4 + 8 * 4
        assert predicted == expect
        assert actual == expect


class TestCompileFactBytes:
    def test_sanitized_compile_records_carry_predicted_vs_actual(self, tmp_path):
        """The AOT path stamps arg_bytes_predicted/actual onto compile
        facts when the sanitizer is armed; on a single-device replicated
        toy the two models must agree exactly."""
        import io

        import optax

        from accelerate_tpu import Accelerator
        from accelerate_tpu.test_utils import RegressionModel

        acc = Accelerator(project_dir=str(tmp_path), telemetry=True, sanitize=True)
        acc.sanitizer._stream = io.StringIO()
        model, opt = acc.prepare(RegressionModel(a=0.0, b=0.0), optax.sgd(0.1))
        try:
            x = np.linspace(-1, 1, 16).astype(np.float32)
            out = model(x=x, y=(2 * x + 3).astype(np.float32))
            acc.backward(out.loss)
            opt.step()
            opt.zero_grad()
            compiles = [
                json.loads(line)
                for line in open(acc.telemetry.jsonl_path)
                if '"compile"' in line
            ]
            compiles = [r for r in compiles if r.get("type") == "compile"]
            assert compiles
            stamped = [r for r in compiles if "arg_bytes_predicted" in r]
            assert stamped, compiles
            for r in stamped:
                assert r["arg_bytes_predicted"] == r["arg_bytes_actual"] > 0
        finally:
            acc.end_training()


class TestValidatedWarnsOnce:
    def test_one_shot_warning_names_path_and_axis(self, caplog):
        import logging

        import jax

        from accelerate_tpu.parallel import sharding as sharding_mod
        from jax.sharding import PartitionSpec as P

        mesh = _mesh4()
        sharding_mod._DIVISIBILITY_WARNED.clear()
        params = {"w": np.zeros((10, 6), np.float32)}  # 10 % 4 != 0
        rules = [("w", P(("fsdp", "tp"), None))]
        with caplog.at_level(logging.WARNING, logger=sharding_mod.__name__):
            sharding_mod.infer_param_sharding(params, mesh, rules=rules)
            sharding_mod.infer_param_sharding(params, mesh, rules=rules)
        hits = [
            rec for rec in caplog.records
            if "SP003" in rec.getMessage() and "'w'" in rec.getMessage()
        ]
        assert len(hits) == 1  # once per (path, axis), not once per call
        assert "does not divide" in hits[0].getMessage()


# ---------------------------------------------------------------------------
# the CLI (real subprocess, same pattern as the lint CLI tests)
# ---------------------------------------------------------------------------


class TestShardCheckCLI:
    def _run(self, args):
        return subprocess.run(
            [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
             "shard-check", *args],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=240,
        )

    def test_clean_flagship_plan_exits_0(self):
        proc = self._run(["--preset", "flagship", "--virtual", "1,2,2", "--json"])
        assert proc.returncode == 0, proc.stderr[-2000:]
        payload = json.loads(proc.stdout)
        assert payload["findings"] == []
        assert set(payload["tiers"]) == {"params", "opt_state", "kv_pool"}
        assert payload["bytes_per_device"] == sum(
            t["bytes_per_device"] for t in payload["tiers"].values()
        )

    def test_dead_rule_exits_2_naming_sp001(self):
        proc = self._run(["--virtual", "1,2,2", "--json",
                          "--extra-rule", "no_such_param=tp"])
        assert proc.returncode == 2, proc.stderr[-2000:]
        payload = json.loads(proc.stdout)
        assert {f["rule"] for f in payload["findings"]} == {"SP001"}

    def test_forced_replicated_exits_2_naming_sp002(self):
        proc = self._run(["--virtual", "1,2,2", "--json", "--ignore", "SP001",
                          "--extra-rule", "embed_tokens="])
        assert proc.returncode == 2, proc.stderr[-2000:]
        payload = json.loads(proc.stdout)
        assert {f["rule"] for f in payload["findings"]} == {"SP002"}
        assert payload["findings"][0]["subject"] == "embed_tokens"

    def test_non_divisible_exits_2_naming_sp003(self):
        proc = self._run(["--virtual", "dp=1,fsdp=1,tp=7", "--json",
                          "--ignore", "SP001,SP002",
                          "--extra-rule", "embed_tokens=None,tp"])
        assert proc.returncode == 2, proc.stderr[-2000:]
        payload = json.loads(proc.stdout)
        assert {f["rule"] for f in payload["findings"]} == {"SP003"}

    def test_over_budget_exits_2_naming_sp004(self):
        proc = self._run(["--preset", "flagship", "--virtual", "1,2,2",
                          "--json", "--hbm-gb", "0.5"])
        assert proc.returncode == 2, proc.stderr[-2000:]
        payload = json.loads(proc.stdout)
        assert {f["rule"] for f in payload["findings"]} == {"SP004"}
        assert payload["findings"][0]["detail"]["tiers"]["opt_state"] > 0

    def test_bad_mesh_spec_exits_1(self):
        assert self._run(["--virtual", "bogus=1"]).returncode == 1

    def test_activation_estimate_failure_exits_1_not_silent(self):
        """--seq over max_position_embeddings: the logits tier cannot be
        priced — a usage error, NOT a silently understated exit-0 plan."""
        proc = self._run(["--preset", "flagship", "--virtual", "1,2,2",
                          "--batch", "8", "--seq", "4096"])
        assert proc.returncode == 1, (proc.returncode, proc.stdout[-500:])
        assert "activation estimate failed" in proc.stderr

    def test_list_rules(self):
        proc = self._run(["--list-rules"])
        assert proc.returncode == 0
        for rid in ("SP001", "SP002", "SP003", "SP004", "SP005", "SP006"):
            assert rid in proc.stdout


# ---------------------------------------------------------------------------
# quantized KV pool planning (kv_dtype policy)
# ---------------------------------------------------------------------------


class TestQuantizedKvPlan:
    def test_plan_kv_pool_int8_adds_scale_leaves(self):
        """int8/fp8 dtypes emit the two f32 amax scale leaves beside the
        payload, kv-head dim sharded over tp like the pools."""
        from accelerate_tpu.analysis.shardplan import plan_kv_pool

        kw = dict(num_layers=2, num_kv_heads=4, head_dim=8, num_slots=2,
                  block_size=8, max_seq_len=64, mesh_sizes=MESH_SIZES)
        plans = plan_kv_pool(dtype="int8", **kw)
        assert [p.path for p in plans] == [
            "kv_pool.k", "kv_pool.v", "kv_pool.k_scale", "kv_pool.v_scale"
        ]
        k = next(p for p in plans if p.path == "kv_pool.k")
        ks = next(p for p in plans if p.path == "kv_pool.k_scale")
        nb = 2 * 8 + 1
        assert k.bytes_global == 2 * nb * 8 * 4 * 8 * 1          # int8 payload
        assert ks.bytes_global == 2 * nb * 8 * 4 * 4             # f32 scales
        assert k.bytes_per_device == k.bytes_global // 2         # tp=2
        assert ks.bytes_per_device == ks.bytes_global // 2
        assert "'tp'" in ks.spec
        # fp8 spelling aliases float8_e4m3fn at the same byte cost
        fp8 = plan_kv_pool(dtype="fp8", **kw)
        assert [p.bytes_global for p in fp8] == [p.bytes_global for p in plans]
        assert fp8[0].dtype == "float8_e4m3fn"
        # float dtypes stay two scale-free leaves (the PR 8 behaviour)
        assert len(plan_kv_pool(dtype="bfloat16", **kw)) == 2

    def test_plan_swap_pool_quantized_matches_live_swap_pool(self):
        """plan_swap_pool's per-block bytes at int8 equal the live
        SwapPool's (payload + scale mirrors)."""
        from accelerate_tpu.analysis.shardplan import plan_swap_pool
        from accelerate_tpu.serving import SwapPool

        geom = dict(num_layers=2, num_kv_heads=4, head_dim=8, block_size=8)
        plan = plan_swap_pool(swap_gb=0.001, dtype="int8", **geom)
        live = SwapPool(dtype=np.int8, capacity_gb=0.001, quantized=True, **geom)
        assert plan["bytes_per_block"] == live.bytes_per_block
        assert plan["swap_blocks"] == live.capacity_blocks

    def test_int8_predicted_pool_bytes_match_live_engine_exactly(self, tiny_paged_model):
        """The acceptance invariant at kv_dtype="int8": predicted kv-pool
        tier bytes (payload + scales) == the live sharded engine's
        _kp/_vp/_ks/_vs shard bytes, per device, exactly."""
        from accelerate_tpu.analysis.shardplan import mesh_sizes_of, plan_kv_pool
        from accelerate_tpu.serving import EngineConfig, InferenceEngine

        mesh = _mesh4()
        cfg = tiny_paged_model.config
        geometry = dict(num_slots=2, block_size=8, max_seq_len=64)
        engine = InferenceEngine(
            tiny_paged_model, EngineConfig(kv_dtype="int8", **geometry), mesh=mesh
        )
        plans = plan_kv_pool(
            num_layers=cfg.num_hidden_layers,
            num_kv_heads=cfg.num_key_value_heads,
            head_dim=cfg.head_dim,
            mesh_sizes=mesh_sizes_of(mesh),
            dtype="int8",
            **geometry,
        )
        dev0 = engine._kp.addressable_shards[0].device
        live = sum(
            int(s.data.nbytes)
            for arr in (engine._kp, engine._vp, engine._ks, engine._vs)
            for s in arr.addressable_shards
            if s.device == dev0
        )
        assert live == sum(p.bytes_per_device for p in plans)

    def test_auto_blocks_capacity_ratio_int8_vs_bf16(self):
        """At equal HBM budget the int8 pool holds ~2x the blocks of the
        bf16 pool (2*hd / (hd+4) — 1.94x at the flagship's hd=128): the
        auto_num_blocks sizing this CLI flag and bench ratio both use."""
        from accelerate_tpu.analysis.shardplan import auto_num_blocks, plan_kv_pool

        sizes = {ax: 1 for ax in MESH_SIZES}
        per_block = {}
        for dtype in ("bfloat16", "int8"):
            per_block[dtype] = sum(
                p.bytes_per_device
                for p in plan_kv_pool(
                    num_layers=16, num_kv_heads=12, head_dim=128, num_slots=1,
                    block_size=16, max_seq_len=512, num_blocks=1,
                    mesh_sizes=sizes, dtype=dtype,
                )
            )
        budget, params = 8 << 30, 2 << 30
        blocks = {
            d: auto_num_blocks(budget, params, pb, full_residency_blocks=10**9,
                               min_blocks=2)[0]
            for d, pb in per_block.items()
        }
        ratio = blocks["int8"] / blocks["bfloat16"]
        assert ratio >= 1.8
        assert abs(ratio - 2 * 128 / (128 + 4)) < 0.01

    def test_shard_check_cli_kv_dtype_json(self):
        """--kv-dtype int8 flows through the real CLI: the JSON report's
        kv_pool tier carries the scale leaves."""
        proc = subprocess.run(
            [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
             "shard-check", "--preset", "tiny", "--virtual", "dp=1,fsdp=1,tp=1",
             "--kv-dtype", "int8", "--json", "--leaves"],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stderr
        report = json.loads(proc.stdout)
        paths = [l["path"] for l in report["leaves"] if l["tier"] == "kv_pool"]
        assert "kv_pool.k_scale" in paths and "kv_pool.v_scale" in paths
        assert next(
            l for l in report["leaves"] if l["path"] == "kv_pool.k"
        )["dtype"] == "int8"
