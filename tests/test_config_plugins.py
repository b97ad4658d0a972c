"""DeepSpeed config-file ingestion + questionnaire depth + test_utils
helpers (reference: ds-config `auto` handling ``accelerator.py:1651-1891``,
``cluster.py:54`` questionnaire, ``test_utils/testing.py``)."""

import json
from unittest import mock

import numpy as np
import optax
import pytest

from accelerate_tpu import Accelerator, DeepSpeedPlugin
from accelerate_tpu.commands.config import ClusterConfig, get_cluster_input
from accelerate_tpu.test_utils import (
    DEFAULT_LAUNCH_COMMAND,
    RegressionDataset,
    RegressionModel,
    get_backend,
    get_launch_command,
    require_cpu,
    require_tpu,
)


def _ds_config(tmp_path, **overrides):
    cfg = {
        "train_micro_batch_size_per_gpu": "auto",
        "train_batch_size": "auto",
        "gradient_accumulation_steps": 2,
        "gradient_clipping": 0.7,
        "zero_optimization": {
            "stage": 3,
            "offload_optimizer": {"device": "cpu"},
            "offload_param": {"device": "auto"},
        },
        "optimizer": {"type": "AdamW", "params": {"lr": "auto"}},
    }
    cfg.update(overrides)
    path = tmp_path / "ds_config.json"
    path.write_text(json.dumps(cfg))
    return str(path)


def test_ds_config_file_overrides_plugin_fields(tmp_path):
    plugin = DeepSpeedPlugin(hf_ds_config=_ds_config(tmp_path))
    assert plugin.zero_stage == 3
    assert plugin.gradient_accumulation_steps == 2
    assert plugin.gradient_clipping == 0.7
    assert plugin.offload_optimizer_device == "cpu"
    assert plugin.offload_param_device is None  # "auto" leaves the default
    assert plugin.to_fsdp_plugin().sharding_strategy == "FULL_SHARD"


def test_ds_config_auto_filled_at_prepare(tmp_path):
    plugin = DeepSpeedPlugin(hf_ds_config=_ds_config(tmp_path))
    accelerator = Accelerator(deepspeed_plugin=plugin)

    class _Loader:
        def __init__(self):
            self.dataset = RegressionDataset(length=64)
            self.batch_size = 16
            self.drop_last = False
            self.sampler = self.batch_sampler = self.collate_fn = None

    model = RegressionModel()
    tx = optax.inject_hyperparams(optax.sgd)(learning_rate=0.05)
    accelerator.prepare(model, tx, _Loader())
    cfg = plugin.deepspeed_config
    assert cfg["train_micro_batch_size_per_gpu"] != "auto"
    assert cfg["train_batch_size"] == 16 * plugin.gradient_accumulation_steps
    assert cfg["optimizer"]["params"]["lr"] == pytest.approx(0.05)


def test_questionnaire_deepspeed_branch():
    answers = iter([
        "jax_tpu",  # compute env
        "1",        # hosts
        "1",        # fsdp extent (1 → offer deepspeed)
        "yes",      # use deepspeed?
        "",         # no config file → questionnaire
        "3",        # zero stage
        "yes",      # offload optimizer
        "no",       # offload params
        "4",        # zero shard extent
        "2",        # tp
        "1",        # cp
        "1",        # ep
        "1",        # pp
        "bf16",     # precision
        "1",        # accumulation
        "no",       # debug
        "main",     # main fn
    ])
    with mock.patch("builtins.input", lambda prompt="": next(answers)):
        cfg = get_cluster_input()
    assert cfg.use_deepspeed
    assert cfg.deepspeed_config["zero_stage"] == 3
    assert cfg.deepspeed_config["offload_optimizer_device"] == "cpu"
    assert cfg.mesh_fsdp == 4 and cfg.use_fsdp
    assert cfg.mesh_tp == 2
    env = cfg.to_environment()
    assert env["ACCELERATE_USE_DEEPSPEED"] == "true"
    assert env["ACCELERATE_DEEPSPEED_ZERO_STAGE"] == "3"


def test_questionnaire_fsdp_branch_roundtrips(tmp_path):
    answers = iter([
        "cpu_mesh", "8",        # env + devices
        "1",                    # hosts
        "2",                    # fsdp extent
        "FULL_SHARD", "0", "yes", "no",  # fsdp sub-questionnaire
        "1", "2", "1",          # tp, cp, ep
        "2",                    # pp
        "ulysses",              # cp mode
        "bf16", "2", "yes",     # precision, accum, debug
        "train",                # main fn
    ])
    with mock.patch("builtins.input", lambda prompt="": next(answers)):
        cfg = get_cluster_input()
    assert cfg.fsdp_config["activation_checkpointing"] is True
    assert cfg.context_parallel_mode == "ulysses"
    assert cfg.debug
    path = cfg.save(str(tmp_path / "cfg.yaml"))
    loaded = ClusterConfig.load(path)
    assert loaded.fsdp_config == cfg.fsdp_config
    assert loaded.main_training_function == "train"


def test_questionnaire_fsdp_answers_build_working_accelerator(monkeypatch):
    """Full round trip: fsdp questionnaire answers → ClusterConfig → launch
    env contract → an Accelerator whose FSDP plugin and mesh reflect every
    answer (reference cluster.py:54 sub-questionnaire → env → plugin)."""
    from accelerate_tpu.state import AcceleratorState, GradientState

    answers = iter([
        "jax_tpu",              # compute env
        "1",                    # hosts
        "2",                    # fsdp extent
        "SHARD_GRAD_OP",        # sharding strategy
        "1000",                 # min_num_params
        "yes",                  # activation checkpointing
        "no",                   # offload params
        "1", "1", "1", "1",     # tp, cp, ep, pp
        "bf16", "1", "no", "main",
    ])
    with mock.patch("builtins.input", lambda prompt="": next(answers)):
        cfg = get_cluster_input()
    assert cfg.use_fsdp and cfg.mesh_fsdp == 2
    assert cfg.fsdp_config["offload_params"] is False

    env = cfg.to_environment()
    assert env["ACCELERATE_USE_FSDP"] == "true"
    assert env["FSDP_SHARDING_STRATEGY"] == "SHARD_GRAD_OP"
    assert env["FSDP_MIN_NUM_PARAMS"] == "1000"
    assert env["FSDP_ACTIVATION_CHECKPOINTING"] == "True"
    for k, v in env.items():
        if k.startswith(("FSDP_", "ACCELERATE_")):
            monkeypatch.setenv(k, v)

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator()
    try:
        plugin = acc.fsdp_plugin
        assert plugin is not None
        assert plugin.sharding_strategy == "SHARD_GRAD_OP"
        assert plugin.min_num_params == 1000
        assert plugin.activation_checkpointing is True
        assert plugin.cpu_offload is False
        assert dict(acc.mesh.shape)["fsdp"] == 2
    finally:
        AcceleratorState._reset_state(reset_partial_state=True)
        GradientState._reset_state()


def test_questionnaire_deepspeed_answers_build_working_accelerator(monkeypatch):
    """DeepSpeed questionnaire answers reach a working Accelerator: zero
    stage + offload map onto the plugin (→ GSPMD fsdp sharding)."""
    from accelerate_tpu.state import AcceleratorState, GradientState

    answers = iter([
        "jax_tpu",  # compute env
        "1",        # hosts
        "1",        # fsdp extent (1 → offer deepspeed)
        "yes",      # use deepspeed?
        "",         # no config file → questionnaire
        "3",        # zero stage
        "no",       # offload optimizer
        "no",       # offload params
        "2",        # zero shard extent
        "1", "1", "1", "1",  # tp, cp, ep, pp
        "bf16", "1", "no", "main",
    ])
    with mock.patch("builtins.input", lambda prompt="": next(answers)):
        cfg = get_cluster_input()
    for k, v in cfg.to_environment().items():
        if k.startswith(("FSDP_", "ACCELERATE_")):
            monkeypatch.setenv(k, v)

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator()
    try:
        assert acc.deepspeed_plugin is not None
        assert acc.deepspeed_plugin.zero_stage == 3
        assert dict(acc.mesh.shape)["fsdp"] == 2
    finally:
        AcceleratorState._reset_state(reset_partial_state=True)
        GradientState._reset_state()


def test_launch_command_builder():
    cmd = get_launch_command(num_cpu_devices=4, mesh_tp=2, debug=True)
    assert "--num_cpu_devices" in cmd and "4" in cmd
    assert "--mesh_tp" in cmd and "2" in cmd
    assert "--debug" in cmd
    assert DEFAULT_LAUNCH_COMMAND[0].endswith("python") or "python" in DEFAULT_LAUNCH_COMMAND[0]


def test_get_backend_and_require_markers():
    platform, count, mem_fn = get_backend()
    assert platform == "cpu" and count == 8
    assert callable(mem_fn)

    @require_cpu
    def runs():
        return True

    assert runs()


@require_tpu
def test_require_tpu_skips_on_cpu():
    raise AssertionError("must be skipped on the CPU mesh")

def test_megatron_plugin_lowers_to_mesh_axes():
    import jax

    from accelerate_tpu.state import AcceleratorState, GradientState
    from accelerate_tpu.utils.dataclasses import MegatronLMPlugin

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator(megatron_lm_plugin=MegatronLMPlugin(tp_degree=2, sequence_parallelism=True))
    shape = dict(acc.mesh.shape)
    assert shape["tp"] == 2
    # SP does NOT multiply the device requirement (Megatron shards over the
    # existing tp group; here the cp axis is sized explicitly by the user)
    assert shape["cp"] == 1


def test_megatron_sp_shards_residual_activations_on_tp():
    """Under tp>1 + sequence_parallelism=True the norm/residual-region
    activations are sequence-sharded over the tp group (Megatron-SP,
    reference ``utils/dataclasses.py:1916-1919,2112``): residual_spec()
    carries tp on the sequence dim, a compiled forward actually lays the
    constrained activation out that way, and the numerics are unchanged
    vs plain TP."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.ops.layers import mesh_constrain as _constrain, residual_spec
    from accelerate_tpu.state import AcceleratorState, GradientState
    from accelerate_tpu.utils.dataclasses import MegatronLMPlugin

    ids = np.random.default_rng(0).integers(0, 256, size=(2, 16)).astype(np.int32)

    def run(sp: bool):
        AcceleratorState._reset_state(reset_partial_state=True)
        GradientState._reset_state()
        acc = Accelerator(
            megatron_lm_plugin=MegatronLMPlugin(tp_degree=2, sequence_parallelism=sp)
        )
        spec = residual_spec()
        model = LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0)
        sharded = jax.jit(
            lambda x: jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(acc.mesh, residual_spec())
            )
        )(jnp.zeros((8, 16, 64)))
        prepared = acc.prepare(model)
        logits = np.asarray(prepared(input_ids=ids).logits.force())
        return spec, sharded.sharding, logits

    spec_sp, sharding_sp, logits_sp = run(True)
    assert spec_sp == P(("dp", "fsdp"), ("cp", "tp"), None)
    # the compiled layout really shards the sequence dim over tp
    assert isinstance(sharding_sp, NamedSharding)
    assert sharding_sp.spec[1] in (("cp", "tp"), "tp") or "tp" in tuple(
        np.atleast_1d(sharding_sp.spec[1])
    )
    spec_tp, sharding_tp, logits_tp = run(False)
    assert spec_tp == P(("dp", "fsdp"), "cp", None)
    np.testing.assert_allclose(logits_sp, logits_tp, rtol=2e-5, atol=2e-5)


def test_megatron_pp_maps_to_pipeline_axis():
    """pp_degree lowers onto the pp mesh axis (GPipe schedule) the way
    tp_degree lowers onto tp (reference delegates both to Megatron,
    utils/dataclasses.py:1836)."""
    from accelerate_tpu.state import AcceleratorState, GradientState
    from accelerate_tpu.utils.dataclasses import MegatronLMPlugin

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator(megatron_lm_plugin=MegatronLMPlugin(pp_degree=2, tp_degree=2))
    shape = dict(acc.mesh.shape)
    assert shape["pp"] == 2
    assert shape["tp"] == 2


def test_ring_with_dp_downgrades_without_timeout_flag(monkeypatch):
    """XLA CPU's default 40s collective rendezvous window aborts ring+dp>1
    training programs on few-core hosts; without the extended-timeout flag
    the accelerator must route to the allgather formulation. With the flag
    (which the launcher/conftest set) the real ring runs."""
    import os

    from accelerate_tpu import ContextParallelPlugin, MeshPlugin
    from accelerate_tpu.ops.attention import get_attention_context
    from accelerate_tpu.state import AcceleratorState, GradientState

    import re

    flags = os.environ.get("XLA_FLAGS", "")
    bare = re.sub(
        r"--xla_cpu_collective_call_terminate_timeout_seconds=\d+", "", flags
    )
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    with monkeypatch.context() as m:
        m.setenv("XLA_FLAGS", bare)
        Accelerator(
            mesh_plugin=MeshPlugin(dp=2, fsdp=2, cp=2),
            context_parallel_plugin=ContextParallelPlugin(mode="ring"),
        )
        assert get_attention_context().cp_mode == "allgather"

    # with the flag present: real ring, even dp>1. Set it explicitly (not
    # every jaxlib supports it, so conftest may have left it out — safe to
    # fake here because only the Accelerator's regex reads it; XLA parsed
    # XLA_FLAGS once at backend init, long before this test)
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    with monkeypatch.context() as m:
        m.setenv(
            "XLA_FLAGS",
            (bare + " --xla_cpu_collective_call_terminate_timeout_seconds=600").strip(),
        )
        Accelerator(
            mesh_plugin=MeshPlugin(dp=2, fsdp=2, cp=2),
            context_parallel_plugin=ContextParallelPlugin(mode="ring"),
        )
        assert get_attention_context().cp_mode == "ring"


def test_fsdp_activation_checkpointing_wires_model_remat():
    """FSDP plugin activation_checkpointing flips the model's remat knob at
    prepare (reference wires checkpoint_wrapper, accelerator.py:1523)."""
    import optax

    from accelerate_tpu import FullyShardedDataParallelPlugin, MeshPlugin
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.state import AcceleratorState, GradientState

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator(
        mesh_plugin=MeshPlugin(dp=4, fsdp=2),
        fsdp_plugin=FullyShardedDataParallelPlugin(activation_checkpointing=True),
    )
    cfg = LlamaConfig.tiny()
    assert cfg.remat is False
    base = LlamaForCausalLM.from_config(cfg, seed=0)
    acc.prepare(base, optax.sgd(0.1))
    # the wiring flips the MODEL's private config copy; the caller's object
    # is untouched (no leak into other models built from the same config)
    assert base.config.remat is True
    assert cfg.remat is False


def test_megatron_ducktyped_plugin_lowers():
    """An upstream-accelerate-style plugin object (degree fields, no
    to_mesh_axes method) still lowers onto the mesh axes."""
    from accelerate_tpu.state import AcceleratorState, GradientState

    class ForeignMegatronPlugin:
        tp_degree = 2
        pp_degree = 2

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator(megatron_lm_plugin=ForeignMegatronPlugin())
    shape = dict(acc.mesh.shape)
    assert shape["tp"] == 2 and shape["pp"] == 2


def test_dummy_optim_and_scheduler_from_ds_config(tmp_path):
    """Reference contract: a ds-config file owns optimizer/scheduler; the
    user passes DummyOptim/DummyScheduler to prepare() and gets real ones
    built from the config with "auto" values filled
    (reference utils/deepspeed.py:229-290)."""
    import json as _json

    import numpy as np
    import optax

    from accelerate_tpu.state import AcceleratorState, GradientState
    from accelerate_tpu.utils import DummyOptim, DummyScheduler

    cfg = {
        "train_micro_batch_size_per_gpu": 4,
        "zero_optimization": {"stage": 2},
        "optimizer": {"type": "AdamW", "params": {"lr": "auto", "weight_decay": 0.01}},
        "scheduler": {
            "type": "WarmupDecayLR",
            "params": {
                "warmup_min_lr": 0.0, "warmup_max_lr": "auto",
                "warmup_num_steps": 4, "total_num_steps": 16,
            },
        },
    }
    path = tmp_path / "ds.json"
    path.write_text(_json.dumps(cfg))

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator(deepspeed_plugin=DeepSpeedPlugin(hf_ds_config=str(path)))
    model = RegressionModel()
    optimizer = DummyOptim(lr=0.05)
    scheduler = DummyScheduler(total_num_steps=16)
    model, opt, sched = acc.prepare(model, optimizer, scheduler)

    x = np.random.default_rng(0).normal(size=(16, 1)).astype("float32")
    y = 2.0 * x + 1.0
    losses = []
    for _ in range(8):
        out = model(x=x)
        loss = ((out.prediction - y) ** 2).mean()
        acc.backward(loss)
        opt.step()
        sched.step()
        opt.zero_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    # the schedule is live AND "auto" warmup_max_lr filled from the
    # optimizer lr: after 8 steps of WarmupDecayLR(warmup=4, total=16,
    # max=0.05) the lr is 0.05 * (1 - 4/12)
    lr = float(opt.param_groups[0]["learning_rate"])
    assert abs(lr - 0.05 * (1 - 4 / 12)) < 1e-6


def test_dummy_optim_without_ds_plugin_raises():
    from accelerate_tpu.state import AcceleratorState, GradientState
    from accelerate_tpu.utils import DummyOptim

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator()
    with pytest.raises(ValueError, match="DummyOptim"):
        acc.prepare(RegressionModel(), DummyOptim())


def test_multi_plugin_deepspeed_selection(tmp_path):
    """Dict-of-plugins with runtime selection (reference
    ``utils/deepspeed.py:25-41`` + ``state.py:1100-1116``)."""
    from accelerate_tpu.utils import get_active_deepspeed_plugin

    z2 = DeepSpeedPlugin(zero_stage=2)
    z3 = DeepSpeedPlugin(hf_ds_config=_ds_config(tmp_path))
    acc = Accelerator(deepspeed_plugin={"student": z2, "teacher": z3})

    # first plugin is active by default
    assert get_active_deepspeed_plugin(acc.state) is z2
    assert acc.deepspeed_plugin is z2
    assert z2.selected and not z3.selected
    assert acc.state.get_deepspeed_plugin("teacher") is z3

    acc.state.select_deepspeed_plugin("teacher")
    assert acc.deepspeed_plugin is z3
    assert z3.selected and not z2.selected
    assert acc.deepspeed_plugin.zero_stage == 3

    with pytest.raises(KeyError, match="registered"):
        acc.state.select_deepspeed_plugin("nope")
    with pytest.raises(ValueError, match="select_deepspeed_plugin"):
        z2.select()
    with pytest.raises(NotImplementedError):
        z2.selected = True


def test_single_plugin_active_and_empty_dict_rejected():
    from accelerate_tpu.utils import get_active_deepspeed_plugin

    plugin = DeepSpeedPlugin(zero_stage=1)
    acc = Accelerator(deepspeed_plugin=plugin)
    assert get_active_deepspeed_plugin(acc.state) is plugin
    with pytest.raises(ValueError, match="named selection"):
        acc.state.select_deepspeed_plugin("any")

    from accelerate_tpu.state import AcceleratorState, GradientState

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    with pytest.raises(ValueError, match="empty"):
        Accelerator(deepspeed_plugin={})


def test_get_active_plugin_without_deepspeed_raises():
    from accelerate_tpu.utils import get_active_deepspeed_plugin

    acc = Accelerator()
    with pytest.raises(ValueError, match="none were enabled"):
        get_active_deepspeed_plugin(acc.state)
