"""``accelerate-tpu config`` — questionnaire → yaml, plus programmatic config.

Reference analog: ``commands/config/`` (cluster.py questionnaire,
config_args.py dataclasses, default.py write_basic_config). The TPU build
asks only questions that exist on TPU (mesh axes, precision, hosts) and
keeps the same file contract: a yaml at
``~/.cache/accelerate_tpu/default_config.yaml`` that ``launch`` reads and
turns into ``ACCELERATE_*`` env vars.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from pathlib import Path

cache_dir = os.path.join(
    os.path.expanduser(os.environ.get("ACCELERATE_TPU_CACHE", "~/.cache/accelerate_tpu"))
)
default_yaml_config_file = os.path.join(cache_dir, "default_config.yaml")
default_json_config_file = os.path.join(cache_dir, "default_config.json")


def _yaml():
    try:
        import yaml

        return yaml
    except ImportError:  # pragma: no cover
        return None


@dataclass
class ClusterConfig:
    """The launch-relevant config (reference ``config_args.py:43-290``)."""

    compute_environment: str = "JAX_TPU"
    distributed_type: str = "TPU"  # NO | TPU | MULTI_HOST_TPU | CPU_MESH
    num_machines: int = 1
    machine_rank: int = 0
    coordinator_address: str | None = None  # host:port for jax.distributed
    mixed_precision: str = "bf16"
    gradient_accumulation_steps: int = 1
    # mesh axes (-1 = absorb remaining devices)
    mesh_dp: int = -1
    mesh_pp: int = 1
    mesh_fsdp: int = 1
    mesh_ep: int = 1
    mesh_cp: int = 1
    mesh_tp: int = 1
    use_fsdp: bool = False
    fsdp_config: dict = field(default_factory=dict)
    use_deepspeed: bool = False
    deepspeed_config: dict = field(default_factory=dict)
    context_parallel_mode: str | None = None  # ring | ulysses | allgather
    debug: bool = False
    num_cpu_devices: int = 0  # >0 → virtual CPU mesh (testing)
    max_restarts: int = 0  # launch fault tolerance: re-exec + auto-resume
    downcast_bf16: bool = False
    tpu_name: str | None = None
    tpu_zone: str | None = None
    main_training_function: str = "main"

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        return {k: v for k, v in d.items() if v is not None}

    def save(self, path: str | None = None) -> str:
        path = path or default_yaml_config_file
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        yaml = _yaml()
        with open(path, "w") as f:
            if path.endswith(".json") or yaml is None:
                json.dump(self.to_dict(), f, indent=2)
            else:
                yaml.safe_dump(self.to_dict(), f)
        return path

    @classmethod
    def load(cls, path: str | None = None) -> "ClusterConfig":
        path = path or (
            default_yaml_config_file
            if os.path.exists(default_yaml_config_file)
            else default_json_config_file
        )
        with open(path) as f:
            if path.endswith(".json"):
                data = json.load(f)
            else:
                yaml = _yaml()
                data = yaml.safe_load(f) if yaml else json.load(f)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in (data or {}).items() if k in known})

    def to_environment(self) -> dict[str, str]:
        """The env-var contract ``Accelerator``/``PartialState`` read."""
        env = {
            "ACCELERATE_MIXED_PRECISION": str(self.mixed_precision),
            "ACCELERATE_GRADIENT_ACCUMULATION_STEPS": str(self.gradient_accumulation_steps),
            "ACCELERATE_MESH_DP": str(self.mesh_dp),
            "ACCELERATE_MESH_PP": str(self.mesh_pp),
            "ACCELERATE_MESH_FSDP": str(self.mesh_fsdp),
            "ACCELERATE_MESH_EP": str(self.mesh_ep),
            "ACCELERATE_MESH_CP": str(self.mesh_cp),
            "ACCELERATE_MESH_TP": str(self.mesh_tp),
        }
        if self.use_fsdp:
            env["ACCELERATE_USE_FSDP"] = "true"
            for k, v in (self.fsdp_config or {}).items():
                env[f"FSDP_{k.upper()}"] = str(v)
        if self.use_deepspeed:
            env["ACCELERATE_USE_DEEPSPEED"] = "true"
            ds = self.deepspeed_config or {}
            if "zero_stage" in ds:
                env["ACCELERATE_DEEPSPEED_ZERO_STAGE"] = str(ds["zero_stage"])
            if ds.get("deepspeed_config_file"):
                env["ACCELERATE_DEEPSPEED_CONFIG_FILE"] = str(ds["deepspeed_config_file"])
        if self.context_parallel_mode:
            env["ACCELERATE_CP_MODE"] = self.context_parallel_mode
        if self.debug:
            env["ACCELERATE_DEBUG_MODE"] = "true"
        if self.num_machines > 1 and self.coordinator_address:
            env["ACCELERATE_COORDINATOR_ADDR"] = self.coordinator_address
            env["ACCELERATE_NUM_PROCESSES"] = str(self.num_machines)
            env["ACCELERATE_PROCESS_ID"] = str(self.machine_rank)
        if self.num_cpu_devices > 0:
            env["JAX_PLATFORMS"] = "cpu"
            flags = os.environ.get("XLA_FLAGS", "")
            flags = (
                flags + f" --xla_force_host_platform_device_count={self.num_cpu_devices}"
            ).strip()
            if "collective_call_terminate_timeout" not in flags:
                # few-core hosts time-slice device threads; the default 40s
                # collective rendezvous window would abort heavy programs.
                # (Guarded: a user-chosen value must not be clobbered —
                # XLA's flag parsing is last-wins.)
                flags += " --xla_cpu_collective_call_terminate_timeout_seconds=600"
            env["XLA_FLAGS"] = flags
        return env


def _ask(prompt: str, default, cast=str):
    raw = input(f"{prompt} [{default}]: ").strip()
    if not raw:
        return default
    if cast is bool:
        return raw.lower() in ("1", "true", "yes", "y")
    return cast(raw)


def get_cluster_input() -> ClusterConfig:
    """Interactive questionnaire (reference ``cluster.py:54``), linearised —
    plain prompts instead of the cursor-menu UI, with the same
    sub-questionnaires (multi-host, FSDP, DeepSpeed-style sharding, context
    parallelism, TPU pod)."""
    cfg = ClusterConfig()
    env = _ask(
        "Compute environment? (jax_tpu / cpu_mesh for local testing)", "jax_tpu"
    )
    if env == "cpu_mesh":
        cfg.compute_environment = "CPU_MESH"
        cfg.distributed_type = "CPU_MESH"
        cfg.num_cpu_devices = _ask("How many virtual CPU devices?", 8, int)

    # -- multi-host sub-questionnaire (reference cluster.py:70-115) ---------
    cfg.num_machines = _ask("How many hosts (machines)?", 1, int)
    if cfg.num_machines > 1:
        cfg.distributed_type = "MULTI_HOST_TPU"
        cfg.machine_rank = _ask("Rank of this machine?", 0, int)
        cfg.coordinator_address = _ask("Coordinator address (host:port)?", "127.0.0.1:8476")
        if _ask("Is this a GCP TPU pod managed via gcloud?", False, bool):
            cfg.tpu_name = _ask("TPU name?", None)
            cfg.tpu_zone = _ask("TPU zone?", None)

    # -- sharding sub-questionnaire (reference FSDP/DeepSpeed menus) --------
    cfg.mesh_fsdp = _ask("FSDP (param-shard) mesh extent?", 1, int)
    cfg.use_fsdp = cfg.mesh_fsdp > 1
    if cfg.use_fsdp:
        cfg.fsdp_config = {
            "sharding_strategy": _ask(
                "FSDP sharding strategy? (FULL_SHARD/SHARD_GRAD_OP/NO_SHARD)", "FULL_SHARD"
            ),
            "min_num_params": _ask("Minimum parameter count to shard a tensor?", 0, int),
            "activation_checkpointing": _ask("Use activation checkpointing?", False, bool),
            # key name matches the env var the plugin reads (FSDP_OFFLOAD_PARAMS)
            "offload_params": _ask("Offload optimizer state to host memory?", False, bool),
        }
    elif _ask("Use a DeepSpeed-style ZeRO config instead?", False, bool):
        cfg.use_deepspeed = True
        ds_file = _ask("Path to a DeepSpeed JSON config (empty = questionnaire)?", "")
        if ds_file:
            cfg.deepspeed_config = {"deepspeed_config_file": ds_file}
        else:
            stage = _ask("ZeRO stage? (0/1/2/3)", 2, int)
            cfg.deepspeed_config = {"zero_stage": stage}
            if stage >= 2 and _ask("Offload optimizer state to host?", False, bool):
                cfg.deepspeed_config["offload_optimizer_device"] = "cpu"
            if stage == 3 and _ask("Offload parameters to host?", False, bool):
                cfg.deepspeed_config["offload_param_device"] = "cpu"
        if cfg.deepspeed_config.get("zero_stage", 0) >= 1:
            cfg.mesh_fsdp = _ask("ZeRO shard extent (mesh fsdp axis)?", 2, int)
            cfg.use_fsdp = cfg.mesh_fsdp > 1

    cfg.mesh_tp = _ask("Tensor-parallel mesh extent?", 1, int)
    cfg.mesh_cp = _ask("Context-parallel (sequence) mesh extent?", 1, int)
    cfg.mesh_ep = _ask("Expert-parallel mesh extent?", 1, int)
    cfg.mesh_pp = _ask("Pipeline-parallel (GPipe stage) mesh extent?", 1, int)
    if cfg.mesh_cp > 1:
        cfg.context_parallel_mode = _ask(
            "Context parallel mode? (ring/ulysses/allgather)", "ring"
        )

    cfg.mixed_precision = _ask("Mixed precision? (no/bf16/fp16/fp8)", "bf16")
    cfg.gradient_accumulation_steps = _ask("Gradient accumulation steps?", 1, int)
    cfg.debug = _ask("Check distributed operations for shape agreement (debug mode)?", False, bool)
    cfg.main_training_function = _ask(
        "Main training function (for notebook_launcher)?", "main"
    )
    return cfg


def write_basic_config(mixed_precision: str = "bf16", save_location: str | None = None):
    """Non-interactive default config (reference ``default.py:142``)."""
    cfg = ClusterConfig(mixed_precision=mixed_precision)
    return cfg.save(save_location)


def config_command(args):
    if getattr(args, "default", False):
        path = write_basic_config(mixed_precision=args.mixed_precision)
    else:
        cfg = get_cluster_input()
        path = cfg.save(args.config_file)
    print(f"configuration saved at {path}")
    return 0


def add_parser(subparsers):
    p = subparsers.add_parser("config", help="Create the launch configuration")
    p.add_argument("--config_file", default=None)
    p.add_argument("--default", action="store_true", help="write defaults, no questions")
    p.add_argument("--mixed_precision", default="bf16")
    p.set_defaults(func=config_command)
    return p
