"""Config dataclasses, enums, kwargs handlers, and parallelism plugins.

Plays the role of the reference's ``utils/dataclasses.py``
(``/root/reference/src/accelerate/utils/dataclasses.py``, 2535 LoC) with a
TPU-native cast:

* ``DistributedType`` enumerates JAX execution environments, not torch
  backends (reference ``dataclasses.py:485``-ish).
* The FSDP/DeepSpeed/Megatron plugin trio collapses onto **one** GSPMD
  sharding model expressed as mesh axes + partition rules; we keep
  plugin classes with the reference's names/fields as façades so user
  configs round-trip, but they all lower to `ShardingPlugin` decisions.
* Mixed precision is a dtype policy (bf16 native); no GradScaler.

Every plugin self-hydrates from ``ACCELERATE_*`` env vars in
``__post_init__`` exactly like the reference (e.g. reference
``dataclasses.py:1599-1672``).
"""

from __future__ import annotations

import enum
import functools
import os
import warnings
from dataclasses import dataclass, field, fields
from datetime import timedelta
from typing import Any, Callable, Iterable, Literal

from .environment import parse_flag_from_env


class BaseEnum(str, enum.Enum):
    def __str__(self) -> str:  # so f-strings / env writes produce bare values
        return self.value

    @classmethod
    def list(cls) -> list[str]:
        return [e.value for e in cls]


class DistributedType(BaseEnum):
    """Execution environment (reference analog: ``DistributedType`` in
    ``utils/dataclasses.py``; here the kinds are JAX-shaped)."""

    NO = "NO"  # single device (1 chip or CPU), no mesh axes > 1
    TPU = "TPU"  # single-process JAX driving all local devices via a Mesh
    MULTI_HOST_TPU = "MULTI_HOST_TPU"  # jax.distributed across hosts (ICI+DCN)
    CPU_MESH = "CPU_MESH"  # forced host-platform mesh (tests / dry runs)


class PrecisionType(BaseEnum):
    NO = "no"
    FP32 = "fp32"
    BF16 = "bf16"
    FP16 = "fp16"
    FP8 = "fp8"
    INT8 = "int8"


class RNGType(BaseEnum):
    JAX = "jax"  # the TrainState PRNG key
    NUMPY = "numpy"
    PYTHON = "python"
    GENERATOR = "generator"  # torch-compat CPU generator, if torch is in play


@dataclass
class KwargsHandler:
    """Base for kwargs-passthrough dataclasses (reference ``dataclasses.py:82``)."""

    def to_dict(self) -> dict[str, Any]:
        return {k: v for k, v in self.__dict__.items()}

    def to_kwargs(self) -> dict[str, Any]:
        default = self.__class__()
        return {k: v for k, v in self.to_dict().items() if getattr(default, k) != v}


@dataclass
class AutocastKwargs(KwargsHandler):
    """(Reference ``dataclasses.py:96``.) ``enabled=False`` makes
    ``Accelerator.autocast(autocast_handler=...)`` suspend the compute-dtype
    cast for the duration of the context — full-precision islands inside a
    mixed-precision run. ``cache_enabled`` is torch-autocast-specific and
    accepted for parity."""

    enabled: bool = True
    cache_enabled: bool | None = None


@dataclass
class InitProcessGroupKwargs(KwargsHandler):
    """Multi-host init knobs → ``jax.distributed.initialize`` arguments.

    (Reference: ``InitProcessGroupKwargs`` ``dataclasses.py:246`` carrying
    backend/timeout into ``torch.distributed.init_process_group``.)
    """

    coordinator_address: str | None = None
    num_processes: int | None = None
    process_id: int | None = None
    timeout: timedelta = field(default_factory=lambda: timedelta(seconds=1800))


@dataclass
class GradScalerKwargs(KwargsHandler):
    """Configures the fp16 dynamic loss scaler (reference
    ``torch.cuda.amp.GradScaler`` kwargs, ``dataclasses.py:215``): the scale
    starts at ``init_scale``, backs off by ``backoff_factor`` on non-finite
    grads, and grows by ``growth_factor`` after ``growth_interval``
    consecutive finite steps (``accelerate_tpu.optimizer.LossScaler``).
    bf16-on-TPU needs no scaling; the handler only matters under
    ``mixed_precision='fp16'``. ``enabled=False`` disables scaling."""

    init_scale: float = 65536.0
    growth_factor: float = 2.0
    backoff_factor: float = 0.5
    growth_interval: int = 2000
    enabled: bool = True


@dataclass
class DistributedDataParallelKwargs(KwargsHandler):
    """API-parity shim (reference ``dataclasses.py:138``). Under GSPMD there
    is no DDP wrapper object; the only semantically meaningful field here is
    ``gradient_as_bucket_view``-style memory behaviour, which XLA handles.
    Fields are accepted and validated so reference configs load."""

    dim: int = 0
    broadcast_buffers: bool = True
    bucket_cap_mb: int = 25
    find_unused_parameters: bool = False
    check_reduction: bool = False
    gradient_as_bucket_view: bool = False
    comm_hook: str = "no"  # reference DDPCommunicationHookType; bf16 hook ≈ bf16 grad psum
    static_graph: bool = False


@dataclass
class ProfileKwargs(KwargsHandler):
    """``jax.profiler`` configuration (reference: torch.profiler builder,
    ``dataclasses.py:406-513``). ``output_trace_dir`` receives TensorBoard /
    Perfetto traces; schedule fields mimic the reference's wait/warmup/active
    stepping so user code ports unchanged.

    Example — trace steps 3-4 of every 5-step cycle, with per-program FLOPs
    dumped to ``flops.json`` (and, when telemetry is on, a ``profile``
    record appended to the JSONL trail when the session closes)::

        kwargs = ProfileKwargs(
            wait=1, warmup=2, active=2, repeat=1,
            with_flops=True, output_trace_dir="/tmp/trace",
        )
        accelerator = Accelerator(kwargs_handlers=[kwargs])
        with accelerator.profile() as prof:
            for batch in dataloader:
                accelerator.backward(model(**batch).loss)
                optimizer.step()
                optimizer.zero_grad()
                prof.step()
    """

    wait: int = 0
    warmup: int = 0
    active: int = 1
    repeat: int = 0
    skip_first: int = 0
    record_shapes: bool = False
    profile_memory: bool = False
    with_stack: bool = False
    with_flops: bool = False
    output_trace_dir: str | None = None

    def build_schedule(self) -> Callable[[int], str]:
        """Returns step → phase ('skip'|'wait'|'warmup'|'active') resolver."""

        def schedule(step: int) -> str:
            if step < self.skip_first:
                return "skip"
            s = step - self.skip_first
            cycle = self.wait + self.warmup + self.active
            if cycle == 0:
                return "active"
            if self.repeat and s >= cycle * self.repeat:
                return "skip"
            pos = s % cycle
            if pos < self.wait:
                return "wait"
            if pos < self.wait + self.warmup:
                return "warmup"
            return "active"

        return schedule


@dataclass
class GradientAccumulationPlugin(KwargsHandler):
    """(Reference ``dataclasses.py`` GradientAccumulationPlugin.) On TPU the
    microbatch loop lives *inside* the compiled step as a ``lax.scan`` when
    ``fuse_in_step`` is True; otherwise the outer-loop ``accumulate()``
    context manager semantics are preserved."""

    num_steps: int = 1
    adjust_scheduler: bool = True
    sync_with_dataloader: bool = True
    sync_each_batch: bool = False
    fuse_in_step: bool = False


@dataclass
class ProjectConfiguration:
    """Checkpoint/artifact layout (reference ``dataclasses.py:748``)."""

    project_dir: str | None = None
    logging_dir: str | None = None
    automatic_checkpoint_naming: bool = False
    total_limit: int | None = None
    iteration: int = 0
    save_on_each_node: bool = False

    def set_directories(self, project_dir: str | None = None) -> None:
        self.project_dir = project_dir
        if self.logging_dir is None:
            self.logging_dir = project_dir

    def __post_init__(self):
        if self.logging_dir is None:
            self.logging_dir = self.project_dir


# ---------------------------------------------------------------------------
# Mesh / sharding plugins — the heart of the TPU-native design.
# ---------------------------------------------------------------------------

#: Canonical mesh axis names, ordered outermost (DCN-friendly) to innermost
#: (ICI-friendly). Data parallel replicas tolerate slow links; tensor/expert
#: parallel collectives must ride ICI — hence dp outermost, tp innermost.
MESH_AXIS_ORDER = ("dp", "pp", "fsdp", "ep", "cp", "tp")


@dataclass
class MeshPlugin(KwargsHandler):
    """Declarative mesh shape. ``-1`` on one axis means "absorb remaining
    devices". This is the single source of truth every other parallelism
    plugin lowers into. (No reference analog — the reference delegates
    topology to torchrun env vars; here the mesh IS the topology.)"""

    dp: int = -1
    pp: int = 1
    fsdp: int = 1
    ep: int = 1
    cp: int = 1
    tp: int = 1
    devices: Any = None  # optional explicit device list
    allow_split_physical_axes: bool = False

    def __post_init__(self):
        for ax in MESH_AXIS_ORDER:
            env = os.environ.get(f"ACCELERATE_MESH_{ax.upper()}")
            if env is not None:
                setattr(self, ax, int(env))

    def axis_sizes(self, num_devices: int) -> dict[str, int]:
        sizes = {ax: getattr(self, ax) for ax in MESH_AXIS_ORDER}
        fixed = 1
        wild = None
        for ax, n in sizes.items():
            if n == -1:
                if wild is not None:
                    raise ValueError("only one mesh axis may be -1")
                wild = ax
            else:
                fixed *= n
        if wild is not None:
            if num_devices % fixed != 0:
                raise ValueError(
                    f"mesh shape {sizes} does not divide {num_devices} devices"
                )
            sizes[wild] = num_devices // fixed
        else:
            total = 1
            for n in sizes.values():
                total *= n
            if total != num_devices:
                raise ValueError(
                    f"mesh shape {sizes} (={total}) != device count {num_devices}"
                )
        return sizes


@dataclass
class FullyShardedDataParallelPlugin(KwargsHandler):
    """GSPMD parameter sharding — the reference FSDP plugin surface
    (``dataclasses.py:1404-1812``) lowered to a ``NamedSharding`` policy over
    the ``fsdp`` mesh axis.

    Field mapping (reference → here):
      * sharding_strategy FULL_SHARD → shard params+grads+optimizer state
        (``reshard_after_forward=True``); SHARD_GRAD_OP → params gathered,
        grad/optimizer state sharded (``reshard_after_forward=False``);
        NO_SHARD → replicated; HYBRID_SHARD → shard intra-slice, replicate
        across slices (dp axis outer).
      * cpu_offload → optimizer state pinned to host memory
        (``jax.device_put(..., memory_kind='pinned_host')``).
      * activation_checkpointing → ``jax.checkpoint`` policy on the block fn.
      * min_num_params / auto_wrap_policy → minimum parameter size that gets
        sharded rather than replicated.
    """

    sharding_strategy: str = "FULL_SHARD"
    reshard_after_forward: bool = True
    cpu_offload: bool = False
    activation_checkpointing: bool = False
    min_num_params: int = 0
    ignored_modules: list[str] | None = None
    use_orig_params: bool = True  # no-op in JAX; params are always "orig"
    sync_module_states: bool = True  # no-op; GSPMD init is deterministic
    param_dtype: str | None = None
    reduce_dtype: str | None = None
    state_dict_type: str = "SHARDED_STATE_DICT"

    def __post_init__(self):
        prefix = "FSDP_"
        self.sharding_strategy = os.environ.get(
            prefix + "SHARDING_STRATEGY", self.sharding_strategy
        )
        if parse_flag_from_env(prefix + "OFFLOAD_PARAMS", self.cpu_offload):
            self.cpu_offload = True
        if parse_flag_from_env(
            prefix + "ACTIVATION_CHECKPOINTING", self.activation_checkpointing
        ):
            self.activation_checkpointing = True
        env_min = os.environ.get(prefix + "MIN_NUM_PARAMS")
        if env_min is not None:
            self.min_num_params = int(env_min)
        if self.sharding_strategy in ("NO_SHARD", "3"):
            self.reshard_after_forward = False

    @property
    def shards_params(self) -> bool:
        return self.sharding_strategy in ("FULL_SHARD", "HYBRID_SHARD", "1", "4",
                                          "SHARD_GRAD_OP", "2")


@dataclass
class TensorParallelPlugin(KwargsHandler):
    """``tp`` axis sharding rules for attention/MLP weight dims (reference
    analog: Megatron ``tensor_model_parallel_size``, ``dataclasses.py:2106``)."""

    tp_size: int = 1
    sequence_parallelism: bool = False  # shard norm/dropout activations on seq


@dataclass
class ContextParallelPlugin(KwargsHandler):
    """Long-context parallelism over the ``cp`` axis — ring attention
    (ppermute'd KV blocks) or Ulysses (all-to-all head↔seq reshard).
    The reference has NO analog (SURVEY §5); this is a capability we add."""

    cp_size: int = 1
    mode: Literal["ring", "ulysses", "allgather"] = "ring"
    chunk_size: int | None = None


@dataclass
class DeepSpeedPlugin(KwargsHandler):
    """Compatibility façade for the reference's DeepSpeedPlugin
    (``dataclasses.py:974-1402``). ZeRO stages lower onto GSPMD:
    stage 1/2 → optimizer-state/grad sharding on ``fsdp`` axis;
    stage 3 → full param sharding (identical to FULL_SHARD);
    offload_optimizer/param → host memory_kind placement."""

    zero_stage: int = 2
    gradient_accumulation_steps: int = 1
    gradient_clipping: float | None = None
    offload_optimizer_device: str | None = None  # "cpu" → pinned_host
    offload_param_device: str | None = None
    zero3_init_flag: bool = False
    zero3_save_16bit_model: bool = False
    hf_ds_config: Any = None

    def __post_init__(self):
        self._selected = True
        self.zero_stage = int(os.environ.get("ACCELERATE_DEEPSPEED_ZERO_STAGE", self.zero_stage))
        self.gradient_accumulation_steps = int(
            os.environ.get(
                "ACCELERATE_GRADIENT_ACCUMULATION_STEPS", self.gradient_accumulation_steps
            )
        )
        if self.hf_ds_config is None:
            self.hf_ds_config = os.environ.get("ACCELERATE_DEEPSPEED_CONFIG_FILE")
        if self.hf_ds_config is not None:
            self._ingest_ds_config()

    def _ingest_ds_config(self):
        """Read a DeepSpeed JSON config (path or dict), honoring ``"auto"``
        values (reference config ingestion ``accelerator.py:1651-1891`` +
        ``dataclasses.py:1131-1151``): concrete values override plugin
        fields; ``"auto"`` entries are resolved at ``prepare`` time by
        :meth:`fill_auto` and readable back via ``deepspeed_config``."""
        import json

        cfg = self.hf_ds_config
        if isinstance(cfg, str):
            with open(cfg) as f:
                cfg = json.load(f)
        if not isinstance(cfg, dict):
            raise ValueError(f"hf_ds_config must be a dict or a JSON path, got {type(cfg)}")
        self.deepspeed_config = cfg
        zero = cfg.get("zero_optimization", {})

        def _take(value, current):
            return current if value in (None, "auto") else value

        self.zero_stage = int(_take(zero.get("stage"), self.zero_stage))
        self.gradient_accumulation_steps = int(
            _take(cfg.get("gradient_accumulation_steps"), self.gradient_accumulation_steps)
        )
        clip = _take(cfg.get("gradient_clipping"), self.gradient_clipping)
        self.gradient_clipping = float(clip) if clip is not None else None
        self.offload_optimizer_device = _take(
            zero.get("offload_optimizer", {}).get("device"), self.offload_optimizer_device
        )
        self.offload_param_device = _take(
            zero.get("offload_param", {}).get("device"), self.offload_param_device
        )

    def fill_auto(self, values: dict):
        """Resolve ``"auto"`` entries from runtime values (reference
        ``fill_match``, ``dataclasses.py:1131-1151``). ``values`` maps
        dotted config keys → concrete values; only keys currently set to
        ``"auto"`` are written."""
        cfg = getattr(self, "deepspeed_config", None)
        if cfg is None:
            return
        for dotted, value in values.items():
            node = cfg
            *parents, leaf = dotted.split(".")
            for p in parents:
                node = node.setdefault(p, {})
            if node.get(leaf) == "auto":
                node[leaf] = value

    def to_fsdp_plugin(self) -> FullyShardedDataParallelPlugin:
        strategy = {0: "NO_SHARD", 1: "SHARD_GRAD_OP", 2: "SHARD_GRAD_OP", 3: "FULL_SHARD"}[
            self.zero_stage
        ]
        return FullyShardedDataParallelPlugin(
            sharding_strategy=strategy,
            cpu_offload=self.offload_optimizer_device == "cpu"
            or self.offload_param_device == "cpu",
        )

    # -- multi-plugin selection (reference ``dataclasses.py:1372-1399``):
    # several named plugins can coexist on AcceleratorState; exactly one is
    # active at a time and runtime code (auto-fill, grad accumulation,
    # dummy-object lowering) reads the active one.

    def select(self, _from_accelerator_state: bool = False):
        if not _from_accelerator_state:
            raise ValueError(
                "A DeepSpeedPlugin is enabled via "
                "`AcceleratorState().select_deepspeed_plugin(name)`, not by "
                "calling `select()` directly."
            )
        self._selected = True

    def _unselect(self):
        self._selected = False

    @property
    def selected(self) -> bool:
        return self._selected

    @selected.setter
    def selected(self, value):
        raise NotImplementedError(
            "`selected` is read-only; use "
            "`AcceleratorState().select_deepspeed_plugin(name)`."
        )


@dataclass
class FaultTolerancePlugin(KwargsHandler):
    """Preemption-safe checkpointing + auto-resume (the ``resilience``
    subsystem; reference analog: torchrun's elastic agent + FSDP sharded
    state dicts, which the reference leans on external runtimes for).

    Handing this to ``Accelerator(fault_tolerance=...)``:

    * installs SIGTERM/SIGINT handlers (``handle_signals``) — a preemption
      notice triggers ONE synchronized emergency ``save_state()`` at the
      next step boundary, then a clean exit (``exit_code``) with a
      ``PREEMPTED.json`` sentinel next to the checkpoints;
    * optionally polls the GCE metadata server for maintenance events
      (``monitor_maintenance``);
    * makes ``prepare()`` auto-resume from the newest checkpoint whose
      manifest validates (``auto_resume``; also forced by
      ``ACCELERATE_AUTO_RESUME=1`` / ``accelerate-tpu launch --auto-resume``);
    * switches ``save_state`` to the per-host sharded format
      (``sharded_io``) — no full-gather on multi-host FSDP;
    * routes checkpoint IO through bounded exponential-backoff retries
      (``io_attempts`` × ``io_backoff_seconds``, exported as
      ``ACCELERATE_FT_IO_ATTEMPTS``/``_BACKOFF`` so background writers
      agree).

    ``consensus_interval`` is the step cadence of the cross-host flag
    all-reduce: 1 reacts within a step; larger values amortize the (tiny)
    collective on huge fleets. Every process must use the same value — it
    is a collective schedule.
    """

    auto_resume: bool = True
    save_on_preemption: bool = True
    handle_signals: bool = True
    handle_sigint: bool = True
    monitor_maintenance: bool = False
    maintenance_poll_seconds: float = 30.0
    consensus_interval: int = 1
    sharded_io: bool = True
    io_attempts: int = 3
    io_backoff_seconds: float = 0.5
    exit_code: int = 143  # 128 + SIGTERM: honest to the launcher's restart logic

    def __post_init__(self):
        env = os.environ
        if "ACCELERATE_AUTO_RESUME" in env:
            self.auto_resume = parse_flag_from_env("ACCELERATE_AUTO_RESUME", self.auto_resume)
        if "ACCELERATE_FT_SHARDED_IO" in env:
            self.sharded_io = parse_flag_from_env("ACCELERATE_FT_SHARDED_IO", self.sharded_io)
        if "ACCELERATE_FT_MONITOR_MAINTENANCE" in env:
            self.monitor_maintenance = parse_flag_from_env(
                "ACCELERATE_FT_MONITOR_MAINTENANCE", self.monitor_maintenance
            )
        if "ACCELERATE_FT_CONSENSUS_INTERVAL" in env:
            self.consensus_interval = int(env["ACCELERATE_FT_CONSENSUS_INTERVAL"])
        if "ACCELERATE_FT_IO_ATTEMPTS" in env:
            self.io_attempts = int(env["ACCELERATE_FT_IO_ATTEMPTS"])
        if "ACCELERATE_FT_IO_BACKOFF" in env:
            self.io_backoff_seconds = float(env["ACCELERATE_FT_IO_BACKOFF"])
        self.consensus_interval = max(1, int(self.consensus_interval))

    def export_io_env(self):
        """Publish the retry knobs where the checkpoint writers (including
        the async background thread) read their defaults."""
        os.environ["ACCELERATE_FT_IO_ATTEMPTS"] = str(self.io_attempts)
        os.environ["ACCELERATE_FT_IO_BACKOFF"] = str(self.io_backoff_seconds)


@dataclass
class DiagnosticsPlugin(KwargsHandler):
    """Distributed tracing + hang watchdog (the ``diagnostics`` subsystem).

    Handing this to ``Accelerator(diagnostics=...)``:

    * **tracing** — per-host Chrome/Perfetto span timelines under
      ``{logging_dir}/traces/host_<n>.trace.json`` covering prepare, the
      AOT trace/lower/compile phases, backward dispatch vs device-blocked
      time, dataloader fetch, eager collectives, and checkpoint
      save/restore; fuse with ``accelerate-tpu trace merge``.
    * **watchdog** — a background deadline of
      ``max(watchdog_multiplier · EMA(step_time), watchdog_floor_seconds)``
      armed around each step; on expiry, ``HANG_REPORT_<host>.json`` with
      all-thread stacks + the open span stack, and (``preempt_on_hang``)
      the resilience subsystem's consensus emergency-save instead of a
      silent burn. Per-host heartbeat files feed
      ``accelerate-tpu monitor``'s straggler naming.

    Env overrides (all optional): ``ACCELERATE_DIAGNOSTICS=1`` enables the
    subsystem with defaults; ``ACCELERATE_WATCHDOG_MULTIPLIER``,
    ``ACCELERATE_WATCHDOG_FLOOR_SECONDS``,
    ``ACCELERATE_WATCHDOG_CHECK_SECONDS``, ``ACCELERATE_WATCHDOG_PREEMPT``
    tune the watchdog; ``ACCELERATE_WATCHDOG=0`` / ``ACCELERATE_TRACING=0``
    switch either half off independently.
    """

    tracing: bool = True
    watchdog: bool = True
    watchdog_multiplier: float = 5.0
    watchdog_floor_seconds: float = 120.0
    watchdog_check_seconds: float = 5.0
    watchdog_ema_alpha: float = 0.2
    #: deadline while the open phase is compile/*, checkpoint/* or prepare
    #: (host-local, legitimately unbounded by step time)
    watchdog_grace_seconds: float = 1800.0
    watchdog_telemetry_tail: int = 50
    preempt_on_hang: bool = False
    heartbeat_interval_seconds: float = 5.0
    trace_buffer_events: int = 16

    def __post_init__(self):
        env = os.environ
        if "ACCELERATE_TRACING" in env:
            self.tracing = parse_flag_from_env("ACCELERATE_TRACING", self.tracing)
        if "ACCELERATE_WATCHDOG" in env:
            self.watchdog = parse_flag_from_env("ACCELERATE_WATCHDOG", self.watchdog)
        if "ACCELERATE_WATCHDOG_MULTIPLIER" in env:
            self.watchdog_multiplier = float(env["ACCELERATE_WATCHDOG_MULTIPLIER"])
        if "ACCELERATE_WATCHDOG_FLOOR_SECONDS" in env:
            self.watchdog_floor_seconds = float(env["ACCELERATE_WATCHDOG_FLOOR_SECONDS"])
        if "ACCELERATE_WATCHDOG_CHECK_SECONDS" in env:
            self.watchdog_check_seconds = float(env["ACCELERATE_WATCHDOG_CHECK_SECONDS"])
        if "ACCELERATE_WATCHDOG_GRACE_SECONDS" in env:
            self.watchdog_grace_seconds = float(env["ACCELERATE_WATCHDOG_GRACE_SECONDS"])
        if "ACCELERATE_WATCHDOG_PREEMPT" in env:
            self.preempt_on_hang = parse_flag_from_env(
                "ACCELERATE_WATCHDOG_PREEMPT", self.preempt_on_hang
            )
        self.watchdog_multiplier = max(1.0, float(self.watchdog_multiplier))
        self.watchdog_floor_seconds = max(0.0, float(self.watchdog_floor_seconds))


@dataclass
class MegatronLMPlugin(KwargsHandler):
    """Compatibility façade (reference ``dataclasses.py:1814+``): tp/pp/sp
    degrees lower to mesh axes; there is no separate Megatron engine.

    ``num_micro_batches`` uses 0 for auto (smallest divisor of the batch
    >= the stage count). For duck-typed upstream-style plugins — whose
    dataclass default is 1, meaning "unset" there — a value of 1 is
    coerced to auto, so an upstream user's *explicit* ``num_micro_batches=1``
    (whole-batch scheduling) cannot be distinguished from the default and
    gets auto microbatching; construct THIS class with
    ``num_micro_batches=1`` to request whole-batch scheduling explicitly.
    """

    tp_degree: int = 1
    pp_degree: int = 1
    num_micro_batches: int = 0  # 0 = auto (smallest divisor >= stages)
    sequence_parallelism: bool = False
    recompute_activations: bool = False

    def to_mesh_axes(self) -> dict[str, int]:
        return {"tp": self.tp_degree, "pp": self.pp_degree}


# ---------------------------------------------------------------------------
# Helpers shared with big-model inference
# ---------------------------------------------------------------------------


class CustomDtype(BaseEnum):
    """Sub-byte / exotic dtypes for memory accounting (reference
    ``dataclasses.py:697``)."""

    FP8 = "fp8"
    INT8 = "int8"
    INT4 = "int4"
    INT2 = "int2"


@dataclass
class DataLoaderConfiguration(KwargsHandler):
    """(Reference ``dataclasses.py`` DataLoaderConfiguration; every knob is
    also env-reachable as ``ACCELERATE_<NAME>`` — exported manually or via
    ``accelerate-tpu launch``'s environment passthrough.)"""

    split_batches: bool = False
    dispatch_batches: bool | None = None
    even_batches: bool = True
    use_seedable_sampler: bool = False
    non_blocking: bool = False
    use_stateful_dataloader: bool = False
    prefetch_batches: int = 2  # background collate+H2D lookahead depth (0 = sync)

    def __post_init__(self):
        # precedence: explicit non-default ctor args > env > defaults
        # (the reference's plugin self-hydration contract)
        from .environment import str_to_bool

        defaults = {f.name: f.default for f in fields(self)}
        for name in (
            "split_batches", "even_batches", "use_seedable_sampler",
            "non_blocking", "use_stateful_dataloader", "dispatch_batches",
        ):
            env = os.environ.get(f"ACCELERATE_{name.upper()}")
            if env is not None and getattr(self, name) == defaults[name]:
                setattr(self, name, bool(str_to_bool(env)))
        env = os.environ.get("ACCELERATE_PREFETCH_BATCHES")
        if env is not None and self.prefetch_batches == defaults["prefetch_batches"]:
            self.prefetch_batches = int(env)
