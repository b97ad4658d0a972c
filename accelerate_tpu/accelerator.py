"""The Accelerator facade — same user contract as the reference
(``/root/reference/src/accelerate/accelerator.py``, 3610 LoC), TPU-native
execution.

Design (SURVEY §7): ``prepare()`` does not mutate user objects in place; it
computes shardings over the named mesh and returns wrappers whose work runs
inside jit-compiled functions. ``backward(loss)`` consumes a deferred loss
(see :mod:`accelerate_tpu.lazy`) and runs a cached compiled
``value_and_grad``; the optimizer wrapper applies updates in a second jitted
step. Collectives (``gather``/``reduce``/…) come from
:mod:`accelerate_tpu.operations`.
"""

from __future__ import annotations

import contextlib
import math
import os
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np
import optax

from . import operations as ops
from .analysis.sanitizer import Sanitizer
from .analysis.sanitizer import get_active_sanitizer as _get_sanitizer
from .analysis.sanitizer import set_active_sanitizer as _set_sanitizer
from .data_loader import DataLoaderShard, prepare_data_loader, skip_first_batches
from .lazy import Deferred, clear_caches, grad_fn_for
from .logging import get_logger
from .mesh import data_sharding, replicated
from .modules import Model, PreparedModel, extract_model_from_parallel
from .optimizer import AcceleratedOptimizer
from .parallel.sharding import (
    infer_param_sharding,
    opt_state_sharding_like,
    shard_params,
)
from .scheduler import AcceleratedScheduler
from .state import AcceleratorState, GradientState, PartialState
from .utils.dataclasses import (
    DataLoaderConfiguration,
    DeepSpeedPlugin,
    DiagnosticsPlugin,
    DistributedDataParallelKwargs,
    DistributedType,
    FaultTolerancePlugin,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    GradScalerKwargs,
    InitProcessGroupKwargs,
    MegatronLMPlugin,
    MeshPlugin,
    PrecisionType,
    ProfileKwargs,
    ProjectConfiguration,
)

logger = get_logger(__name__)


class _PendingNorm:
    """Return value of a fused-path clip: the true pre-clip norm, resolved
    after the fused step ran (or by flushing to the split path on demand)."""

    def __init__(self, accelerator, opt):
        self._accelerator = accelerator
        self._opt = opt

    def _resolve(self):
        if self._opt._last_norm is not None:
            return self._opt._last_norm
        if self._opt._pending_loss is not None:
            self._accelerator._flush_pending(self._opt)  # sets _last_norm via clip
        return self._opt._last_norm if self._opt._last_norm is not None else jnp.asarray(0.0)

    def item(self):
        return float(np.asarray(self._resolve()))

    def __float__(self):
        return self.item()

    def __array__(self, dtype=None):
        return np.asarray(self._resolve(), dtype=dtype)

    def __lt__(self, o): return self.item() < o
    def __le__(self, o): return self.item() <= o
    def __gt__(self, o): return self.item() > o
    def __ge__(self, o): return self.item() >= o
    def __add__(self, o): return self.item() + o
    def __radd__(self, o): return o + self.item()
    def __mul__(self, o): return self.item() * o
    def __rmul__(self, o): return o * self.item()
    def __truediv__(self, o): return self.item() / o
    def __sub__(self, o): return self.item() - o
    def __rsub__(self, o): return o - self.item()

    def __repr__(self):
        return f"PendingNorm({self._opt._last_norm})"


class ProfileContext:
    """Schedule-driven ``jax.profiler`` session (the reference's
    torch.profiler schedule semantics, ``dataclasses.py:406-513``): call
    ``step()`` once per training step; capture runs only during 'active'
    phases of the wait/warmup/active/repeat cycle."""

    def __init__(self, handler: ProfileKwargs, trace_dir: str, telemetry=None):
        self.handler = handler
        self.trace_dir = trace_dir
        self.schedule = handler.build_schedule()
        self.step_num = 0
        self.active_steps = 0
        self._tracing = False
        self._telemetry = telemetry
        if handler.with_flops:
            # record XLA cost analyses of every compiled step executed
            # during the session (dumped to flops.json at exit)
            from .lazy import set_cost_collection

            set_cost_collection(True)

    def _maybe_start(self):
        if self.schedule(self.step_num) == "active" and not self._tracing:
            jax.profiler.start_trace(
                self.trace_dir,
                create_perfetto_trace=bool(self.handler.with_stack),
            )
            self._tracing = True

    def _maybe_stop(self):
        if self._tracing and self.schedule(self.step_num) != "active":
            jax.profiler.stop_trace()
            self._tracing = False

    def step(self):
        if self.schedule(self.step_num) == "active":
            self.active_steps += 1
        if self.handler.profile_memory and self.schedule(self.step_num) == "active":
            import os as _os

            jax.profiler.save_device_memory_profile(
                _os.path.join(self.trace_dir, f"memory_{self.step_num}.prof")
            )
        self.step_num += 1
        self._maybe_stop()
        self._maybe_start()

    def _finish(self):
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
        if self.handler.with_flops:
            import json as _json
            import os as _os

            from .lazy import PROFILE_COST_STATS, set_cost_collection

            set_cost_collection(False)
            # the tracer creates trace_dir only when a window went active
            _os.makedirs(self.trace_dir, exist_ok=True)
            with open(_os.path.join(self.trace_dir, "flops.json"), "w") as f:
                _json.dump(
                    {
                        "compiled_programs": PROFILE_COST_STATS,
                        "total_flops": sum(
                            s["flops"] for s in PROFILE_COST_STATS if s.get("flops")
                        ),
                    },
                    f,
                )
        if self._telemetry:
            self._telemetry.record_profile(
                trace_dir=self.trace_dir,
                steps=self.step_num,
                active_steps=self.active_steps,
            )


class Accelerator:
    """Create once, ``prepare()`` your objects, train (reference
    ``Accelerator`` class ``accelerator.py:162``)."""

    _os_kernel_checked = False  # one warning per process, not per instance
    _dynamo_warned = False      # ditto for the no-op dynamo_backend knob

    def __init__(
        self,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: str | None = None,
        gradient_accumulation_steps: int = 1,
        cpu: bool = False,
        dataloader_config: DataLoaderConfiguration | None = None,
        deepspeed_plugin: DeepSpeedPlugin | dict[str, DeepSpeedPlugin] | None = None,
        fsdp_plugin: FullyShardedDataParallelPlugin | None = None,
        megatron_lm_plugin=None,
        mesh_plugin: MeshPlugin | None = None,
        context_parallel_plugin=None,
        rng_types: list[str] | None = None,
        log_with=None,
        project_dir: str | None = None,
        project_config: ProjectConfiguration | None = None,
        gradient_accumulation_plugin: GradientAccumulationPlugin | None = None,
        step_scheduler_with_optimizer: bool = True,
        kwargs_handlers: list | None = None,
        dynamo_backend=None,  # accepted for parity; XLA always compiles
        even_batches: bool = True,
        use_seedable_sampler: bool = False,
        telemetry: bool | None = None,
        fault_tolerance: FaultTolerancePlugin | bool | None = None,
        diagnostics: DiagnosticsPlugin | bool | None = None,
        sanitize: bool | None = None,
    ):
        self.project_configuration = project_config or ProjectConfiguration(project_dir=project_dir)
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)

        # plugin resolution from args/env (reference :293-376)
        if deepspeed_plugin is None and os.environ.get("ACCELERATE_USE_DEEPSPEED", "false") == "true":
            deepspeed_plugin = DeepSpeedPlugin()
        if fsdp_plugin is None and os.environ.get("ACCELERATE_USE_FSDP", "false") == "true":
            fsdp_plugin = FullyShardedDataParallelPlugin()
        # several named plugins may coexist (reference supports a dict with
        # runtime selection, ``utils/deepspeed.py:25-41``); the first is
        # active until ``state.select_deepspeed_plugin(name)`` switches
        if isinstance(deepspeed_plugin, dict):
            if not deepspeed_plugin:
                raise ValueError("deepspeed_plugin dict must not be empty")
            for key, p in deepspeed_plugin.items():
                if not isinstance(p, DeepSpeedPlugin):
                    raise TypeError(
                        f"deepspeed_plugin[{key!r}] must be a DeepSpeedPlugin, "
                        f"got {type(p).__name__}"
                    )
                p._unselect()
            next(iter(deepspeed_plugin.values())).select(_from_accelerator_state=True)
        self._deepspeed_plugins = deepspeed_plugin
        active_ds = (
            next(p for p in deepspeed_plugin.values() if p.selected)
            if isinstance(deepspeed_plugin, dict)
            else deepspeed_plugin
        )
        if active_ds is not None and fsdp_plugin is None:
            fsdp_plugin = active_ds.to_fsdp_plugin()
        self.fsdp_plugin = fsdp_plugin
        self.megatron_lm_plugin = megatron_lm_plugin
        self.context_parallel_plugin = context_parallel_plugin

        # Megatron facade lowers onto mesh axes (SURVEY §2.2: tp_degree →
        # tp axis; pp_degree → pp axis, which runs the GPipe schedule in
        # parallel/pipeline.py for stacked-layer models). Megatron-SP shards
        # activations over the EXISTING tp group, which has no 1:1 GSPMD
        # mapping here; the cp axis is this framework's (strictly more
        # general) sequence sharding, so the flag only points users there
        # rather than silently multiplying the device requirement.
        if megatron_lm_plugin is not None and mesh_plugin is None:
            if getattr(megatron_lm_plugin, "sequence_parallelism", False):
                logger.info(
                    "Megatron sequence_parallelism maps onto the cp mesh axis "
                    "here; size it explicitly (MeshPlugin(cp=...) or "
                    "--mesh_cp) to shard sequence activations"
                )
            # duck-typed: upstream-accelerate MegatronLMPlugin objects have
            # the degree fields but not our to_mesh_axes()
            if hasattr(megatron_lm_plugin, "to_mesh_axes"):
                mesh_plugin = MeshPlugin(**megatron_lm_plugin.to_mesh_axes())
            else:
                mesh_plugin = MeshPlugin(
                    tp=getattr(megatron_lm_plugin, "tp_degree", 1),
                    pp=getattr(megatron_lm_plugin, "pp_degree", 1),
                )

        # torch.compile has no TPU meaning (XLA always compiles); accept the
        # knob for config parity but never silently — a user passing a real
        # backend should know it does nothing here.
        self.dynamo_backend = dynamo_backend
        if (
            dynamo_backend is not None
            and str(dynamo_backend).lower() != "no"  # reference spells it "NO"
            and not Accelerator._dynamo_warned
        ):
            Accelerator._dynamo_warned = True
            logger.warning(
                "dynamo_backend=%r has no effect on TPU: every prepared step "
                "is already XLA-compiled. The flag is accepted for config "
                "compatibility only.",
                dynamo_backend,
            )

        # kwargs handlers (reference :387-421)
        from .ops.fp8 import FP8RecipeKwargs

        self.scaler_handler = None
        self.init_handler = None
        self.profile_handler = None
        self.fp8_recipe_handler = None
        self.ddp_handler = None
        for handler in kwargs_handlers or []:
            if isinstance(handler, GradScalerKwargs):
                self.scaler_handler = handler
            elif isinstance(handler, InitProcessGroupKwargs):
                self.init_handler = handler
            elif isinstance(handler, ProfileKwargs):
                self.profile_handler = handler
            elif isinstance(handler, FP8RecipeKwargs):
                self.fp8_recipe_handler = handler
            elif isinstance(handler, DistributedDataParallelKwargs):
                self.ddp_handler = handler

        init_kwargs = self.init_handler.to_kwargs() if self.init_handler else {}
        self.state = AcceleratorState(
            mixed_precision=mixed_precision,
            cpu=cpu,
            mesh_plugin=mesh_plugin,
            fsdp_plugin=fsdp_plugin,
            _from_accelerator=True,
            **init_kwargs,
        )
        # AcceleratorState is shared (Borg): only publish plugins this
        # Accelerator actually brought — a later plain Accelerator() must
        # not clear an earlier one's registration
        if self._deepspeed_plugins is not None:
            self.state.deepspeed_plugins = self._deepspeed_plugins

        # attention routing: bake the cp mode + mesh into every step compiled
        # from here on (models read this at trace time)
        from .ops.attention import AttentionContext, set_attention_context

        cp_mode = None
        pp_microbatches = 0
        mesh_shape = dict(self.state.mesh.shape)
        if mesh_shape.get("pp", 1) > 1:
            # fail at construction, not at the first forward
            from .parallel.pipeline import validate_pipeline_axes

            validate_pipeline_axes(mesh_shape)

            # honour the requested schedule depth (reference field
            # ``num_micro_batches``, utils/dataclasses.py:1912). Our plugin
            # defaults to 0 (= auto) so an explicit 1 is honoured; foreign
            # duck-typed plugins default to 1, which means "unset" there —
            # see the MegatronLMPlugin docstring for the coercion rule
            _mb = getattr(megatron_lm_plugin, "num_micro_batches", 0) or 0
            if not isinstance(megatron_lm_plugin, MegatronLMPlugin):
                _mb = _mb if _mb > 1 else 0
            pp_microbatches = _mb
        if mesh_shape.get("cp", 1) > 1:
            if context_parallel_plugin is not None:
                cp_mode = context_parallel_plugin.mode
            else:
                # honour `launch --cp_mode` / config (written as ACCELERATE_CP_MODE);
                # a cp axis in the mesh defaults to ring attention
                cp_mode = os.environ.get("ACCELERATE_CP_MODE", "ring")
                if cp_mode not in ("ring", "ulysses", "allgather"):
                    raise ValueError(
                        f"ACCELERATE_CP_MODE={cp_mode!r} — expected ring|ulysses|allgather"
                    )
            import re as _re

            timeout_match = _re.search(
                r"collective_call_terminate_timeout_seconds=(\d+)",
                os.environ.get("XLA_FLAGS", ""),
            )
            # ≥300s gives a 1-core host room to schedule the subgroup
            # collectives; a smaller value is as unsafe as none. (A flag
            # exported after backend init is undetectable — the launcher
            # and test conftest both set it before.)
            timeout_ok = timeout_match is not None and int(timeout_match.group(1)) >= 300
            if (
                cp_mode == "ring"
                and self.device.platform == "cpu"
                and mesh_shape.get("dp", 1) > 1
                and not timeout_ok
            ):
                # On few-core hosts, XLA CPU's default 40s collective
                # rendezvous window ABORTS training programs that mix
                # per-dp-replica cp ppermute subgroups with dp reduction
                # groups (slow cross-subgroup scheduling, not a true
                # deadlock — verified to complete with the window raised).
                # The launcher/conftest set
                # --xla_cpu_collective_call_terminate_timeout_seconds, which
                # lets the real ring run; without it, protect the user with
                # the numerically identical allgather formulation:
                logger.warning(
                    "cp_mode='ring' with dp>1 runs as 'allgather' on the CPU "
                    "debug backend without "
                    "--xla_cpu_collective_call_terminate_timeout_seconds in "
                    "XLA_FLAGS (the default 40s rendezvous window aborts); "
                    "TPU executes the real ring"
                )
                cp_mode = "allgather"
        # Megatron-SP (reference dataclasses.py:1916-1919,2112): under
        # tp>1 the norm/residual-region activations are sequence-sharded
        # over the SAME tp group — models consult this flag at their
        # residual sharding constraints (models/llama.py residual_spec)
        # and GSPMD inserts the all-gather into / reduce-scatter out of
        # the matmul regions that Megatron codes by hand.
        megatron_sp = bool(
            megatron_lm_plugin is not None
            and getattr(megatron_lm_plugin, "sequence_parallelism", False)
            and mesh_shape.get("tp", 1) > 1
        )
        set_attention_context(
            AttentionContext(
                mesh=self.state.mesh, cp_mode=cp_mode,
                pipeline_microbatches=pp_microbatches, megatron_sp=megatron_sp,
            )
        )

        self.dataloader_config = dataloader_config or DataLoaderConfiguration(
            split_batches=split_batches,
            even_batches=even_batches,
            use_seedable_sampler=use_seedable_sampler,
        )
        if gradient_accumulation_plugin is None:
            env_steps = int(os.environ.get("ACCELERATE_GRADIENT_ACCUMULATION_STEPS", 1))
            steps = gradient_accumulation_steps if gradient_accumulation_steps > 1 else env_steps
            if steps == 1 and active_ds is not None:
                # a ds-config's accumulation governs the loop (reference
                # merges it in ``accelerator.py:1669-1830``)
                steps = active_ds.gradient_accumulation_steps
            gradient_accumulation_plugin = GradientAccumulationPlugin(num_steps=steps)
        self.gradient_state = GradientState(gradient_accumulation_plugin=gradient_accumulation_plugin)

        self.device_placement = device_placement
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer
        self.rng_types = rng_types or ["python", "numpy", "jax"]

        # one-time old-kernel warning (reference accelerator.py:544)
        if not Accelerator._os_kernel_checked:
            Accelerator._os_kernel_checked = True
            from .utils.other import check_os_kernel

            check_os_kernel()

        # fp16 → dynamic loss scaler (reference GradScaler semantics,
        # accelerator.py:496-520); bf16 needs none. GradScalerKwargs drives
        # init/growth/backoff; enabled=False opts out entirely.
        self._loss_scale = None
        if self.mixed_precision == "fp16" and (
            self.scaler_handler is None or self.scaler_handler.enabled
        ):
            from .optimizer import LossScaler

            h = self.scaler_handler
            self._loss_scale = LossScaler(
                init_scale=h.init_scale if h else 65536.0,
                growth_factor=h.growth_factor if h else 2.0,
                backoff_factor=h.backoff_factor if h else 0.5,
                growth_interval=h.growth_interval if h else 2000,
            )

        # DDP communication hook analog: compressed dp-axis gradient
        # reduction (reference DDPCommunicationHookType, dataclasses.py:117).
        # bf16/fp16 halve the gradient-sync bytes-on-wire — on a multi-slice
        # DCN mesh that is the same lever the reference's hook pulls on the
        # NCCL ring. DP-only, like the reference's DDP scope.
        self._grad_comm_hook = None
        hook = str(getattr(self.ddp_handler, "comm_hook", "no") or "no").lower()
        if hook not in ("no", "none"):
            shape = dict(self.mesh.shape) if self.mesh is not None else {}
            dp_only = all(shape.get(a, 1) == 1 for a in ("tp", "pp", "cp", "ep", "fsdp"))
            if hook in ("bf16", "fp16") and dp_only and shape.get("dp", 1) > 1:
                self._grad_comm_hook = hook
            elif hook in ("bf16", "fp16"):
                logger.warning(
                    "comm_hook=%r needs a data-parallel-only mesh with dp>1 "
                    "(got %s); gradients keep the default full-precision "
                    "reduction", hook, shape,
                )
            else:
                logger.warning(
                    "comm_hook=%r is not supported on TPU (powerSGD-style "
                    "hooks have no XLA lowering here); choose 'bf16' or "
                    "'fp16'", hook,
                )

        self._models: list[PreparedModel] = []
        self._optimizers: list[AcceleratedOptimizer] = []
        self._schedulers: list[AcceleratedScheduler] = []
        self._dataloaders: list[DataLoaderShard] = []
        self._custom_objects: list = []
        self.step = 0
        self.flag_tensor = None

        from .tracking import filter_trackers

        self.log_with = filter_trackers(log_with, self.logging_dir)
        self.trackers = []

        # step-level telemetry (telemetry.py): opt-in via the constructor or
        # ACCELERATE_TELEMETRY=1; disabled holds the no-op singleton so the
        # hot path pays one attribute read
        from .telemetry import NULL_TELEMETRY, TelemetryRecorder, set_active_recorder
        from .utils.environment import parse_flag_from_env

        if telemetry is None:
            telemetry = parse_flag_from_env("ACCELERATE_TELEMETRY")
        if telemetry:
            self.telemetry = TelemetryRecorder(
                logging_dir=self.logging_dir,
                tracker_sink=self._telemetry_tracker_sink,
            )
            set_active_recorder(self.telemetry)
        else:
            self.telemetry = NULL_TELEMETRY
            # Borg semantics: the newest Accelerator owns the process-wide
            # observability state — a disabled one must silence a stale
            # recorder left by an earlier telemetry=True instance, or
            # "disabled" keeps writing to the old run's trail
            from .lazy import set_compile_callback

            set_active_recorder(None)
            set_compile_callback(None)

        # in-process metrics registry (metrics/): ACCELERATE_METRICS=1 arms
        # the GET /metrics aggregation surface the telemetry/span hooks
        # feed (main-process-gated; the sidecar `accelerate-tpu metrics
        # export` covers jobs that leave this off)
        from .metrics.registry import MetricsRegistry, set_active_registry

        if parse_flag_from_env("ACCELERATE_METRICS"):
            self.metrics_registry = MetricsRegistry()
            set_active_registry(self.metrics_registry)
        else:
            from .metrics.registry import get_active_registry

            # no takeover here (unlike telemetry): a registry set by an
            # outer owner — the serve CLI's /metrics surface — must keep
            # aggregating across Accelerator constructions
            self.metrics_registry = get_active_registry()

        # diagnostics (tracing + hang watchdog, diagnostics/): opt-in via
        # the constructor or ACCELERATE_DIAGNOSTICS=1; same Borg takeover
        # semantics as telemetry — the newest Accelerator owns the
        # process-wide tracer/watchdog
        from .diagnostics import NULL_TRACER, Tracer, Watchdog, get_tracer, set_active_tracer
        from .diagnostics.watchdog import get_active_watchdog

        if diagnostics is None:
            diagnostics = parse_flag_from_env("ACCELERATE_DIAGNOSTICS")
        if diagnostics is True:
            diagnostics = DiagnosticsPlugin()
        elif diagnostics is False:
            diagnostics = None
        self.diagnostics_plugin: DiagnosticsPlugin | None = diagnostics
        self.tracer = NULL_TRACER
        self.watchdog = None
        stale_watchdog = get_active_watchdog()
        if stale_watchdog is not None:
            stale_watchdog.stop()
        stale_tracer = get_tracer()
        if stale_tracer:
            # flush+close BEFORE a new tracer appends its clock_sync: the
            # old instance's buffered events must not land after the new
            # epoch marker, or the merge shifts them with the wrong offset
            stale_tracer.close()
        if diagnostics is not None and diagnostics.tracing:
            self.tracer = Tracer(
                logging_dir=self.logging_dir,
                buffer_events=diagnostics.trace_buffer_events,
            )
            set_active_tracer(self.tracer)
        else:
            set_active_tracer(None)
        if diagnostics is not None and diagnostics.watchdog:
            self.watchdog = Watchdog(
                logging_dir=self.logging_dir,
                multiplier=diagnostics.watchdog_multiplier,
                floor_seconds=diagnostics.watchdog_floor_seconds,
                check_interval_seconds=diagnostics.watchdog_check_seconds,
                ema_alpha=diagnostics.watchdog_ema_alpha,
                heartbeat_interval_seconds=diagnostics.heartbeat_interval_seconds,
                grace_seconds=diagnostics.watchdog_grace_seconds,
                telemetry_tail=diagnostics.watchdog_telemetry_tail,
                preempt_on_hang=diagnostics.preempt_on_hang,
                telemetry=self.telemetry if self.telemetry else None,
            )
            self.watchdog.start()

        # runtime sanitizer (analysis/): opt-in via the constructor or
        # ACCELERATE_SANITIZE=1 — recompile naming, donation report,
        # per-host collective digests, NaN/inf loss probe. Same Borg
        # takeover as telemetry: the newest Accelerator owns the
        # process-wide sanitizer, and disabled mode is one global read
        # at every instrumentation site
        if sanitize is None:
            sanitize = parse_flag_from_env("ACCELERATE_SANITIZE")
        if sanitize:
            self.sanitizer = Sanitizer(logging_dir=self.logging_dir)
            _set_sanitizer(self.sanitizer)
        else:
            self.sanitizer = None
            _set_sanitizer(None)

        # fault tolerance (resilience subsystem): opt-in via the
        # constructor, ACCELERATE_FAULT_TOLERANCE=1, or — so launcher
        # restarts are preemption-safe too — ACCELERATE_AUTO_RESUME=1
        if fault_tolerance is None and (
            parse_flag_from_env("ACCELERATE_FAULT_TOLERANCE")
            or parse_flag_from_env("ACCELERATE_AUTO_RESUME")
        ):
            fault_tolerance = True
        if fault_tolerance is True:
            fault_tolerance = FaultTolerancePlugin()
        elif fault_tolerance is False:
            fault_tolerance = None
        self.fault_tolerance_plugin: FaultTolerancePlugin | None = fault_tolerance
        self._preemption_handler = None
        self._ft_boundary_count = 0
        if fault_tolerance is not None:
            fault_tolerance.export_io_env()
            from .resilience.preemption import PreemptionHandler

            self._preemption_handler = PreemptionHandler(
                handle_sigint=fault_tolerance.handle_sigint,
                monitor_maintenance=fault_tolerance.monitor_maintenance,
                poll_seconds=fault_tolerance.maintenance_poll_seconds,
                handle_signals=fault_tolerance.handle_signals,
            )
            self._preemption_handler.install()

    # ------------------------------------------------------------------
    # properties delegating to state (reference :525-760)
    # ------------------------------------------------------------------

    @property
    def distributed_type(self):
        return self.state.distributed_type

    @property
    def deepspeed_plugin(self):
        """The ACTIVE DeepSpeedPlugin (or None): with a dict of named
        plugins, selection via ``state.select_deepspeed_plugin(name)``
        changes what this returns (reference ``utils/deepspeed.py:25``)."""
        if self._deepspeed_plugins is None:
            return None
        from .utils.deepspeed import get_active_deepspeed_plugin

        return get_active_deepspeed_plugin(self.state)

    @property
    def deepspeed_plugins(self):
        return self._deepspeed_plugins

    @property
    def num_processes(self):
        return self.state.num_processes

    @property
    def process_index(self):
        return self.state.process_index

    @property
    def local_process_index(self):
        return self.state.local_process_index

    @property
    def device(self):
        return self.state.device

    @property
    def mesh(self):
        return self.state.mesh

    @property
    def is_main_process(self):
        return self.state.is_main_process

    @property
    def is_local_main_process(self):
        return self.state.is_local_main_process

    @property
    def is_last_process(self):
        return self.state.is_last_process

    @property
    def use_distributed(self):
        return self.state.use_distributed

    @property
    def mixed_precision(self):
        return self.state.mixed_precision

    @property
    def scaler(self):
        """The fp16 :class:`~accelerate_tpu.optimizer.LossScaler` (None
        outside fp16) — reference ``self.scaler``, ``accelerator.py:496``."""
        return self._loss_scale

    @property
    def split_batches(self):
        return self.dataloader_config.split_batches

    @property
    def even_batches(self):
        return self.dataloader_config.even_batches

    @even_batches.setter
    def even_batches(self, value):
        self.dataloader_config.even_batches = value

    @property
    def use_seedable_sampler(self):
        return self.dataloader_config.use_seedable_sampler

    @property
    def non_blocking(self):
        return self.dataloader_config.non_blocking

    @property
    def project_dir(self):
        return self.project_configuration.project_dir

    @property
    def logging_dir(self):
        return self.project_configuration.logging_dir

    @property
    def save_iteration(self):
        return self.project_configuration.iteration

    @property
    def sync_gradients(self):
        return self.gradient_state.sync_gradients

    @sync_gradients.setter
    def sync_gradients(self, value):
        self.gradient_state.sync_gradients = value

    @property
    def gradient_accumulation_steps(self):
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, value):
        self.gradient_state.plugin_kwargs.update({"num_steps": value})

    @property
    def compute_dtype(self):
        # fp8: non-matmul compute stays bf16; the zoo's dense projections
        # additionally lower to scaled-float8 matmuls (ops/fp8.py) via the
        # recipe attached in prepare_model
        return {
            "bf16": jnp.bfloat16,
            "fp16": jnp.float16,
            "fp8": jnp.bfloat16,
        }.get(self.mixed_precision)

    # ------------------------------------------------------------------
    # process control (delegation)
    # ------------------------------------------------------------------

    def wait_for_everyone(self):
        self.state.wait_for_everyone()

    def print(self, *args, **kwargs):
        self.state.print(*args, **kwargs)

    def on_main_process(self, function):
        return self.state.on_main_process(function)

    def on_local_main_process(self, function):
        return self.state.on_local_main_process(function)

    def on_last_process(self, function):
        return self.state.on_last_process(function)

    def on_process(self, function=None, process_index=None):
        return self.state.on_process(function, process_index)

    def on_local_process(self, function=None, local_process_index=None):
        return self.state.on_local_process(function, local_process_index)

    @contextlib.contextmanager
    def main_process_first(self):
        with self.state.main_process_first():
            yield

    @contextlib.contextmanager
    def local_main_process_first(self):
        with self.state.local_main_process_first():
            yield

    def split_between_processes(self, inputs, apply_padding: bool = False):
        return self.state.split_between_processes(inputs, apply_padding=apply_padding)

    # ------------------------------------------------------------------
    # prepare
    # ------------------------------------------------------------------

    def prepare(self, *args, device_placement: list[bool] | None = None):
        """Shard, place, and wrap objects (reference ``prepare``
        ``accelerator.py:1225``). Pass any combination of models
        (:class:`Model` / flax module+params), optax transformations,
        dataloaders and schedule fns; order is preserved."""
        from .diagnostics.tracing import trace_span

        # the module-level entry point (not self.tracer.span) so a
        # watchdog-only configuration still sees prepare as live progress
        with trace_span("prepare", n_objects=len(args)):
            return self._prepare_inner(*args, device_placement=device_placement)

    def _prepare_inner(self, *args, device_placement: list[bool] | None = None):
        import time as _time

        _prepare_t0 = _time.perf_counter()
        _models_before = len(self._models)
        if device_placement is None:
            device_placement = [None] * len(args)

        # ds-config-driven placeholders → real optax objects (reference
        # utils/deepspeed.py:229-290; engine-built at accelerator.py:1651+)
        from .utils.deepspeed import (
            DummyOptim,
            DummyScheduler,
            optimizer_from_ds_config,
            scheduler_from_ds_config,
        )

        ds_cfg = getattr(self.deepspeed_plugin, "deepspeed_config", None)
        if any(isinstance(a, (DummyOptim, DummyScheduler)) for a in args):
            if self.deepspeed_plugin is None:
                raise ValueError(
                    "DummyOptim/DummyScheduler require a DeepSpeedPlugin "
                    "(usually with a config file defining the "
                    "optimizer/scheduler sections)"
                )
            # resolve the optimizer lr first: an "auto" warmup_max_lr in the
            # scheduler section fills from it (reference semantics)
            opt_lr = None
            for a in args:
                if isinstance(a, DummyOptim):
                    opt_params = dict((ds_cfg or {}).get("optimizer", {}).get("params", {}))
                    raw_lr = opt_params.get("lr")
                    opt_lr = a.lr if raw_lr in (None, "auto") else float(raw_lr)
            args = tuple(
                optimizer_from_ds_config(ds_cfg, a) if isinstance(a, DummyOptim)
                else scheduler_from_ds_config(ds_cfg, a, optimizer_lr=opt_lr)
                if isinstance(a, DummyScheduler)
                else a
                for a in args
            )

        # pass 1: everything except schedulers (they need bound optimizers)
        prepared = []
        for obj, dp in zip(args, device_placement):
            if _is_model(obj):
                prepared.append(self.prepare_model(obj, device_placement=dp))
            elif _is_optimizer(obj):
                prepared.append(self.prepare_optimizer(obj, device_placement=dp))
            elif _is_dataloader(obj):
                prepared.append(self.prepare_data_loader(obj, device_placement=dp))
            else:
                prepared.append(obj)

        # bind optimizers to models by position pairing
        models = [p for p in prepared if isinstance(p, PreparedModel)]
        optimizers = [p for p in prepared if isinstance(p, AcceleratedOptimizer)]
        for i, opt in enumerate(optimizers):
            if opt.model is None:
                model = models[min(i, len(models) - 1)] if models else None
                if model is None:
                    raise ValueError("an optimizer was passed to prepare() without any model")
                opt_sharding = opt_state_sharding_like(
                    opt.optimizer, model.params, model.param_sharding, self.mesh
                )
                opt.bind(model, opt_state_sharding=opt_sharding)

        # pass 2: schedulers
        result = []
        for obj, p in zip(args, prepared):
            if p is obj and _is_scheduler(obj):
                result.append(self.prepare_scheduler(obj))
            else:
                result.append(p)
        if self.deepspeed_plugin is not None:
            self._fill_deepspeed_auto()
        self._maybe_auto_resume()
        if self.telemetry:
            self.telemetry.record_event(
                "prepare",
                seconds=_time.perf_counter() - _prepare_t0,
                n_objects=len(args),
                n_params=sum(
                    m.num_parameters() for m in self._models[_models_before:]
                ),
            )
        return result[0] if len(result) == 1 else tuple(result)

    # ------------------------------------------------------------------
    # fault tolerance (resilience subsystem)
    # ------------------------------------------------------------------

    @property
    def preemption_requested(self) -> bool:
        """Has a SIGTERM/SIGINT/maintenance event raised the LOCAL
        preemption flag? (Cross-host agreement happens in
        :meth:`check_preemption`.)"""
        return (
            self._preemption_handler is not None
            and self._preemption_handler.preemption_requested
        )

    def check_preemption(self):
        """Step-boundary preemption check (called from ``backward``; user
        loops that never call backward — eval sweeps — may call it
        directly). Every ``consensus_interval`` boundaries the local flag
        is all-reduced across hosts; on agreement, ONE synchronized
        emergency ``save_state()`` runs and the process exits cleanly with
        a sentinel file. Collective cadence: all processes count the same
        boundaries, so the all-reduce lines up.

        Mid-accumulation the save is DEFERRED to the window boundary (a
        save with half a gradient window pending would drop those
        micro-batches' work while their dataloader positions stay
        consumed), bounded at 2× the window so a pathological loop still
        saves before the preemption deadline. The batch whose ``backward``
        triggered the check never trains — resume is within ONE optimizer
        step of the kill, never worse."""
        handler = self._preemption_handler
        if handler is None:
            return
        plugin = self.fault_tolerance_plugin
        self._ft_boundary_count += 1
        multi = self.num_processes > 1
        if multi:
            if self._ft_boundary_count % plugin.consensus_interval != 0:
                return
            preempt = handler.consensus()
        else:
            preempt = handler.preemption_requested
        if not preempt:
            return
        # clean window boundary: no parked loss, no accumulated grads
        # (deterministic across hosts — every host runs the same schedule)
        clean = all(
            o._pending_loss is None and o._grads is None for o in self._optimizers
        )
        if not clean:
            self._ft_deferred_boundaries = getattr(self, "_ft_deferred_boundaries", 0) + 1
            if self._ft_deferred_boundaries <= max(2 * self.gradient_accumulation_steps, 4):
                return
            logger.warning(
                "emergency save forced mid-accumulation after %d deferrals "
                "(the partial gradient window is dropped)",
                self._ft_deferred_boundaries,
            )
        self._emergency_save_and_exit()

    def _emergency_save_and_exit(self):
        handler = self._preemption_handler
        plugin = self.fault_tolerance_plugin
        reason = handler.reason or "preemption"
        logger.warning("preemption consensus (%s): emergency checkpoint", reason)
        if self.watchdog is not None:
            # the emergency save may legitimately take longer than a step
            # deadline; a hang report fired *during* the save would be noise
            self.watchdog.stop()
        checkpoint = None
        if plugin.save_on_preemption:
            if self.project_dir is None:
                logger.warning(
                    "emergency save skipped: no project_dir configured on "
                    "this Accelerator"
                )
            else:
                try:
                    # synchronous on purpose: durability outranks step time
                    # when the host is about to disappear
                    checkpoint = self.save_state()
                except Exception:
                    logger.error("emergency save FAILED", exc_info=True)
        if self.telemetry:
            self.telemetry.record_event(
                "preemption", reason=reason, checkpoint=checkpoint, step=self.step
            )
            self.telemetry.close()
        if self.tracer:
            self.tracer.instant("preemption", reason=reason)
            self.tracer.close()
        sentinel_dir = (
            os.path.join(self.project_dir, "checkpoints")
            if self.project_dir is not None
            else os.getcwd()
        )
        if self.is_main_process:
            handler.write_sentinel(sentinel_dir, checkpoint, self.step)
        handler.uninstall()
        logger.warning(
            "exiting cleanly after preemption (checkpoint=%s, exit_code=%d)",
            checkpoint, plugin.exit_code,
        )
        raise SystemExit(plugin.exit_code)

    def _maybe_auto_resume(self):
        """Launcher fault tolerance: a run re-exec'd by ``accelerate-tpu
        launch --max_restarts`` carries ``ACCELERATE_AUTO_RESUME=true``; once
        the training objects are prepared, reload the latest ``checkpoint_*``
        under the project_dir so the restarted process continues where the
        crashed one last saved (SURVEY §5 checkpoint-autoresume — the
        TPU-native stand-in for torchrun's elastic restarts, reference
        ``launchers.py:231-245``)."""
        from .utils.environment import parse_flag_from_env

        # Re-resume on EVERY prepare() until training starts (first
        # backward): a script may prepare its objects across several calls
        # (loader first, model+opt later), and a resume that fired after
        # the first call would leave the later objects at fresh init —
        # silent divergence. Once grads have flowed, further prepare()
        # calls must NOT clobber live training state with the checkpoint.
        if getattr(self, "_training_started", False):
            return
        plugin_resume = (
            self.fault_tolerance_plugin is not None
            and self.fault_tolerance_plugin.auto_resume
        )
        if not (plugin_resume or parse_flag_from_env("ACCELERATE_AUTO_RESUME")):
            return
        if self.project_dir is None:
            return
        from .resilience.manifest import SENTINEL_NAME, find_latest_valid_checkpoint

        checkpoints_dir = os.path.join(self.project_dir, "checkpoints")
        # manifest-validated selection: corrupt/partial checkpoints (and
        # `.tmp` dirs from an interrupted save) are skipped for the newest
        # one that verifies completely. Multi-host: the MAIN process alone
        # validates (one CRC pass over the candidates, not host_count of
        # them) and broadcasts its choice — per-host selection could
        # diverge if validation raced a commit/rotation, silently resuming
        # different checkpoints on different hosts.
        if self.num_processes > 1:
            from .operations import broadcast_object_list

            choice = [
                find_latest_valid_checkpoint(checkpoints_dir)
                if self.is_main_process
                else None
            ]
            latest = broadcast_object_list(choice)[0]
        else:
            latest = find_latest_valid_checkpoint(checkpoints_dir)
        if latest is None:
            if not getattr(self, "_auto_resume_warned", False):
                self._auto_resume_warned = True
                logger.warning(
                    "auto-resume is on but no valid checkpoint_* exists under "
                    "%s; starting fresh", checkpoints_dir
                )
            return
        logger.info("auto-resuming from %s", latest)
        self.load_state(latest)
        sentinel = os.path.join(checkpoints_dir, SENTINEL_NAME)
        if self.is_main_process and os.path.exists(sentinel):
            # consumed: this run IS the resume the sentinel asked for
            try:
                os.remove(sentinel)
            except OSError:
                pass

    def _fill_deepspeed_auto(self):
        """Resolve ``"auto"`` entries of an ingested DeepSpeed config file
        from the prepared objects (reference ``accelerator.py:1669-1830``)."""
        values = {
            "gradient_accumulation_steps": self.gradient_accumulation_steps,
            "zero_optimization.stage": self.deepspeed_plugin.zero_stage,
        }
        if self.deepspeed_plugin.gradient_clipping is not None:
            values["gradient_clipping"] = self.deepspeed_plugin.gradient_clipping
        if self._dataloaders:
            try:
                total = self._dataloaders[0].total_batch_size
                micro = max(total // max(self.state.data_parallel_size, 1), 1)
                values["train_micro_batch_size_per_gpu"] = micro
                values["train_batch_size"] = total * self.gradient_accumulation_steps
            except (ValueError, AttributeError):
                pass
        if self._optimizers:
            lr = self._optimizers[0].learning_rate
            if lr is not None:
                values["optimizer.params.lr"] = lr
        self.deepspeed_plugin.fill_auto(values)

    def prepare_model(self, model, device_placement: bool | None = None, evaluation_mode: bool = False):
        """(Reference ``prepare_model`` ``accelerator.py:1361``.)"""
        if isinstance(model, PreparedModel):
            return model
        model = _as_model(model)
        # FSDP activation checkpointing → the model's remat knob (reference
        # wires torch's checkpoint_wrapper at ``accelerator.py:1523``). Only
        # upgrades: a model already configured to remat keeps its setting.
        if (
            self.fsdp_plugin is not None
            and getattr(self.fsdp_plugin, "activation_checkpointing", False)
            and hasattr(model, "config")
            and hasattr(model.config, "remat")
            and not model.config.remat
        ):
            model.config.remat = True
        rules = model.partition_rules
        sharding = infer_param_sharding(model.params, self.mesh, self.fsdp_plugin, rules)
        params = shard_params(model.params, sharding)
        prepared = PreparedModel(
            model,
            accelerator=self,
            compute_dtype=self.compute_dtype,
            param_sharding=sharding,
        )
        if self.mixed_precision == "fp8":
            from .ops.fp8 import FP8RecipeKwargs

            prepared.fp8_recipe = self.fp8_recipe_handler or FP8RecipeKwargs()
        prepared.params = params
        prepared.training = not evaluation_mode
        self._models.append(prepared)
        return prepared

    def prepare_optimizer(self, optimizer, device_placement: bool | None = None):
        if isinstance(optimizer, AcceleratedOptimizer):
            return optimizer
        wrapped = AcceleratedOptimizer(optimizer, scaler=self._loss_scale)
        if self._grad_comm_hook is not None:
            wrapped.comm_hook = (self._grad_comm_hook, self.mesh)
        if self.telemetry:
            wrapped.telemetry = self.telemetry
        if self.watchdog is not None:
            wrapped.watchdog = self.watchdog
        self._optimizers.append(wrapped)
        return wrapped

    def prepare_data_loader(self, data_loader, device_placement: bool | None = None, slice_fn_for_dispatch=None):
        if isinstance(data_loader, DataLoaderShard):
            return data_loader
        prepared = prepare_data_loader(
            data_loader,
            num_processes=self.num_processes,
            process_index=self.process_index,
            split_batches=self.split_batches,
            put_on_device=device_placement if device_placement is not None else self.device_placement,
            rng_types=self.rng_types,
            dispatch_batches=self.dataloader_config.dispatch_batches,
            even_batches=self.even_batches,
            use_seedable_sampler=self.use_seedable_sampler,
            slice_fn_for_dispatch=slice_fn_for_dispatch,
            use_stateful_dataloader=self.dataloader_config.use_stateful_dataloader,
            sharding=data_sharding(self.mesh),
            prefetch_batches=self.dataloader_config.prefetch_batches,
        )
        self._dataloaders.append(prepared)
        return prepared

    def prepare_scheduler(self, scheduler):
        if isinstance(scheduler, AcceleratedScheduler):
            return scheduler
        wrapped = AcceleratedScheduler(
            scheduler,
            self._optimizers,
            step_with_optimizer=self.step_scheduler_with_optimizer,
            split_batches=self.split_batches,
        )
        self._schedulers.append(wrapped)
        return wrapped

    # ------------------------------------------------------------------
    # training step surface
    # ------------------------------------------------------------------

    def backward(self, loss, **kwargs):
        """Stage gradients of a deferred loss (reference ``backward``
        ``accelerator.py:2218``; 1/accumulation-steps scaling :2240).

        Fast path: in the common case (single bound optimizer, no
        accumulation in flight) nothing executes here — the loss graph is
        parked on the optimizer and ``opt.step()`` runs ONE donated compiled
        function doing forward+backward+clip+update, same cost as a
        hand-fused pjit step. Anything that breaks fusion (accumulation,
        multiple models, forcing the loss early) falls back to the split
        grad path transparently."""
        if not isinstance(loss, Deferred):
            raise TypeError(
                "backward() expects the deferred loss produced by a prepared "
                "model call; got a concrete value. Compute the loss from "
                "model outputs (e.g. model(**batch).loss)."
            )
        self._training_started = True  # freezes auto-resume (see _maybe_auto_resume)
        if self._preemption_handler is not None:
            # step boundary: the previous step is fully applied, this one
            # hasn't staged yet — the one consistent point to emergency-save
            self.check_preemption()
        from .diagnostics.tracing import trace_span

        with trace_span("backward/dispatch"):
            if self.telemetry:
                self._backward_instrumented(loss)
                return
            self._backward_core(loss)

    def _backward_core(self, loss):
        opt = self._fusable_optimizer(loss)
        if opt is not None:
            if opt._pending_loss is not None:
                self._flush_pending(opt)
            if opt._grads is None:  # may have been set by the flush above
                opt._pending_loss = loss
                opt._pending_clip = None
                opt._last_norm = None  # a stale norm must not satisfy _PendingNorm
                object.__setattr__(loss, "_pre_force_hook", lambda: self._flush_pending(opt))
                return
        self._backward_split(loss)

    def _backward_instrumented(self, loss):
        """Telemetry-enabled backward: feed the step's batch geometry (from
        the deferred graph's input leaves) and the host time spent here to
        the recorder; the matching ``record_step`` fires in
        ``AcceleratedOptimizer.step``."""
        import time as _time

        from .lazy import linearize
        from .telemetry import batch_geometry

        t0 = _time.perf_counter()
        try:
            _, inputs, _ = linearize(loss._node)
            self.telemetry.note_batch(*batch_geometry(inputs))
        except Exception:
            pass
        self._backward_core(loss)
        self.telemetry.note_backward(_time.perf_counter() - t0)

    def _fusable_optimizer(self, loss):
        """The single optimizer eligible for the fused step, or None."""
        if self.gradient_accumulation_steps != 1 or not self.gradient_state.sync_gradients:
            return None
        bound = [o for o in self._optimizers if o.model is not None]
        if len(bound) != 1 or bound[0]._grads is not None:
            return None
        from .lazy import linearize

        _, _, models = linearize(loss._node)
        if bound[0].model not in models:
            return None  # loss doesn't touch this model: split path degrades gracefully
        return bound[0]

    def _backward_split(self, loss):
        """Split path: compute grads now, accumulate into optimizers."""
        scale = float(self.gradient_accumulation_steps)
        dynamic = self._loss_scale is not None  # fp16: loss scaled UP on device
        trainable = [opt.model for opt in self._optimizers if opt.model is not None]
        if not trainable:
            trainable = list(self._models)
        hook = (
            (self._grad_comm_hook, self.mesh) if self._grad_comm_hook is not None else None
        )
        jitted, trainables, frozen, inputs = grad_fn_for(
            loss, trainable, scale, dynamic_scale=dynamic, comm_hook=hook
        )
        train_params = [m.params for m in trainables]
        frozen_params = [m.params for m in frozen]
        extra = (self._loss_scale.scale,) if dynamic else ()
        (scaled_loss, unscaled_loss), grads = jitted(
            train_params, frozen_params, inputs, *extra
        )
        loss._set_forced(unscaled_loss)
        sanitizer = _get_sanitizer()
        if sanitizer:
            # split path computes the loss here, so this is its step
            # boundary; the probe forces the value (sanitize-mode cost)
            sanitizer.check_loss(unscaled_loss, step=self.step)
        for model, g in zip(trainables, grads):
            opt = self._optimizer_for(model)
            if opt is not None:
                opt._accumulate_grads(g)
            else:
                # optimizer-less model: grads exposed via PreparedModel.grads
                # for manual updates (reference analog: .grad on parameters)
                model.accumulate_grads(g)

    def _flush_pending(self, opt):
        """Demote a parked fused loss to the split path (the user forced the
        loss, clipped with an immediate-norm need, or issued a second
        backward before stepping)."""
        loss = opt._pending_loss
        if loss is None:
            return
        opt._pending_loss = None
        pending_clip = opt._pending_clip
        opt._pending_clip = None
        object.__setattr__(loss, "_pre_force_hook", None)
        self._backward_split(loss)
        if pending_clip is not None:
            self.clip_grad_norm_(opt, pending_clip)

    def _optimizer_for(self, model) -> AcceleratedOptimizer | None:
        for opt in self._optimizers:
            if opt.model is model:
                return opt
        return None

    def _do_sync(self):
        """(Reference ``accelerator.py:1034-1041``.)"""
        if self.gradient_state.sync_with_dataloader and self.gradient_state.end_of_dataloader:
            self.step = 0
            self.gradient_state._set_sync_gradients(True)
        else:
            self.step += 1
            self.gradient_state._set_sync_gradients(
                (self.step % self.gradient_state.num_steps) == 0
            )

    @contextlib.contextmanager
    def accumulate(self, *models):
        """(Reference ``accumulate`` ``accelerator.py:1060``.)"""
        self._do_sync()
        with contextlib.ExitStack() as stack:
            if not self.sync_gradients:
                for m in models:
                    stack.enter_context(self.no_sync(m))
            yield

    @contextlib.contextmanager
    def no_sync(self, model):
        """Under GSPMD gradients are reduced inside the compiled step, so
        there is no cross-rank traffic to skip (reference ``no_sync``
        ``accelerator.py:945-983`` suppresses DDP allreduce); the context
        keeps the API and the ``sync_gradients`` bookkeeping."""
        old = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(False)
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(old)

    @contextlib.contextmanager
    def trigger_sync_in_backward(self, model):
        old = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(True)
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(old)

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables, even_batches=None):
        """Even batches are the default data contract on TPU (static shapes);
        this context only toggles the dataloader flag (reference
        ``accelerator.py:1105-1191``)."""
        if even_batches is not None:
            old = self.even_batches
            self.even_batches = even_batches
            try:
                yield
            finally:
                self.even_batches = old
        else:
            yield

    def clip_grad_norm_(self, parameters, max_norm, norm_type=2):
        """Clip accumulated grads; returns the pre-clip global norm
        (reference ``clip_grad_norm_`` ``accelerator.py:2346``; like the
        reference's ``unscale_gradients`` there, fp16 loss-scaled grads are
        unscaled before clipping so both the clip and the returned norm are
        in true gradient units)."""
        opt = self._match_optimizer_for_parameters(parameters)
        if opt is None:
            return jnp.asarray(0.0)
        if opt._pending_loss is not None:
            if opt._pending_clip is None:
                # fused path: record the clip; the fused step applies it and
                # the true pre-clip norm is available after step()
                opt._pending_clip = float(max_norm)
                return _PendingNorm(self, opt)
            # a second clip before step(): fused supports one — demote so
            # both clips apply sequentially like the split path
            self._flush_pending(opt)
        if opt.grads is None:
            return jnp.asarray(0.0)
        opt.unscale_gradients()
        clip = opt._jit_cache.get("clip_norm")
        if clip is None:
            def _clip(grads, max_norm):
                norm = optax.global_norm(grads)
                factor = jnp.minimum(1.0, max_norm / (norm + 1e-6))
                return jax.tree.map(lambda g: g * factor, grads), norm

            clip = jax.jit(_clip, donate_argnums=(0,))
            opt._jit_cache["clip_norm"] = clip
        new_grads, norm = clip(opt._grads, float(max_norm))
        opt._grads = new_grads
        opt._last_norm = norm
        return norm

    def clip_grad_value_(self, parameters, clip_value):
        """(Reference ``accelerator.py:2403``.)"""
        opt = self._match_optimizer_for_parameters(parameters)
        if opt is None:
            return
        if opt._pending_loss is not None:
            self._flush_pending(opt)  # value-clip is not fused; use split path
        if opt.grads is None:
            return
        opt.unscale_gradients()
        clip = opt._jit_cache.get("clip_value")
        if clip is None:
            def _clip(grads, v):
                return jax.tree.map(lambda g: jnp.clip(g, -v, v), grads)

            clip = jax.jit(_clip, donate_argnums=(0,))
            opt._jit_cache["clip_value"] = clip
        opt._grads = clip(opt._grads, float(clip_value))

    def unscale_gradients(self, optimizer=None):
        """(Reference ``unscale_gradients`` ``accelerator.py:2311``.)"""
        opts = [optimizer] if optimizer is not None else self._optimizers
        for opt in opts:
            opt.unscale_gradients()

    def _match_optimizer_for_parameters(self, parameters):
        if isinstance(parameters, PreparedModel):
            return self._optimizer_for(parameters)
        if isinstance(parameters, AcceleratedOptimizer):
            return parameters
        # params pytree: match by identity against bound models
        for opt in self._optimizers:
            if opt.model is not None and opt.model.params is parameters:
                return opt
        return self._optimizers[0] if self._optimizers else None

    # ------------------------------------------------------------------
    # collectives
    # ------------------------------------------------------------------

    def _force_deferred(self, tensor):
        return jax.tree.map(
            lambda t: t.force() if isinstance(t, Deferred) else t,
            tensor,
            is_leaf=lambda t: isinstance(t, Deferred),
        )

    def gather(self, tensor):
        """(Reference ``gather`` ``accelerator.py:2414``.)"""
        return ops.gather(self._force_deferred(tensor))

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """Gather + drop the duplicated tail on the last batch (reference
        ``accelerator.py:2462-2533`` using ``GradientState.remainder``)."""
        input_data = self._force_deferred(input_data)
        try:
            recursively_check = ops.find_batch_size(input_data) is not None
        except Exception:
            recursively_check = False
        if use_gather_object or not recursively_check:
            data = ops.gather_object(
                input_data if isinstance(input_data, list) else [input_data]
            )
            return data
        data = ops.gather(input_data)
        remainder = self.gradient_state.remainder
        if self.gradient_state.end_of_dataloader and remainder > 0:
            def _truncate(t):
                return t[:remainder] if hasattr(t, "ndim") and t.ndim > 0 else t

            data = jax.tree.map(_truncate, data)
        return data

    def reduce(self, tensor, reduction="sum", scale=1.0):
        return ops.reduce(self._force_deferred(tensor), reduction=reduction, scale=scale)

    def pad_across_processes(self, tensor, dim=0, pad_index=0, pad_first=False):
        return ops.pad_across_processes(
            self._force_deferred(tensor), dim=dim, pad_index=pad_index, pad_first=pad_first
        )

    # -- trigger API (reference ``accelerator.py:2252-2309``) ----------------

    def set_trigger(self):
        self.flag_tensor = np.ones((), dtype=np.int32)

    def check_trigger(self) -> bool:
        flag = self.flag_tensor if self.flag_tensor is not None else np.zeros((), dtype=np.int32)
        total = ops.reduce(flag, reduction="sum")
        triggered = bool(np.asarray(total) >= 1)
        if triggered:
            self.flag_tensor = None
        return triggered

    # ------------------------------------------------------------------
    # contexts
    # ------------------------------------------------------------------

    @contextlib.contextmanager
    def autocast(self, autocast_handler=None):
        """Precision is a trace-time dtype policy on TPU; with
        ``AutocastKwargs(enabled=False)`` the compute-dtype cast is suspended
        for the context — a full-precision island inside a mixed-precision
        run (reference ``accelerator.py:3435``)."""
        if autocast_handler is not None and not getattr(autocast_handler, "enabled", True):
            # suspend BOTH precision policies: the dtype cast and the fp8
            # matmul recipe (deferred calls snapshot them at record time)
            saved = [(m, m.compute_dtype, m.fp8_recipe) for m in self._models]
            for m, _, _ in saved:
                m.compute_dtype = None
                m.fp8_recipe = None
            try:
                yield
            finally:
                for m, dtype, recipe in saved:
                    m.compute_dtype = dtype
                    m.fp8_recipe = recipe
            return
        yield

    @contextlib.contextmanager
    def profile(self, profile_handler: ProfileKwargs | None = None):
        """``jax.profiler`` capture (reference builds torch.profiler,
        ``accelerator.py:3462-3519``). Yields a :class:`ProfileContext`
        whose ``step()`` drives the wait/warmup/active schedule — tracing
        starts on entering an active window and stops on leaving it, exactly
        the reference's ``torch.profiler.schedule`` contract.
        ``profile_memory`` additionally writes ``memory_<step>.prof``
        (pprof-format device memory snapshots)."""
        handler = profile_handler or self.profile_handler or ProfileKwargs()
        trace_dir = handler.output_trace_dir
        if trace_dir is None:
            yield None
            return
        ctx = ProfileContext(handler, trace_dir, telemetry=self.telemetry)
        try:
            ctx._maybe_start()
            yield ctx
        finally:
            ctx._finish()

    # ------------------------------------------------------------------
    # model/optimizer interop
    # ------------------------------------------------------------------

    def unwrap_model(self, model, keep_fp32_wrapper: bool = True):
        return extract_model_from_parallel(model, keep_fp32_wrapper)

    def free_memory(self, *objects):
        """Release prepared references + compiled-step caches (reference
        ``free_memory`` ``accelerator.py:3282``)."""
        self._models.clear()
        self._optimizers.clear()
        self._schedulers.clear()
        self._dataloaders.clear()
        self.step = 0
        clear_caches()
        jax.clear_caches()
        return objects

    def clear(self, *objects):
        return self.free_memory(*objects)

    def get_state_dict(self, model, unwrap=True):
        if isinstance(model, PreparedModel):
            return model.state_dict()
        if isinstance(model, Model):
            return PreparedModel(model).state_dict()
        raise TypeError(f"cannot extract state dict from {type(model)}")

    # ------------------------------------------------------------------
    # checkpointing facade (impl in checkpointing.py)
    # ------------------------------------------------------------------

    def register_for_checkpointing(self, *objects):
        for obj in objects:
            if not (hasattr(obj, "state_dict") and hasattr(obj, "load_state_dict")):
                raise ValueError(
                    f"{obj} must define state_dict/load_state_dict to be registered"
                )
        self._custom_objects.extend(objects)

    def save_state(self, output_dir: str | None = None, **save_model_func_kwargs):
        from .checkpointing import save_accelerator_state

        return save_accelerator_state(self, output_dir, **save_model_func_kwargs)

    def load_state(self, input_dir: str | None = None, **load_model_func_kwargs):
        from .checkpointing import load_accelerator_state

        return load_accelerator_state(self, input_dir, **load_model_func_kwargs)

    def save_model(self, model, save_directory: str, max_shard_size="10GB", safe_serialization=True):
        from .checkpointing import save_model_weights

        return save_model_weights(self, model, save_directory, max_shard_size, safe_serialization)

    def save(self, obj, f, safe_serialization=False):
        from .checkpointing import save_object

        if self.is_main_process:
            save_object(obj, f, safe_serialization=safe_serialization)

    # ------------------------------------------------------------------
    # tracking facade (impl in tracking.py)
    # ------------------------------------------------------------------

    def init_trackers(self, project_name: str, config: dict | None = None, init_kwargs: dict | None = None):
        from .tracking import init_trackers

        self.trackers = init_trackers(
            self.log_with, project_name, self.logging_dir, config, init_kwargs or {}
        )

    def get_tracker(self, name: str, unwrap: bool = False):
        for tracker in self.trackers:
            if getattr(tracker, "name", None) == name:
                return tracker.tracker if unwrap else tracker
        from .tracking import GeneralTracker

        return GeneralTracker(_blank=True)

    def log(self, values: dict, step: int | None = None, log_kwargs: dict | None = None):
        for tracker in self.trackers:
            tracker.log(values, step=step, **(log_kwargs or {}).get(tracker.name, {}))

    def _telemetry_tracker_sink(self, values: dict, step: int | None):
        """Telemetry → tracker fan-out (the recorder gates this to the main
        process, matching ``tracking.on_main_process``)."""
        self.log(values, step=step)

    def end_training(self):
        for tracker in self.trackers:
            tracker.finish()
        self.telemetry.close()
        if self.sanitizer is not None:
            # release only OUR sanitizer — a newer Accelerator's Borg
            # takeover must not be clobbered by an old one's teardown
            if _get_sanitizer() is self.sanitizer:
                _set_sanitizer(None)
        if self.watchdog is not None:
            self.watchdog.stop()
        self.tracer.close()
        if self._preemption_handler is not None:
            self._preemption_handler.uninstall()
        from .checkpointing import _join_writer_then_barrier

        # a trailing async save must land AND commit before exit — the
        # barriered join is the only place a multi-host commit is safe
        _join_writer_then_barrier(self)
        self.wait_for_everyone()

    # ------------------------------------------------------------------
    # misc parity helpers
    # ------------------------------------------------------------------

    def skip_first_batches(self, dataloader, num_batches: int = 0):
        return skip_first_batches(dataloader, num_batches)

    def __repr__(self):
        return repr(self.state)


# ---------------------------------------------------------------------------
# type sniffing for prepare()
# ---------------------------------------------------------------------------


def _is_model(obj) -> bool:
    return isinstance(obj, (Model, PreparedModel))


def _as_model(obj) -> Model:
    if isinstance(obj, Model):
        return obj
    raise TypeError(
        f"cannot prepare {type(obj)} as a model; wrap it in accelerate_tpu.Model "
        "(for flax modules: Model.from_flax(module, variables))"
    )


def _is_optimizer(obj) -> bool:
    if isinstance(obj, AcceleratedOptimizer):
        return True
    return isinstance(obj, optax.GradientTransformation) or (
        hasattr(obj, "init") and hasattr(obj, "update") and not hasattr(obj, "apply_fn")
    )


def _is_dataloader(obj) -> bool:
    if isinstance(obj, DataLoaderShard):
        return True
    if hasattr(obj, "dataset") and (hasattr(obj, "batch_size") or hasattr(obj, "batch_sampler")):
        return True
    mod = type(obj).__module__ or ""
    return mod.startswith("torch.utils.data")


def _is_scheduler(obj) -> bool:
    """A schedule is an optax schedule fn (closure from the optax package, or
    a 1-arg function whose parameter is step-like) or a torch-style
    scheduler object (step + get_last_lr). Everything else passes through
    prepare() untouched, matching the reference's behaviour for
    unrecognized objects (loss fns, tokenizers, collate fns, …)."""
    import functools as _ft
    import inspect
    import types as _t

    if isinstance(obj, AcceleratedScheduler):
        return True
    if hasattr(obj, "step") and hasattr(obj, "get_last_lr"):
        return True
    if not isinstance(obj, (_t.FunctionType, _ft.partial)) or _is_optimizer(obj):
        return False
    if (getattr(obj, "__module__", "") or "").startswith("optax"):
        return True
    try:
        params = list(inspect.signature(obj).parameters.values())
    except (TypeError, ValueError):
        return False
    return len(params) == 1 and params[0].name in (
        "step", "count", "t", "epoch", "iteration", "step_count", "global_step"
    )
