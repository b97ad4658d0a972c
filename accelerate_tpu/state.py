"""Process/accelerator state singletons.

TPU-native re-design of ``/root/reference/src/accelerate/state.py`` (1257
LoC). Same Borg-singleton contract — ``PartialState`` (reference
``state.py:115``), ``AcceleratorState`` (``state.py:816``), ``GradientState``
(``state.py:1134``) share state across all instances so library helpers
(``get_logger``, ``gather``…) work without passing handles — but the
execution environment is JAX:

* "process" == JAX host process (one per machine, driving all its local
  chips), not one-process-per-device. ``num_processes`` is
  ``jax.process_count()``.
* backend selection/process-group init (reference ``state.py:710-767``)
  becomes ``jax.distributed.initialize`` + named-``Mesh`` construction
  (see :mod:`accelerate_tpu.mesh`).
* ``wait_for_everyone`` (reference ``state.py:343``) lowers to
  ``multihost_utils.sync_global_devices``.
* there is no ``xm.mark_step()`` bookkeeping — dispatch is explicit under
  ``jit``, so ``GradientState`` keeps only the accumulation/remainder
  semantics.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import math
import os
from typing import Any, Callable, Iterable

import jax

from .mesh import (
    batch_axis_size,
    build_mesh,
    configure_compile_cache,
    device_topology,
    initialize_distributed,
    single_device_mesh,
)
from .utils.dataclasses import (
    DistributedType,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    MeshPlugin,
    PrecisionType,
)
from .utils.environment import parse_choice_from_env, parse_flag_from_env

logger = logging.getLogger(__name__)


class PartialState:
    """Singleton holding the topology view + process-control primitives.

    Reference: ``PartialState`` ``state.py:115`` (``_prepare_backend``
    :710, ``set_device`` :769, ``wait_for_everyone`` :343,
    ``split_between_processes`` :389, ``main_process_first`` :477,
    ``on_*_process`` decorators :519-675).
    """

    _shared_state: dict[str, Any] = {}
    _known_attrs = [
        "debug",
        "device",
        "distributed_type",
        "local_process_index",
        "num_processes",
        "process_index",
        "mesh",
        "mesh_plugin",
    ]

    def __init__(self, cpu: bool = False, mesh_plugin: MeshPlugin | None = None, **kwargs):
        self.__dict__ = self._shared_state
        if self.initialized:
            return
        self.debug = parse_flag_from_env("ACCELERATE_DEBUG_MODE")
        # Multi-host rendezvous first (no-op unless coordinator env/flag set).
        initialize_distributed(
            coordinator_address=kwargs.pop("coordinator_address", None),
            num_processes=kwargs.pop("num_processes", None),
            process_id=kwargs.pop("process_id", None),
        )
        if cpu or parse_flag_from_env("ACCELERATE_USE_CPU"):
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
        configure_compile_cache()  # before the backend's first compile
        topo = device_topology()
        self.num_processes = topo["process_count"]
        self.process_index = topo["process_index"]
        self.local_process_index = 0  # one JAX process per host
        self.mesh_plugin = mesh_plugin or MeshPlugin()
        if topo["num_devices"] == 1:
            self.distributed_type = DistributedType.NO
            self.mesh = single_device_mesh()
        else:
            if self.num_processes > 1:
                self.distributed_type = DistributedType.MULTI_HOST_TPU
            elif topo["platform"] == "cpu":
                self.distributed_type = DistributedType.CPU_MESH
            else:
                self.distributed_type = DistributedType.TPU
            self.mesh = build_mesh(self.mesh_plugin)
        self.device = jax.local_devices()[0]

    # -- lifecycle -----------------------------------------------------------

    @property
    def initialized(self) -> bool:
        return "distributed_type" in self.__dict__

    @classmethod
    def _reset_state(cls):
        cls._shared_state.clear()

    def destroy_process_group(self):  # API parity; JAX owns teardown
        self._reset_state()

    # -- identity ------------------------------------------------------------

    @property
    def use_distributed(self) -> bool:
        return self.distributed_type != DistributedType.NO

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        return self.local_process_index == 0

    @property
    def is_last_process(self) -> bool:
        return self.process_index == self.num_processes - 1

    @property
    def local_device_count(self) -> int:
        return jax.local_device_count()

    @property
    def num_devices(self) -> int:
        return self.mesh.size

    @property
    def data_parallel_size(self) -> int:
        """How many ways the global batch is split (dp × fsdp axes)."""
        return batch_axis_size(self.mesh)

    # -- process control -----------------------------------------------------

    def wait_for_everyone(self):
        """Cross-host barrier (reference ``state.py:343``)."""
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("accelerate_tpu.wait_for_everyone")

    def consensus_any(self, flag: bool) -> bool:
        """Does ANY process assert ``flag``? A tiny all-gather of one int —
        the primitive behind preemption consensus (resilience subsystem)
        and any one-host-decides breaker. COLLECTIVE when multi-process:
        every process must call it at the same point."""
        if self.num_processes <= 1:
            return bool(flag)
        import numpy as np
        from jax.experimental import multihost_utils

        gathered = multihost_utils.process_allgather(
            np.asarray([1 if flag else 0], dtype=np.int32)
        )
        return bool(np.asarray(gathered).any())

    @contextlib.contextmanager
    def main_process_first(self):
        """Main process runs the body before others (download-then-load idiom;
        reference ``state.py:477``)."""
        if not self.is_main_process:
            self.wait_for_everyone()
        yield
        if self.is_main_process:
            self.wait_for_everyone()

    @contextlib.contextmanager
    def local_main_process_first(self):
        with self.main_process_first():  # 1 process per host ⇒ same thing
            yield

    def on_main_process(self, function: Callable = None):
        def wrapper(fn):
            @functools.wraps(fn)
            def inner(*args, **kwargs):
                if self.is_main_process:
                    return fn(*args, **kwargs)

            return inner

        return wrapper(function) if function is not None else wrapper

    def on_local_main_process(self, function: Callable = None):
        return self.on_main_process(function)

    def on_last_process(self, function: Callable):
        @functools.wraps(function)
        def inner(*args, **kwargs):
            if self.is_last_process:
                return function(*args, **kwargs)

        return inner

    def on_process(self, function: Callable = None, process_index: int = None):
        if function is None:
            return functools.partial(self.on_process, process_index=process_index)

        @functools.wraps(function)
        def inner(*args, **kwargs):
            if self.process_index == process_index:
                return function(*args, **kwargs)

        return inner

    def on_local_process(self, function: Callable = None, local_process_index: int = None):
        if function is None:
            return functools.partial(self.on_local_process, local_process_index=local_process_index)

        @functools.wraps(function)
        def inner(*args, **kwargs):
            if self.local_process_index == local_process_index:
                return function(*args, **kwargs)

        return inner

    @contextlib.contextmanager
    def split_between_processes(self, inputs, apply_padding: bool = False):
        """Split a list/tuple/dict/array between processes, last process
        padded when uneven and ``apply_padding`` (reference ``state.py:389``)."""
        if self.num_processes == 1:
            yield inputs
            return
        length = len(inputs)
        num_per = math.ceil(length / self.num_processes)
        start = self.process_index * num_per
        end = min(start + num_per, length)

        def _slice(obj):
            sliced = obj[start:end]
            if apply_padding and len(sliced) < num_per and len(obj) > 0:
                pad = [obj[-1]] * (num_per - len(sliced))
                if isinstance(sliced, list):
                    sliced = sliced + pad
                else:
                    import numpy as np

                    sliced = np.concatenate([sliced, np.stack(pad)])
            return sliced

        if isinstance(inputs, dict):
            yield {k: _slice(v) for k, v in inputs.items()}
        else:
            yield _slice(inputs)

    def print(self, *args, **kwargs):
        if self.is_main_process:
            print(*args, **kwargs)

    def __repr__(self) -> str:
        return (
            f"Distributed environment: {self.distributed_type}\n"
            f"Num processes: {self.num_processes}\n"
            f"Process index: {self.process_index}\n"
            f"Local process index: {self.local_process_index}\n"
            f"Device: {self.device}\n"
            f"Mesh: {dict(self.mesh.shape)}\n"
        )


def _require_initialized(method):
    @functools.wraps(method)
    def inner(self, *args, **kwargs):
        if not self.initialized:
            raise RuntimeError(
                f"`{method.__name__}` requires AcceleratorState to be initialized — "
                "construct an `Accelerator()` first."
            )
        return method(self, *args, **kwargs)

    return inner


class AcceleratorState:
    """Adds precision + plugin decisions on top of PartialState (reference
    ``state.py:816``; plugin merge :893-941)."""

    _shared_state: dict[str, Any] = {}

    def __init__(
        self,
        mixed_precision: str | None = None,
        cpu: bool = False,
        mesh_plugin: MeshPlugin | None = None,
        fsdp_plugin: FullyShardedDataParallelPlugin | None = None,
        _from_accelerator: bool = False,
        **kwargs,
    ):
        self.__dict__ = self._shared_state
        if self.initialized:
            if mixed_precision is not None and mixed_precision != self._mixed_precision:
                raise ValueError(
                    "AcceleratorState already initialized with "
                    f"mixed_precision={self._mixed_precision!r}; call "
                    "AcceleratorState._reset_state() to change it."
                )
            return
        self._partial = PartialState(cpu=cpu, mesh_plugin=mesh_plugin, **kwargs)
        if mixed_precision is None:
            mixed_precision = parse_choice_from_env("ACCELERATE_MIXED_PRECISION", "no")
        mixed_precision = PrecisionType(mixed_precision).value
        self._mixed_precision = mixed_precision
        self.fsdp_plugin = fsdp_plugin
        self.dynamo_plugin = None  # XLA always compiles; kept for API parity
        self.deepspeed_plugins = None  # plugin | dict[str, plugin] | None
        self.initialized_trackers = []

    @property
    def initialized(self) -> bool:
        return "_partial" in self.__dict__

    @classmethod
    def _reset_state(cls, reset_partial_state: bool = False):
        cls._shared_state.clear()
        if reset_partial_state:
            PartialState._reset_state()
        from .ops.attention import set_attention_context

        set_attention_context(None)

    @property
    def mixed_precision(self) -> str:
        return self._mixed_precision

    # -- multi-plugin DeepSpeed selection (reference ``state.py:1100-1116``) --

    def _named_deepspeed_plugins(self) -> dict:
        plugins = self.__dict__.get("deepspeed_plugins")
        if plugins is None:
            raise ValueError(
                "No DeepSpeedPlugin is enabled — pass `deepspeed_plugin=` "
                "(a plugin or a dict of named plugins) to Accelerator first."
            )
        if not isinstance(plugins, dict):
            raise ValueError(
                "A single (unnamed) DeepSpeedPlugin is enabled; named "
                "selection needs a dict of plugins passed to Accelerator."
            )
        return plugins

    @_require_initialized
    def get_deepspeed_plugin(self, name: str):
        """The DeepSpeedPlugin registered under ``name``."""
        return self._named_deepspeed_plugins()[name]

    @_require_initialized
    def select_deepspeed_plugin(self, name: str = None):
        """Activate the plugin registered under ``name`` and deactivate all
        others; runtime consumers (auto-fill, accumulation, dummy-object
        lowering) immediately see the newly active plugin's config."""
        plugins = self._named_deepspeed_plugins()
        if name not in plugins:
            raise KeyError(
                f"no DeepSpeedPlugin named {name!r}; registered: {sorted(plugins)}"
            )
        for key, plugin in plugins.items():
            if key != name:
                plugin._unselect()
        plugins[name].select(_from_accelerator_state=True)

    def __getattr__(self, name: str):
        # Delegate topology/process-control surface to PartialState.
        if name in ("_shared_state", "__dict__", "_partial"):
            raise AttributeError(name)
        partial = self.__dict__.get("_partial")
        if partial is not None and hasattr(partial, name):
            return getattr(partial, name)
        raise AttributeError(f"AcceleratorState has no attribute {name!r}")

    def __repr__(self):
        return self._partial.__repr__() + f"Mixed precision: {self.mixed_precision}\n"


class GradientState:
    """Gradient-accumulation bookkeeping shared between Accelerator,
    dataloaders, optimizer and scheduler wrappers (reference
    ``state.py:1134``: sync_gradients / num_steps / remainder /
    end_of_dataloader; the TPU build drops the ``xm.mark_step`` hook at
    :1228-1237 — dispatch is explicit)."""

    _shared_state: dict[str, Any] = {}

    def __init__(self, gradient_accumulation_plugin: GradientAccumulationPlugin | None = None):
        self.__dict__ = self._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self.active_dataloader = None
            self.dataloader_references = [None]
            self.plugin_kwargs = (
                gradient_accumulation_plugin.to_dict()
                if gradient_accumulation_plugin is not None
                else {}
            )
            self._is_xla_gradients_synced = True  # parity attr; always True
        if gradient_accumulation_plugin is not None and self.plugin_kwargs != gradient_accumulation_plugin.to_dict():
            self.plugin_kwargs = gradient_accumulation_plugin.to_dict()

    @property
    def initialized(self) -> bool:
        return "sync_gradients" in self.__dict__

    @classmethod
    def _reset_state(cls):
        cls._shared_state.clear()

    @property
    def num_steps(self) -> int:
        return self.plugin_kwargs.get("num_steps", 1)

    @property
    def adjust_scheduler(self) -> bool:
        return self.plugin_kwargs.get("adjust_scheduler", False)

    @property
    def sync_with_dataloader(self) -> bool:
        return self.plugin_kwargs.get("sync_with_dataloader", True)

    @property
    def end_of_dataloader(self) -> bool:
        if not self.in_dataloader:
            return False
        return self.active_dataloader.end_of_dataloader

    @property
    def remainder(self) -> int:
        if not self.in_dataloader:
            return -1
        return self.active_dataloader.remainder

    @property
    def in_dataloader(self) -> bool:
        return self.active_dataloader is not None

    def _set_sync_gradients(self, sync_gradients: bool):
        self.sync_gradients = sync_gradients

    def _add_dataloader(self, dataloader):
        self.active_dataloader = dataloader
        self.dataloader_references.append(dataloader)

    def _remove_dataloader(self, dataloader):
        if dataloader in self.dataloader_references:
            self.dataloader_references.remove(dataloader)
        self.active_dataloader = self.dataloader_references[-1]

    def __repr__(self):
        return (
            f"Sync gradients: {self.sync_gradients}\n"
            f"At end of current dataloader: {self.end_of_dataloader}\n"
            f"Extra samples added: {self.remainder}\n"
            f"Gradient accumulation plugin: {self.plugin_kwargs}\n"
        )


def is_initialized() -> bool:
    return AcceleratorState().initialized
