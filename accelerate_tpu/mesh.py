"""Device-mesh construction over TPU topology (ICI within slice, DCN across).

This module is the TPU-native replacement for the reference's backend
selection + process-group init (``/root/reference/src/accelerate/state.py:710-767``
and ``state.py:194-252``): instead of picking a torch.distributed backend and
calling ``init_process_group``, we call ``jax.distributed.initialize`` (when
multi-host) and build a named ``jax.sharding.Mesh`` whose axes —
``('dp', 'fsdp', 'ep', 'cp', 'tp')`` — are the only parallelism vocabulary
the rest of the framework speaks.

Axis-order rationale (the scaling-book recipe): the leftmost mesh dimension
changes slowest across the physical device order, so putting ``dp`` first
keeps pure-replica traffic on the slice boundary (DCN-tolerant) while
``tp``/``cp`` — which carry per-layer collectives — map onto adjacent
chips' ICI links.
"""

from __future__ import annotations

import logging
import os
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .utils.dataclasses import MESH_AXIS_ORDER, MeshPlugin

logger = logging.getLogger(__name__)

P = PartitionSpec

#: where compiled programs persist when ``JAX_COMPILATION_CACHE_DIR`` names
#: no place: one fixed path under the checkout, derived from this file's
#: location and never from ``tempfile``, a pid or a time — so every process
#: of a run (``launch`` children, ``serve`` replicas, ``chip_smoke.py``
#: phases) reads what the others compiled without being
#: told, and a later run finds it again (the path is part of the cache key)
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".compile_cache"
)


def configure_compile_cache() -> str:
    """Give JAX's persistent compilation cache a directory before the first
    compile, and return the one in use. Where ``JAX_COMPILATION_CACHE_DIR``
    is set the cache is placed from outside: JAX reads the variable itself
    and no code sets another directory. Called where each program first
    reaches the backend (``PartialState``, the ``serve`` engine factory)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def device_topology() -> dict:
    """Probe the attached JAX topology (reference analog: the env-var rank
    bookkeeping in ``state.py:254-275``)."""
    devices = jax.devices()
    return {
        "num_devices": len(devices),
        "local_device_count": jax.local_device_count(),
        "process_count": jax.process_count(),
        "process_index": jax.process_index(),
        "platform": devices[0].platform if devices else "none",
        "device_kind": devices[0].device_kind if devices else "none",
    }


def build_mesh(plugin: MeshPlugin | None = None, devices: Sequence | None = None) -> Mesh:
    """Build the named mesh from a :class:`MeshPlugin` shape declaration.

    Uses ``mesh_utils.create_device_mesh`` so the physical ICI torus is
    respected where possible; falls back to a plain reshape, with a
    warning, for shapes it cannot map.
    """
    plugin = plugin or MeshPlugin()
    if devices is None:
        devices = plugin.devices if plugin.devices is not None else jax.devices()
    devices = list(devices)
    sizes = plugin.axis_sizes(len(devices))
    shape = tuple(sizes[ax] for ax in MESH_AXIS_ORDER)
    try:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(
            shape, devices=np.asarray(devices),
            allow_split_physical_axes=plugin.allow_split_physical_axes,
        )
    except (ValueError, AssertionError, TypeError) as e:  # exotic shapes
        # on a TPU this loses the ICI-aware device order: say so
        logger.warning("create_device_mesh failed (%s); falling back to reshape", e)
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, MESH_AXIS_ORDER)


def single_device_mesh(device=None) -> Mesh:
    """Degenerate 1-device mesh so single-chip code paths are shape-identical
    to sharded ones (everything is a NamedSharding; no special cases)."""
    device = device or jax.devices()[0]
    dev_array = np.asarray([device]).reshape((1,) * len(MESH_AXIS_ORDER))
    return Mesh(dev_array, MESH_AXIS_ORDER)


def data_sharding(mesh: Mesh, *, extra_axes: tuple[str, ...] = ("fsdp",)) -> NamedSharding:
    """Sharding for a global batch: leading (batch) dim split over every
    data-like axis — ``dp`` plus ``fsdp`` (and ``ep`` when experts act as
    data parallel for the dense parts). This is the TPU-native equivalent of
    the reference's per-rank ``BatchSamplerShard`` slice."""
    axes = tuple(ax for ax in ("dp",) + tuple(extra_axes) if mesh.shape[ax] >= 1)
    return NamedSharding(mesh, P(axes))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def mesh_axis_sizes(mesh: Mesh, trivial: bool = False) -> dict[str, int]:
    """``{axis: size}`` for the mesh — by default only the non-trivial axes
    (size > 1), the form telemetry/serving stats record so a reader sees
    "fsdp=2, tp=2" instead of five 1s."""
    return {
        str(ax): int(n)
        for ax, n in mesh.shape.items()
        if trivial or int(n) > 1
    }


def device_hbm_bytes(device=None) -> int | None:
    """Per-device accelerator memory limit in bytes, or ``None`` when the
    backend doesn't report one (CPU; some older runtimes). The shard-check
    capacity model's default budget: on a real TPU ``serve --auto-blocks``
    can size the pool without the operator looking up the chip's HBM."""
    try:
        device = device or jax.local_devices()[0]
        stats = device.memory_stats() or {}
    except Exception:
        return None
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    return int(limit) if limit else None


def batch_axis_size(mesh: Mesh, extra_axes: tuple[str, ...] = ("fsdp",)) -> int:
    """Number of ways the global batch is split (the 'dp world size')."""
    n = mesh.shape["dp"]
    for ax in extra_axes:
        n *= mesh.shape[ax]
    return n


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Multi-host bring-up — the ``init_process_group`` analog. Reads the
    same env contract the launcher writes (``ACCELERATE_COORDINATOR_ADDR``
    etc.; reference: MASTER_ADDR/RANK envs consumed at ``state.py:214-249``).
    No-op when single-host or already initialized."""
    coordinator_address = coordinator_address or os.environ.get("ACCELERATE_COORDINATOR_ADDR")
    if num_processes is None:
        env = os.environ.get("ACCELERATE_NUM_PROCESSES")
        num_processes = int(env) if env else None
    if process_id is None:
        env = os.environ.get("ACCELERATE_PROCESS_ID")
        process_id = int(env) if env else None
    if coordinator_address is None:
        # No coordinator: the only recoverable multi-process case is a real
        # TPU pod, where jax.distributed.initialize() with all-None args
        # auto-detects the rendezvous from the TPU metadata server. Anywhere
        # else (stale ACCELERATE_NUM_PROCESSES export, CPU repro of a pod
        # config) stay a single-process no-op as before.
        on_tpu_vm = os.path.exists("/dev/accel0") or any(
            k in os.environ for k in ("TPU_WORKER_ID", "CLOUD_TPU_TASK_ID", "TPU_WORKER_HOSTNAMES")
        )
        if not (num_processes and num_processes > 1 and on_tpu_vm):
            return
        if jax.distributed.is_initialized():
            return
        jax.distributed.initialize()
        return
    if jax.distributed.is_initialized():
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
