"""GSPMD sharding planner — the FSDP/ZeRO-3 equivalent.

The reference wraps modules in ``torch.distributed.fsdp`` with plugin-driven
kwargs (``/root/reference/src/accelerate/accelerator.py:1473-1592``). Here
"fully sharded" is a *placement decision*, not a wrapper: every parameter
gets a ``NamedSharding`` over the ``fsdp`` mesh axis (and ``tp`` when rules
say so), XLA inserts the all-gathers on use and reduce-scatters on grads —
ZeRO-3's gather-on-use is GSPMD's native execution model.

Sharding policy, in priority order:
1. model-provided partition rules (path-regex → PartitionSpec), for tensor
   parallelism and hand-tuned layouts;
2. FSDP policy: shard the largest dimension divisible by the ``fsdp`` axis
   extent, for params with ≥ ``min_num_params`` elements;
3. replicate.
"""

from __future__ import annotations

import logging
import re
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..utils.dataclasses import FullyShardedDataParallelPlugin

logger = logging.getLogger(__name__)

P = PartitionSpec

#: (param path, axis repr) pairs already warned about — the divisibility
#: fallback warns ONCE per site, not once per step (the runtime twin of
#: shard-check's SP003 finding)
_DIVISIBILITY_WARNED: set[tuple[str, str]] = set()


def _path_to_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return ".".join(parts)


@dataclass(frozen=True)
class PlacementDecision:
    """One parameter's placement, with the *why* attached — the record the
    ``shard-check`` static analyzer turns into SP001/SP002/SP003 findings.

    ``dropped`` lists rule entries the divisibility validation discarded:
    ``(dim, axis_repr, extent)`` triples, ``extent`` 0 when the axis is
    absent from the mesh entirely."""

    spec: PartitionSpec
    #: "rule" (a partition rule matched), "fsdp" (size policy), or
    #: "replicated" (no rule, policy declined or found no divisible dim)
    source: str
    rule_index: int | None
    dropped: tuple[tuple[int, str, int], ...]


def explain_partition_spec(
    path_str: str,
    shape: tuple[int, ...],
    mesh,
    plugin: FullyShardedDataParallelPlugin | None,
    rules: list[tuple[str, PartitionSpec]] | None,
) -> PlacementDecision:
    """Decide one parameter's PartitionSpec and say why. ``mesh`` only needs
    a ``.shape`` mapping — the shard-check analyzer passes a virtual axis
    map, the runtime passes a real :class:`jax.sharding.Mesh`."""
    # GPipe stage placement: layer-stacked params (leading [layers] axis,
    # path under "layers") split their stack over the pp axis so each stage
    # group holds only its own layers. Applied as an overlay on whatever
    # rule/policy decides for the other dims.
    sizes = dict(mesh.shape)
    pp_size = sizes.get("pp", 1)
    stacked = (
        pp_size > 1
        and re.search(r"(^|\.)layers(\.|$)", path_str) is not None
        and len(shape) >= 1
        and shape[0] % pp_size == 0
    )

    def overlay(spec: PartitionSpec) -> PartitionSpec:
        if not stacked:
            return spec
        entries = list(tuple(spec)) + [None] * (len(shape) - len(tuple(spec)))
        if entries[0] is None:
            entries[0] = "pp"
        return P(*entries)

    if rules:
        for i, (pattern, spec) in enumerate(rules):
            if re.search(pattern, path_str):
                validated, dropped = _validated(spec, shape, mesh)
                return PlacementDecision(overlay(validated), "rule", i, dropped)
    if plugin is None or not plugin.shards_params:
        return PlacementDecision(overlay(P()), "replicated", None, ())
    fsdp_size = sizes.get("fsdp", 1)
    if fsdp_size <= 1:
        return PlacementDecision(overlay(P()), "replicated", None, ())
    n_elements = int(np.prod(shape)) if shape else 0
    if n_elements < max(plugin.min_num_params, 2):
        return PlacementDecision(overlay(P()), "replicated", None, ())
    # shard the largest divisible dim over fsdp (dim 0 is reserved for the
    # stage split when the pp overlay applies)
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for dim in order:
        if stacked and dim == 0:
            continue
        if shape[dim] % fsdp_size == 0:
            spec = [None] * len(shape)
            spec[dim] = "fsdp"
            return PlacementDecision(overlay(P(*spec)), "fsdp", None, ())
    return PlacementDecision(overlay(P()), "replicated", None, ())


def partition_spec_for(
    path_str: str,
    shape: tuple[int, ...],
    mesh: Mesh,
    plugin: FullyShardedDataParallelPlugin | None,
    rules: list[tuple[str, PartitionSpec]] | None,
) -> PartitionSpec:
    """Decide the PartitionSpec for one parameter. A rule entry the
    divisibility validation discards is warned about once per (param, axis)
    — silently replicating a dim a rule asked to shard is exactly the
    surprise ``shard-check``'s SP003 exists to catch before the run."""
    decision = explain_partition_spec(path_str, shape, mesh, plugin, rules)
    for dim, axis, extent in decision.dropped:
        key = (path_str, axis)
        if key in _DIVISIBILITY_WARNED:
            continue
        _DIVISIBILITY_WARNED.add(key)
        if extent:
            logger.warning(
                "partition rule for %r asks to shard dim %d (size %s) over "
                "axis %s (extent %d), which does not divide — falling back "
                "to unsharded for that dim (shard-check names this SP003)",
                path_str, dim, shape[dim] if dim < len(shape) else "?",
                axis, extent,
            )
        else:
            logger.warning(
                "partition rule for %r names axis %s, which is not a mesh "
                "axis — entry ignored (shard-check names this SP003; lint "
                "rule TPU012 catches the literal)",
                path_str, axis,
            )
    return decision.spec


def _validated(
    spec: PartitionSpec, shape: tuple[int, ...], mesh
) -> tuple[PartitionSpec, tuple[tuple[int, str, int], ...]]:
    """Drop axes that don't divide the dim (defensive against bad rules).
    Returns the surviving spec plus the dropped entries as
    ``(dim, axis_repr, extent)`` — extent 0 for an axis the mesh lacks."""
    sizes = dict(mesh.shape)
    out = []
    dropped: list[tuple[int, str, int]] = []
    for i, entry in enumerate(tuple(spec)):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        extent = 1
        known = True
        for ax in axes:
            if ax not in sizes:
                known = False
                continue
            extent *= sizes[ax]
        if known and i < len(shape) and shape[i] % extent == 0:
            out.append(entry)
        else:
            out.append(None)
            dropped.append((i, repr(entry), extent if known else 0))
    return P(*out), tuple(dropped)


def infer_param_sharding(
    params: Any,
    mesh: Mesh,
    plugin: FullyShardedDataParallelPlugin | None = None,
    rules: list[tuple[str, PartitionSpec]] | None = None,
):
    """NamedSharding pytree matching ``params`` structure."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    shardings = []
    for path, leaf in flat:
        spec = partition_spec_for(
            _path_to_str(path), tuple(np.shape(leaf)), mesh, plugin, rules
        )
        shardings.append(NamedSharding(mesh, spec))
    return jax.tree.unflatten(jax.tree.structure(params), shardings)


def shard_params(params: Any, shardings: Any):
    """Place params per the sharding tree (idempotent for already-placed)."""
    return jax.tree.map(lambda p, s: jax.device_put(p, s), params, shardings)


def paged_kv_sharding(mesh: Mesh, num_kv_heads: int, latent: bool = False) -> NamedSharding:
    """Sharding for the serving engine's block-paged KV pools
    (``[layers, num_blocks, block_size, n_kv*head_dim]`` — heads folded
    into the lane dimension, head ``n`` at lanes ``[n*hd, (n+1)*hd)``): the
    folded dimension over ``tp``, which is whole kv heads per shard when
    ``tp`` divides ``num_kv_heads`` — K/V are *produced* tp-sharded by the
    wk/wv projections (see ``LLAMA_PARTITION_RULES``), so storing the pool
    the same way keeps the block scatter/gather collective-free. Falls
    back to replicated when ``tp`` doesn't divide the head count (GQA
    models with few kv heads). A latent pool (``models.cache.CacheSpec``
    ``latent_rank``: one vector a token for every head) has no kv head and so
    nothing to put over ``tp``: asking for its placement is refused."""
    if latent:
        raise ValueError(
            "a latent pool keeps one vector a token for every head: there is no kv head "
            "to put over tp, and no placement of it under sharded query heads is built "
            "(ROADMAP Reach 3)")
    return _paged_heads_sharding(mesh, num_kv_heads)


def paged_kv_scale_sharding(mesh: Mesh, num_kv_heads: int) -> NamedSharding:
    """Sharding for the quantized pool's amax scale arrays
    (``[layers, num_blocks, block_size, n_kv]``): the kv-head dim follows
    :func:`paged_kv_sharding` exactly — a scale row must live with the
    payload lanes it dequantizes, or every fused-attention block read
    becomes a collective."""
    return _paged_heads_sharding(mesh, num_kv_heads)


def _paged_heads_sharding(mesh: Mesh, num_kv_heads: int) -> NamedSharding:
    # pool lanes and scale heads are both the last of four dimensions
    tp = mesh.shape["tp"]
    if tp > 1 and num_kv_heads % tp == 0:
        return NamedSharding(mesh, P(None, None, None, "tp"))
    return NamedSharding(mesh, P())


def opt_state_sharding_like(tx, params, param_shardings, mesh: Mesh):
    """Sharding tree for ``tx.init(params)``'s state: param-shaped leaves
    inherit the param's sharding (matched via optax's param-tree mirroring),
    scalars replicate. The torch analog is FSDP sharding optimizer state
    alongside flat params (reference ``utils/fsdp_utils.py``)."""
    import optax

    state_shape = jax.eval_shape(tx.init, params)
    replicated = NamedSharding(mesh, P())

    # Build shape→sharding lookup from params (the default policy makes the
    # spec a pure function of shape, so collisions are consistent).
    shape_map: dict[tuple, Any] = {}
    for leaf, sh in zip(jax.tree.leaves(params), jax.tree.leaves(param_shardings)):
        shape_map.setdefault(tuple(np.shape(leaf)), sh)

    def _sharding_for(leaf):
        return shape_map.get(tuple(leaf.shape), replicated)

    try:
        # Precise structural matching when optax can mirror the param tree.
        spec = optax.tree_map_params(
            tx,
            lambda _, s: s,
            state_shape,
            param_shardings,
            transform_non_params=lambda leaf: _sharding_for(leaf)
            if hasattr(leaf, "shape")
            else replicated,
        )
        return spec
    except Exception:
        return jax.tree.map(_sharding_for, state_shape)
