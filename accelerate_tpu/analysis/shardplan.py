"""Static sharding-plan analyzer — the pre-flight behind
``accelerate-tpu shard-check``.

The lint engine (:mod:`.rules`) answers "is this *code* TPU-correct";
this module answers "what will this *plan* cost" — per-device HBM bytes
and collective wire bytes, computed from abstract shapes before anything
compiles or allocates. Today the only way to learn that a partition rule
silently replicated a 700M-param tensor, or that the paged block pool
won't fit next to the optimizer state, is to OOM on the TPU.

Findings carry stable IDs like the lint rules:

* **SP001** (error) — a partition rule that matches no parameter (dead
  rule: a path-regex typo means the layout you think you asked for
  doesn't exist).
* **SP002** (error) — a parameter above a size threshold that ends up
  fully replicated on a multi-device mesh (every device pays its full
  bytes).
* **SP003** (error) — a rule entry whose mesh-axis extent does not divide
  the dimension it shards (the ``_validated`` silent-fallback path in
  ``parallel/sharding.py``, surfaced as a named finding).
* **SP004** (error) — predicted per-device HBM over the ``--hbm-gb``
  budget, with a tier breakdown and the ``big_modeling`` offload
  suggestion.
* **SP005** (warning) — reshard/all-gather ops in compiled HLO whose
  in/out shapes differ, ranked by estimated wire bytes per step (the same
  HLO text the collective digest walks).
* **SP006** (warning) — sharded-vs-replicated disagreement between a
  checkpoint manifest's piece table (``resilience/``) and the live plan
  (restore would take the gather-from-manifest slow path).

jax is imported lazily inside the functions that need it (the
``analysis/compiled.py`` convention): importing this module must work on
a box with no accelerator stack, so ``monitor``/``route`` stay jax-free.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

from ..utils.dataclasses import MESH_AXIS_ORDER
from ..utils.hlo import _DTYPE_BYTES

#: canonical mesh axes — the single source of truth is
#: utils.dataclasses.MESH_AXIS_ORDER (stdlib-only at import, so this stays
#: jax-free); rules._KNOWN_MESH_AXES mirrors it for the lint engine
MESH_AXES = tuple(MESH_AXIS_ORDER)


@dataclass(frozen=True)
class PlanRule:
    id: str
    severity: str  # "error" | "warning"
    summary: str
    fixit: str


#: the shard-plan finding catalogue — IDs are append-only, like the lint
#: rules; the CLI's --select/--ignore, the docs table, and the tests all
#: key on this dict
SP_RULES: dict[str, PlanRule] = {
    r.id: r
    for r in (
        PlanRule(
            "SP001",
            "error",
            "partition rule matches no parameter (dead rule)",
            "fix the path regex (or delete the rule) — the layout it asks for "
            "is silently not applied to anything",
        ),
        PlanRule(
            "SP002",
            "error",
            "large parameter is fully replicated on a multi-device mesh",
            "add a partition rule for it, or lower min_num_params so the FSDP "
            "policy shards it — every device is paying its full bytes",
        ),
        PlanRule(
            "SP003",
            "error",
            "mesh axis does not divide the parameter dimension it shards",
            "pick an axis extent that divides the dim (or pad the dim) — the "
            "runtime silently replicates that dim instead",
        ),
        PlanRule(
            "SP004",
            "error",
            "predicted per-device HBM footprint exceeds the budget",
            "shard more (rules / fsdp), shrink the serving block pool, or tier "
            "to host memory: FullyShardedDataParallelPlugin(cpu_offload=True) "
            "pins optimizer state to pinned_host, and big_modeling's "
            "cpu_offload/dispatch_model streams weights from host/disk",
        ),
        PlanRule(
            "SP005",
            "warning",
            "compiled HLO reshards between differing shardings (wire bytes)",
            "align producer/consumer shardings (with_sharding_constraint) so "
            "XLA stops paying this all-gather/all-to-all every step",
        ),
        PlanRule(
            "SP006",
            "warning",
            "checkpoint manifest sharding disagrees with the live plan",
            "restore will take the gather-from-manifest slow path — re-save "
            "under the current plan, or expect a one-time cross-mesh gather",
        ),
    )
}


@dataclass
class PlanFinding:
    rule: str
    severity: str
    message: str
    fixit: str
    #: what the finding is about: a param path, a rule pattern, a tier name,
    #: an HLO op — the plan-space analog of the lint Finding's path:line
    subject: str
    detail: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "rule": self.rule,
            "severity": self.severity,
            "message": self.message,
            "fixit": self.fixit,
            "subject": self.subject,
            "detail": self.detail,
        }

    def render(self) -> str:
        return (
            f"{self.subject}: {self.rule} [{self.severity}] {self.message}"
            f"\n    fix: {self.fixit}"
        )


def _finding(rule_id: str, subject: str, detail_msg: str = "", **detail) -> PlanFinding:
    rule = SP_RULES[rule_id]
    message = rule.summary + (f" ({detail_msg})" if detail_msg else "")
    return PlanFinding(
        rule=rule_id,
        severity=rule.severity,
        message=message,
        fixit=rule.fixit,
        subject=subject,
        detail=detail,
    )


def normalize_sp_ids(raw: str | None) -> set[str] | None:
    """``"SP001,sp4"`` → ``{"SP001", "SP004"}``; None passes through;
    unknown IDs raise ValueError (a typo'd --select must fail loudly)."""
    if not raw:
        return None
    out: set[str] = set()
    for part in raw.split(","):
        part = part.strip().upper()
        if not part:
            continue
        if part.startswith("SP"):
            part = "SP" + part[2:].zfill(3)
        if part not in SP_RULES:
            raise ValueError(
                f"unknown finding id {part!r} (known: {', '.join(sorted(SP_RULES))})"
            )
        out.add(part)
    return out or None


# ---------------------------------------------------------------------------
# the per-leaf plan
# ---------------------------------------------------------------------------


@dataclass
class LeafPlan:
    """One tensor's placement + cost under the plan."""

    path: str
    shape: tuple[int, ...]
    dtype: str
    tier: str  # "params" | "opt_state" | "grads" | "kv_pool" | "activations"
    spec: str  # str(PartitionSpec) of the validated placement
    source: str  # "rule" | "fsdp" | "replicated"
    rule_index: int | None
    dropped: tuple  # (dim, axis_repr, extent) entries validation discarded
    bytes_global: int
    bytes_per_device: int

    def to_dict(self) -> dict:
        return {
            "path": self.path,
            "shape": list(self.shape),
            "dtype": self.dtype,
            "tier": self.tier,
            "spec": self.spec,
            "source": self.source,
            "rule_index": self.rule_index,
            "dropped": [list(d) for d in self.dropped],
            "bytes_global": self.bytes_global,
            "bytes_per_device": self.bytes_per_device,
        }


class _PlanMesh:
    """Duck-typed mesh stand-in: just enough ``.shape`` for the placement
    planner, so a plan can be analyzed for a topology that isn't attached
    (``--virtual dp,fsdp,tp``) without touching any device."""

    def __init__(self, sizes: dict[str, int]):
        self.shape = dict(sizes)


def parse_mesh_spec(spec: str) -> dict[str, int]:
    """``"1,2,2"`` (positional dp,fsdp,tp) or ``"dp=1,fsdp=2,tp=2"`` →
    a full axis map (unnamed axes 1)."""
    sizes = {ax: 1 for ax in MESH_AXES}
    parts = [p.strip() for p in spec.split(",") if p.strip()]
    if any("=" in p for p in parts):
        for p in parts:
            name, _, val = p.partition("=")
            name = name.strip()
            if name not in sizes:
                raise ValueError(
                    f"unknown mesh axis {name!r} (known: {', '.join(MESH_AXES)})"
                )
            sizes[name] = int(val)
    else:
        positional = ("dp", "fsdp", "tp")
        if len(parts) > len(positional):
            raise ValueError(
                "positional --virtual takes at most dp,fsdp,tp — use the "
                "named form (dp=1,fsdp=2,...) for other axes"
            )
        for name, val in zip(positional, parts):
            sizes[name] = int(val)
    for name, val in sizes.items():
        if val < 1:
            raise ValueError(f"mesh axis {name} must be >= 1, got {val}")
    return sizes


def mesh_sizes_of(mesh) -> dict[str, int]:
    """Full ``{axis: size}`` map from a real Mesh, a _PlanMesh, or a dict."""
    if isinstance(mesh, dict):
        sizes = dict(mesh)
    else:
        sizes = {str(ax): int(n) for ax, n in dict(mesh.shape).items()}
    for ax in MESH_AXES:
        sizes.setdefault(ax, 1)
    return sizes


def _spec_divisor(spec, sizes: dict[str, int]) -> int:
    """Number of distinct shards a validated spec splits a tensor into
    (product of the named axes' extents). Exact: validation already
    guaranteed every sharded dim divides."""
    div = 1
    for entry in tuple(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        for ax in axes:
            div *= sizes.get(ax, 1)
    return div


#: itemsize fallback for dtype names plain numpy only resolves once
#: ml_dtypes is imported — this module stays importable jax-free
_EXT_DTYPE_ITEMSIZE = {
    "bfloat16": 2,
    "float8_e4m3fn": 1,
    "float8_e5m2": 1,
    "fp8": 1,
}


def _leaf_nbytes(shape, dtype) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        itemsize = _EXT_DTYPE_ITEMSIZE[str(dtype)]
    return n * itemsize


#: storage-dtype names the kv pool treats as quantized (scale arrays ride
#: beside the payload); "fp8" is the CLI spelling of float8_e4m3fn
_KV_QUANTIZED_DTYPES = ("int8", "fp8", "float8_e4m3fn")


def kv_storage_name(kv_dtype: str | None, compute_dtype: str = "float32") -> str:
    """CLI ``kv_dtype`` policy name → the storage dtype string the
    planners price blocks with. ONE mapping for ``serve --auto-blocks``
    and ``shard-check --kv-dtype`` (both must price exactly what the
    engine allocates, or predicted-vs-live bytes drift); ``auto`` follows
    the params' compute dtype, matching ``EngineConfig`` resolution."""
    if kv_dtype in (None, "auto"):
        return compute_dtype
    return {
        "f32": "float32",
        "bf16": "bfloat16",
        "int8": "int8",
        "fp8": "float8_e4m3fn",
    }[kv_dtype]


def plan_params(
    params,
    mesh_sizes: dict[str, int],
    rules=None,
    plugin=None,
    tier: str = "params",
) -> list[LeafPlan]:
    """Placement plan for every leaf of ``params`` (concrete arrays or
    ``jax.eval_shape`` structs — only ``.shape``/``.dtype`` are read)."""
    import jax

    from ..parallel.sharding import _path_to_str, explain_partition_spec
    from ..utils.dataclasses import FullyShardedDataParallelPlugin

    if plugin is None:
        plugin = FullyShardedDataParallelPlugin()
    mesh = _PlanMesh(mesh_sizes)
    out: list[LeafPlan] = []
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        path_str = _path_to_str(path)
        shape = tuple(int(d) for d in np.shape(leaf))
        dtype = str(getattr(leaf, "dtype", np.float32().dtype))
        decision = explain_partition_spec(path_str, shape, mesh, plugin, rules)
        divisor = _spec_divisor(decision.spec, mesh_sizes)
        nbytes = _leaf_nbytes(shape, dtype)
        out.append(
            LeafPlan(
                path=path_str,
                shape=shape,
                dtype=dtype,
                tier=tier,
                spec=str(decision.spec),
                source=decision.source,
                rule_index=decision.rule_index,
                dropped=decision.dropped,
                bytes_global=nbytes,
                bytes_per_device=nbytes // divisor,
            )
        )
    return out


class _Replicated:
    """Sentinel carrier for opt-state leaves with no param twin (adam's
    ``count`` scalar) — must be a non-pytree object so optax/jax treat it
    as a leaf."""


_REPLICATED = _Replicated()

_OPTIMIZERS = ("adam", "adamw", "sgd")


def plan_opt_state(
    optimizer: str,
    params,
    param_plans: list[LeafPlan],
    mesh_sizes: dict[str, int],
) -> list[LeafPlan]:
    """Placement plan for ``tx.init(params)``'s state, mirroring
    :func:`parallel.sharding.opt_state_sharding_like` exactly: param-shaped
    leaves inherit the param's placement (matched via optax's param-tree
    mirroring, shape-map fallback), everything else replicates — so the
    predicted bytes match the live placement byte-for-byte."""
    import jax
    import optax

    tx = {
        "adam": lambda: optax.adam(1e-3),
        "adamw": lambda: optax.adamw(1e-3),
        "sgd": lambda: optax.sgd(1e-3),
    }[optimizer]()
    state_shape = jax.eval_shape(tx.init, params)
    flat_params, treedef = jax.tree_util.tree_flatten(params)
    plan_tree = jax.tree_util.tree_unflatten(treedef, param_plans)

    shape_map: dict[tuple, LeafPlan] = {}
    for plan in param_plans:
        shape_map.setdefault(plan.shape, plan)

    def _for_leaf(leaf):
        return shape_map.get(tuple(np.shape(leaf)), _REPLICATED)

    try:
        mirror = optax.tree_map_params(
            tx,
            lambda _, plan: plan,
            state_shape,
            plan_tree,
            transform_non_params=lambda leaf: _for_leaf(leaf)
            if hasattr(leaf, "shape")
            else _REPLICATED,
        )
    except Exception:
        mirror = jax.tree_util.tree_map(_for_leaf, state_shape)

    state_flat, _ = jax.tree_util.tree_flatten_with_path(state_shape)
    carriers = jax.tree_util.tree_leaves(mirror)
    out: list[LeafPlan] = []
    for (path, leaf), carrier in zip(state_flat, carriers):
        shape = tuple(int(d) for d in np.shape(leaf))
        dtype = str(getattr(leaf, "dtype", np.float32().dtype))
        nbytes = _leaf_nbytes(shape, dtype)
        if isinstance(carrier, LeafPlan) and carrier.shape == shape:
            spec, source = carrier.spec, carrier.source
            divisor = max(carrier.bytes_global // max(carrier.bytes_per_device, 1), 1)
        else:
            spec, source, divisor = "PartitionSpec()", "replicated", 1
        out.append(
            LeafPlan(
                path="opt" + jax.tree_util.keystr(path),
                shape=shape,
                dtype=dtype,
                tier="opt_state",
                spec=spec,
                source=source,
                rule_index=None,
                dropped=(),
                bytes_global=nbytes,
                bytes_per_device=nbytes // divisor,
            )
        )
    return out


def plan_kv_pool(
    num_layers: int,
    num_kv_heads: int,
    head_dim: int,
    num_slots: int,
    block_size: int,
    max_seq_len: int,
    mesh_sizes: dict[str, int],
    num_blocks: int | None = None,
    dtype: str = "float32",
    pool_leaves: tuple = ("k", "v"),
) -> list[LeafPlan]:
    """Placement plan for the serving engine's two paged pools (``pool_leaves``
    ``("k",)``: a latent spec's one pool — ``num_kv_heads`` 1, ``head_dim``
    its stored ``pool_width`` — which is never sharded), mirroring
    :func:`parallel.sharding.paged_kv_sharding`: the pools are stored
    lane-folded (``[layers, num_blocks, block_size, n_kv*head_dim]``) and
    the folded dim goes over ``tp`` — whole kv heads per shard — when
    ``tp`` divides ``num_kv_heads``, else replicated. ``num_blocks``
    defaults to the engine's full-residency default (slots × per-slot max
    + null block). A model whose paged layers are of several kinds
    (``models/cache.py:PagedKind``) is planned a kind at a time: this is the
    plan of ONE kind's pool (``num_layers`` its layers), which for the first
    kind is what ``num_blocks`` counts; a kind that keeps a window has a pool
    of ``CacheSpec.window_pools`` blocks, priced as a fixed cost by
    ``CacheSpec.window_pool_bytes`` (``engine_preflight``'s ``state_bytes``,
    ``serve --auto-blocks``).

    Quantized storage (``dtype`` of ``int8``/``fp8``/``float8_e4m3fn`` —
    the engine's ``kv_dtype`` policy) adds the two f32 amax scale arrays
    (``[layers, num_blocks, block_size, n_kv]``, kv-head dim sharded the
    same way) so predicted pool bytes stay byte-exact against the live
    engine's ``_kp/_vp/_ks/_vs`` footprint."""
    blocks_per_slot = -(-max_seq_len // block_size)  # ceil
    if num_blocks is None:
        num_blocks = num_slots * blocks_per_slot + 1
    quantized = str(dtype) in _KV_QUANTIZED_DTYPES
    if str(dtype) == "fp8":
        dtype = "float8_e4m3fn"
    shape = (num_layers, num_blocks, block_size, num_kv_heads * head_dim)
    tp = mesh_sizes.get("tp", 1)
    sharded = tp > 1 and num_kv_heads % tp == 0
    divisor = tp if sharded else 1

    # pool lanes and scale heads are both the last of four dimensions
    spec = "PartitionSpec(None, None, None, 'tp')" if sharded else "PartitionSpec()"

    def _leaf(name, shape, dtype):
        nbytes = _leaf_nbytes(shape, dtype)
        return LeafPlan(
            path=f"kv_pool.{name}",
            shape=shape,
            dtype=str(dtype),
            tier="kv_pool",
            spec=spec,
            source="rule" if sharded else "replicated",
            rule_index=None,
            dropped=(),
            bytes_global=nbytes,
            bytes_per_device=nbytes // divisor,
        )

    leaves = [_leaf(name, shape, dtype) for name in pool_leaves]
    if quantized:
        scale_shape = (num_layers, num_blocks, block_size, num_kv_heads)
        leaves += [_leaf(name + "_scale", scale_shape, "float32") for name in pool_leaves]
    return leaves


def plan_swap_pool(
    num_layers: int,
    num_kv_heads: int,
    head_dim: int,
    block_size: int,
    swap_gb: float,
    dtype: str = "float32",
) -> dict:
    """Host-DRAM footprint of the serving engine's KV swap tier
    (``EngineConfig(swap_gb=...)``): the capacity-bounded NumPy mirror
    preempted requests' unshared blocks are parked in. This is **host**
    memory, deliberately excluded from the per-device HBM totals — it is
    reported alongside them so an ``--hbm-gb`` pre-flight stays truthful
    about where the swapped bytes actually live. Quantized ``dtype``
    (``kv_dtype`` int8/fp8) adds the f32 scale mirrors per block, exactly
    matching :class:`serving.radix.SwapPool`'s accounting."""
    quantized = str(dtype) in _KV_QUANTIZED_DTYPES
    if str(dtype) == "fp8":
        dtype = "float8_e4m3fn"
    block_shape = (num_layers, block_size, num_kv_heads, head_dim)
    per_block = 2 * _leaf_nbytes(block_shape, dtype)  # K + V mirrors
    if quantized:
        per_block += 2 * _leaf_nbytes(block_shape[:-1], "float32")  # scales
    blocks = max(0, int(swap_gb * (1 << 30)) // per_block) if per_block else 0
    return {
        "swap_gb": float(swap_gb),
        "swap_blocks": blocks,
        "bytes_per_block": per_block,
        "swap_pool_host_bytes": blocks * per_block,
    }


def plan_activation_estimate(
    apply_fn,
    params,
    batch: int,
    seq: int,
    hidden: int,
    num_layers: int,
    mesh_sizes: dict[str, int],
    remat: bool = False,
    dtype: str = "float32",
) -> list[LeafPlan]:
    """Coarse forward-liveness ESTIMATE (explicitly a lower bound, not the
    exact XLA live set): the output leaves of ``jax.eval_shape`` on the
    apply fn (the logits buffer dominates) plus one residual
    ``[b, s, h]`` per non-rematerialized layer. Batch-sharded over
    dp×fsdp, the residual-spec policy."""
    import jax

    leaves: list[LeafPlan] = []
    div = mesh_sizes.get("dp", 1) * mesh_sizes.get("fsdp", 1)
    if batch % div != 0:
        div = 1  # non-divisible batch: be conservative, count full bytes

    ids = jax.ShapeDtypeStruct((batch, seq), np.int32)
    try:
        out_shape = jax.eval_shape(lambda p, i: apply_fn(p, input_ids=i), params, ids)
    except Exception as e:
        # swallowing this would silently drop the DOMINANT tier (the
        # logits buffer) and understate the capacity estimate — the exact
        # lie this tool exists to prevent; fail loudly instead
        raise ValueError(
            f"activation estimate failed: eval_shape of the apply fn at "
            f"batch={batch}, seq={seq} raised {type(e).__name__}: {e} — "
            f"is --seq within the model's max_position_embeddings?"
        ) from e
    out_bytes = sum(
        _leaf_nbytes(tuple(leaf.shape), str(leaf.dtype))
        for leaf in jax.tree_util.tree_leaves(out_shape)
    )
    leaves.append(
        LeafPlan(
            path="activations.outputs",
            shape=(batch, seq),
            dtype="mixed",
            tier="activations",
            spec=f"PartitionSpec(('dp', 'fsdp'), ...) /{div}",
            source="fsdp",
            rule_index=None,
            dropped=(),
            bytes_global=out_bytes,
            bytes_per_device=out_bytes // div,
        )
    )
    live_layers = 1 if remat else max(num_layers, 1)
    res_bytes = _leaf_nbytes((batch, seq, hidden), dtype) * live_layers
    leaves.append(
        LeafPlan(
            path=f"activations.residuals_x{live_layers}",
            shape=(batch, seq, hidden),
            dtype=str(np.dtype(dtype)),
            tier="activations",
            spec=f"PartitionSpec(('dp', 'fsdp'), 'cp', None) /{div}",
            source="fsdp",
            rule_index=None,
            dropped=(),
            bytes_global=res_bytes,
            bytes_per_device=res_bytes // div,
        )
    )
    return leaves


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------


@dataclass
class PlanReport:
    mesh: dict[str, int]
    leaves: list[LeafPlan]
    findings: list[PlanFinding]
    hbm_budget_bytes: int | None = None
    #: host-DRAM tiers (the KV swap pool) — reported alongside HBM but
    #: never summed into ``bytes_per_device`` (they live on the host)
    host: dict | None = None

    @property
    def tiers(self) -> dict[str, dict[str, int]]:
        out: dict[str, dict[str, int]] = {}
        for leaf in self.leaves:
            tier = out.setdefault(leaf.tier, {"bytes_global": 0, "bytes_per_device": 0})
            tier["bytes_global"] += leaf.bytes_global
            tier["bytes_per_device"] += leaf.bytes_per_device
        return out

    @property
    def bytes_per_device(self) -> int:
        return sum(leaf.bytes_per_device for leaf in self.leaves)

    @property
    def errors(self) -> list[PlanFinding]:
        return [f for f in self.findings if f.severity == "error"]

    def to_dict(self) -> dict:
        return {
            "mesh": self.mesh,
            "devices": int(np.prod(list(self.mesh.values()))),
            "hbm_budget_bytes": self.hbm_budget_bytes,
            "bytes_per_device": self.bytes_per_device,
            "host": self.host,
            "tiers": self.tiers,
            "errors": len(self.errors),
            "warnings": len(self.findings) - len(self.errors),
            "findings": [f.to_dict() for f in self.findings],
            "leaves": [leaf.to_dict() for leaf in self.leaves],
        }


def _gb(nbytes: int) -> str:
    return f"{nbytes / (1 << 30):.3f} GiB"


def plan_findings(
    leaves: list[LeafPlan],
    rules,
    mesh_sizes: dict[str, int],
    hbm_budget_bytes: int | None = None,
    replicated_threshold_bytes: int = 16 << 20,
) -> list[PlanFinding]:
    """SP001-SP004 over a computed plan."""
    findings: list[PlanFinding] = []
    param_leaves = [l for l in leaves if l.tier == "params"]

    # SP001: dead rules — never SELECTED for any parameter (a rule shadowed
    # by an earlier match for every path it would hit is equally dead)
    if rules:
        used = {l.rule_index for l in param_leaves if l.rule_index is not None}
        for i, (pattern, spec) in enumerate(rules):
            if i not in used:
                findings.append(
                    _finding(
                        "SP001",
                        f"rule[{i}] {pattern!r}",
                        f"pattern {pattern!r} -> {spec} selected no parameter",
                        rule_index=i,
                        pattern=str(pattern),
                    )
                )

    # SP002: big replicated params on a mesh with sharding axes to spare
    multi = any(mesh_sizes.get(ax, 1) > 1 for ax in ("fsdp", "tp"))
    if multi:
        for leaf in param_leaves:
            if (
                leaf.bytes_global >= replicated_threshold_bytes
                and leaf.bytes_per_device == leaf.bytes_global
            ):
                cause = {
                    "rule": f"rule[{leaf.rule_index}] forces {leaf.spec}",
                    "fsdp": "FSDP policy found no divisible dim",
                    "replicated": "no rule matched and the FSDP policy declined",
                }[leaf.source]
                findings.append(
                    _finding(
                        "SP002",
                        leaf.path,
                        f"{_gb(leaf.bytes_global)} replicated on every device — {cause}",
                        bytes=leaf.bytes_global,
                        shape=list(leaf.shape),
                        source=leaf.source,
                    )
                )

    # SP003: validation-dropped rule entries
    for leaf in leaves:
        for dim, axis, extent in leaf.dropped:
            dim_size = leaf.shape[dim] if dim < len(leaf.shape) else None
            detail = (
                f"axis {axis} absent from the mesh"
                if extent == 0
                else f"extent {extent} does not divide dim {dim} (size {dim_size})"
            )
            findings.append(
                _finding(
                    "SP003",
                    leaf.path,
                    detail + " — dim silently replicated at runtime",
                    dim=dim,
                    axis=axis,
                    extent=extent,
                    shape=list(leaf.shape),
                )
            )

    # SP004: over budget
    if hbm_budget_bytes is not None:
        total = sum(l.bytes_per_device for l in leaves)
        if total > hbm_budget_bytes:
            tiers: dict[str, int] = {}
            for leaf in leaves:
                tiers[leaf.tier] = tiers.get(leaf.tier, 0) + leaf.bytes_per_device
            breakdown = ", ".join(
                f"{tier}={_gb(b)}" for tier, b in sorted(tiers.items(), key=lambda kv: -kv[1])
            )
            findings.append(
                _finding(
                    "SP004",
                    "hbm_budget",
                    f"{_gb(total)}/device > budget {_gb(hbm_budget_bytes)} "
                    f"({breakdown})",
                    bytes_per_device=total,
                    budget_bytes=hbm_budget_bytes,
                    tiers=tiers,
                )
            )
    return findings


def analyze_plan(
    params,
    mesh: dict[str, int],
    rules=None,
    plugin=None,
    optimizer: str | None = "adam",
    kv_pool: dict | None = None,
    activations: dict | None = None,
    include_grads: bool = False,
    hbm_gb: float | None = None,
    swap_gb: float | None = None,
    replicated_threshold_bytes: int = 16 << 20,
    draft_layers: int | None = None,
    stacked_prefix: str = "layers",
) -> PlanReport:
    """The full static pre-flight: tiers (params, optimizer state, grads,
    paged KV pool, the speculative ``draft_params`` tier when
    ``draft_layers`` is set, activation estimate) per device, plus
    SP001-SP004 findings (SP004's breakdown names every tier, the draft
    included).

    ``params`` may be concrete or abstract (``jax.eval_shape`` output);
    ``mesh`` is an axis-size map (from a real Mesh via
    :func:`mesh_sizes_of`, or virtual via :func:`parse_mesh_spec`).
    ``kv_pool``/``activations`` are kwargs dicts for
    :func:`plan_kv_pool`/:func:`plan_activation_estimate`.
    """
    sizes = mesh_sizes_of(mesh)
    leaves = plan_params(params, sizes, rules=rules, plugin=plugin)
    if optimizer and optimizer != "none":
        leaves += plan_opt_state(optimizer, params, list(leaves), sizes)
    if include_grads:
        leaves += [
            LeafPlan(
                path="grads." + l.path,
                shape=l.shape,
                dtype=l.dtype,
                tier="grads",
                spec=l.spec,
                source=l.source,
                rule_index=None,
                dropped=(),
                bytes_global=l.bytes_global,
                bytes_per_device=l.bytes_per_device,
            )
            for l in leaves
            if l.tier == "params"
        ]
    if draft_layers:
        # appended AFTER the optimizer mirror: plan_opt_state unflattens
        # the params-tier list against the params treedef, which a mixed
        # list would break
        leaves += plan_draft_params(
            params, sizes, rules, draft_layers, stacked_prefix=stacked_prefix
        )
    if kv_pool:
        leaves += plan_kv_pool(mesh_sizes=sizes, **kv_pool)
    host = None
    if kv_pool and swap_gb:
        host = plan_swap_pool(
            num_layers=kv_pool["num_layers"],
            num_kv_heads=kv_pool["num_kv_heads"],
            head_dim=kv_pool["head_dim"],
            block_size=kv_pool["block_size"],
            swap_gb=swap_gb,
            dtype=kv_pool.get("dtype", "float32"),
        )
    if activations:
        leaves += plan_activation_estimate(mesh_sizes=sizes, **activations)
    budget = int(hbm_gb * (1 << 30)) if hbm_gb is not None else None
    findings = plan_findings(
        leaves,
        rules,
        sizes,
        hbm_budget_bytes=budget,
        replicated_threshold_bytes=replicated_threshold_bytes,
    )
    return PlanReport(
        mesh=sizes, leaves=leaves, findings=findings,
        hbm_budget_bytes=budget, host=host,
    )


# ---------------------------------------------------------------------------
# SP005: resharding cost from compiled HLO text
# ---------------------------------------------------------------------------

#: the collective walk the PR 6 digest uses, extended with operand capture:
#: result shape(s), op, async suffix, operand list
_HLO_RESHARD = re.compile(
    r"=\s*\(?((?:\w+\[[0-9,]*\][^)=]*?,?\s*)+)\)?\s*"
    r"(all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(([^)]*)\)"
)
_HLO_SHAPE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shapes_bytes(text: str) -> tuple[list[str], int]:
    shapes, total = [], 0
    for m in _HLO_SHAPE.finditer(text):
        dtype, dims = m.group(1), m.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        shapes.append(f"{dtype}[{dims}]")
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return shapes, total


def resharding_report(hlo_text: str, min_bytes: int = 1 << 20) -> list[dict]:
    """Reshard ops in a compiled module, ranked by estimated wire bytes.

    All-gather/reduce-scatter entries count when operand and result shapes
    differ (the op redistributes data across devices — in/out shardings
    differ by construction); all-to-all/collective-permute are pure
    reshards and always count. Bytes are result-shape bytes, the same
    ICI/DCN proxy ``utils/hlo.py`` uses. Entries under ``min_bytes`` are
    dropped (an FSDP program legitimately all-gathers small params)."""
    out = []
    for m in _HLO_RESHARD.finditer(hlo_text):
        results, op, start, operands = m.group(1), m.group(2), m.group(3), m.group(4)
        res_shapes, res_bytes = _shapes_bytes(results)
        if start and len(res_shapes) > 1:
            # async -start returns (operand-alias, result): count the result
            res_shapes, res_bytes = _shapes_bytes(res_shapes[-1])
        op_shapes, _ = _shapes_bytes(operands)
        if op in ("all-gather", "reduce-scatter") and res_shapes == op_shapes:
            continue  # no shape change: not a reshard of this buffer
        if res_bytes < min_bytes:
            continue
        out.append(
            {
                "op": op + ("-start" if start else ""),
                "result_shapes": res_shapes,
                "operand_shapes": op_shapes,
                "bytes": res_bytes,
            }
        )
    out.sort(key=lambda e: -e["bytes"])
    return out


def resharding_findings(
    hlo_text: str, label: str = "hlo", min_bytes: int = 1 << 20, top: int = 5
) -> list[PlanFinding]:
    """SP005 findings for the top reshard offenders of one module."""
    entries = resharding_report(hlo_text, min_bytes=min_bytes)
    findings = []
    for rank, entry in enumerate(entries[:top], start=1):
        findings.append(
            _finding(
                "SP005",
                f"{label}#{rank} {entry['op']}",
                f"~{entry['bytes'] / 1e6:.1f} MB/step "
                f"({', '.join(entry['operand_shapes'][:2]) or '?'} -> "
                f"{', '.join(entry['result_shapes'][:2])})",
                **entry,
            )
        )
    if len(entries) > top:
        skipped = sum(e["bytes"] for e in entries[top:])
        findings.append(
            _finding(
                "SP005",
                f"{label}#{top + 1}+",
                f"{len(entries) - top} more reshard ops totalling "
                f"~{skipped / 1e6:.1f} MB/step",
                more=len(entries) - top,
                bytes=skipped,
            )
        )
    return findings


# ---------------------------------------------------------------------------
# SP006: checkpoint manifest vs the live plan
# ---------------------------------------------------------------------------

_SPEC_AXIS = re.compile(r"'(\w+)'")


def _spec_is_sharded(spec_str: str | None) -> bool | None:
    """True/False from a manifest spec repr; None when unrecorded."""
    if spec_str is None:
        return None
    return bool(_SPEC_AXIS.findall(spec_str))


def manifest_findings(manifest: dict, param_plans: list[LeafPlan]) -> list[PlanFinding]:
    """SP006: keys in the manifest's piece table whose recorded sharding
    class (sharded vs replicated) disagrees with the live plan's."""
    plan_by_path = {p.path: p for p in param_plans}
    findings: list[PlanFinding] = []
    for component, entries in (manifest.get("arrays") or {}).items():
        for key, entry in entries.items():
            plan = plan_by_path.get(key)
            if plan is None:
                continue
            saved = _spec_is_sharded(entry.get("spec"))
            if saved is None:
                continue
            planned = plan.bytes_per_device < plan.bytes_global
            if saved != planned:
                findings.append(
                    _finding(
                        "SP006",
                        f"{component}/{key}",
                        f"checkpoint saved {'sharded' if saved else 'replicated'} "
                        f"({entry.get('spec')}), plan places it "
                        f"{'sharded' if planned else 'replicated'} ({plan.spec})",
                        component=component,
                        key=key,
                        saved_spec=entry.get("spec"),
                        planned_spec=plan.spec,
                    )
                )
    return findings


# ---------------------------------------------------------------------------
# runtime seams: engine pre-flight, auto block sizing, compile-fact bytes
# ---------------------------------------------------------------------------


def plan_draft_params(
    params,
    mesh_sizes: dict[str, int],
    rules,
    draft_layers: int,
    stacked_prefix: str = "layers",
) -> list["LeafPlan"]:
    """The speculative-decoding ``draft_params`` tier: the first
    ``draft_layers`` entries of the layer-stacked parameter leaves — what
    an ``early_exit:N`` draft costs. The engine slices these **in-trace**
    (no persistent copy), but the compiled spec executable still
    materialises the slice as a transient buffer, so the pre-flight prices
    it conservatively as a resident tier under the same partition rules as
    the full stack (the slice inherits the leaves' sharding)."""
    import jax

    stacked = params.get(stacked_prefix) if isinstance(params, dict) else None
    if stacked is None:
        raise ValueError(
            f"draft tier needs layer-stacked params under {stacked_prefix!r}"
        )
    draft_tree = {
        stacked_prefix: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                (draft_layers, *tuple(a.shape)[1:]), a.dtype
            ),
            stacked,
        )
    }
    leaves = plan_params(draft_tree, mesh_sizes, rules=rules, tier="draft_params")
    # the tier rides the same rules as params, so rule usage accounting
    # must not double-claim: SP001 dead-rule detection reads params leaves
    # only (plan_findings filters by tier), and these leaves are renamed so
    # a report never shows two identical paths in different tiers
    for leaf in leaves:
        leaf.path = "draft." + leaf.path
    return leaves


def engine_preflight(
    params,
    rules,
    mesh,
    pool_shape: tuple[int, ...],
    num_kv_heads: int,
    pool_dtype,
    hbm_budget_gb: float,
    swap_gb: float | None = None,
    draft_layers: int | None = None,
    stacked_prefix: str = "layers",
    state_bytes: int = 0,
    pool_leaves: tuple = ("k", "v"),
) -> dict:
    """The serving engine's capacity check, run BEFORE the pools allocate:
    predicted per-device bytes of params (under the same planner
    ``_place_on_mesh`` uses) + the two paged pools (``pool_shape`` is the
    engine's stored, lane-folded ``[layers, num_blocks, block_size,
    num_kv_heads*head_dim]``), vs the budget.

    Returns ``{params_bytes, pool_bytes, total_bytes, budget_bytes,
    headroom_bytes, over}`` — the engine raises on ``over`` (the SP004
    contract: refuse to start, don't OOM mid-request). With ``swap_gb``
    set, ``swap_pool_host_bytes`` reports the host-DRAM swap tier's
    footprint alongside — deliberately *excluded* from ``total_bytes`` (a
    swapped block lives in host memory, not HBM), so the HBM pre-flight
    stays truthful with swap on. ``draft_layers`` (speculative decoding
    armed) adds the ``draft_params`` tier — :func:`plan_draft_params` —
    into ``total_bytes`` and reports it as ``draft_bytes``. ``state_bytes``
    is a model's per-slot state beside the pool (``models/cache.py``:
    fixed per slot, replicated), counted into ``total_bytes`` and reported
    as such."""
    sizes = mesh_sizes_of(mesh) if mesh is not None else {ax: 1 for ax in MESH_AXES}
    param_plans = plan_params(params, sizes, rules=rules)
    params_bytes = sum(p.bytes_per_device for p in param_plans)
    draft_bytes = 0
    if draft_layers:
        draft_bytes = sum(
            p.bytes_per_device
            for p in plan_draft_params(
                params, sizes, rules, draft_layers, stacked_prefix=stacked_prefix
            )
        )
    pool_plans = plan_kv_pool(
        num_layers=pool_shape[0],
        num_blocks=pool_shape[1],
        block_size=pool_shape[2],
        num_kv_heads=num_kv_heads,
        head_dim=pool_shape[3] // num_kv_heads,
        num_slots=1,  # num_blocks is explicit; slots only feed the default
        max_seq_len=pool_shape[2],
        mesh_sizes=sizes,
        dtype=str(np.dtype(pool_dtype)),
        pool_leaves=pool_leaves,
    )
    pool_bytes = sum(p.bytes_per_device for p in pool_plans)
    budget = int(hbm_budget_gb * (1 << 30))
    total = params_bytes + draft_bytes + pool_bytes + int(state_bytes)
    report = {
        "params_bytes": params_bytes,
        "pool_bytes": pool_bytes,
        "state_bytes": int(state_bytes),
        "total_bytes": total,
        "budget_bytes": budget,
        "headroom_bytes": budget - total,
        "over": total > budget,
    }
    if draft_layers:
        report["draft_bytes"] = draft_bytes
        report["draft_layers"] = int(draft_layers)
    if swap_gb:
        report["swap_pool_host_bytes"] = plan_swap_pool(
            num_layers=pool_shape[0],
            num_kv_heads=num_kv_heads,
            head_dim=pool_shape[3] // num_kv_heads,
            block_size=pool_shape[2],
            swap_gb=swap_gb,
            dtype=str(np.dtype(pool_dtype)),
        )["swap_pool_host_bytes"]
    return report


def auto_num_blocks(
    budget_bytes: int,
    params_bytes: int,
    per_block_bytes: int,
    full_residency_blocks: int,
    min_blocks: int,
    reserve_frac: float = 0.05,
) -> tuple[int, int]:
    """Size the paged pool from the HBM model instead of a hand-picked
    constant: as many blocks as fit under ``budget*(1-reserve) - params``,
    capped at full residency (more is pure waste). Returns
    ``(num_blocks, headroom_bytes)``; raises ValueError (the SP004
    refusal) when even ``min_blocks`` don't fit."""
    avail = int(budget_bytes * (1.0 - reserve_frac)) - params_bytes
    fit = avail // per_block_bytes if per_block_bytes > 0 else 0
    n = int(min(full_residency_blocks, fit))
    if n < min_blocks:
        raise ValueError(
            f"SP004: HBM budget {_gb(budget_bytes)} leaves room for {max(fit, 0)} "
            f"KV block(s) after {_gb(params_bytes)} of params "
            f"({per_block_bytes / 1e6:.2f} MB/block/device) — need at least "
            f"{min_blocks} to admit one request. Shard more, shrink "
            f"max_seq_len/block_size, or raise --hbm-gb"
        )
    headroom = budget_bytes - params_bytes - n * per_block_bytes
    return n, headroom


def arg_bytes_report(args) -> tuple[int, int]:
    """(predicted, actual) per-device bytes of one compiled call's args —
    the numbers the AOT path stamps onto compile facts under the
    sanitizer. Predicted divides each leaf's global bytes by its
    NamedSharding's axis extents (the static model); actual sums the real
    shard buffers living on each leaf's first addressable device.
    Uncommitted host leaves count full-size on both sides (GSPMD
    replicates them)."""
    import jax

    predicted = actual = 0
    for leaf in jax.tree_util.tree_leaves(args):
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", None)
        if dtype is None:
            continue
        nbytes = _leaf_nbytes(shape, dtype)
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        div = 1
        if spec is not None and getattr(sharding, "mesh", None) is not None:
            div = _spec_divisor(spec, mesh_sizes_of(sharding.mesh))
        predicted += nbytes // div
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            dev0 = shards[0].device
            actual += sum(int(s.data.nbytes) for s in shards if s.device == dev0)
        else:
            actual += nbytes
    return predicted, actual
