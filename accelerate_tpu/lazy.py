"""Deferred computation graph — the define-by-run autodiff shim.

The reference's user contract is imperative: ``outputs = model(**batch)``
then ``accelerator.backward(loss)`` (reference ``accelerator.py:2218``)
relies on torch's define-by-run autograd. JAX is define-then-run, so the
prepared model does **not** execute eagerly: calling it records a
:class:`Node` graph and returns :class:`Deferred` proxies. When the user
calls ``backward(loss)`` (or forces a value, e.g. ``.item()`` /
``gather_for_metrics``), the graph is replayed inside a single
``jit``-compiled function — compiled **once per graph signature** and cached,
so step 2..N of a training loop reuse the same executable with fresh batch
leaves. SURVEY §7 "API impedance" is resolved here.

Supported deferred surface: arithmetic (+,-,*,/,**,negation, comparisons),
reductions (mean/sum/max/min), shaping (reshape/transpose/squeeze/getitem),
``argmax``/``astype``, attribute/item access on model outputs, and
:func:`defer_call` for arbitrary traceable functions. Anything outside this
follows the same restriction class as ``torch.compile`` in the reference.
"""

from __future__ import annotations

import functools
import operator
import time
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from .analysis.sanitizer import get_active_sanitizer as _get_sanitizer
from .diagnostics.tracing import get_tracer as _get_tracer, trace_span as _trace_span


# ---------------------------------------------------------------------------
# graph nodes
# ---------------------------------------------------------------------------


class Node:
    __slots__ = ("op", "args", "static")

    def __init__(self, op: str, args: tuple, static: tuple = ()):
        self.op = op          # operation name
        self.args = args      # operand Nodes / raw leaves
        self.static = static  # hashable non-array parameters (axis, fn id, …)


class InputNode(Node):
    """A concrete array fed in at execution time (a batch tensor, a constant).
    Concrete operands are *always* lifted to inputs — never baked into the
    trace — so a cached executable replays correctly with fresh data."""

    __slots__ = ("value", "_input_idx")

    def __init__(self, value):
        super().__init__("input", ())
        self.value = value
        self._input_idx = -1


class ModelCallNode(Node):
    """Application of a prepared model to a pytree of (possibly deferred)
    inputs. ``model`` is static (closed over at trace time); array leaves of
    args/kwargs become graph inputs.

    ``compute_dtype``/``fp8_recipe`` snapshot the model's precision policy
    AT CALL TIME — replay happens later (at ``step()``/``force()``), by
    which point an ``autocast(enabled=False)`` island has exited; the
    snapshot is what makes the island apply to deferred calls made inside
    it. Both are part of the jit-cache signature (see ``linearize``)."""

    __slots__ = ("model", "call_args", "call_kwargs", "compute_dtype", "fp8_recipe")

    def __init__(self, model, call_args: tuple, call_kwargs: dict):
        super().__init__("model_call", ())
        self.model = model
        self.call_args = call_args
        self.call_kwargs = call_kwargs
        self.compute_dtype = getattr(model, "compute_dtype", None)
        self.fp8_recipe = getattr(model, "fp8_recipe", None)


def _is_array(x) -> bool:
    return isinstance(x, (jax.Array, np.ndarray)) or np.isscalar(x)


def as_node(x) -> Node:
    if isinstance(x, Deferred):
        return x._node
    if isinstance(x, Node):
        return x
    return InputNode(x)


# ---------------------------------------------------------------------------
# signature + linearisation
# ---------------------------------------------------------------------------


def _leaf_sig(v) -> tuple:
    if isinstance(v, (jax.Array, np.ndarray)):
        return ("arr", tuple(v.shape), str(v.dtype))
    return ("scalar", type(v).__name__)


def linearize(root: Node):
    """Topological walk collecting (signature, input_leaves, model_set).

    ``signature`` is a hashable canonical description of the graph with
    array leaves abstracted to shape/dtype — the jit-cache key.
    ``input_leaves`` are the concrete arrays in deterministic order.
    """
    sig_parts: list = []
    inputs: list = []
    models: list = []
    seen: dict[int, int] = {}

    def walk(node: Node) -> int:
        nid = id(node)
        if nid in seen:
            return seen[nid]
        if isinstance(node, InputNode):
            idx = len(inputs)
            inputs.append(node.value)
            my_id = len(sig_parts)
            sig_parts.append(("input", idx, _leaf_sig(node.value)))
        elif isinstance(node, ModelCallNode):
            if node.model not in models:
                models.append(node.model)
            m_idx = models.index(node.model)
            # split args/kwargs into structure + leaves; deferred leaves recurse
            flat, treedef = jax.tree.flatten(
                (node.call_args, node.call_kwargs),
                is_leaf=lambda x: isinstance(x, Deferred),
            )
            arg_ids = []
            for leaf in flat:
                if isinstance(leaf, Deferred):
                    arg_ids.append(("node", walk(leaf._node)))
                else:
                    idx = len(inputs)
                    inputs.append(leaf)
                    arg_ids.append(("leaf", idx, _leaf_sig(leaf)))
            my_id = len(sig_parts)
            sig_parts.append(
                (
                    "model_call", m_idx, str(treedef), tuple(arg_ids),
                    str(node.compute_dtype),
                    getattr(node.fp8_recipe, "fp8_format", None),
                )
            )
        else:
            child_ids = tuple(walk(as_node(a)) for a in node.args)
            my_id = len(sig_parts)
            sig_parts.append((node.op, child_ids, node.static))
        seen[nid] = my_id
        return my_id

    root_id = walk(root)
    return tuple(sig_parts) + (("root", root_id),), inputs, models


# ---------------------------------------------------------------------------
# replay
# ---------------------------------------------------------------------------

_BINARY = {
    "add": operator.add, "sub": operator.sub, "mul": operator.mul,
    "truediv": operator.truediv, "pow": operator.pow, "mod": operator.mod,
    "matmul": operator.matmul,
    "radd": lambda a, b: b + a, "rsub": lambda a, b: b - a,
    "rmul": lambda a, b: b * a, "rtruediv": lambda a, b: b / a,
    "lt": operator.lt, "le": operator.le, "gt": operator.gt, "ge": operator.ge,
    "eq": lambda a, b: a == b, "ne": lambda a, b: a != b,
}

_REDUCTIONS = {"mean": jnp.mean, "sum": jnp.sum, "max": jnp.max, "min": jnp.min}


def replay(root: Node, input_values: list, params_env: dict[int, Any]):
    """Execute the graph. ``params_env`` maps id(model) → params pytree to
    use for each model call (this is how ``value_and_grad`` threads the
    differentiated params in)."""
    cache: dict[int, Any] = {}

    def ev(node: Node):
        nid = id(node)
        if nid in cache:
            return cache[nid]
        if isinstance(node, InputNode):
            out = input_values[node._input_idx]
        elif isinstance(node, ModelCallNode):
            flat, treedef = jax.tree.flatten(
                (node.call_args, node.call_kwargs),
                is_leaf=lambda x: isinstance(x, Deferred),
            )
            resolved = [
                ev(leaf._node) if isinstance(leaf, Deferred)
                else input_values[leaf_idx_map[id(node)][i]]
                for i, leaf in enumerate(flat)
            ]
            args, kwargs = jax.tree.unflatten(treedef, resolved)
            params = params_env.get(id(node.model))
            out = node.model._raw_apply(
                params, *args,
                _compute_dtype=node.compute_dtype,
                _fp8_recipe=node.fp8_recipe,
                **kwargs,
            )
        elif node.op in _BINARY:
            out = _BINARY[node.op](ev(as_node(node.args[0])), ev(as_node(node.args[1])))
        elif node.op in _REDUCTIONS:
            a = ev(as_node(node.args[0]))
            axis = node.static[0] if node.static else None
            out = _REDUCTIONS[node.op](a, axis=axis)
        elif node.op == "getattr":
            out = getattr(ev(as_node(node.args[0])), node.static[0])
        elif node.op == "getitem":
            key = node.static[0]
            out = ev(as_node(node.args[0]))[key]
        elif node.op == "getitem_node":
            out = ev(as_node(node.args[0]))[ev(as_node(node.args[1]))]
        elif node.op == "neg":
            out = -ev(as_node(node.args[0]))
        elif node.op == "abs":
            out = jnp.abs(ev(as_node(node.args[0])))
        elif node.op == "astype":
            out = ev(as_node(node.args[0])).astype(node.static[0])
        elif node.op == "reshape":
            out = ev(as_node(node.args[0])).reshape(node.static[0])
        elif node.op == "transpose":
            out = jnp.transpose(ev(as_node(node.args[0])), node.static[0] or None)
        elif node.op == "squeeze":
            out = jnp.squeeze(ev(as_node(node.args[0])), node.static[0])
        elif node.op == "argmax":
            out = jnp.argmax(ev(as_node(node.args[0])), axis=node.static[0])
        elif node.op == "call_fn":
            fn = node.static[0]
            kwargs = dict(node.static[1])
            vals = [ev(as_node(a)) for a in node.args]
            out = fn(*vals, **kwargs)
        else:
            raise NotImplementedError(f"deferred op {node.op!r}")
        cache[nid] = out
        return out

    # Pre-compute per-model-call leaf index maps (aligned with linearize order)
    leaf_idx_map: dict[int, dict[int, int]] = {}
    _assign_input_indices(root, leaf_idx_map)
    return ev(root)


def _assign_input_indices(root: Node, leaf_idx_map: dict):
    """Mirror linearize()'s walk to annotate nodes with their input slots."""
    counter = [0]
    seen: set[int] = set()

    def walk(node: Node):
        nid = id(node)
        if nid in seen:
            return
        seen.add(nid)
        if isinstance(node, InputNode):
            node._input_idx = counter[0]
            counter[0] += 1
        elif isinstance(node, ModelCallNode):
            flat, _ = jax.tree.flatten(
                (node.call_args, node.call_kwargs),
                is_leaf=lambda x: isinstance(x, Deferred),
            )
            idx_map = {}
            for i, leaf in enumerate(flat):
                if isinstance(leaf, Deferred):
                    walk(leaf._node)
                else:
                    idx_map[i] = counter[0]
                    counter[0] += 1
            leaf_idx_map[nid] = idx_map
        else:
            for a in node.args:
                if isinstance(a, (Node, Deferred)):
                    walk(as_node(a))

    walk(root)


# ---------------------------------------------------------------------------
# Deferred proxy
# ---------------------------------------------------------------------------


class Deferred:
    """Lazy array/namespace proxy. Cheap to build; forcing compiles+runs."""

    __slots__ = ("_node", "_forced", "_pre_force_hook", "_children")

    def __init__(self, node: Node):
        object.__setattr__(self, "_node", node)
        object.__setattr__(self, "_forced", None)
        object.__setattr__(self, "_pre_force_hook", None)
        object.__setattr__(self, "_children", None)

    def _child(self, key, build):
        """Memoize derived proxies so ``out.loss`` is the SAME object on
        every access — forced values and pending-step hooks must be shared."""
        children = self._children
        if children is None:
            children = {}
            object.__setattr__(self, "_children", children)
        if key not in children:
            children[key] = build()
        return children[key]

    # -- graph builders ------------------------------------------------------

    def _bin(self, op, other):
        return Deferred(Node(op, (self._node, as_node(other))))

    def __add__(self, o): return self._bin("add", o)
    def __radd__(self, o): return self._bin("radd", o)
    def __sub__(self, o): return self._bin("sub", o)
    def __rsub__(self, o): return self._bin("rsub", o)
    def __mul__(self, o): return self._bin("mul", o)
    def __rmul__(self, o): return self._bin("rmul", o)
    def __truediv__(self, o): return self._bin("truediv", o)
    def __rtruediv__(self, o): return self._bin("rtruediv", o)
    def __pow__(self, o): return self._bin("pow", o)
    def __matmul__(self, o): return self._bin("matmul", o)
    def __neg__(self): return Deferred(Node("neg", (self._node,)))
    def __abs__(self): return Deferred(Node("abs", (self._node,)))
    def __lt__(self, o): return self._bin("lt", o)
    def __le__(self, o): return self._bin("le", o)
    def __gt__(self, o): return self._bin("gt", o)
    def __ge__(self, o): return self._bin("ge", o)
    def __eq__(self, o): return self._bin("eq", o)
    def __ne__(self, o): return self._bin("ne", o)
    __hash__ = object.__hash__  # identity hash despite custom __eq__

    def mean(self, axis=None): return Deferred(Node("mean", (self._node,), (axis,)))
    def sum(self, axis=None): return Deferred(Node("sum", (self._node,), (axis,)))
    def max(self, axis=None): return Deferred(Node("max", (self._node,), (axis,)))
    def min(self, axis=None): return Deferred(Node("min", (self._node,), (axis,)))
    def argmax(self, axis=-1): return Deferred(Node("argmax", (self._node,), (axis,)))
    def astype(self, dtype): return Deferred(Node("astype", (self._node,), (jnp.dtype(dtype).name,)))
    def reshape(self, *shape):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return Deferred(Node("reshape", (self._node,), (shape,)))

    def transpose(self, *axes):
        return Deferred(Node("transpose", (self._node,), (axes or None,)))

    def squeeze(self, axis=None): return Deferred(Node("squeeze", (self._node,), (axis,)))

    def __getitem__(self, key):
        if isinstance(key, Deferred):
            return Deferred(Node("getitem_node", (self._node, key._node)))
        try:
            hash(key)
        except TypeError:
            key = tuple(key)
        return self._child(
            ("getitem", key), lambda: Deferred(Node("getitem", (self._node,), (key,)))
        )

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        return self._child(
            ("getattr", name), lambda: Deferred(Node("getattr", (self._node,), (name,)))
        )

    # -- forcing -------------------------------------------------------------

    def _set_forced(self, value):
        object.__setattr__(self, "_forced", value)

    def force(self):
        if self._forced is not None:
            return self._forced
        if self._pre_force_hook is not None:
            hook = self._pre_force_hook
            object.__setattr__(self, "_pre_force_hook", None)
            hook()  # e.g. flush a pending fused backward, which sets _forced
            if self._forced is not None:
                return self._forced
        value = force_value(self)
        self._set_forced(value)
        return value

    def item(self) -> float:
        v = self.force()
        return np.asarray(v).item() if hasattr(v, "shape") else v

    def __float__(self): return float(self.item())
    def __int__(self): return int(self.item())

    def __bool__(self):
        # force so `if a == b:` is truthful; numpy raises on non-scalars,
        # matching torch's "Boolean value of Tensor is ambiguous"
        return bool(np.asarray(self.force()))
    def __array__(self, dtype=None):
        return np.asarray(self.force(), dtype=dtype)

    def __repr__(self):
        if self._forced is not None:
            return f"Deferred(forced={self._forced!r})"
        return f"Deferred(op={self._node.op!r})"

    def float(self):  # torch-style alias
        return self.astype(jnp.float32)

    @property
    def shape(self):
        return self.force().shape


def defer_call(fn: Callable, *args, **kwargs) -> Deferred:
    """Defer an arbitrary jnp-traceable function over deferred/concrete args.
    ``fn`` must be a stable (module-level) callable — its identity is part of
    the compile-cache key. Keyword args must be hashable statics."""
    node = Node("call_fn", tuple(as_node(a) for a in args), (fn, tuple(sorted(kwargs.items()))))
    return Deferred(node)


def is_deferred(x) -> bool:
    return isinstance(x, Deferred)


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

_FORCE_CACHE: dict = {}
_GRAD_CACHE: dict = {}

#: compiled-step cost analyses collected while a profile session with
#: ``with_flops`` is live (reference wires the flag into torch.profiler,
#: ``dataclasses.py:487-513``; here the XLA compiler's own cost model is
#: the source of truth)
PROFILE_COST_STATS: list = []
_COLLECT_COSTS = False
#: (label, signature) → (AOT-compiled executable, cost facts), so each
#: signature compiles ONCE (the executable both serves the calls and
#: answers cost_analysis)
_AOT_CACHE: dict = {}
#: signatures already appended to PROFILE_COST_STATS this collection session
_COST_SEEN: set = set()

#: telemetry compile-miss hook: called with a cost-facts dict every time a
#: signature compiles while instrumentation is active (see telemetry.py)
_COMPILE_CALLBACK = None


def set_cost_collection(enabled: bool) -> None:
    global _COLLECT_COSTS
    _COLLECT_COSTS = bool(enabled)
    if enabled:
        PROFILE_COST_STATS.clear()
        _COST_SEEN.clear()


def set_compile_callback(callback) -> None:
    """Register the compile-event observer (one per process; the telemetry
    recorder owns it). None unregisters."""
    global _COMPILE_CALLBACK
    _COMPILE_CALLBACK = callback


def get_compile_callback():
    return _COMPILE_CALLBACK


def _compile_facts(jitted, args, label: str) -> tuple:
    """AOT-compile one signature, timing trace+lower and compile separately
    and extracting the program's static cost facts: XLA-cost-model FLOPs /
    bytes accessed, and collective bytes and the number of Mosaic (Pallas)
    custom calls parsed from the compiled HLO.

    The phases are wrapped in diagnostics spans (``compile/trace_lower``,
    ``compile/compile``) and the facts carry the phases' raw *monotonic*
    timestamps (``mono``) so telemetry's compile records line up with the
    trace timeline, not just the wall clock."""
    t0 = time.perf_counter()
    with _trace_span("compile/trace_lower", label=label):
        lowered = jitted.lower(*args)
    t1 = time.perf_counter()
    with _trace_span("compile/compile", label=label):
        compiled = lowered.compile()
    t2 = time.perf_counter()
    try:
        stats = compiled.cost_analysis() or {}
    except Exception:
        stats = {}
    if isinstance(stats, (list, tuple)):  # older jax: one dict per device
        stats = stats[0] if stats else {}
    facts = {
        "label": label,
        "lower_s": t1 - t0,
        "compile_s": t2 - t1,
        "mono": {"lower_start": t0, "compile_start": t1, "compile_end": t2},
        "flops": stats.get("flops"),
        "bytes_accessed": stats.get("bytes accessed"),
        "collective_bytes": None,
    }
    text = compiled.as_text()
    #: Pallas kernels in the program as compiled for a TPU (0 elsewhere,
    #: and 0 on a TPU means a kernel route was not taken)
    facts["mosaic_custom_calls"] = text.count('custom_call_target="tpu_custom_call"')
    try:
        from .utils.hlo import total_collective_bytes

        facts["collective_bytes"] = total_collective_bytes(text)
    except Exception:
        pass
    return compiled, facts


def _cost_aware_jit(fn, donate_argnums=(), label="", arg_names=(), out_shardings=None):
    """``jax.jit`` that, while instrumentation is active (a profile session
    with ``with_flops``, or a telemetry recorder's compile callback),
    AOT-compiles each new signature explicitly — timing trace+lower+compile
    and recording the program's cost analysis once. The executable is kept
    and serves the calls, so instrumentation never compiles a program
    twice. Zero overhead when both are off (one global read per call)."""
    jitted = jax.jit(fn, donate_argnums=donate_argnums, out_shardings=out_shardings)

    def call(*args):
        callback = _COMPILE_CALLBACK
        sanitizer = _get_sanitizer()
        # an active tracer also wants the explicit AOT path: it is what
        # separates trace/lower/compile into spans a flame graph shows.
        # An active sanitizer does too: the donation / fingerprint /
        # collective-digest checks need the compiled artifact in hand.
        if (
            not (_COLLECT_COSTS or callback is not None or sanitizer)
            and not _get_tracer()
        ):
            return jitted(*args)
        # every leaf participates: truncating the signature would hand
        # a cached executable mismatched avals if two calls differ only
        # in later-leaf shapes (shape/dtype tuples are cheap to hash).
        # Shardings are part of the key for the same reason jit keys on
        # them: step 1 compiles against the as-prepared placement, the
        # donated outputs come back with GSPMD's chosen shardings, and an
        # executable replayed against re-sharded args raises instead of
        # recompiling. ``fn`` itself (not id(fn)) keys the entry: the
        # reference pins the closure alive, so a recycled id can never
        # alias two programs.
        sig = (label, fn) + tuple(
            (
                tuple(getattr(l, "shape", ())),
                str(getattr(l, "dtype", "")),
                getattr(l, "sharding", None),
            )
            for l in jax.tree.leaves(args)
        )
        entry = _AOT_CACHE.get(sig)
        if entry is None:
            # a compile failure (a Mosaic refusal, an OOM) surfaces here
            # with its own message, exactly as it would from the plain jit
            entry = _compile_facts(jitted, args, label)
            _AOT_CACHE[sig] = entry
            # recompile fingerprint: hash the abstract signature with
            # leaf PATHS attached, so a later compile of the same label
            # can NAME the argument whose shape/dtype changed. Shared
            # global history — the telemetry record, the sanitizer's
            # stderr report, and the serving engine's assertion all
            # diff against the same baseline.
            from .analysis.compiled import (
                format_signature_diff,
                note_signature,
                signature_entries,
            )

            try:
                # leaf paths read as ['inputs'][0] instead of [3][0]
                # when the call site named its positional args
                if arg_names and len(args) <= len(arg_names):
                    named = dict(zip(arg_names, args))
                else:
                    named = args
                entries = signature_entries(named)
                fingerprint, diff = note_signature(label, entries)
                entry[1]["fingerprint"] = fingerprint
                if diff is not None:
                    entry[1]["changed_args"] = format_signature_diff(diff)
            except Exception:
                entries, diff = (), None
            if sanitizer:
                # predicted-vs-actual per-device arg bytes: the static
                # shard-plan model (global bytes / sharding extents)
                # against the real shard buffers — a drift means the
                # placement the planner promised is not the placement
                # the program got
                try:
                    from .analysis.shardplan import arg_bytes_report

                    predicted, actual = arg_bytes_report(args)
                    entry[1]["arg_bytes_predicted"] = predicted
                    entry[1]["arg_bytes_actual"] = actual
                except Exception:
                    pass
                # the digest also rides the compile record so the
                # telemetry trail carries cross-host-comparable state;
                # observe_compile already computed it for the host
                # digest file — reuse it rather than rendering the
                # (multi-MB) HLO text a second time
                digest = sanitizer.observe_compile(
                    label,
                    entries,
                    diff,
                    fn=fn,
                    args=args,
                    donate_argnums=donate_argnums,
                    compiled=entry[0],
                )
                if digest is not None:
                    entry[1]["collective_digest"] = digest
            if callback is not None:
                # the human-readable shape key: label + the leaf signature
                # (the part of the cache key a batch-shape change perturbs).
                # A big step's args include every param/opt-state leaf, so
                # cap the readable part and pin identity with a digest —
                # distinct shapes must stay distinct without writing a
                # multi-KB key into every compile record.
                key = f"{label}:{sig[2:]}"
                if len(key) > 512:
                    import hashlib

                    digest = hashlib.sha1(key.encode()).hexdigest()[:16]
                    key = f"{key[:480]}...#{digest}"
                callback(dict(entry[1], static_key=key))
        compiled, facts = entry
        if _COLLECT_COSTS and sig not in _COST_SEEN:
            _COST_SEEN.add(sig)
            PROFILE_COST_STATS.append(
                {
                    "label": facts["label"],
                    "flops": facts["flops"],
                    "bytes_accessed": facts["bytes_accessed"],
                }
            )
        return compiled(*args)

    return call


def scope_table(label: str) -> dict:
    """``{instruction name: (result shape, scope stack)}`` over every
    program compiled under ``label`` (``"fused_step"``, ...) while a
    compile callback, a tracer or a cost collection kept its executable —
    what puts the device operations of a trace under the model's
    ``jax.named_scope``s (:func:`accelerate_tpu.utils.hlo.op_scopes`)."""
    from .utils.hlo import op_scopes

    table: dict = {}
    for compiled, facts in _AOT_CACHE.values():
        if facts["label"] == label:
            table.update(op_scopes(compiled.as_text()))
    return table


def clear_caches():
    _FORCE_CACHE.clear()
    _GRAD_CACHE.clear()
    _FUSED_CACHE.clear()
    _AOT_CACHE.clear()
    _COST_SEEN.clear()
    from .analysis.compiled import GLOBAL_FINGERPRINTS

    GLOBAL_FINGERPRINTS.clear()


def force_value(deferred: Deferred):
    """Execute the graph (forward only), jitted + cached per signature."""
    root = deferred._node
    sig, inputs, models = linearize(root)
    key = (sig, tuple(id(m) for m in models))
    entry = _FORCE_CACHE.get(key)
    if entry is None:
        def fn(model_params: list, input_values: list):
            env = {id(m): p for m, p in zip(models, model_params)}
            return replay(root, input_values, env)

        entry = (
            _cost_aware_jit(fn, label="forward", arg_names=("model_params", "inputs")),
            models,
        )
        _FORCE_CACHE[key] = entry
    jitted, cached_models = entry
    params = [m.params for m in cached_models]
    return jitted(params, inputs)


def grad_fn_for(
    loss: Deferred,
    trainable_models: list,
    loss_scale: float = 1.0,
    dynamic_scale: bool = False,
    comm_hook: tuple | None = None,  # (hook_str, mesh) → ddp_compressed_vag
):
    """Compiled ``(loss, grads_per_model) = f(params_list, inputs[, scale])``
    for the loss graph; cached per signature. ``loss_scale`` divides the loss
    (the reference divides by gradient_accumulation_steps inside ``backward``,
    ``accelerator.py:2240``). With ``dynamic_scale`` the jitted fn takes one
    extra device-scalar argument that MULTIPLIES the loss — the fp16
    LossScaler's current scale, traced so backoff/growth never recompiles."""
    root = loss._node
    sig, inputs, models = linearize(root)
    trainables = [m for m in models if m in trainable_models]
    frozen = [m for m in models if m not in trainable_models]
    key = (sig, tuple(id(m) for m in models), tuple(id(m) for m in trainables), loss_scale,
           dynamic_scale, comm_hook[0] if comm_hook else None)
    entry = _GRAD_CACHE.get(key)
    if entry is None:
        def loss_fn(train_params: list, frozen_params: list, input_values: list, *scale):
            env = {id(m): p for m, p in zip(trainables, train_params)}
            env.update({id(m): p for m, p in zip(frozen, frozen_params)})
            out = replay(root, input_values, env)
            out = jnp.asarray(out)
            if out.ndim != 0:
                raise ValueError(
                    f"backward() needs a scalar loss; got shape {out.shape}. "
                    "Reduce it (e.g. .mean()) first."
                )
            unscaled = out.astype(jnp.float32)
            scaled = unscaled / loss_scale
            if dynamic_scale:
                scaled = scaled * scale[0]
            return scaled, unscaled

        if comm_hook is not None:
            vag = ddp_compressed_vag(loss_fn, comm_hook[1], inputs, comm_hook[0])
        else:
            vag = jax.value_and_grad(loss_fn, argnums=0, has_aux=True)
        entry = (
            _cost_aware_jit(
                vag,
                label="grad",
                arg_names=("params", "frozen_params", "inputs", "loss_scale"),
            ),
            trainables,
            frozen,
        )
        _GRAD_CACHE[key] = entry
    jitted, trainables, frozen = entry
    return jitted, trainables, frozen, inputs


def ddp_compressed_vag(loss_fn, mesh, input_values, hook: str):
    """``value_and_grad`` with an EXPLICIT data-parallel gradient reduction
    whose wire dtype is compressed — the TPU-native analog of the
    reference's DDP communication hooks (``fp16_compress_hook`` /
    ``bf16_compress_hook``, reference ``utils/dataclasses.py:117-214``).

    Under plain GSPMD the cross-replica grad all-reduce is implicit (XLA
    inserts it in the grads' dtype), so there is no seam to compress. This
    helper creates that seam: the loss/grad computation runs under
    ``shard_map`` over the batch axes, each shard computes LOCAL grads, and
    the cross-shard reduction is an explicit ``psum`` in bf16/fp16 — on a
    multi-slice DCN mesh that halves bytes-on-wire for the gradient sync,
    which is the whole point of the reference's hook. Semantics match DDP:
    gradients are AVERAGED across shards; the returned loss is the
    cross-shard mean of local losses.

    Scope (same as the reference's DDP hooks, which are DP-only): a mesh
    whose non-batch axes (tp/pp/cp/ep/fsdp) all have extent 1 — params
    replicated, batch sharded.
    """
    from jax.sharding import PartitionSpec as P

    wire = {"bf16": jnp.bfloat16, "fp16": jnp.float16}[hook]
    shape = dict(mesh.shape)
    batch_axes = tuple(a for a in ("dp", "fsdp") if shape.get(a, 1) > 1)
    n_shards = 1
    for a in batch_axes:
        n_shards *= shape[a]

    def _spec_for(x):
        spec = getattr(getattr(x, "sharding", None), "spec", None)
        if not spec:
            return P()
        names: set = set()
        for entry in spec:
            if entry is None:
                continue
            names.update(entry if isinstance(entry, (tuple, list)) else (entry,))
        return P(*spec) if names & set(batch_axes) else P()

    input_specs = [_spec_for(x) for x in input_values]

    def vag(params, frozen_params, inputs, *rest):
        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(), P(), input_specs) + (P(),) * len(rest),
            out_specs=((P(), P()), P()),
            check_vma=False,
        )
        def inner(params, frozen_params, inputs, *rest):
            (scaled, unscaled), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, frozen_params, inputs, *rest
            )
            grads = jax.tree.map(
                lambda g: jax.lax.psum(g.astype(wire), batch_axes).astype(g.dtype)
                / n_shards,
                grads,
            )
            return (
                jax.lax.pmean(scaled, batch_axes),
                jax.lax.pmean(unscaled, batch_axes),
            ), grads

        return inner(params, frozen_params, inputs, *rest)

    return vag


_FUSED_CACHE: dict = {}


def fused_step_fn_for(
    loss: Deferred,
    model,
    tx,
    *,
    clip_norm: bool = False,
    grad_scaler=None,  # optimizer.LossScaler | None
    comm_hook: tuple | None = None,  # (hook_str, mesh) → ddp_compressed_vag
    opt_state=None,
):
    loss_scale = 1.0  # fusion only engages without accumulation in flight
    """One donated, jitted train step for the common single-model loop:
    forward + backward + (unscale) + (clip) + optimizer update. This is the
    fast path `backward()`/`step()` take when nothing forces a split
    (no accumulation in flight, single bound optimizer) — it makes the
    compat loop cost what a hand-fused pjit step costs.

    Returns (jitted, frozen_models, inputs). jitted signature:
      (params, opt_state, frozen_params, inputs, max_norm, scaler_state)
        -> (new_params, new_opt_state, loss, grad_norm, step_ok,
            new_scaler_state)
    The new params and optimizer state are pinned to the placement the
    old ones (``model.params``, ``opt_state``) arrive under: left to
    itself GSPMD hands small replicated leaves back sharded over ``fsdp``,
    the donated buffers cannot be reused for them, and step 2 meets new
    input shardings and compiles the whole step a second time.
    ``step_ok`` is False when fp16 grads were non-finite (update skipped).
    With fp16, ``scaler_state`` is the LossScaler's (scale, good_steps)
    device pair: the scale is a traced INPUT (growth/backoff never
    recompiles; only the grow/backoff constants are baked into the trace)
    and the updated pair comes back as the last output. Without a scaler,
    pass ``()`` and ``()`` is returned.
    """
    import optax

    root = loss._node
    sig, inputs, models = linearize(root)
    if model not in models:
        raise ValueError("the pending loss does not involve the optimizer's model")
    frozen = [m for m in models if m is not model]
    key = (sig, id(model), id(tx), tuple(id(m) for m in frozen), loss_scale, clip_norm,
           None if grad_scaler is None else grad_scaler.trace_key,
           comm_hook[0] if comm_hook else None)
    entry = _FUSED_CACHE.get(key)
    if entry is None:
        def loss_fn(params, frozen_params, input_values, scale):
            env = {id(model): params}
            env.update({id(m): p for m, p in zip(frozen, frozen_params)})
            out = jnp.asarray(replay(root, input_values, env))
            if out.ndim != 0:
                raise ValueError(
                    f"backward() needs a scalar loss; got shape {out.shape}."
                )
            unscaled = out.astype(jnp.float32)
            scaled = unscaled / loss_scale
            if grad_scaler is not None:
                scaled = scaled * scale  # fp16: scale up against underflow
            return scaled, unscaled

        if comm_hook is not None:
            _vag = ddp_compressed_vag(loss_fn, comm_hook[1], inputs, comm_hook[0])
        else:
            _vag = jax.value_and_grad(loss_fn, has_aux=True)

        def step(params, opt_state, frozen_params, input_values, max_norm, scaler_state):
            scale = scaler_state[0] if grad_scaler is not None else jnp.float32(1.0)
            with jax.named_scope("loss"):  # forward and backward of the model
                (_, loss_value), grads = _vag(
                    params, frozen_params, input_values, scale
                )
            step_ok = jnp.bool_(True)
            new_scaler_state = scaler_state
            if grad_scaler is not None:
                inv = 1.0 / scale
                grads = jax.tree.map(lambda g: g * inv, grads)
                finite = [jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads)]
                step_ok = jnp.all(jnp.stack(finite))
                new_scaler_state = grad_scaler.next_state(
                    scale, scaler_state[1], step_ok
                )
            if clip_norm:
                norm = optax.global_norm(grads)
                factor = jnp.minimum(1.0, max_norm / (norm + 1e-6))
                grads = jax.tree.map(lambda g: g * factor, grads)
            else:
                # no clip requested: don't pay a full reduction pass over the
                # grads just to report a norm nobody asked for
                norm = jnp.asarray(0.0, jnp.float32)
            with jax.named_scope("optimizer"):
                updates, new_opt_state = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
            # fp16 non-finite: keep old state (structure-preserving select)
            if grad_scaler is not None:
                keep = lambda new, old: jax.tree.map(
                    lambda a, b: jnp.where(step_ok, a, b), new, old
                )
                new_params = keep(new_params, params)
                new_opt_state = keep(new_opt_state, opt_state)
            return new_params, new_opt_state, loss_value, norm, step_ok, new_scaler_state

        placement = [
            jax.tree.map(lambda x: getattr(x, "sharding", None), tree)
            for tree in (model.params, opt_state)
        ]
        entry = (
            _cost_aware_jit(
                step,
                donate_argnums=(0, 1),
                label="fused_step",
                arg_names=(
                    "params", "opt_state", "frozen_params", "inputs",
                    "max_norm", "scaler_state",
                ),
                out_shardings=(*placement, None, None, None, None),
            ),
            frozen,
        )
        _FUSED_CACHE[key] = entry
    jitted, frozen = entry
    return jitted, frozen, inputs
