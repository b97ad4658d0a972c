"""Model containers: the functional (apply_fn, params) unit and its prepared,
mesh-sharded wrapper.

There is no ``nn.Module`` mutation here (reference ``prepare_model``
``accelerator.py:1361-1612`` wraps/patches the torch module in place): a
model is a pure apply function plus a params pytree; ``prepare`` produces a
:class:`PreparedModel` whose params carry ``NamedSharding``s and whose calls
are recorded into the deferred graph (:mod:`accelerate_tpu.lazy`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .lazy import Deferred, ModelCallNode


class ModelOutput(dict):
    """Dict with attribute access (the transformers-style output object the
    reference's examples rely on: ``outputs.loss`` / ``outputs.logits``).
    Registered as a pytree (below) so jit/vmap can return it and tree ops
    traverse into it like a plain dict."""

    def __getattr__(self, name):
        try:
            return self[name]
        except KeyError as e:
            raise AttributeError(name) from e

    def __setattr__(self, name, value):
        self[name] = value


jax.tree_util.register_pytree_with_keys(
    ModelOutput,
    lambda d: (
        tuple((jax.tree_util.DictKey(k), d[k]) for k in sorted(d)),
        tuple(sorted(d)),
    ),
    lambda keys, children: ModelOutput(zip(keys, children)),
)


class Model:
    """A pure functional model: ``apply_fn(params, *args, **kwargs)`` +
    params pytree + optional partition rules (path-regex → PartitionSpec)
    used by the sharding planner.

    Build one directly, or adapt:
    * flax.linen — ``Model.from_flax(module, variables)``
    * our ``models/`` zoo — each model class returns one of these.
    """

    def __init__(
        self,
        apply_fn: Callable,
        params: Any,
        partition_rules: list[tuple[str, Any]] | None = None,
        name: str | None = None,
        mutable_state: Any = None,
    ):
        self.apply_fn = apply_fn
        self.params = params
        self.partition_rules = partition_rules
        self.name = name or getattr(apply_fn, "__name__", "model")
        self.mutable_state = mutable_state

    @classmethod
    def from_flax(cls, module, variables, partition_rules=None, **apply_kwargs):
        params = variables.get("params", variables) if isinstance(variables, dict) else variables

        def apply_fn(p, *args, **kwargs):
            return module.apply({"params": p}, *args, **kwargs, **apply_kwargs)

        return cls(apply_fn, params, partition_rules=partition_rules, name=type(module).__name__)

    def num_parameters(self) -> int:
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(self.params))


def _cast_floats(tree, dtype):
    def _c(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree.map(_c, tree)


class PreparedModel:
    """What ``Accelerator.prepare`` returns for a model. Calling it records a
    :class:`ModelCallNode` and returns a :class:`Deferred` — execution
    happens inside the compiled step when ``backward``/forcing runs.

    Mixed precision: params are kept in fp32 (the "master" copy the
    optimizer updates); ``_raw_apply`` casts params + float inputs to the
    compute dtype and upcasts float outputs back to fp32 — the analog of
    the reference's autocast-wrap + ``convert_outputs_to_fp32``
    (``accelerator.py:1401-1412``).
    """

    def __init__(self, model: Model, accelerator=None, compute_dtype=None, param_sharding=None):
        self._model = model
        self._accelerator = accelerator
        self.compute_dtype = compute_dtype
        self.param_sharding = param_sharding
        self.training = True
        self._pending_grads = None  # grads for optimizer-less models
        self.fp8_recipe = None  # set by prepare when mixed_precision='fp8'

    # -- identity ------------------------------------------------------------

    @property
    def name(self):
        return self._model.name

    @property
    def partition_rules(self):
        return self._model.partition_rules

    @property
    def params(self):
        """The one live copy of the parameters, kept on the wrapped model:
        ``prepare`` re-shards it and every optimizer step replaces it. A
        second reference on the wrapper would keep the unsharded originals
        alive — under fsdp, the whole model on device 0."""
        return self._model.params

    @params.setter
    def params(self, value):
        self._model.params = value

    def unwrap(self) -> Model:
        return self._model

    def num_parameters(self) -> int:
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(self.params))

    @property
    def grads(self):
        """Accumulated grads when no optimizer is bound (the ``.grad``
        analog for manual-update workflows); cleared by ``zero_grad``."""
        return self._pending_grads

    def accumulate_grads(self, grads):
        if self._pending_grads is None:
            self._pending_grads = grads
        else:
            self._pending_grads = jax.tree.map(jnp.add, self._pending_grads, grads)

    def zero_grad(self):
        self._pending_grads = None

    def train(self, mode: bool = True):
        self.training = mode
        return self

    def eval(self):
        return self.train(False)

    # -- execution -----------------------------------------------------------

    _DTYPE_UNSET = object()

    def _raw_apply(
        self, params, *args, _compute_dtype=_DTYPE_UNSET, _fp8_recipe=_DTYPE_UNSET, **kwargs
    ):
        """Called at trace time from the deferred replay. ``_compute_dtype``
        / ``_fp8_recipe`` are the policies snapshotted when the call was
        RECORDED (autocast islands must bind at call time, not at the later
        trace time)."""
        import contextlib

        unset = PreparedModel._DTYPE_UNSET
        compute_dtype = self.compute_dtype if _compute_dtype is unset else _compute_dtype
        fp8_recipe = self.fp8_recipe if _fp8_recipe is unset else _fp8_recipe
        if params is None:
            params = self.params
        if compute_dtype is not None:
            params = _cast_floats(params, compute_dtype)
            args = _cast_floats(args, compute_dtype)
            kwargs = _cast_floats(kwargs, compute_dtype)
        if fp8_recipe is not None:
            from .ops.fp8 import fp8_autocast

            ctx = fp8_autocast(enabled=True, fp8_format=fp8_recipe.fp8_format)
        else:
            ctx = contextlib.nullcontext()
        with ctx:
            if self._model.mutable_state is not None:
                out = self.apply_with_state(params, *args, **kwargs)
            else:
                out = self._model.apply_fn(params, *args, **kwargs)
        if compute_dtype is not None:
            out = jax.tree.map(
                lambda x: x.astype(jnp.float32)
                if hasattr(x, "dtype") and x.dtype in (jnp.bfloat16, jnp.float16)
                else x,
                out,
            )
        return out

    def apply_with_state(self, params, *args, **kwargs):
        return self._model.apply_fn(params, self._model.mutable_state, *args, **kwargs)

    def __call__(self, *args, **kwargs) -> Deferred:
        return Deferred(ModelCallNode(self, args, kwargs))

    def forward(self, *args, **kwargs) -> Deferred:
        return self(*args, **kwargs)

    # -- state dict (safetensors-compatible flat naming) ----------------------

    def state_dict(self) -> dict[str, np.ndarray]:
        flat = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(self.params)[0]:
            key = ".".join(_path_str(p) for p in path)
            flat[key] = np.asarray(jax.device_get(leaf))
        return flat

    def load_state_dict(self, state_dict: dict[str, np.ndarray]):
        paths = jax.tree_util.tree_flatten_with_path(self.params)
        leaves, treedef = jax.tree.flatten(self.params)
        new_leaves = []
        for (path, leaf) in paths[0]:
            key = ".".join(_path_str(p) for p in path)
            if key not in state_dict:
                raise KeyError(f"missing parameter {key!r} in state dict")
            value = jnp.asarray(state_dict[key], dtype=leaf.dtype)
            if value.shape != leaf.shape:
                raise ValueError(f"shape mismatch for {key}: {value.shape} vs {leaf.shape}")
            if isinstance(leaf, jax.Array) and hasattr(leaf, "sharding"):
                value = jax.device_put(value, leaf.sharding)
            new_leaves.append(value)
        self.params = jax.tree.unflatten(treedef, new_leaves)
        return self


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


def extract_model_from_parallel(model, keep_fp32_wrapper: bool = True):
    """Reference ``utils/other.py:62`` analog."""
    if isinstance(model, PreparedModel):
        return model.unwrap()
    return model
