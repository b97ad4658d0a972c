"""Seeded weights, made on the device in one jitted call.

The benchmark owns the weights: the program under test is handed them, and
the plain reference makes them again from the same seed, one layer at a
time, without touching anything the program holds. A leaf is named by its
path in the parameter tree (``layers.wq``); a leaf under ``layers`` is
stacked ``[L, ...]`` and its layer ``l`` is drawn from its own key, so one
layer can be made alone.

Recipe (the same for every configuration): a vector leaf (a norm weight) is
``1 + 0.05 * normal``; ``embed_tokens`` is ``normal`` (so the residual
stream keeps the token's identity); every matrix is ``normal / sqrt(fan_in)``
with ``fan_in`` its second-to-last dimension. Values are drawn in float32
and cast to the served type, so a reference that upcasts the served values
sees exactly what the program sees.

A configuration may state ``weight_scales`` — ``{leaf name: factor}``, applied
to that leaf's draw before the cast — where the plain recipe leaves a layer
with nothing to do (see the serve configuration's ``weight_scales_why``).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

STACKED_PREFIX = "layers."


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (the driver's seeds pass
    2**31, which a 32-bit signed seed cannot hold)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _name_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _draw(key, name: str, shape, dtype, scale: float = 1.0):
    """One unstacked leaf."""
    shape = tuple(shape)
    x = jax.random.normal(key, shape, jnp.float32)
    if len(shape) == 1:
        x = 1.0 + 0.05 * x
    elif name != "embed_tokens":
        x = x * (1.0 / float(shape[-2]) ** 0.5)
    if scale != 1.0:
        x = x * scale
    return x.astype(dtype)


def leaf(key, name: str, shape, dtype, layer: int | None = None, scales=None):
    """Leaf ``name``; for a stacked leaf, ``layer=None`` gives the whole
    stack ``[L, ...]`` and ``layer=l`` its layer ``l`` alone ``[...]``.
    ``scales`` is a configuration's ``weight_scales``."""
    k = _name_key(key, name)
    scale = float((scales or {}).get(name, 1.0))
    if not name.startswith(STACKED_PREFIX):
        return _draw(k, name, shape, dtype, scale)
    if layer is not None:
        return _draw(jax.random.fold_in(k, layer), name, shape[1:], dtype, scale)
    return jax.vmap(
        lambda l: _draw(jax.random.fold_in(k, l), name, shape[1:], dtype, scale)
    )(jnp.arange(shape[0]))


def flat_names(tree, prefix: str = "") -> dict:
    """``{dotted path: leaf}`` of a nested dict tree."""
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flat_names(v, path + "."))
        else:
            out[path] = v
    return out


def unflatten(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, last = path.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return tree


def make_tree(seed: int, abstract_tree, dtype=None, out_shardings=None, scales=None):
    """Every leaf of ``abstract_tree`` (a nested dict of things with
    ``.shape`` and ``.dtype``), drawn from ``seed`` in ONE jitted call.
    ``dtype`` overrides the leaves' own; ``out_shardings`` (a matching tree)
    places the result without a second copy; ``scales`` is a configuration's
    ``weight_scales``."""
    shapes = {
        name: (tuple(a.shape), jnp.dtype(dtype or a.dtype))
        for name, a in flat_names(abstract_tree).items()
    }

    def build(key):
        return unflatten(
            {name: leaf(key, name, shape, dt, scales=scales)
             for name, (shape, dt) in shapes.items()}
        )

    return jax.jit(build, out_shardings=out_shardings)(root_key(seed))
