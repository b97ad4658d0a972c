"""What a served model keeps for a sequence, declared by the model and
allocated by the serving engine.

Two kinds of cache exist: ``paged`` — K and V per token, in blocks of a
shared pool, for the layers that attend — and ``slot_state`` — arrays of a
fixed size per slot (a recurrent state, a convolution's tail) for the
layers that carry one. A model sets ``model.cache_spec``; one without the
attribute is every-layer-paged (:func:`cache_spec_of`). The engine's pool
is ``[paged_layers, num_blocks, block_size, kv_heads * head_dim]`` and each
slot-state leaf ``[layers, num_slots, *shape]``; the step programs get both
in one donated dict (``paged_kv=``) and hand it back whole.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import numpy as np


#: the engine's ``state_dtype`` policy names (beside ``auto``) -> storage dtype
STATE_DTYPES = {"bf16": "bfloat16"}


@dataclass(frozen=True)
class SlotStateLeaf:
    """One per-slot array: ``layers`` of it, ``shape`` each, stored as
    ``dtype`` (``None``: the compute dtype). A slot is zeroed when a
    request is placed in it."""

    layers: int
    shape: tuple
    dtype: str | None = None

    def array_shape(self, num_slots: int) -> tuple:
        return (self.layers, num_slots, *self.shape)

    def bytes_per_slot(self, compute_dtype) -> int:
        itemsize = np.dtype(self.dtype or compute_dtype).itemsize
        return int(self.layers * np.prod(self.shape) * itemsize)


@dataclass(frozen=True)
class CacheSpec:
    #: layers that hold block-paged K/V (the pool's leading dimension)
    paged_layers: int
    kv_heads: int
    head_dim: int
    #: name -> leaf, the per-slot state beside the pool (none: blocks are
    #: the whole of a request's past)
    slot_state: dict = field(default_factory=dict)

    def state_bytes_per_slot(self, compute_dtype) -> int:
        return sum(leaf.bytes_per_slot(compute_dtype) for leaf in self.slot_state.values())

    @property
    def state_layers(self) -> int:
        return max((leaf.layers for leaf in self.slot_state.values()), default=0)

    def with_state_dtype(self, policy: str | None) -> "CacheSpec":
        """The spec under the engine's ``state_dtype`` policy: ``auto`` is the
        spec as declared; ``bf16`` stores every leaf the model keeps at a
        precision of its own (a recurrent state; not a leaf kept in the
        compute dtype) at that width instead."""
        if policy in (None, "auto"):
            return self
        if policy not in STATE_DTYPES:
            raise ValueError(f"state_dtype {policy!r}: want one of auto, {', '.join(STATE_DTYPES)}")
        if not any(leaf.dtype for leaf in self.slot_state.values()):
            raise ValueError(
                f"state_dtype={policy} for a model that keeps no per-slot state at a "
                "precision of its own: blocks are all of its cache (kv_dtype is their policy)")
        return dataclasses.replace(self, slot_state={
            name: dataclasses.replace(leaf, dtype=STATE_DTYPES[policy]) if leaf.dtype else leaf
            for name, leaf in self.slot_state.items()})


def cache_spec_of(model) -> CacheSpec:
    """The model's declared spec, or the one every attention-only model of
    the zoo means: every layer paged, no slot state."""
    spec = getattr(model, "cache_spec", None)
    if spec is not None:
        return spec
    cfg = model.config
    return CacheSpec(
        paged_layers=cfg.num_hidden_layers,
        kv_heads=getattr(cfg, "num_key_value_heads", None) or cfg.num_attention_heads,
        head_dim=cfg.head_dim,
    )
