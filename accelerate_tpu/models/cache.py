"""What a served model keeps for a sequence, declared by the model and
allocated by the serving engine.

Three kinds of cache exist: ``paged`` — K and V per token, in blocks of a
shared pool, for the layers that attend; a model whose attention layers differ
in how much of the past they keep declares them as :class:`PagedKind`s, a
pool and a block table each —, ``latent`` — paged too, but ONE
vector per token and layer that every query head shares and whose leading
entries are also the values (multi-head latent attention: no kv head, no
separate V) — and ``slot_state`` — arrays of a fixed size per slot (a
recurrent state, a convolution's tail) for the layers that carry one. A
model sets ``model.cache_spec``; one without the attribute is
every-layer-paged (:func:`cache_spec_of`). The engine's pool is
``[paged_layers, num_blocks, block_size, kv_heads * head_dim]`` — the leaves
``"k"`` and ``"v"``, or the one leaf ``"k"`` of a latent spec — and each
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
class PagedKind:
    """One kind of paged layer: ``layers`` of them share a pool ``[layers,
    num_blocks, block_size, kv_heads * head_dim]`` and a block table a slot.
    ``window`` 0 keeps the whole past; above 0 a query at position ``p``
    attends positions ``p - window < j <= p`` and no later query attends an
    earlier one, so a block that lies wholly behind the window of a slot's
    oldest query in flight goes back to the kind's allocator. The kind's
    table addresses a position as every table does (entry ``pos //
    block_size``); the entries behind the window point at the null block."""

    name: str
    layers: int
    kv_heads: int
    head_dim: int
    window: int = 0

    def bytes_per_token(self, store_dtype, quantized: bool = False) -> int:
        """Bytes one cached position costs across this kind's layers (K and
        V rows, and a float32 scale a row and kv head where quantized)."""
        return 2 * self.layers * (
            self.kv_heads * self.head_dim * np.dtype(store_dtype).itemsize
            + (4 * self.kv_heads if quantized else 0))

    def resident_tokens(self, context: int, chunk: int = 1) -> int:
        """Positions of a request at ``context`` that this kind keeps while a
        dispatch of ``chunk`` queries runs: all of them without a window;
        with one, the ``window - 1`` before the dispatch's first query and
        the dispatch's own."""
        if not self.window:
            return int(context)
        return int(min(context, self.window - 1 + chunk))

    def blocks_per_slot(self, max_seq_len: int, block_size: int, chunk: int) -> int:
        """The most blocks of this kind one slot ever holds: the table's
        width without a window; with one, the blocks ``window - 1 + chunk``
        consecutive positions can touch (one more than they fill, since the
        span starts anywhere in a block)."""
        full = -(-int(max_seq_len) // int(block_size))
        if not self.window:
            return full
        return min(full, -(-self.resident_tokens(max_seq_len, chunk) // int(block_size)) + 1)

    def pool_leaf(self, leaf: str, first: bool) -> str:
        """The name of this kind's ``"k"`` / ``"v"`` array in the cache dict:
        the first kind's are ``"k"`` and ``"v"``, as a model of one kind has
        them; a further kind's carry its name (``"k_window"``)."""
        return leaf if first else f"{leaf}_{self.name}"


def pool_leaf_names(cache, kind: PagedKind | None = None, first: bool = True) -> tuple:
    """The names in the cache dict ``cache`` of one paged kind's pool arrays,
    in the order :func:`~..ops.layers.write_paged_kv` returns them: ``"k"``,
    ``"v"`` and, where the pool is quantized, the float32 ``"k_scale"``,
    ``"v_scale"`` beside them - under the kind's own names
    (:meth:`PagedKind.pool_leaf`) for a ``kind`` that is not the ``first``.
    A step takes ``[cache[n] for n in names]`` out and puts
    ``zip(names, leaves)`` back."""
    leaves = ("k", "v", "k_scale", "v_scale")[: 4 if "k_scale" in cache else 2]
    return leaves if kind is None else tuple(kind.pool_leaf(leaf, first) for leaf in leaves)


@dataclass(frozen=True)
class CacheSpec:
    #: layers that hold block-paged K/V (the pool's leading dimension; with
    #: ``kinds`` the sum over them)
    paged_layers: int
    kv_heads: int
    head_dim: int
    #: name -> leaf, the per-slot state beside the pool (none: blocks are
    #: the whole of a request's past)
    slot_state: dict = field(default_factory=dict)
    #: above 0 the paged layers are latent: ``kv_heads`` is 1, ``head_dim``
    #: the whole vector kept a token (DeepSeek-V3: 512 + 64 rotated = 576),
    #: and its first ``latent_rank`` entries are what attention sums as the
    #: values, so the pool is the one leaf ``"k"`` and nothing is kept twice
    latent_rank: int = 0
    #: the kinds of paged layer, where they differ (:class:`PagedKind`); empty:
    #: ONE kind of ``paged_layers`` layers that keeps the whole past, which is
    #: what every consumer allocates, prices and traces for a model that says
    #: nothing. The first kind is the one ``num_blocks`` counts and the
    #: scheduler admits by; it keeps the whole past
    kinds: tuple = ()

    def __post_init__(self):
        if self.kinds:
            if self.latent_rank or self.kinds[0].window:
                raise ValueError(
                    "kinds of paged layers: the first keeps the whole past (window 0) "
                    "and none is latent")
            if sum(k.layers for k in self.kinds) != self.paged_layers:
                raise ValueError(
                    f"kinds hold {sum(k.layers for k in self.kinds)} layers, "
                    f"paged_layers says {self.paged_layers}")
        if self.latent_rank and (self.kv_heads != 1 or not 0 < self.latent_rank <= self.head_dim):
            raise ValueError(
                f"a latent cache keeps one vector a token for all heads: kv_heads "
                f"{self.kv_heads} (want 1), latent_rank {self.latent_rank} of head_dim "
                f"{self.head_dim}")

    @property
    def paged_kinds(self) -> tuple:
        """The kinds, the undeclared one kind included."""
        return self.kinds or (
            PagedKind("full", self.paged_layers, self.kv_heads, self.head_dim),)

    @property
    def window_kinds(self) -> tuple:
        """The kinds that keep a window of the past (none: blocks are the
        whole of a request's past in every paged layer)."""
        return tuple(k for k in self.paged_kinds if k.window)

    @property
    def pool_leaves(self) -> tuple:
        """The pool's arrays by their names in the cache dict (a quantized
        pool adds ``<leaf>_scale`` beside each)."""
        return ("k",) if self.latent_rank else ("k", "v")

    @property
    def pool_width(self) -> int:
        """The minor dimension of a pool leaf: the kv heads folded into the
        lanes; a latent row padded with zeros to whole tiles of 128 lanes
        (576 -> 640), which is the room a TPU gives it whatever shape it is
        declared with, and the only width the paged kernels may slice out of
        the pool. The padding is stored, and priced, everywhere."""
        width = self.kv_heads * self.head_dim
        return -(-width // 128) * 128 if self.latent_rank else width

    def bytes_per_token(self, store_dtype, quantized: bool = False) -> int:
        """Bytes one cached token costs across the paged layers: every pool
        leaf's stored row, and a float32 scale a row and kv head where the
        pool is quantized."""
        if self.kinds:
            # every kind's, as if each kept the position: what a position
            # costs while it lies inside every window
            return sum(k.bytes_per_token(store_dtype, quantized) for k in self.kinds)
        return len(self.pool_leaves) * self.paged_layers * (
            self.pool_width * np.dtype(store_dtype).itemsize
            + (4 * self.kv_heads if quantized else 0))

    def window_pools(self, num_slots: int, max_seq_len: int, block_size: int,
                     chunk: int) -> dict:
        """``{kind name: (blocks a slot, blocks of the pool)}`` of the kinds
        that keep a window: a pool holds every slot's window and the null
        block, whatever ``num_blocks`` (the first kind's count) is. ``chunk``
        is the most queries one dispatch asks of a row."""
        out = {}
        for k in self.window_kinds:
            per_slot = k.blocks_per_slot(max_seq_len, block_size, chunk)
            out[k.name] = (per_slot, num_slots * per_slot + 1)
        return out

    def window_pool_bytes(self, num_slots: int, max_seq_len: int, block_size: int,
                          chunk: int, store_dtype, quantized: bool = False) -> int:
        """Bytes of the window kinds' pools: a fixed cost like the per-slot
        state, priced by what a slot keeps resident (``resident_tokens``
        rounded up to blocks), not by ``num_blocks``."""
        pools = self.window_pools(num_slots, max_seq_len, block_size, chunk)
        return sum(pools[k.name][1] * block_size * k.bytes_per_token(store_dtype, quantized)
                   for k in self.window_kinds)

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


@dataclass(frozen=True)
class BlockDecode:
    """What a model that generates by diffusion over blocks declares beside
    its cache spec (``model.block_decode``), and all the engine reads of it:
    the sequence is cut into blocks of ``block_length`` positions, attention
    is causal from block to block and bidirectional inside one, and a block
    is generated by filling it with ``mask_token_id`` and unmasking it over
    some forward passes, after which one pass over the clean block writes
    the keys and values later blocks attend. The logits at a masked position
    are the distribution of the token AT that position (no shift). The
    model's paged step applies the visibility rule itself; the engine runs
    the rounds (``serving/engine.py:_build_block_decode_fn``)."""

    block_length: int
    mask_token_id: int


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
