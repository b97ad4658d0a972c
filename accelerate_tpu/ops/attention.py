"""Attention dispatch: one entry point the models call, routed by the
active parallelism context.

Routing (decided at trace time, baked into the compiled step):

1. ``cp`` mesh extent > 1 and a context-parallel mode configured →
   :func:`accelerate_tpu.parallel.context.context_parallel_attention`
   (ring / Ulysses / allgather under shard_map);
2. on TPU → the Pallas flash kernel;
3. otherwise → blockwise (CPU) attention.

The context is set by ``Accelerator.prepare`` (from ``MeshPlugin`` +
``ContextParallelPlugin``) via :func:`set_attention_context`; models stay
pure and read it only while being traced.
"""

from __future__ import annotations

import functools
from contextlib import contextmanager
from dataclasses import dataclass, replace
from typing import Literal

import jax
from jax.sharding import PartitionSpec as P

from .flash_attention import blockwise_attention, flash_attention
from .layers import causal_attention


@dataclass(frozen=True)
class AttentionContext:
    mesh: object | None = None  # jax.sharding.Mesh
    cp_mode: Literal["ring", "ulysses", "allgather"] | None = None
    cp_axis: str = "cp"
    batch_axes: tuple[str, ...] = ("dp", "fsdp")
    head_axis: str = "tp"
    impl: Literal["auto", "flash", "blockwise", "reference"] = "auto"
    #: flash-kernel tile sizes; None = auto (512/1024 at short seq, a
    #: 1024-row q tile from seq 2048 up). Explicit values win.
    block_q: int | None = None
    block_kv: int | None = None
    #: session default for the GPipe microbatch count (0 = auto), carried
    #: here so it travels atomically with the mesh it was configured for
    #: (a new Accelerator swaps mesh + schedule depth together instead of
    #: leaving a stale microbatch global paired with a fresh mesh).
    pipeline_microbatches: int = 0
    #: Megatron-style sequence parallelism: norm/residual-region
    #: activations additionally sequence-shard over the tp axis
    #: (models/llama.py ``residual_spec``)
    megatron_sp: bool = False


_current = AttentionContext()


def set_attention_context(ctx: AttentionContext | None) -> None:
    global _current
    _current = ctx or AttentionContext()


def get_attention_context() -> AttentionContext:
    return _current


@contextmanager
def attention_context(**overrides):
    global _current
    prev = _current
    _current = replace(prev, **overrides)
    try:
        yield _current
    finally:
        _current = prev


def adapt_attention_specs(
    mesh_shape: dict, b: int, nh: int, n_kv: int,
    batch_axes: tuple[str, ...], head_axis: str,
) -> tuple[tuple | None, str | None]:
    """(batch_entry, head_entry) for attention shard_map specs: keep only
    the sharding axes that divide the corresponding dim (e.g. batch 1 on a
    dp=2 mesh stays replicated). Shared by the flash GSPMD wrapper and
    ``context_parallel_attention``."""
    kept_batch: list[str] = []
    extent = 1
    for ax in batch_axes:
        if b % (extent * mesh_shape.get(ax, 1)) == 0:
            kept_batch.append(ax)
            extent *= mesh_shape.get(ax, 1)
    batch_entry = tuple(kept_batch) if kept_batch else None
    head_ext = mesh_shape.get(head_axis, 1)
    head_entry = head_axis if (nh % head_ext == 0 and n_kv % head_ext == 0) else None
    return batch_entry, head_entry


def resolve_flash_blocks(seq_len: int, ctx: AttentionContext) -> tuple[int, int]:
    """Effective (block_q, block_kv) for the flash kernel: the context's
    explicit values win; auto picks 512 q-rows below seq 2048 and 1024
    from there (the deeper grid amortises the online-softmax bookkeeping
    once there are enough kv blocks per q tile). Every larger tile
    (1024x2048, 2048x*) exceeds Mosaic's scoped VMEM at d=128; the choice
    among those that fit predates the ledger and is not one of the
    benchmark's measurements (the train cell runs 1024x1024 at seq 4096)."""
    block_q = ctx.block_q if ctx.block_q is not None else (1024 if seq_len >= 2048 else 512)
    block_kv = ctx.block_kv if ctx.block_kv is not None else 1024
    return block_q, block_kv


def _flash_sharded(q, k, v, segment_mask, causal, scale, ctx: AttentionContext, window=0):
    """Run the flash kernel under shard_map: batch over dp/fsdp, heads over
    tp, sequence replicated (cp==1 on this path — cp>1 routes to
    ``context_parallel_attention``). Axes that don't divide the corresponding
    dim stay replicated; if nothing shards, fall back to the plain call."""
    mesh = ctx.mesh
    shape = dict(mesh.shape)
    b, _, nh, _ = q.shape
    n_kv = k.shape[2]

    batch_entry, head_entry = adapt_attention_specs(
        shape, b, nh, n_kv, ctx.batch_axes, ctx.head_axis
    )
    block_q, block_kv = resolve_flash_blocks(q.shape[1], ctx)
    if batch_entry is None and head_entry is None:
        return flash_attention(
            q, k, v, segment_mask=segment_mask, causal=causal, scale=scale,
            block_q=block_q, block_kv=block_kv, window=window,
        )

    qkv_spec = P(batch_entry, None, head_entry, None)
    mask_spec = P(batch_entry, None)
    has_mask = segment_mask is not None
    in_specs = (qkv_spec,) * 3 + ((mask_spec,) if has_mask else ())

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=in_specs, out_specs=qkv_spec, check_vma=False
    )
    def _inner(q_, k_, v_, *mask_):
        return flash_attention(
            q_, k_, v_,
            segment_mask=mask_[0] if mask_ else None,
            causal=causal, scale=scale,
            block_q=block_q, block_kv=block_kv, window=window,
        )

    args = (q, k, v, segment_mask) if has_mask else (q, k, v)
    return _inner(*args)


def attention(
    q: jax.Array,  # [b, s, nh, d]
    k: jax.Array,  # [b, s, n_kv, d]
    v: jax.Array,
    segment_mask: jax.Array | None = None,  # [b, s] 1 = valid token
    causal: bool = True,
    scale: float | None = None,
    window: int = 0,
) -> jax.Array:
    """``window`` (static) above 0 is a causal sliding window: the query at
    ``p`` sees the keys ``p - window < j <= p``, on the flash, blockwise and
    reference routes; context parallelism refuses it. At 0 every route
    traces what it traced before the parameter was there."""
    ctx = _current
    if window and not causal:
        raise ValueError("a sliding window is built for causal attention only")
    if (
        ctx.mesh is not None
        and ctx.cp_mode is not None
        and dict(ctx.mesh.shape).get(ctx.cp_axis, 1) > 1
    ):
        from ..parallel.context import context_parallel_attention

        if window:
            raise ValueError("a sliding window under context parallelism is not built: "
                             "the ring's chunks are attended whole")
        return context_parallel_attention(
            q, k, v, segment_mask,
            mesh=ctx.mesh,
            mode=ctx.cp_mode,
            causal=causal,
            scale=scale,
            cp_axis=ctx.cp_axis,
            batch_axes=ctx.batch_axes,
            head_axis=ctx.head_axis,
        )
    impl = ctx.impl
    if impl == "auto":
        impl = "flash" if jax.devices()[0].platform == "tpu" else "blockwise"
    if impl == "flash":
        if ctx.mesh is not None and any(e > 1 for e in dict(ctx.mesh.shape).values()):
            # GSPMD treats the Mosaic custom call as opaque, so on a sharded
            # mesh the kernel must run under shard_map with explicit batch /
            # head partitioning — otherwise XLA replicates q,k,v per device.
            return _flash_sharded(q, k, v, segment_mask, causal, scale, ctx, window)
        block_q, block_kv = resolve_flash_blocks(q.shape[1], ctx)
        return flash_attention(
            q, k, v, segment_mask=segment_mask, causal=causal, scale=scale,
            block_q=block_q, block_kv=block_kv, window=window,
        )
    if impl == "blockwise":
        # the pure-JAX fallback has its own sweet spot — the Pallas-tuned
        # kv block would 8x the materialised score tile on CPU
        return blockwise_attention(
            q, k, v, segment_mask=segment_mask, causal=causal, scale=scale,
            block_kv=min(max(ctx.block_kv or 1024, 128), 512), window=window,
        )
    if not causal:
        from .layers import dot_product_attention

        mask = None
        if segment_mask is not None:
            mask = segment_mask[:, None, None, :].astype(bool)
        return dot_product_attention(q, k, v, mask=mask, scale=scale)
    return causal_attention(q, k, v, segment_mask=segment_mask, window=window)
