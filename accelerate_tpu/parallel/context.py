"""Context parallelism: ring attention and Ulysses over the ``cp`` mesh axis.

The reference has NO long-context machinery (SURVEY §5: grep finds only
Megatron's SP flag) — this module is capability the TPU build adds. Design:

* **ring attention** — activations stay sequence-sharded on ``cp``; each
  device holds one Q chunk and streams every KV chunk past it with
  ``jax.lax.ppermute`` (one ICI hop per step), merging per-chunk partial
  attention with the online-softmax rule. Peak memory is O(s_local · s_local)
  per step instead of O(s²); comm is the KV chunk, fully overlappable.
* **Ulysses** — ``all_to_all`` reshards [seq-sharded, all heads] →
  [all seq, head-sharded], runs dense (flash) attention locally, reshards
  back. Cheaper compute (one softmax), more comm; wins when heads ≥ cp.
* **allgather** — baseline: gather full KV on every device (what GSPMD
  would do implicitly); kept for cross-checking and tiny cp sizes.

Gradients flow through ``ppermute``/``all_to_all`` natively (their
transposes are the inverse permutation / the reverse all_to_all), so one
``jax.grad`` over the whole step differentiates the ring.

These functions run *inside* ``shard_map``; :func:`context_parallel_attention`
is the jit-level entry that wraps them over the mesh.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.flash_attention import NEG_INF, blockwise_attention, flash_attention


# ---------------------------------------------------------------------------
# per-device building block: one Q-chunk × one KV-chunk online-softmax update
# ---------------------------------------------------------------------------


def _chunk_update(carry, q, k_chunk, v_chunk, kv_valid, q_offset, kv_offset, scale, causal):
    """Merge attention of local Q against one KV chunk into (acc, m, l).

    q: [b, sq, h, d]; k_chunk/v_chunk: [b, sk, h, d]; kv_valid: [b, sk] bool.
    q_offset/kv_offset are *global token offsets* (traced) of the chunks.
    """
    acc, m_run, l_run = carry
    b, sq, h, d = q.shape
    sk = k_chunk.shape[1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k_chunk.astype(jnp.float32)
    ) * scale
    mask = kv_valid[:, None, None, :]
    if causal:
        q_pos = q_offset + jnp.arange(sq)
        kv_pos = kv_offset + jnp.arange(sk)
        mask = mask & (q_pos[:, None] >= kv_pos[None, :])[None, None]
    s = jnp.where(mask, s, NEG_INF)
    m_cur = jnp.max(s, axis=-1)  # [b,h,sq]
    m_new = jnp.maximum(m_run, m_cur)
    # fully-masked rows: m_new == NEG_INF (finite) would give exp(0)=1,
    # turning the row into mean(v); zero p so l stays 0 → output 0
    p = jnp.where(m_new[..., None] == NEG_INF, 0.0, jnp.exp(s - m_new[..., None]))
    alpha = jnp.exp(m_run - m_new)
    l_new = alpha * l_run + jnp.sum(p, axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_chunk.astype(jnp.float32)
    )
    return acc, m_new, l_new


def ring_attention_local(
    q: jax.Array,  # [b, s_local, h, d]
    k: jax.Array,
    v: jax.Array,
    kv_valid: jax.Array,  # [b, s_local] bool
    *,
    axis_name: str = "cp",
    causal: bool = True,
    scale: float | None = None,
    use_flash: bool | None = None,
    cp_index: jax.Array | None = None,
) -> jax.Array:
    """Ring attention body (call inside shard_map over ``axis_name``).

    On TPU the per-chunk compute runs the Mosaic flash kernel with a
    whole-ring custom VJP (``ops/ring_flash.py``) — O(s) memory and
    MXU-tiled chunk attention; elsewhere (and as the numerical oracle) the
    einsum online-softmax body below.

    ``cp_index`` (a ``[1]`` array holding this shard's ring position,
    plumbed in as data by :func:`context_parallel_attention`) replaces
    ``jax.lax.axis_index``: inside a NESTED manual region (cp attention in
    a GPipe 'pp' stage body) the axis_index lowering claims the parent's
    manual axes and the verifier rejects it."""
    if use_flash is None:
        use_flash = jax.devices()[0].platform == "tpu"
    if use_flash:
        from ..ops.ring_flash import ring_flash_attention_local

        return ring_flash_attention_local(
            q, k, v, kv_valid, axis_name=axis_name, causal=causal, scale=scale,
            cp_index=cp_index,
        )
    n = jax.lax.axis_size(axis_name)
    idx = (
        cp_index.reshape(()).astype(jnp.int32)
        if cp_index is not None
        else jax.lax.axis_index(axis_name)
    )
    b, s_loc, h, d = q.shape
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))

    acc = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s_loc), jnp.float32)

    q_offset = idx * s_loc
    k_cur, v_cur, valid_cur = k, v, kv_valid
    perm = [(i, (i + 1) % n) for i in range(n)]
    for step in range(n):
        src = (idx - step) % n  # chunk id currently held
        acc, m, l = _chunk_update(
            (acc, m, l), q, k_cur, v_cur, valid_cur, q_offset, src * s_loc, scale, causal
        )
        if step != n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            valid_cur = jax.lax.ppermute(valid_cur, axis_name, perm)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ulysses_attention_local(
    q: jax.Array,  # [b, s_local, h, d] — h divisible by cp size
    k: jax.Array,
    v: jax.Array,
    kv_valid: jax.Array,  # [b, s_local]
    *,
    axis_name: str = "cp",
    causal: bool = True,
    scale: float | None = None,
    use_flash: bool | None = None,
    cp_index: jax.Array | None = None,  # unused: no per-shard offsets here
) -> jax.Array:
    """Ulysses body: all_to_all seq↔head reshard around dense local attention."""
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    # [b, s_loc, h, d] -> [b, s, h/n, d]
    qg = a2a(q, split_axis=2, concat_axis=1)
    kg = a2a(k, split_axis=2, concat_axis=1)
    vg = a2a(v, split_axis=2, concat_axis=1)
    valid_g = jax.lax.all_gather(kv_valid, axis_name, axis=1, tiled=True)  # [b, s]
    if use_flash is None:
        use_flash = jax.devices()[0].platform == "tpu"
    if use_flash:
        out = flash_attention(qg, kg, vg, segment_mask=valid_g, causal=causal, scale=scale)
    else:
        out = blockwise_attention(qg, kg, vg, segment_mask=valid_g, causal=causal, scale=scale)
    # [b, s, h/n, d] -> [b, s_loc, h, d]
    return a2a(out, split_axis=1, concat_axis=2)


def allgather_attention_local(
    q, k, v, kv_valid, *, axis_name="cp", causal=True, scale=None, use_flash=None,
    cp_index=None,
):
    """Baseline: gather all KV chunks, run dense attention on the local Q
    chunk with the right global offset."""
    n = jax.lax.axis_size(axis_name)
    idx = (
        cp_index.reshape(()).astype(jnp.int32)
        if cp_index is not None
        else jax.lax.axis_index(axis_name)
    )
    b, s_loc, h, d = q.shape
    kg = jax.lax.all_gather(k, axis_name, axis=1, tiled=True)
    vg = jax.lax.all_gather(v, axis_name, axis=1, tiled=True)
    valid_g = jax.lax.all_gather(kv_valid, axis_name, axis=1, tiled=True)
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))
    # causal with offset: reuse the chunk-update math in one shot
    acc = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s_loc), jnp.float32)
    acc, m, l = _chunk_update((acc, m, l), q, kg, vg, valid_g, idx * s_loc, 0, scale, causal)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)


_LOCAL_BODIES = {
    "ring": ring_attention_local,
    "ulysses": ulysses_attention_local,
    "allgather": allgather_attention_local,
}


# ---------------------------------------------------------------------------
# jit-level entry: shard_map the body over the mesh
# ---------------------------------------------------------------------------


def context_parallel_attention(
    q: jax.Array,  # [b, s, h, d] global (GSPMD-sharded) arrays
    k: jax.Array,
    v: jax.Array,
    segment_mask: jax.Array | None = None,  # [b, s] 1 = valid KV token
    *,
    mesh: Mesh,
    mode: Literal["ring", "ulysses", "allgather"] = "ring",
    causal: bool = True,
    scale: float | None = None,
    cp_axis: str = "cp",
    batch_axes: tuple[str, ...] = ("dp", "fsdp"),
    head_axis: str = "tp",
) -> jax.Array:
    """Sequence-parallel attention over ``cp``, batch over dp/fsdp, heads
    over tp. GQA KV heads are repeated to full head count first (they must
    divide the tp extent anyway)."""
    b, s, nh, d = q.shape
    if k.shape[2] != nh:
        rep = nh // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if segment_mask is None:
        segment_mask = jnp.ones((b, s), dtype=bool)
    else:
        segment_mask = segment_mask.astype(bool)

    # Adapt specs to the actual shapes: drop sharding axes that don't divide
    # the corresponding dim (e.g. batch 1 on a dp=2 mesh stays replicated).
    from ..ops.attention import adapt_attention_specs

    shape = dict(mesh.shape)
    batch_entry, head_entry = adapt_attention_specs(
        shape, b, nh, nh, batch_axes, head_axis
    )
    cp_extent = shape.get(cp_axis, 1)
    if s % cp_extent != 0:
        raise ValueError(
            f"sequence length {s} must be divisible by the {cp_axis!r} mesh "
            f"extent {cp_extent} for context parallelism"
        )
    if mode == "ulysses":
        # the all_to_all splits the *local* head dim (after any tp sharding)
        local_heads = nh // shape.get(head_axis, 1) if head_entry else nh
        if local_heads % cp_extent != 0:
            raise ValueError(
                f"ulysses context parallelism re-shards heads over {cp_axis!r}: "
                f"per-shard head count {local_heads} (= {nh} heads"
                + (f" / {head_axis}={shape.get(head_axis, 1)}" if head_entry else "")
                + f") must be divisible by the {cp_axis!r} mesh extent {cp_extent}"
            )
    qkv_spec = P(batch_entry, cp_axis, head_entry, None)
    mask_spec = P(batch_entry, cp_axis)
    body = _LOCAL_BODIES[mode]

    # claim every mesh axis that is not manual already: Mosaic refuses to
    # lower inside a region that leaves any axis to GSPMD ("cannot be
    # automatically partitioned"), so the flash-kernel ring body needs them
    # all; the axes the specs do not name carry replicated data anyway.
    # When tracing inside another manual region (the GPipe stage body is
    # shard_map'd over 'pp'), the nested map must be built on the CURRENT
    # abstract mesh — the one where 'pp' is already Manual — not the
    # concrete mesh, and must not claim 'pp' again, or jax rejects it
    mesh_arg = mesh
    claimed = set(mesh.axis_names)
    am = jax.sharding.get_abstract_mesh()
    if am.shape:
        mesh_arg = am
        claimed -= set(am.manual_axes)

    # this shard's ring position as DATA (a cp-sharded iota): inside a
    # nested manual region jax.lax.axis_index's lowering claims the
    # parent's manual axes, so the bodies take the index as an argument
    cp_pos = jnp.arange(cp_extent, dtype=jnp.float32)

    @functools.partial(
        jax.shard_map,
        mesh=mesh_arg,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec, P(cp_axis)),
        out_specs=qkv_spec,
        axis_names=claimed,
        check_vma=False,
    )
    def _sharded(q_, k_, v_, valid_, cp_pos_):
        return body(
            q_, k_, v_, valid_, axis_name=cp_axis, causal=causal, scale=scale,
            cp_index=cp_pos_,
        )

    return _sharded(q, k, v, segment_mask, cp_pos)
