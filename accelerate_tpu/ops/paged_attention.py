"""Fused paged attention: walk the block table, never materialise the span.

The PR 4 paged decode path gathered each slot's **entire** block-table span
(``gather_paged_kv`` → ``[b, max_blocks*bs, n_kv, hd]``), ``jnp.repeat``-ed
KV heads for GQA, and only then ran ``cached_attention`` — so the bytes a
decode step moves scale with the *maximum* context and the GQA expansion,
not the valid prefix. This module computes attention **block-by-block**
straight off the block table:

* one pool block ``[bs, n_kv*hd]`` is loaded per table entry, straight out
  of the stacked pool at ``(layer, block)`` — the pool is stored
  lane-folded (``[layers, num_blocks, bs, n_kv*hd]``), which is the view
  the kernel reads, so neither a layer's slab nor a relayout of it is
  ever produced — dequantized in registers when the pool is int8/fp8
  (``ops/fp8.py`` scales), and consumed by an **online softmax** (running
  max / sum / accumulator — the flash-attention recurrence), so no
  ``[b, max_blocks*bs, ...]`` buffer ever exists;
* GQA uses a **grouped-head einsum** (``[b, s, n_kv, rep, hd]`` against
  ``[b, bs, n_kv, hd]``, a reshape of the small gathered block) — repeated KV heads are never materialised;
* positions past each row's valid prefix are masked inside the recurrence
  (same policy as ``cached_attention``).

Two implementations behind one dispatcher
(:func:`default_paged_attention_impl` — the Pallas kernel on TPU, the
pure-lax ``scan``-over-blocks everywhere else; the gather-then-dense
reference survives as the parity/bench baseline). Both run in f32
scores/softmax like every attention in this codebase.

**The Pallas kernel walks a row's own blocks.** Its grid runs over the rows
of the call; inside a row's step a loop whose trip count is data —
``(idx[row] + s - 1) // block_size + 1``, read from the prefetched ``idx`` —
visits the table entries that hold a block some query of the row attends,
and no others. So a call costs what is live: a free slot one entry, a short
row its length, whatever ``max_blocks`` is (a grid over ``(row, entry)``
cost every layer of every decode step 64 x 256 visits at 5 % of them live).
The pools stay in HBM as they are stored; the kernel copies block
``(layer, block_tables[row, j])`` into VMEM itself, a few entries ahead of
the one its online softmax consumes (``_IN_FLIGHT``). The trip count is an
operand, not a shape: one executable serves every occupancy. ``serving/engine.py``
counts the same entries on the host (``stats()``
``paged_entries_walked_total`` against ``paged_entries_table_total``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .fp8 import dequantize_kv
from .layers import last_visible

_NEG_INF = float(np.finfo(np.float32).min)


def default_paged_attention_impl() -> str:
    """The route :func:`paged_attention` takes when none is forced: the
    Pallas block-table kernel on a TPU backend, the pure-lax scan over
    blocks on CPU/GPU (Mosaic lowers for TPU only). A static choice by
    platform — a kernel that fails to build there is an error, never a
    quiet change of route."""
    return "pallas" if jax.default_backend() == "tpu" else "lax"


def _dequant_block(block, scale_rows, n_kv):
    """Gathered pool blocks ``[..., bs, n_kv*hd]`` → f32 ``[..., bs, n_kv,
    hd]``, applying per-row scales if present."""
    block = block.reshape(*block.shape[:-1], n_kv, block.shape[-1] // n_kv)
    if scale_rows is None:
        return block.astype(jnp.float32)
    return dequantize_kv(block, scale_rows)


def paged_attention(
    q,                      # [b, s, n_heads, hd]
    k_pool,                 # [layers, num_blocks, bs, n_kv*hd] (storage dtype)
    v_pool,                 # [layers, num_blocks, bs, n_kv*hd]
    layer,                  # int32 scalar (may be traced): the pool layer read
    block_tables,           # [b, max_blocks] int32
    idx,                    # [b] int32 — first query's cache position
    k_scale=None,           # [layers, num_blocks, bs, n_kv] f32 (quantized pools)
    v_scale=None,
    impl: str | None = None,
    interpret: bool = False,
    block_len: int = 1,
):
    """Attention of ``q`` against each row's block-table span in layer
    ``layer`` of the stacked pools. Query ``j`` of row ``b`` attends
    logical cache positions ``<= idx[b]+j`` — the same per-row valid-prefix
    + intra-chunk causal policy as :func:`ops.layers.cached_attention`, so
    paged decode keeps matching dense decode. ``block_len`` (static; not the
    pool's block size) is a block-diffusion model's: with it above 1 the
    query attends every position before the end of its own block of
    ``block_len`` positions, ``< ((idx[b]+j) // block_len + 1) * block_len``
    (:func:`ops.layers.last_visible`), on every route alike; the caller has
    written what of that span a query may see. At 1 each route traces what
    it traced before the parameter was there. Every route addresses the
    pool at ``(layer, block)``; none slices the layer out first. ``impl``:
    ``None`` routes via :func:`default_paged_attention_impl`;
    ``"lax"``/``"pallas"``/``"gather"`` force a path (``"gather"`` is the
    PR 4 materialise-the-span reference, kept for parity tests and the
    fused-vs-gather bench ratio). ``interpret`` runs the Pallas kernel in
    the Pallas interpreter — how tests exercise it off-TPU."""
    if impl is None:
        impl = default_paged_attention_impl()
    layer = jnp.asarray(layer, jnp.int32)
    if impl == "lax":
        return _paged_attention_lax(
            q, k_pool, v_pool, layer, block_tables, idx, k_scale, v_scale, block_len
        )
    if impl == "pallas":
        return _paged_attention_pallas_sharded(
            q, k_pool, v_pool, layer, block_tables, idx, k_scale, v_scale,
            interpret=interpret, block_len=block_len,
        )
    if impl == "gather":
        return _paged_attention_gather(
            q, k_pool, v_pool, layer, block_tables, idx, k_scale, v_scale, block_len
        )
    raise ValueError(f"unknown paged attention impl {impl!r}")


def _kv_heads(q, k_pool) -> int:
    """kv heads in the (possibly per-shard) pool: the folded lane width
    over the query's head size."""
    return k_pool.shape[-1] // q.shape[-1]


# ---------------------------------------------------------------------------
# pure-lax fallback: scan over table entries, online softmax
# ---------------------------------------------------------------------------


def _paged_attention_lax(q, k_pool, v_pool, layer, block_tables, idx, k_scale, v_scale,
                         block_len=1):
    b, s, nh, hd = q.shape
    bs = k_pool.shape[2]
    n_kv = _kv_heads(q, k_pool)
    rep = nh // n_kv
    mb = block_tables.shape[1]
    bt = jnp.asarray(block_tables, jnp.int32)
    idx = jnp.asarray(idx, jnp.int32).reshape(b)

    # scale folded into q once (not per block); grouped heads for GQA
    qg = (q.astype(jnp.float32) / np.sqrt(float(hd))).reshape(b, s, n_kv, rep, hd)
    q_pos = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # [b, s]
    q_last = last_visible(q_pos, block_len)

    def body(carry, j):
        m, l, acc = carry
        blk = bt[:, j]                                   # [b]
        kb = _dequant_block(
            k_pool[layer, blk], None if k_scale is None else k_scale[layer, blk], n_kv
        )
        vb = _dequant_block(
            v_pool[layer, blk], None if v_scale is None else v_scale[layer, blk], n_kv
        )
        # [b, n_kv, rep, s, bs]: contraction over hd, batched over kv head
        sc = jnp.einsum("bsnrd,btnd->bnrst", qg, kb)
        pos = j * bs + jnp.arange(bs, dtype=jnp.int32)   # logical positions
        valid = pos[None, None, :] <= q_last[:, :, None]  # [b, s, bs]
        vmask = valid[:, None, None, :, :]
        sc = jnp.where(vmask, sc, _NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        # while every position so far is masked, m_new == _NEG_INF and
        # sc - m_new == 0 — the explicit mask keeps those lanes at p = 0
        p = jnp.where(vmask, jnp.exp(sc - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bnrst,btnd->bnrsd", p, vb)
        return (m_new, l, acc), None

    init = (
        jnp.full((b, n_kv, rep, s), _NEG_INF, jnp.float32),
        jnp.zeros((b, n_kv, rep, s), jnp.float32),
        jnp.zeros((b, n_kv, rep, s, hd), jnp.float32),
    )
    (_, l, acc), _ = jax.lax.scan(body, init, jnp.arange(mb, dtype=jnp.int32))
    out = acc / jnp.maximum(l, 1e-30)[..., None]         # [b, n_kv, rep, s, hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, nh, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# gather reference (the PR 4 path, kept for parity tests + bench baseline)
# ---------------------------------------------------------------------------


def _paged_attention_gather(q, k_pool, v_pool, layer, block_tables, idx, k_scale, v_scale,
                            block_len=1):
    """Materialise each row's logical cache — ``[b, max_blocks*bs, n_kv,
    hd]`` gathered through the table; logical position ``p`` lands at
    gathered index ``p`` (tables are ordered) — and feed
    :func:`ops.layers.cached_attention` unchanged, so the reference shares
    the dense decode path's masking/softmax/dtype policy by construction."""
    from .layers import cached_attention

    bt = jnp.asarray(block_tables, jnp.int32)
    b, mb = bt.shape
    n_kv = _kv_heads(q, k_pool)

    def span(pool, scale):
        g = _dequant_block(                      # [b, mb, bs, n_kv, hd]
            pool[layer, bt], None if scale is None else scale[layer, bt], n_kv
        )
        return g.reshape(b, mb * g.shape[2], *g.shape[3:])

    return cached_attention(
        q, span(k_pool, k_scale), span(v_pool, v_scale),
        jnp.asarray(idx, jnp.int32).reshape(b), block_len=block_len,
    )


# ---------------------------------------------------------------------------
# Pallas TPU kernel: a grid over rows, each walking its own table entries
# ---------------------------------------------------------------------------

#: pool blocks of a row on their way from HBM while the kernel consumes one
#: (so ``_IN_FLIGHT + 1`` VMEM buffers a pool). Chosen on the v5e (PERF.md
#: section 6, PR 29); a constant of the kernel, not an option of its callers
_IN_FLIGHT = 1


def _pallas_kernel(bt_ref, idx_ref, layer_ref, q_ref, *rest,
                   bs, n_kv, rep, hd, quantized, block_len=1):
    """Grid ``(b,)``: step ``i`` is row ``i``, and a loop inside it walks
    the row's own table entries ``0 .. n_i - 1``, ``n_i = (idx[i] + s - 1)
    // bs + 1`` (at most the table's width; with ``block_len`` above 1 the
    last query's last visible position takes the place of ``idx[i] + s - 1``) — a trip count read from the
    prefetched ``idx``, so a dead slot (``idx`` 0) costs one entry and a
    short row costs its length, whatever the table could hold. The pools
    (and a quantized pool's scales) stay in HBM, whole; the kernel copies
    block ``(layer_ref[0], bt_ref[i, j])`` into one of its VMEM buffers
    itself, ``_IN_FLIGHT`` entries ahead of the one it consumes. The online
    softmax state lives in VMEM scratch across the loop; entries are
    consumed in table order, one block a softmax step.

    Every operand is 2-D inside the kernel: heads are folded into the lane
    dimension (``[.., n*hd]`` — how the pool is stored; a reshape of the
    small query outside), and head ``h`` is the static lane
    slice ``[h*hd, (h+1)*hd)`` — Mosaic tiles the two minor dimensions, so
    a head axis kept second-minor (12 rows padded to 16) and a 4-D
    batched-in-the-middle einsum cost a prefill chunk 119 MB of VMEM."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_pools = 4 if quantized else 2
    pools, (out_ref, *rest) = rest[:n_pools], rest[n_pools:]
    bufs, (sems, m_ref, l_ref, acc_ref) = rest[:n_pools], rest[n_pools:]
    k_buf, v_buf, *scale_bufs = bufs
    i = pl.program_id(0)
    mb = bt_ref.shape[1]
    s = q_ref.shape[1]
    depth = _IN_FLIGHT + 1
    # the pools at this call's layer; the scales come in as the one layer's
    # already (``_scale_blocks``)
    layers = [p.at[layer_ref[0]] for p in pools[:2]] + [p.at[0] for p in pools[2:]]

    def copies(j):
        """The DMAs of the row's ``j``-th entry, one a pool operand."""
        slot, blk = j % depth, bt_ref[i, j]
        return [
            pltpu.make_async_copy(pool.at[blk], buf.at[slot], sems.at[a, slot])
            for a, (pool, buf) in enumerate(zip(layers, bufs))
        ]

    first = idx_ref[i]
    # the row's own entries: up to the one that holds its last query's last
    # visible position
    live = jnp.minimum(last_visible(first + s - 1, block_len) // bs + 1, mb)
    for j in range(min(_IN_FLIGHT, mb)):
        @pl.when(j < live)
        def _first():
            for dma in copies(j):
                dma.start()

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step(j, carry):
        @pl.when(j + _IN_FLIGHT < live)
        def _ahead():
            for dma in copies(j + _IN_FLIGHT):
                dma.start()

        for dma in copies(j):
            dma.wait()
        slot = j % depth
        q_pos = first + jax.lax.broadcasted_iota(jnp.int32, (s, bs), 0)
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (s, bs), 1)
        valid = k_pos <= last_visible(q_pos, block_len)
        for n in range(n_kv):
            kv_lanes = slice(n * hd, (n + 1) * hd)
            kb = k_buf[slot, :, kv_lanes].astype(jnp.float32)   # [bs, hd]
            vb = v_buf[slot, :, kv_lanes].astype(jnp.float32)
            if quantized:
                kb = kb * scale_bufs[0][slot, :, n:n + 1]
                vb = vb * scale_bufs[1][slot, :, n:n + 1]
            for h in range(n * rep, (n + 1) * rep):
                lanes = slice(h * hd, (h + 1) * hd)
                qh = q_ref[0, :, lanes].astype(jnp.float32) / np.sqrt(float(hd))
                sc = jax.lax.dot_general(            # [s, bs], contract hd
                    qh, kb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                sc = jnp.where(valid, sc, _NEG_INF)
                m_prev, l_prev = m_ref[h], l_ref[h]  # [s, 1]
                m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
                # while every position so far is masked, m_new == _NEG_INF
                # and sc - m_new == 0 — the mask keeps those lanes at p = 0
                p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
                alpha = jnp.exp(m_prev - m_new)
                m_ref[h] = m_new
                l_ref[h] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
                acc_ref[:, lanes] = acc_ref[:, lanes] * alpha + jnp.dot(
                    p, vb, preferred_element_type=jnp.float32
                )
        return carry

    jax.lax.fori_loop(0, live, _step, 0)

    for h in range(n_kv * rep):
        lanes = slice(h * hd, (h + 1) * hd)
        out = acc_ref[:, lanes] / jnp.maximum(l_ref[h], 1e-30)
        out_ref[0, :, lanes] = out.astype(out_ref.dtype)


def _scale_blocks(scale, layer):
    """Layer ``layer`` of a quantized pool's scales ``[layers, num_blocks,
    bs, n_kv]`` with the kv heads padded to a vreg's 128 lanes, ``[1,
    num_blocks, bs, 128]``: Mosaic copies no slice out of HBM whose minor
    dimension is narrower than that, and ``n_kv`` is. XLA keeps the scales
    in that padded layout already (PERF.md section 7), so this is one pass
    over the layer's; a pool that stored them lane-dense would make it a view."""
    blocks = jax.lax.dynamic_index_in_dim(scale, layer, 0)
    return jnp.pad(blocks, [(0, 0)] * 3 + [(0, -scale.shape[-1] % 128)])


def _paged_attention_pallas(q, k_pool, v_pool, layer, block_tables, idx,
                            k_scale, v_scale, *, interpret, block_len=1):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, nh, hd = q.shape
    bs, width = k_pool.shape[2], k_pool.shape[3]
    n_kv = width // hd
    quantized = k_scale is not None

    def row(i, bt, ix, ly):
        return (i, 0, 0)

    # the pool operands are the stored pools, whole and left in HBM: the
    # kernel addresses them at (layer, block) itself, so no slab exists
    pools = [k_pool, v_pool]
    if quantized:
        pools += [_scale_blocks(k_scale, layer), _scale_blocks(v_scale, layer)]
    buffers = _IN_FLIGHT + 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block_tables + idx + layer steer the walk
        grid=(b,),
        in_specs=[pl.BlockSpec((1, s, nh * hd), row)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=pl.BlockSpec((1, s, nh * hd), row),
        scratch_shapes=[pltpu.VMEM((buffers, *p.shape[2:]), p.dtype) for p in pools]
        + [
            pltpu.SemaphoreType.DMA((len(pools), buffers)),
            pltpu.VMEM((nh, s, 1), jnp.float32),
            pltpu.VMEM((nh, s, 1), jnp.float32),
            pltpu.VMEM((s, nh * hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _pallas_kernel, bs=bs, n_kv=n_kv, rep=nh // n_kv, hd=hd,
            quantized=quantized, block_len=block_len,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, nh * hd), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="paged_attention",
    )(
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(idx, jnp.int32).reshape(b),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q.reshape(b, s, nh * hd),
        *pools,
    )
    return out.reshape(b, s, nh, hd)


def _paged_attention_pallas_sharded(q, k_pool, v_pool, layer, block_tables, idx,
                                    k_scale, v_scale, *, interpret, block_len=1):
    """The kernel under the active mesh: GSPMD treats a Mosaic call as
    opaque, so with the pool's folded kv-head lanes sharded over the head
    axis (``parallel.sharding.paged_kv_sharding`` — whole heads per shard)
    the call must run under ``shard_map`` with the heads partitioned
    explicitly — each device walks the block table over its own heads'
    lanes of the pool; a bare call on a sharded mesh is refused at lowering
    ("Mosaic kernels cannot be automatically partitioned"). The mesh is
    the one the engine (or ``prepare``) set on the attention context;
    heads the axis does not divide stay replicated, like the pool."""
    from .attention import get_attention_context

    ctx = get_attention_context()
    kernel = functools.partial(_paged_attention_pallas, interpret=interpret,
                               block_len=block_len)
    extent = 1 if ctx.mesh is None else dict(ctx.mesh.shape).get(ctx.head_axis, 1)
    if extent == 1 or q.shape[2] % extent or _kv_heads(q, k_pool) % extent:
        return kernel(q, k_pool, v_pool, layer, block_tables, idx, k_scale, v_scale)
    heads = P(None, None, ctx.head_axis, None)
    lanes = P(None, None, None, ctx.head_axis)   # pool lanes and scale heads alike
    operands = [
        q, k_pool, v_pool, layer,
        jnp.asarray(block_tables, jnp.int32), jnp.asarray(idx, jnp.int32),
    ]
    in_specs = [heads, lanes, lanes, P(), P(), P()]
    if k_scale is not None:
        operands += [k_scale, v_scale]
        in_specs += [lanes, lanes]

    def per_shard(q_, k_, v_, layer_, bt_, idx_, *scales):
        return kernel(q_, k_, v_, layer_, bt_, idx_, *(scales or (None, None)))

    return jax.shard_map(
        per_shard, mesh=ctx.mesh, in_specs=tuple(in_specs), out_specs=heads,
        check_vma=False,
    )(*operands)
