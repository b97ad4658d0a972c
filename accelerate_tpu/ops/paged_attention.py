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
``(idx[row] + s - 1) // block_size + 1`` entries, read from the prefetched
``idx`` — visits the table entries that hold a block some query of the row
attends, and no others. So a call costs what is live: a free slot one entry,
a short row its length, whatever ``max_blocks`` is (a grid over ``(row,
entry)`` cost every layer of every decode step 64 x 256 visits at 5 % of
them live). The pools stay in HBM as they are stored; the kernel copies block
``(layer, block_tables[row, j])`` into VMEM itself, ahead of the softmax
step that consumes it (``_IN_FLIGHT``). The trip count is an operand, not a
shape: one executable serves every occupancy.

**A softmax step of the kernel is a tile against a query group.** The loop
takes a tile of consecutive table entries a step - ``_TILE`` of them
(``_TILE x block_size`` key positions) in a call whose stacked query rows
fit one grid step (a decode step, a block round, a verify round),
``_CHUNK_TILE`` in a chunk's call, whose step also takes a taller block of
rows (:func:`_step_geometry`: the geometry is chosen from the call's own
static shape) - each live entry copied into its ``block_size`` rows of one
VMEM buffer and none past the row's last, against ALL the query heads of a kv
head at once: the query is handed in grouped, a kv head's ``rep`` heads
stacked along the rows. One entry against one query head at a time made a
score tile 16 of a vreg's 1,024 elements and paid the fixed cost of two
products, a max, an exp and a sum 32 times an entry: 1-2.4 us for the
0.04-0.08 us an entry's bytes take. Rows of a tile that no copy wrote hold
whatever the scratch held, so V's rows are selected by key position
(``0 x NaN`` is NaN). ``serving/engine.py`` counts the same entries and
tiles on the host (``stats()`` ``paged_entries_walked_total`` against
``paged_entries_table_total``; entries over ``paged_tiles_walked_total x
_TILE`` is how full the tiles are: a chunk's wide step is booked as the
``_TILE``-entry tiles it holds, and ``paged_chunk_steps_total`` counts those
steps themselves).
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
    window: int = 0,
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
    it traced before the parameter was there. ``window`` (static) above 0
    is a sliding window: the query at position ``p`` attends ``p - window < j
    <= p``, ``window`` keys with its own among them, on every route alike;
    the Pallas kernel starts a row's walk at the first table entry that
    holds a visible position and never reads the entries behind it (which
    the engine has given back: they point at the null block). At 0 every
    route traces what it traced before the parameter was there; with
    ``block_len`` above 1 it is refused. Every route addresses the
    pool at ``(layer, block)``; none slices the layer out first. ``impl``:
    ``None`` routes via :func:`default_paged_attention_impl`;
    ``"lax"``/``"pallas"``/``"gather"`` force a path (``"gather"`` is the
    PR 4 materialise-the-span reference, kept for parity tests and the
    fused-vs-gather bench ratio). ``interpret`` runs the Pallas kernel in
    the Pallas interpreter — how tests exercise it off-TPU."""
    if impl is None:
        impl = default_paged_attention_impl()
    if window and block_len != 1:
        raise ValueError(f"window {window} with block_len {block_len}: a sliding window "
                         "is built for the causal rule only")
    layer = jnp.asarray(layer, jnp.int32)
    if impl == "lax":
        return _paged_attention_lax(
            q, k_pool, v_pool, layer, block_tables, idx, k_scale, v_scale, block_len, window
        )
    if impl == "pallas":
        return _paged_attention_pallas_sharded(
            q, k_pool, v_pool, layer, block_tables, idx, k_scale, v_scale,
            interpret=interpret, block_len=block_len, window=window,
        )
    if impl == "gather":
        return _paged_attention_gather(
            q, k_pool, v_pool, layer, block_tables, idx, k_scale, v_scale, block_len, window
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
                         block_len=1, window=0):
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
        if window:
            valid = valid & (pos[None, None, :] > q_pos[:, :, None] - window)
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
                            block_len=1, window=0):
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
        **({"window": window} if window else {}),
    )


# ---------------------------------------------------------------------------
# Pallas TPU kernel: a grid over rows, each walking its own table entries
# ---------------------------------------------------------------------------

#: table entries a softmax step takes at once: ``_TILE x block_size`` key
#: positions (128 at blocks of 16: a vreg's lanes, the MXU's columns), clamped
#: to the table's width. And the tiles of a row on their way from HBM while the
#: kernel consumes one (so ``_IN_FLIGHT + 1`` VMEM buffers a pool). Both chosen
#: on the v5e (PERF.md section 6, PR 39); constants of the kernel, not options
#: of its callers
_TILE = 8
_IN_FLIGHT = 1

#: stacked query rows a grid step takes: a kv head's whole group where it is
#: smaller (decode, a block round, a verify round: ONE block, and the program of
#: such a call is what it was before a chunk had a geometry of its own). A call
#: of more stacked rows is a chunk's, and takes ``_CHUNK_TILE`` and
#: ``_CHUNK_ROWS`` instead
_ROW_BLOCK = 256

#: a chunk's call (more than ``_ROW_BLOCK`` stacked rows, ``rep x s``): table
#: entries a softmax step takes (1,024 positions at blocks of 16), and stacked
#: rows x kv heads a grid step holds (the rows are split evenly over as few grid
#: steps as that allows, in whole blocks of ``_ROW_BLOCK`` that the step's body
#: takes one at a time: a SmallThinker chunk of 7 x 1,024 rows at 4 kv heads
#: goes in 4 steps of 1,792 where it went in 28 of 256, a Mistral chunk of 4 x
#: 256 at 8 kv heads in one). Every block of rows walks and copies the row's
#: span again, and every step of a walk pays, a kv head and block, the float32
#: accumulator read, scaled and rewritten, the softmax state, the query's
#: conversion and the masks whatever the tile holds; the tile's copies are ONE
#: loop over its live entries and a whole tile's are waited for at once, as the
#: latent kernel's. Timed on the v5e (PERF.md sections 5 and 6, PR 45; ms a
#: layer's call, tile 8 / 16 / 32 / 64 at 8,192 rows x kv heads, the parent's
#: geometry first). SmallThinker's chunk (28 / 4 heads of 128, 1,024 tokens)
#: that ends at 4,096: 2.52 | 2.04 / 1.71 / 0.91 / 0.62 with the whole past,
#: 2.30 | 2.04 / 1.69 / 0.90 / 0.66 behind a window of 4,096; at 16,384: 9.87 |
#: 8.01 / 6.67 / 3.48 / 2.30 and 2.70 | 2.53 / 2.10 / 1.11 / 0.81; a first
#: chunk (1,024): 0.68 | 0.55 / 0.46 / 0.26 / 0.19. Mistral's (32 / 8 of 128,
#: 256 tokens) at 256 / 1,536 / 3,072: 0.069 / 0.218 / 0.401 | tile 16 0.059 /
#: 0.207 / 0.385, 32 0.062 / 0.125 / 0.214, 64 0.068 / 0.113 / 0.155; LFM2's
#: (heads of 64): 0.071 / 0.281 / 0.536 | 64: 0.073 / 0.119 / 0.169: a first
#: chunk of 256 tokens reads at 64 what it read at the parent's 8, and 0.006 ms
#: over 32; every later one gains. Rows x kv heads at tile 64, 1,024 / 2,048 /
#: 4,096 / 8,192 (SmallThinker at 4,096, whole past): 0.82 / 0.74 / 0.65 / 0.62.
#: The body written out for a whole block of rows took the compiler 6-13 s a
#: kernel (compiled for the v5e, not run); a block of rows at a time it takes
#: 2-4 s (the parent's 0.6-0.9). Constants of the kernel, chosen by the call's
#: shape
_CHUNK_TILE = 64
_CHUNK_ROWS = 8192

#: VMEM a chunk's call may take, over the compiler's 16 MB default (as
#: ``_LATENT_VMEM_LIMIT``): query and output blocks twice, the float32
#: accumulator and the lane-padded softmax state of ``_CHUNK_ROWS`` rows x kv
#: heads (21 MB at heads of 128), the tiles in flight (4 MB at 4 kv heads of
#: 128, 34 at 32) and one block of rows' scores and probabilities: 30-36 MB in
#: the benchmark's cells, 70 at 32 kv heads
_CHUNK_VMEM_LIMIT = 96 << 20


def _step_geometry(stacked: int, table_width: int, n_kv: int = 1):
    """``(table entries a softmax step, stacked rows a grid step, chunk)`` of a
    call whose kv heads' query groups stack to ``stacked`` rows (``rep x s``):
    what fits one block of ``_ROW_BLOCK`` keeps ``_TILE`` and one block of its
    rows (padded to whole sublanes); a chunk takes ``_CHUNK_TILE`` and its
    rows split evenly over the fewest grid steps of at most ``_CHUNK_ROWS``
    rows x kv heads, in whole blocks of ``_ROW_BLOCK`` (the step's body takes
    them one at a time)."""
    if stacked <= _ROW_BLOCK:
        return min(_TILE, table_width), -(-stacked // 8) * 8, False
    steps = -(-stacked // max(_CHUNK_ROWS // n_kv, _ROW_BLOCK))
    return (min(_CHUNK_TILE, table_width),
            -(-stacked // (_ROW_BLOCK * steps)) * _ROW_BLOCK, True)


def tile_entries(table_width: int, latent: bool = False, stacked: int = 1) -> int:
    """Table entries a softmax step takes of a table this wide in a call of
    ``stacked`` stacked query rows (``rep x s``; the default is a decode
    row's, the unit ``stats()`` hands out as ``paged_tile_entries``).
    ``latent``: of the latent kernel, which has one tile for every call."""
    if latent:
        return min(_LATENT_TILE, table_width)
    return _step_geometry(stacked, table_width)[0]


def tiles_walked(entries, table_width: int, latent: bool = False, first=None,
                 stacked: int = 1):
    """Softmax steps the kernel takes for rows that walk the table entries
    up to ``entries`` each (the host's count, ``serving/engine.py``), from
    entry ``first`` on where a window cuts the walk's start (``None``: from
    the table's first entry), at the tile of a call of ``stacked`` stacked
    rows: the tiles are laid from entry 0 whatever the start, so the first
    and the last one walked may be part full."""
    tile = tile_entries(table_width, latent, stacked)
    whole = -(-entries // tile)
    return whole if first is None else whole - first // tile


def window_walk(first, queries: int, window: int, block_size: int, table_width: int):
    """``(first entry, one past the last entry)`` of the table that rows whose
    first query stands at ``first`` (an integer or an array of them) walk in
    a layer of ``window`` (0: the whole past) for ``queries`` queries a row:
    what :func:`_pallas_kernel` reads from the same numbers for a row whose
    queries are one block of stacked rows."""
    first = np.asarray(first, np.int64)
    end = np.minimum((first + queries - 1) // block_size + 1, table_width)
    if not window:
        return np.zeros_like(end), end
    return np.minimum(np.maximum(first - window + 1, 0) // block_size, end), end


def _pallas_kernel(bt_ref, idx_ref, layer_ref, q_ref, *rest,
                   bs, tile, s, quantized, block_len=1, window=0, chunk=False):
    """Grid ``(b, row blocks)``: step ``(i, r)`` is block ``r`` of row
    ``i``'s stacked queries (one block but for a chunk), and a loop inside
    it walks the row's own table entries ``0 .. n_i - 1``, ``n_i = (idx[i] + s - 1)
    // bs + 1`` (at most the table's width; with ``block_len`` above 1 the
    last query's last visible position takes the place of ``idx[i] + s - 1``),
    ``tile`` entries a step: ``ceil(n_i / tile)`` steps, a trip count read
    from the prefetched ``idx``, so a dead slot (``idx`` 0) costs one step of
    one entry and a short row costs its length, whatever the table could
    hold. The pools (and a quantized pool's scales) stay in HBM, whole; the
    kernel copies block ``(layer_ref[0], bt_ref[i, j])`` into rows ``[e * bs,
    (e + 1) * bs)`` of one of its VMEM buffers itself, for the LIVE entries
    ``j = t * tile + e`` of a tile and no others, ``_IN_FLIGHT`` tiles ahead
    of the one it consumes.

    **A softmax step is a tile against a kv head's whole query group.** The
    query comes in grouped (``[1, n_kv, rows, hd]``: the ``rep`` heads that
    share kv head ``n`` stacked along the rows, head-major, padded to whole
    sublanes), so a step is ``[rows, hd] x [tile * bs, hd]^T``, one max / exp
    / sum over a score tile whose lanes are full, and ``[rows, tile * bs] x
    [tile * bs, hd]`` a kv head - not one ``[s, hd] x [bs, hd]^T`` a query
    head and entry, whose fixed cost was the kernel's time. The running max,
    sum and accumulator live in VMEM scratch across the loop, a kv head's
    group each; more than ``_ROW_BLOCK`` stacked rows (a chunk) are taken a
    block of rows a grid step.

    **V's rows are selected by key position.** The rows of a buffer that no
    copy of this tile wrote hold what the scratch held: an earlier tile's or
    an earlier row's blocks, or nothing at all. Their ``p`` is 0, but ``0 x
    NaN`` is NaN in ``p @ v``, so V's rows past the row's last visible
    position are replaced by zeros (K's are harmless: their scores are
    replaced before the max). Nothing rests on which grid step ran first.

    Every operand is 2-D where it is computed on: kv heads are folded into
    the lane dimension (``[.., n*hd]`` - how the pool is stored) and kv head
    ``n`` is the static lane slice ``[n*hd, (n+1)*hd)``, cut once a tile.

    **A window cuts the walk at both ends.** With ``window`` above 0 a query
    at ``p`` attends ``p - window < j <= p``. A grid step's stacked rows are
    the queries ``j0 .. j0 + nq - 1`` of the row (one head's, where the row
    block divides the chunk; all of them otherwise), so it walks the entries
    ``max(0, idx + j0 - window + 1) // bs`` to ``(idx + j0 + nq - 1) // bs``
    and no others: the tiles stay where they are laid (tile ``t`` is entries
    ``t * tile ..``), the loop starts at the first entry's tile, copies of
    entries outside the span are not made, and the mask adds the lower edge.
    V's rows outside the span are zeroed like those past the row's end. At
    ``window`` 0 nothing of this is traced.

    **A chunk's call** (``chunk``: more than ``_ROW_BLOCK`` stacked rows,
    :func:`_step_geometry`) takes a wide tile against a tall block of rows -
    the same products in fewer and larger steps - and rolls a tile's copies
    into ONE loop of as many trips as the tile has live entries (behind a
    window: from the span's first entry), a whole tile's waited for with one
    wait a pool for all their bytes and a part tile's entry by entry, as
    ``_latent_kernel`` does: 32 copies written out cost the tracing of every
    run (PR 37, PR 43). Without ``chunk`` the kernel traces what it traced
    before a chunk had a geometry of its own."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_pools = 4 if quantized else 2
    pools, (out_ref, *rest) = rest[:n_pools], rest[n_pools:]
    bufs, (sems, m_ref, l_ref, acc_ref) = rest[:n_pools], rest[n_pools:]
    k_buf, v_buf, *scale_bufs = bufs
    i = pl.program_id(0)
    mb = bt_ref.shape[1]
    _, n_kv, rows, hd = q_ref.shape
    depth = _IN_FLIGHT + 1
    span = tile * bs
    # the pools at this call's layer; the scales come in as the one layer's
    # already (``_scale_blocks``)
    layers = [p.at[layer_ref[0]] for p in pools[:2]] + [p.at[0] for p in pools[2:]]

    first = idx_ref[i]
    # the row's own entries: up to the one that holds its last query's last
    # visible position
    last = last_visible(first + s - 1, block_len)
    live = jnp.minimum(last // bs + 1, mb)
    row0 = pl.program_id(1) * rows
    if window:
        # this grid step's queries, and the span of positions they see
        in_a_head = s % rows == 0
        j0 = jax.lax.rem(row0, s) if in_a_head else 0
        lo_pos = jnp.maximum(first + j0 - window + 1, 0)
        last = jnp.minimum(first + j0 + (rows if in_a_head else s) - 1, last)
        live = jnp.minimum(last // bs + 1, mb)
        lo_entry = jnp.minimum(lo_pos // bs, live)
        t_first = lo_entry // tile
    else:
        lo_entry = t_first = 0

    def for_live_entries(t, act):
        """``act`` on the DMAs of tile ``t``'s live entries, one a pool
        operand and entry; nothing for an entry past the row's last (or
        behind its window). A chunk's: one loop over them."""
        slot = t % depth
        if chunk:
            def _entry(e, carry):
                blk = bt_ref[i, t * tile + e]
                for a, (pool, buf) in enumerate(zip(layers, bufs)):
                    act(pltpu.make_async_copy(
                        pool.at[blk], buf.at[slot, pl.ds(pl.multiple_of(e * bs, bs), bs)],
                        sems.at[a, slot]))
                return carry

            jax.lax.fori_loop(jnp.clip(lo_entry - t * tile, 0, tile) if window else 0,
                              jnp.clip(live - t * tile, 0, tile), _entry, 0)
            return
        for e in range(tile):
            @pl.when((t * tile + e < live) & (t * tile + e >= lo_entry) if window
                     else t * tile + e < live)
            def _entry():
                blk = bt_ref[i, t * tile + e]
                for a, (pool, buf) in enumerate(zip(layers, bufs)):
                    act(pltpu.make_async_copy(
                        pool.at[blk], buf.at[slot, pl.ds(e * bs, bs)], sems.at[a, slot]))

    def wait_live_entries(t):
        """Wait for tile ``t``'s copies. A chunk's share a semaphore a pool,
        so a whole tile's are ONE wait for all their bytes; its part tiles
        (the row's last, a window's first) wait entry by entry."""
        if not chunk:
            return for_live_entries(t, lambda dma: dma.wait())
        slot = t % depth
        whole = live - t * tile >= tile
        if window:
            whole &= lo_entry <= t * tile

        @pl.when(whole)
        def _():
            for a, buf in enumerate(bufs):
                pltpu.make_async_copy(buf.at[slot], buf.at[slot], sems.at[a, slot]).wait()

        @pl.when(jnp.logical_not(whole))
        def _():
            for_live_entries(t, lambda dma: dma.wait())

    for t in range(_IN_FLIGHT):
        for_live_entries(t_first + t, lambda dma: dma.start())

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def visible(g0, n, k_pos):
        """Which of a tile's key positions the ``n`` stacked rows from ``g0``
        on may see by the causal (or block) rule, ``[n, span]``, and the
        rows' positions: stacked row g of the group is query g % s of its head."""
        g = g0 + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
        q_pos = first + jax.lax.rem(g, s)
        return k_pos <= last_visible(q_pos, block_len), q_pos

    def attend(n, at, kb, vb, valid):
        """One softmax step of kv head ``n``'s stacked rows ``at`` (an index
        behind the head's: nothing for all of the block's) against the tile's
        keys and values ``[span, hd]``, float32."""
        qg = q_ref[(0, n) + at].astype(jnp.float32) / np.sqrt(float(hd))
        sc = jax.lax.dot_general(                            # contract hd
            qg, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        sc = jnp.where(valid, sc, _NEG_INF)
        m_prev, l_prev = m_ref[(n,) + at], l_ref[(n,) + at]  # [rows, 1]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        # while every position so far is masked, m_new == _NEG_INF
        # and sc - m_new == 0 - the mask keeps those lanes at p = 0
        p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[(n,) + at] = m_new
        l_ref[(n,) + at] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[(n,) + at] = acc_ref[(n,) + at] * alpha + jnp.dot(
            p, vb, preferred_element_type=jnp.float32
        )

    def _step(t, carry):
        for_live_entries(t + _IN_FLIGHT, lambda dma: dma.start())
        wait_live_entries(t)
        slot = t % depth
        k_pos = t * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        if not chunk:
            valid, q_pos = visible(row0, rows, k_pos)         # [rows, span]
        v_pos = t * span + jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0)
        seen = v_pos <= last
        if window:
            if not chunk:
                valid = valid & (k_pos > q_pos - window)
            seen = seen & (v_pos >= lo_entry * bs)
        for n in range(n_kv):
            lanes = slice(n * hd, (n + 1) * hd)
            kb = k_buf[slot, :, lanes].astype(jnp.float32)      # [span, hd]
            vb = v_buf[slot, :, lanes].astype(jnp.float32)
            if quantized:
                kb = kb * scale_bufs[0][slot, :, n:n + 1]
                vb = vb * scale_bufs[1][slot, :, n:n + 1]
            vb = jnp.where(seen, vb, 0.0)
            if not chunk:
                attend(n, (), kb, vb, valid)
                continue

            # a chunk's tall block, ``_ROW_BLOCK`` rows at a time: the body the
            # compiler writes out stays a decode step's, whatever the block holds
            def _rows(r, carry, n=n, kb=kb, vb=vb):
                r0 = pl.multiple_of(r * _ROW_BLOCK, _ROW_BLOCK)
                valid, q_pos = visible(row0 + r0, _ROW_BLOCK, k_pos)
                if window:
                    valid = valid & (k_pos > q_pos - window)
                attend(n, (pl.ds(r0, _ROW_BLOCK),), kb, vb, valid)
                return carry

            jax.lax.fori_loop(0, rows // _ROW_BLOCK, _rows, 0)
        return carry

    jax.lax.fori_loop(t_first, (live + tile - 1) // tile, _step, 0)

    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    out_ref[0] = out.astype(out_ref.dtype)


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
                            k_scale, v_scale, *, interpret, block_len=1, window=0):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, nh, hd = q.shape
    bs, width = k_pool.shape[2], k_pool.shape[3]
    n_kv = width // hd
    rep = nh // n_kv
    quantized = k_scale is not None
    # a kv head's query group stacked along the rows, head-major, padded to whole
    # sublanes, in ONE block of rows or a chunk's blocks (``_step_geometry``): a
    # reshape of the small query outside the kernel
    tile, block_rows, chunk = _step_geometry(rep * s, block_tables.shape[1], n_kv)
    rows = -(-rep * s // block_rows) * block_rows
    grouped = q.reshape(b, s, n_kv, rep, hd).transpose(0, 2, 3, 1, 4).reshape(b, n_kv, rep * s, hd)
    grouped = jnp.pad(grouped, [(0, 0), (0, 0), (0, rows - rep * s), (0, 0)])

    def row(i, r, bt, ix, ly):
        return (i, 0, r, 0)

    # the pool operands are the stored pools, whole and left in HBM: the
    # kernel addresses them at (layer, block) itself, so no slab exists
    pools = [k_pool, v_pool]
    if quantized:
        pools += [_scale_blocks(k_scale, layer), _scale_blocks(v_scale, layer)]
    buffers = _IN_FLIGHT + 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block_tables + idx + layer steer the walk
        grid=(b, rows // block_rows),
        in_specs=[pl.BlockSpec((1, n_kv, block_rows, hd), row)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=pl.BlockSpec((1, n_kv, block_rows, hd), row),
        scratch_shapes=[
            pltpu.VMEM((buffers, tile * bs, p.shape[3]), p.dtype) for p in pools
        ] + [
            pltpu.SemaphoreType.DMA((len(pools), buffers)),
            pltpu.VMEM((n_kv, block_rows, 1), jnp.float32),
            pltpu.VMEM((n_kv, block_rows, 1), jnp.float32),
            pltpu.VMEM((n_kv, block_rows, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _pallas_kernel, bs=bs, tile=tile, s=s, quantized=quantized, block_len=block_len,
            **({"window": window} if window else {}), **({"chunk": True} if chunk else {}),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, rows, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            **({"vmem_limit_bytes": _CHUNK_VMEM_LIMIT} if chunk else {})),
        interpret=interpret,
        name="paged_attention",
    )(
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(idx, jnp.int32).reshape(b),
        jnp.asarray(layer, jnp.int32).reshape(1),
        grouped,
        *pools,
    )
    out = out[:, :, :rep * s].reshape(b, n_kv, rep, s, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, nh, hd)


def _paged_attention_pallas_sharded(q, k_pool, v_pool, layer, block_tables, idx,
                                    k_scale, v_scale, *, interpret, block_len=1, window=0):
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
                               block_len=block_len, **({"window": window} if window else {}))
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


# ---------------------------------------------------------------------------
# latent attention: one cached vector a token for every head (MLA, absorbed)
# ---------------------------------------------------------------------------

#: table entries a softmax step of the latent kernel takes (``_TILE``'s twin for
#: one "kv head" of 640 lanes against 128 query heads): 512 positions at blocks
#: of 16. What a step pays whatever it takes - the float32 accumulator read,
#: scaled and rewritten, the softmax state, the copies' start and wait - is paid
#: a quarter as often as at 8. Timed on the v5e (PERF.md sections 5 and 6, PR
#: 43; a 512-token chunk at 16k of context, ms a layer's call at 8 / 16 / 32 /
#: 64): 37.0 / 22.0 / 20.6 / 19.3 at a row block of 512; 64 loses at short
#: contexts what it gains at long ones (a part tile's products run whole), and
#: a decode row reads best at 32 too, a row of the cell's few live ones within
#: 0.01 ms of 16
_LATENT_TILE = 32

#: stacked query rows (heads x queries, head-major) a grid step of the latent
#: kernel takes: every head of a decode row at once (128), four heads' queries
#: of a 512-token chunk. Every row block walks and copies the row's context
#: again, and pays a tile's own costs (its copies' loop, its mask, the select
#: of its values) once more; 512 / 1,024 / 2,048 / 4,096 read 19.7 / 17.0 /
#: 15.4 / 15.4 ms at 16k and 1.65 / 1.65 / 1.55 / 1.74 at 1k (PERF.md, PR 43)
_LATENT_ROW_BLOCK = 2048

#: VMEM the latent kernel may take: a row block of 2,048 keeps 28 MB (a
#: 1,024-token chunk 32: query and output blocks twice, the float32
#: accumulator, scores and probabilities), over the compiler's 16 MB default
_LATENT_VMEM_LIMIT = 64 << 20


def latent_attention(
    q,                      # [b, s, n_heads, width]: absorbed queries
    pool,                   # [layers, num_blocks, bs, width] (storage dtype)
    layer,                  # int32 scalar (may be traced)
    block_tables,           # [b, max_blocks] int32
    idx,                    # [b] int32 — first query's cache position
    *,
    rank: int,
    scale: float,
    pool_scale=None,        # [layers, num_blocks, bs, 1] f32 (a quantized pool)
    impl: str | None = None,
    interpret: bool = False,
):
    """Multi-head latent attention in its absorbed form, off the block table.
    The cache keeps ONE vector of ``width`` entries a token and layer — the
    compressed key/value ``c`` (``rank`` entries), behind it the rotated key
    all heads share, then zeros up to whole lane tiles
    (``models.cache.CacheSpec.pool_width``) — and each head's query has been
    carried into those coordinates (``q_nope W_kvb_k^T`` | rotated ``q_pe`` |
    zeros, padded by the caller to the pool's width), so head ``h`` of
    query ``p`` scores ``q[p, h] . cache[j] * scale`` against every position
    ``j <= idx + p`` and sums the first ``rank`` entries of the cached vectors
    under the softmax: ``[b, s, n_heads, rank]``, which the caller carries
    back through ``W_kvb_v``. One "kv head" of ``width`` lanes whose leading
    lanes are also V: the pool is read once for both products, ``2 * nh *
    (width + rank)`` operations a cached entry of ``width`` values.

    Routes as :func:`paged_attention`: ``"pallas"`` (the kernel
    ``latent_attention``: the same walk over a row's own table entries, a
    tile of ``_LATENT_TILE`` entries a softmax step, the products in the
    pool's type with float32 accumulation), ``"lax"`` (a scan over table
    entries) and ``"gather"`` (the span materialised, then
    :func:`ops.layers.cached_latent_attention`). No mesh: one vector for all
    heads leaves nothing to split over a head axis."""
    if impl is None:
        impl = default_paged_attention_impl()
    layer = jnp.asarray(layer, jnp.int32)
    bt = jnp.asarray(block_tables, jnp.int32)
    idx = jnp.asarray(idx, jnp.int32).reshape(q.shape[0])
    if impl == "lax":
        return _latent_attention_lax(q, pool, layer, bt, idx, pool_scale, rank, scale)
    if impl == "pallas":
        return _latent_attention_pallas(q, pool, layer, bt, idx, pool_scale, rank, scale,
                                        interpret=interpret)
    if impl == "gather":
        from .layers import cached_latent_attention

        b, mb = bt.shape
        span = _dequant_block(
            pool[layer, bt], None if pool_scale is None else pool_scale[layer, bt], 1)
        return cached_latent_attention(
            q, span.reshape(b, mb * pool.shape[2], pool.shape[3]), idx, rank, scale)
    raise ValueError(f"unknown latent attention impl {impl!r}")


def _latent_attention_lax(q, pool, layer, bt, idx, pool_scale, rank, scale):
    b, s, nh, _ = q.shape
    bs = pool.shape[2]
    qf = q.astype(jnp.float32) * scale
    q_pos = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]       # [b, s]

    def body(carry, j):
        m, l, acc = carry
        blk = bt[:, j]
        cb = _dequant_block(
            pool[layer, blk], None if pool_scale is None else pool_scale[layer, blk], 1)[:, :, 0]
        sc = jnp.einsum("bshd,btd->bhst", qf, cb)
        pos = j * bs + jnp.arange(bs, dtype=jnp.int32)
        vmask = (pos[None, None, :] <= q_pos[:, :, None])[:, None]       # [b, 1, s, bs]
        sc = jnp.where(vmask, sc, _NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.where(vmask, jnp.exp(sc - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhst,btd->bhsd", p, cb[..., :rank])
        return (m_new, l, acc), None

    init = (
        jnp.full((b, nh, s), _NEG_INF, jnp.float32),
        jnp.zeros((b, nh, s), jnp.float32),
        jnp.zeros((b, nh, s, rank), jnp.float32),
    )
    (_, l, acc), _ = jax.lax.scan(body, init, jnp.arange(bt.shape[1], dtype=jnp.int32))
    out = acc / jnp.maximum(l, 1e-30)[..., None]                         # [b, nh, s, rank]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _latent_kernel(bt_ref, idx_ref, layer_ref, q_ref, *rest, bs, tile, s, rank, scale,
                   quantized):
    """:func:`_pallas_kernel`'s walk for a latent pool. Grid ``(b, row
    blocks)``; a step holds a block of the row's stacked queries ``[rows,
    width]`` (head-major: stacked row ``g`` is query ``g % s`` of head ``g //
    s``) and walks the row's live table entries a tile at a time, each live
    entry copied from ``(layer, block)`` of the pool in HBM into its rows of
    a VMEM buffer, ``_IN_FLIGHT`` tiles ahead. The copies of a tile are
    started by ONE loop of as many trips as the tile has live entries
    (written out for a whole tile they read 3-6 % faster on a chunk and cost
    every run of the DeepSeek-V3 cell 4 s of tracing, ``setup_s`` 21.0
    against 17.2: PERF.md section 6, PR 43), and a whole tile's are waited
    for at once. A softmax step is ``[rows, width] x [tile * bs, width]^T``
    (``width`` the pool's stored one, whole lane tiles: the query's padding
    lanes are zeros), one max / exp / sum, and ``[rows, tile * bs] x [tile *
    bs, rank]``: the buffer's leading lanes are the values. The products
    take their operands in the query's type (a quantized pool's rows times
    their scale first) and accumulate in float32. Rows of the
    buffer that no copy of this tile wrote (up to ``tile - 1`` entries of
    the row's last tile) are masked out of the scores and zeroed where they
    are used as values (``0 x NaN``), as there."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_pools = 2 if quantized else 1
    pools, (out_ref, *rest) = rest[:n_pools], rest[n_pools:]
    bufs, (sems, m_ref, l_ref, acc_ref) = rest[:n_pools], rest[n_pools:]
    i = pl.program_id(0)
    mb = bt_ref.shape[1]
    rows = q_ref.shape[1]
    depth = _IN_FLIGHT + 1
    span = tile * bs
    layers = [pools[0].at[layer_ref[0]]] + [p.at[0] for p in pools[1:]]

    first = idx_ref[i]
    last = first + s - 1
    live = jnp.minimum(last // bs + 1, mb)
    row0 = pl.program_id(1) * rows

    def for_live_entries(t, act):
        """``act`` on the copies of tile ``t``'s live entries, one a pool
        operand and entry: a loop of as many trips as the tile has live
        entries, none for a tile past the row's last."""
        slot = t % depth

        def _entry(e, carry):
            blk = bt_ref[i, t * tile + e]
            for a, (pool, buf) in enumerate(zip(layers, bufs)):
                act(pltpu.make_async_copy(
                    pool.at[blk], buf.at[slot, pl.ds(pl.multiple_of(e * bs, bs), bs)],
                    sems.at[a, slot]))
            return carry

        jax.lax.fori_loop(0, jnp.clip(live - t * tile, 0, tile), _entry, 0)

    def wait_live_entries(t):
        """Wait for tile ``t``'s copies: they share a semaphore a pool, so a
        whole tile's are ONE wait for all their bytes; the row's last, part
        tile waits entry by entry."""
        slot = t % depth
        whole = live - t * tile >= tile

        @pl.when(whole)
        def _():
            for a, buf in enumerate(bufs):
                pltpu.make_async_copy(buf.at[slot], buf.at[slot], sems.at[a, slot]).wait()

        @pl.when(jnp.logical_not(whole))
        def _():
            for_live_entries(t, lambda dma: dma.wait())

    for t in range(_IN_FLIGHT):
        for_live_entries(t, lambda dma: dma.start())

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0]                                                 # [rows, width]
    contract = (((1,), (1,)), ((), ()))

    def _step(t, carry):
        for_live_entries(t + _IN_FLIGHT, lambda dma: dma.start())
        wait_live_entries(t)
        slot = t % depth
        k_pos = t * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        if s == 1:  # a decode row: every head's one query stands at ``first``
            valid = jnp.broadcast_to(k_pos <= first, (rows, span))
        else:
            g = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            valid = k_pos <= first + jax.lax.rem(g, s)            # [rows, span]
        seen = t * span + jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0) <= last
        cb = bufs[0][slot]                                        # [span, width]
        if quantized:
            cb = (cb.astype(jnp.float32) * bufs[1][slot, :, 0:1]).astype(q.dtype)
        cb = cb.astype(q.dtype)
        sc = jax.lax.dot_general(q, cb, contract, preferred_element_type=jnp.float32)
        sc = jnp.where(valid, sc * scale, _NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]                   # [rows, 1]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        vb = jnp.where(seen, cb[:, :rank], jnp.zeros((), cb.dtype))
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(cb.dtype), vb, preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, (live + tile - 1) // tile, _step, 0)
    out_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def _latent_attention_pallas(q, pool, layer, bt, idx, pool_scale, rank, scale, *, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, nh, width = q.shape
    bs = pool.shape[2]
    quantized = pool_scale is not None
    tile = tile_entries(bt.shape[1], latent=True)
    block_rows = min(-(-nh * s // 8) * 8, _LATENT_ROW_BLOCK)
    rows = -(-nh * s // block_rows) * block_rows
    stacked = q.transpose(0, 2, 1, 3).reshape(b, nh * s, width)
    stacked = jnp.pad(stacked, [(0, 0), (0, rows - nh * s), (0, 0)])

    def row(i, r, bt_, ix, ly):
        return (i, r, 0)

    pools = [pool] + ([_scale_blocks(pool_scale, layer)] if quantized else [])
    buffers = _IN_FLIGHT + 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, rows // block_rows),
        in_specs=[pl.BlockSpec((1, block_rows, width), row)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=pl.BlockSpec((1, block_rows, rank), row),
        scratch_shapes=[
            pltpu.VMEM((buffers, tile * bs, p.shape[3]), p.dtype) for p in pools
        ] + [
            pltpu.SemaphoreType.DMA((len(pools), buffers)),
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, rank), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_kernel, bs=bs, tile=tile, s=s, rank=rank,
                          scale=float(scale), quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_LATENT_VMEM_LIMIT),
        interpret=interpret,
        name="latent_attention",
    )(bt, idx, layer.reshape(1), stacked, *pools)
    return out[:, :nh * s].reshape(b, nh, s, rank).transpose(0, 2, 1, 3)
