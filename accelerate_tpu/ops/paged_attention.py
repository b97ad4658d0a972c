"""Fused paged attention: walk the block table, never materialise the span.

The PR 4 paged decode path gathered each slot's **entire** block-table span
(``gather_paged_kv`` → ``[b, max_blocks*bs, n_kv, hd]``), ``jnp.repeat``-ed
KV heads for GQA, and only then ran ``cached_attention`` — so the bytes a
decode step moves scale with the *maximum* context and the GQA expansion,
not the valid prefix. This module computes attention **block-by-block**
straight off the block table:

* one pool block ``[bs, n_kv, hd]`` is loaded per table entry, dequantized
  in registers when the pool is int8/fp8 (``ops/fp8.py`` scales), and
  consumed by an **online softmax** (running max / sum / accumulator — the
  flash-attention recurrence), so no ``[b, max_blocks*bs, ...]`` buffer
  ever exists;
* GQA uses a **grouped-head einsum** (``[b, s, n_kv, rep, hd]`` against
  ``[b, bs, n_kv, hd]``) — repeated KV heads are never materialised;
* positions past each row's valid prefix are masked inside the recurrence
  (same policy as ``cached_attention``), and the Pallas kernel skips the
  compute of fully-invalid table entries.

Two implementations behind one dispatcher
(:func:`default_paged_attention_impl` — the Pallas kernel on TPU, the
pure-lax ``scan``-over-blocks everywhere else; the gather-then-dense
reference survives as the parity/bench baseline). Both run in f32
scores/softmax like every attention in this codebase.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .fp8 import dequantize_kv

_NEG_INF = float(np.finfo(np.float32).min)


def default_paged_attention_impl() -> str:
    """The route :func:`paged_attention` takes when none is forced: the
    Pallas block-table kernel on a TPU backend, the pure-lax scan over
    blocks on CPU/GPU (Mosaic lowers for TPU only). A static choice by
    platform — a kernel that fails to build there is an error, never a
    quiet change of route."""
    return "pallas" if jax.default_backend() == "tpu" else "lax"


def _dequant_block(block, scale_rows):
    """One gathered pool block → f32, applying per-row scales if present."""
    if scale_rows is None:
        return block.astype(jnp.float32)
    return dequantize_kv(block, scale_rows)


def paged_attention(
    q,                      # [b, s, n_heads, hd]
    k_pages_l,              # [num_blocks, bs, n_kv, hd] (storage dtype)
    v_pages_l,              # [num_blocks, bs, n_kv, hd]
    block_tables,           # [b, max_blocks] int32
    idx,                    # [b] int32 — first query's cache position
    k_scale_l=None,         # [num_blocks, bs, n_kv] f32 (quantized pools)
    v_scale_l=None,
    impl: str | None = None,
    interpret: bool = False,
):
    """Attention of ``q`` against each row's block-table span. Query ``j``
    of row ``b`` attends logical cache positions ``<= idx[b]+j`` — the
    same per-row valid-prefix + intra-chunk causal policy as
    :func:`ops.layers.cached_attention`, so paged decode keeps matching
    dense decode. ``impl``: ``None`` routes via
    :func:`default_paged_attention_impl`;
    ``"lax"``/``"pallas"``/``"gather"`` force a path (``"gather"`` is the
    PR 4 materialise-the-span reference, kept for parity tests and the
    fused-vs-gather bench ratio). ``interpret`` runs the Pallas kernel in
    the Pallas interpreter — how tests exercise it off-TPU."""
    if impl is None:
        impl = default_paged_attention_impl()
    if impl == "lax":
        return _paged_attention_lax(
            q, k_pages_l, v_pages_l, block_tables, idx, k_scale_l, v_scale_l
        )
    if impl == "pallas":
        return _paged_attention_pallas_sharded(
            q, k_pages_l, v_pages_l, block_tables, idx, k_scale_l, v_scale_l,
            interpret=interpret,
        )
    if impl == "gather":
        return _paged_attention_gather(
            q, k_pages_l, v_pages_l, block_tables, idx, k_scale_l, v_scale_l
        )
    raise ValueError(f"unknown paged attention impl {impl!r}")


# ---------------------------------------------------------------------------
# pure-lax fallback: scan over table entries, online softmax
# ---------------------------------------------------------------------------


def _paged_attention_lax(q, k_pages_l, v_pages_l, block_tables, idx, k_scale_l, v_scale_l):
    b, s, nh, hd = q.shape
    _, bs, n_kv, _ = k_pages_l.shape
    rep = nh // n_kv
    mb = block_tables.shape[1]
    bt = jnp.asarray(block_tables, jnp.int32)
    idx = jnp.asarray(idx, jnp.int32).reshape(b)

    # scale folded into q once (not per block); grouped heads for GQA
    qg = (q.astype(jnp.float32) / np.sqrt(float(hd))).reshape(b, s, n_kv, rep, hd)
    q_pos = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # [b, s]

    def body(carry, j):
        m, l, acc = carry
        blk = bt[:, j]                                   # [b]
        kb = _dequant_block(k_pages_l[blk], None if k_scale_l is None else k_scale_l[blk])
        vb = _dequant_block(v_pages_l[blk], None if v_scale_l is None else v_scale_l[blk])
        # [b, n_kv, rep, s, bs]: contraction over hd, batched over kv head
        sc = jnp.einsum("bsnrd,btnd->bnrst", qg, kb)
        pos = j * bs + jnp.arange(bs, dtype=jnp.int32)   # logical positions
        valid = pos[None, None, :] <= q_pos[:, :, None]  # [b, s, bs]
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


def _paged_attention_gather(q, k_pages_l, v_pages_l, block_tables, idx, k_scale_l, v_scale_l):
    from .layers import cached_attention, gather_paged_kv

    if k_scale_l is not None:
        bt = jnp.asarray(block_tables, jnp.int32)
        b, mb = bt.shape
        bs = k_pages_l.shape[1]
        k_g = dequantize_kv(k_pages_l[bt], k_scale_l[bt])
        v_g = dequantize_kv(v_pages_l[bt], v_scale_l[bt])
        k_g = k_g.reshape(b, mb * bs, *k_g.shape[3:])
        v_g = v_g.reshape(b, mb * bs, *v_g.shape[3:])
    else:
        k_g, v_g = gather_paged_kv(k_pages_l, v_pages_l, block_tables)
    return cached_attention(q, k_g, v_g, jnp.asarray(idx, jnp.int32).reshape(q.shape[0]))


# ---------------------------------------------------------------------------
# Pallas TPU kernel: block-table-indexed BlockSpecs via scalar prefetch
# ---------------------------------------------------------------------------


def _pallas_kernel(bt_ref, idx_ref, q_ref, k_ref, v_ref, *rest,
                   bs, n_kv, rep, hd, quantized):
    """Grid ``(b, max_blocks)``: step ``(i, j)`` consumes row ``i``'s
    ``j``-th table entry — the BlockSpec index maps already steered the
    right pool block into VMEM via the prefetched block table. Online
    softmax state lives in VMEM scratch across the ``j`` steps (the last
    grid axis iterates fastest); entries wholly past the row's valid
    prefix skip their compute.

    Every operand is 2-D inside the kernel: heads are folded into the lane
    dimension outside (``[.., n*hd]``), and head ``h`` is the static lane
    slice ``[h*hd, (h+1)*hd)`` — Mosaic tiles the two minor dimensions, so
    a head axis kept second-minor (12 rows padded to 16) and a 4-D
    batched-in-the-middle einsum cost a prefill chunk 119 MB of VMEM."""
    import jax.experimental.pallas as pl

    if quantized:
        ks_ref, vs_ref, out_ref, m_ref, l_ref, acc_ref = rest
    else:
        out_ref, m_ref, l_ref, acc_ref = rest
    i = pl.program_id(0)
    j = pl.program_id(1)
    s = q_ref.shape[1]
    first = idx_ref[i]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bs <= first + s - 1)             # any position valid?
    def _step():
        q_pos = first + jax.lax.broadcasted_iota(jnp.int32, (s, bs), 0)
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (s, bs), 1)
        valid = k_pos <= q_pos
        for n in range(n_kv):
            kv_lanes = slice(n * hd, (n + 1) * hd)
            kb = k_ref[0, :, kv_lanes].astype(jnp.float32)   # [bs, hd]
            vb = v_ref[0, :, kv_lanes].astype(jnp.float32)
            if quantized:
                kb = kb * ks_ref[0, :, n:n + 1]
                vb = vb * vs_ref[0, :, n:n + 1]
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

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        for h in range(n_kv * rep):
            lanes = slice(h * hd, (h + 1) * hd)
            out = acc_ref[:, lanes] / jnp.maximum(l_ref[h], 1e-30)
            out_ref[0, :, lanes] = out.astype(out_ref.dtype)


def _paged_attention_pallas(q, k_pages_l, v_pages_l, block_tables, idx,
                            k_scale_l, v_scale_l, *, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, nh, hd = q.shape
    nb, bs, n_kv, _ = k_pages_l.shape
    mb = block_tables.shape[1]
    quantized = k_scale_l is not None

    def row(i, j, bt, ix):
        return (i, 0, 0)

    def block(i, j, bt, ix):
        return (bt[i, j], 0, 0)

    in_specs = [
        pl.BlockSpec((1, s, nh * hd), row),
        pl.BlockSpec((1, bs, n_kv * hd), block),
        pl.BlockSpec((1, bs, n_kv * hd), block),
    ]
    # heads fold into lanes: free reshapes of contiguous minor dimensions
    args = [
        q.reshape(b, s, nh * hd),
        k_pages_l.reshape(nb, bs, n_kv * hd),
        v_pages_l.reshape(nb, bs, n_kv * hd),
    ]
    if quantized:
        in_specs += [pl.BlockSpec((1, bs, n_kv), block)] * 2
        args += [k_scale_l, v_scale_l]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables + idx steer the index maps
        grid=(b, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, s, nh * hd), row),
        scratch_shapes=[
            pltpu.VMEM((nh, s, 1), jnp.float32),
            pltpu.VMEM((nh, s, 1), jnp.float32),
            pltpu.VMEM((s, nh * hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _pallas_kernel, bs=bs, n_kv=n_kv, rep=nh // n_kv, hd=hd,
            quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, nh * hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="paged_attention",
    )(
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(idx, jnp.int32).reshape(b),
        *args,
    )
    return out.reshape(b, s, nh, hd)


def _paged_attention_pallas_sharded(q, k_pages_l, v_pages_l, block_tables, idx,
                                    k_scale_l, v_scale_l, *, interpret):
    """The kernel under the active mesh: GSPMD treats a Mosaic call as
    opaque, so with the pool's kv heads sharded over the head axis
    (``parallel.sharding.paged_kv_sharding``) the call must run under
    ``shard_map`` with the heads partitioned explicitly — each device
    walks the block table over its own heads' slice of the pool; a bare
    call on a sharded mesh is refused at lowering ("Mosaic kernels cannot
    be automatically partitioned"). The mesh is the one the engine (or
    ``prepare``) set on the attention context; heads the axis does not
    divide stay replicated, like the pool."""
    from .attention import get_attention_context

    ctx = get_attention_context()
    kernel = functools.partial(_paged_attention_pallas, interpret=interpret)
    extent = 1 if ctx.mesh is None else dict(ctx.mesh.shape).get(ctx.head_axis, 1)
    if extent == 1 or q.shape[2] % extent or k_pages_l.shape[2] % extent:
        return kernel(q, k_pages_l, v_pages_l, block_tables, idx, k_scale_l, v_scale_l)
    heads = P(None, None, ctx.head_axis, None)
    operands = [
        q, k_pages_l, v_pages_l,
        jnp.asarray(block_tables, jnp.int32), jnp.asarray(idx, jnp.int32),
    ]
    in_specs = [heads, heads, heads, P(), P()]
    if k_scale_l is not None:
        operands += [k_scale_l, v_scale_l]
        in_specs += [P(None, None, ctx.head_axis)] * 2

    def per_shard(q_, k_, v_, bt_, idx_, *scales):
        return kernel(q_, k_, v_, bt_, idx_, *(scales or (None, None)))

    return jax.shard_map(
        per_shard, mesh=ctx.mesh, in_specs=tuple(in_specs), out_specs=heads,
        check_vma=False,
    )(*operands)
