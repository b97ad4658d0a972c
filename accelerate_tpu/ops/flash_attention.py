"""Flash attention as a Pallas TPU kernel (fwd + bwd), plus a blockwise
pure-JAX fallback.

No reference analog — the reference (relh/accelerate) ships no kernels; its
models get attention from `transformers`+CUDA. Here the hot op is built for
the MXU: tiled Q/K/V blocks staged through VMEM, online softmax in fp32,
causal block skipping, and a custom VJP whose backward is two more Pallas
kernels (dq and dk/dv) recomputing probabilities from the saved logsumexp
rather than materialising the [s, s] matrix.

Layouts: public API takes ``[batch, seq, heads, head_dim]`` (the model
layout); kernels run on ``[batch, heads, seq, head_dim]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)
# lanes used for the per-row m/l scratch (TPU wants a 128-wide minor dim)
_MIN_LANE = 128


def _compiler_params(n_grid: int):
    """Mark every grid dim except the (sequential, accumulating) last one as
    parallel so Mosaic can reorder freely."""
    sem = ("parallel",) * (n_grid - 1) + ("arbitrary",)
    return pltpu.CompilerParams(dimension_semantics=sem)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _block_runs(qi, ki, block_q, block_kv, causal, window):
    """Whether key block ``ki`` holds a key some query of block ``qi`` sees:
    not strictly above the diagonal (``causal``) and, with a ``window``, not
    wholly behind the window of the block's first query. ``True`` (static)
    where neither rule is on."""
    should_run = True
    if causal:
        should_run = (qi + 1) * block_q > ki * block_kv
    if window:
        should_run = should_run & ((ki + 1) * block_kv > qi * block_q - window + 1)
    return should_run


def _block_mask(s, qi, ki, block_q, block_kv, causal, window):
    """``s`` with the keys a query does not see replaced: those after it
    (``causal``) and, with a ``window``, those ``window`` or more before it."""
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        cols = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = rows >= cols
        if window:
            mask = mask & (cols > rows - window)
        s = jnp.where(mask, s, NEG_INF)
    return s


def _fwd_kernel(
    q_ref,  # (1, 1, bq, d)
    k_ref,  # (1, 1, bkv, d)
    v_ref,  # (1, 1, bkv, d)
    bias_ref,  # (1, 1, 1, bkv) or None
    o_ref,  # (1, 1, bq, d)
    lse_ref,  # (1, 1, bq, 1) — trailing unit lane so the block spec is
    #           Mosaic-legal (a rank-3 (1, 1, bq) block has second-minor 1,
    #           which real-TPU lowering rejects unless heads == 1)
    m_scr,  # (bq, _MIN_LANE) f32
    l_scr,  # (bq, _MIN_LANE) f32
    acc_scr,  # (bq, d) f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
    window: int = 0,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    # causal: skip blocks strictly above the diagonal (and, with a window,
    # those wholly behind it)
    should_run = _block_runs(qi, ki, block_q, block_kv, causal, window)

    @pl.when(should_run)
    def _compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0, 0, :][None, :].astype(jnp.float32)
        s = _block_mask(s, qi, ki, block_q, block_kv, causal, window)

        m_prev = m_scr[:, 0][:, None]  # (bq, 1)
        m_cur = jnp.max(s, axis=-1)[:, None]
        m_new = jnp.maximum(m_prev, m_cur)
        # NEG_INF is finite, so a fully-masked row has s == m_new == NEG_INF
        # and exp(s - m_new) would be 1; zero it so l stays 0 and the row
        # resolves to output 0 / lse NEG_INF instead of mean(v).
        p = jnp.where(m_new == NEG_INF, 0.0, jnp.exp(s - m_new))  # (bq, bkv)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = alpha * l_scr[:, 0][:, None] + jnp.sum(p, axis=-1)[:, None]

        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha + pv

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[:, 0][:, None]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        m = m_scr[:, 0][:, None]
        lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
        lse_ref[0, 0, :, :] = lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_scr,
    *, scale, causal, block_q, block_kv, num_kv_blocks, window=0,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    should_run = _block_runs(qi, ki, block_q, block_kv, causal, window)

    @pl.when(should_run)
    def _compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, :]  # (bq, 1)
        delta = delta_ref[0, 0, :, :]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0, 0, :][None, :].astype(jnp.float32)
        s = _block_mask(s, qi, ki, block_q, block_kv, causal, window)
        # NEG_INF is the finite float32 min, so for a fully-masked row both s
        # and lse are NEG_INF and exp(s - lse) = exp(0) = 1 — zero those rows
        # explicitly (partially-masked entries underflow to 0 on their own).
        p = jnp.where(lse == NEG_INF, 0.0, jnp.exp(s - lse))  # (bq, bkv)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_scr, dv_scr,
    *, scale, causal, block_q, block_kv, num_q_blocks, window=0,
):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    should_run = _block_runs(qi, ki, block_q, block_kv, causal, window)

    @pl.when(should_run)
    def _compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, :]  # (bq, 1)
        delta = delta_ref[0, 0, :, :]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0, 0, :][None, :].astype(jnp.float32)
        s = _block_mask(s, qi, ki, block_q, block_kv, causal, window)
        # see dq kernel: fully-masked rows have lse == NEG_INF and must give 0
        p = jnp.where(lse == NEG_INF, 0.0, jnp.exp(s - lse))  # (bq, bkv)
        # dv += p^T @ do
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale  # (bq, bkv)
        # dk += ds^T @ q
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------


def _pad_to(x, size, axis):
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _round_up(x: int, m: int) -> int:
    return ((max(x, 1) + m - 1) // m) * m


def _fit_block(seq: int, requested: int, align: int) -> int:
    """Block size ≤ the request that splits ``seq`` into near-equal
    ``align``-aligned blocks — the minimal block count the request allows,
    without the pathological padding a fixed block gives mid-range lengths
    (600 @ request 512 → two 304-blocks padded to 608, not a 512-block
    padded to 1024)."""
    requested = _round_up(requested, align)
    n_blocks = max(1, int(np.ceil(seq / requested)))
    return min(requested, _round_up(int(np.ceil(seq / n_blocks)), align))


def _fwd_call(q, k, v, bias, scale, causal, block_q, block_kv, interpret, window=0):
    b, h, sq, d = q.shape
    skv = k.shape[2]
    nq = sq // block_q
    nkv = skv // block_kv
    grid = (b, h, nq, nkv)

    def qmap(bi, hi, qi, ki):
        return (bi, hi, qi, 0)

    def kvmap(bi, hi, qi, ki):
        return (bi, hi, ki, 0)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), qmap),
        pl.BlockSpec((1, 1, block_kv, d), kvmap),
        pl.BlockSpec((1, 1, block_kv, d), kvmap),
    ]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, 1, 1, block_kv), lambda bi, hi, qi, ki: (bi, 0, 0, ki)))
        args.append(bias)

    if bias is None:
        def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr):
            return _fwd_kernel(
                q_ref, k_ref, v_ref, None, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                scale=scale, causal=causal, block_q=block_q, block_kv=block_kv,
                num_kv_blocks=nkv, window=window,
            )
    else:
        def kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr):
            return _fwd_kernel(
                q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                scale=scale, causal=causal, block_q=block_q, block_kv=block_kv,
                num_kv_blocks=nkv, window=window,
            )

    out_shape = [
        jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, block_q, d), qmap),
        pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, _MIN_LANE), jnp.float32),
            pltpu.VMEM((block_q, _MIN_LANE), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_compiler_params(len(grid)),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*args)
    return o, lse


def _bwd_call(q, k, v, bias, o, lse, do, scale, causal, block_q, block_kv, interpret,
              window=0):
    b, h, sq, d = q.shape
    skv = k.shape[2]
    nq = sq // block_q
    nkv = skv // block_kv

    # (b, h, sq, 1): rank-4 with a unit lane, matching the lse layout
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True)

    def qmap4(bi, hi, qi, ki):
        return (bi, hi, qi, 0)

    def kvmap4(bi, hi, qi, ki):
        return (bi, hi, ki, 0)

    def rowmap(bi, hi, qi, ki):
        return (bi, hi, qi, 0)

    # --- dq: grid (b, h, nq, nkv) ---
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), qmap4),
        pl.BlockSpec((1, 1, block_kv, d), kvmap4),
        pl.BlockSpec((1, 1, block_kv, d), kvmap4),
    ]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, 1, 1, block_kv), lambda bi, hi, qi, ki: (bi, 0, 0, ki)))
        args.append(bias)
    in_specs += [
        pl.BlockSpec((1, 1, block_q, d), qmap4),
        pl.BlockSpec((1, 1, block_q, 1), rowmap),
        pl.BlockSpec((1, 1, block_q, 1), rowmap),
    ]
    args += [do, lse, delta]

    if bias is None:
        def dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr):
            return _bwd_dq_kernel(
                q_ref, k_ref, v_ref, None, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
                scale=scale, causal=causal, block_q=block_q, block_kv=block_kv,
                num_kv_blocks=nkv, window=window,
            )
    else:
        def dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr):
            return _bwd_dq_kernel(
                q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
                scale=scale, causal=causal, block_q=block_q, block_kv=block_kv,
                num_kv_blocks=nkv, window=window,
            )

    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, nq, nkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, d), qmap4),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(4),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(*args)

    # --- dk/dv: grid (b, h, nkv, nq) ---
    def qmap_t(bi, hi, ki, qi):
        return (bi, hi, qi, 0)

    def kvmap_t(bi, hi, ki, qi):
        return (bi, hi, ki, 0)

    def rowmap_t(bi, hi, ki, qi):
        return (bi, hi, qi, 0)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), qmap_t),
        pl.BlockSpec((1, 1, block_kv, d), kvmap_t),
        pl.BlockSpec((1, 1, block_kv, d), kvmap_t),
    ]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, 1, 1, block_kv), lambda bi, hi, ki, qi: (bi, 0, 0, ki)))
        args.append(bias)
    in_specs += [
        pl.BlockSpec((1, 1, block_q, d), qmap_t),
        pl.BlockSpec((1, 1, block_q, 1), rowmap_t),
        pl.BlockSpec((1, 1, block_q, 1), rowmap_t),
    ]
    args += [do, lse, delta]

    if bias is None:
        def dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr):
            return _bwd_dkv_kernel(
                q_ref, k_ref, v_ref, None, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                scale=scale, causal=causal, block_q=block_q, block_kv=block_kv,
                num_q_blocks=nq, window=window,
            )
    else:
        def dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr):
            return _bwd_dkv_kernel(
                q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                scale=scale, causal=causal, block_q=block_q, block_kv=block_kv,
                num_q_blocks=nq, window=window,
            )

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, nkv, nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, d), kvmap_t),
            pl.BlockSpec((1, 1, block_kv, d), kvmap_t),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, skv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ],
        compiler_params=_compiler_params(4),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(*args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp public op (bhsd layout)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_bhsd(q, k, v, bias, scale, causal, block_q, block_kv, interpret, window=0):
    o, _ = _fwd_call(q, k, v, bias, scale, causal, block_q, block_kv, interpret, window)
    return o


def _flash_fwd(q, k, v, bias, scale, causal, block_q, block_kv, interpret, window=0):
    o, lse = _fwd_call(q, k, v, bias, scale, causal, block_q, block_kv, interpret, window)
    return o, (q, k, v, bias, o, lse)


def _flash_bwd(scale, causal, block_q, block_kv, interpret, window, res, do):
    q, k, v, bias, o, lse = res
    dq, dk, dv = _bwd_call(
        q, k, v, bias, o, lse, do, scale, causal, block_q, block_kv, interpret, window
    )
    dbias = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, dbias


_flash_bhsd.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,  # [b, s, nh, d]
    k: jax.Array,  # [b, skv, n_kv, d]
    v: jax.Array,
    segment_mask: jax.Array | None = None,  # [b, skv] 1 = valid
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 512,
    block_kv: int = 1024,
    interpret: bool | None = None,
    window: int = 0,
) -> jax.Array:
    """Flash attention in model layout. GQA handled by repeating KV heads.
    ``window`` above 0 (with ``causal``): the query at ``p`` sees the keys
    ``p - window < j <= p``; key blocks wholly behind a query block's window
    are skipped like those above the diagonal, in all three kernels.

    Default blocks (512, 1024): measured 28% faster fwd+bwd than (128, 128)
    on v5e at s=2048/d=64 (fewer grid steps, better MXU occupancy) and well
    inside VMEM for head dims up to 128; both clamp to the padded sequence
    for short inputs.

    Sequences are padded up to block multiples inside; padded KV columns are
    masked via the bias, padded Q rows are sliced away on return.
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    b, sq, nh, d = q.shape
    skv, n_kv = k.shape[1], k.shape[2]
    if n_kv != nh:
        rep = nh // n_kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))

    # Mosaic block constraints: second-minor multiple of 8 (q rows), and the
    # bias block's minor dim (= block_kv) a multiple of 128. Blocks adapt to
    # the sequence: keep the number of blocks the requested size implies,
    # but size them near-equally so padding waste stays bounded (sq=600 with
    # a 512 request must give ONE 608-block, not a 512-block padded to 1024).
    block_q = _fit_block(sq, block_q, 8)
    block_kv = _fit_block(skv, block_kv, 128)
    sq_p = int(np.ceil(sq / block_q)) * block_q
    skv_p = int(np.ceil(skv / block_kv)) * block_kv

    qt = _pad_to(q.transpose(0, 2, 1, 3), sq_p, 2)  # [b, h, sq_p, d]
    kt = _pad_to(k.transpose(0, 2, 1, 3), skv_p, 2)
    vt = _pad_to(v.transpose(0, 2, 1, 3), skv_p, 2)

    bias = None
    if segment_mask is not None or skv_p != skv:
        valid = (
            jnp.ones((b, skv), dtype=bool)
            if segment_mask is None
            else segment_mask.astype(bool)
        )
        valid = _pad_to(valid, skv_p, 1)
        bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)[:, None, None, :]

    if window and not causal:
        raise ValueError("a sliding window is built for causal attention only")
    o = _flash_bhsd(qt, kt, vt, bias, scale, causal, block_q, block_kv, interpret, window)
    return o[:, :, :sq, :].transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# blockwise (pure-JAX) memory-efficient attention — CPU fallback / oracle
# ---------------------------------------------------------------------------


def blockwise_attention(
    q: jax.Array,  # [b, s, nh, d]
    k: jax.Array,
    v: jax.Array,
    segment_mask: jax.Array | None = None,
    causal: bool = True,
    scale: float | None = None,
    block_kv: int = 512,
    window: int = 0,
) -> jax.Array:
    """Online-softmax attention as a ``lax.scan`` over KV blocks: O(s·bkv)
    live memory, fully differentiable through the scan. The same math as the
    Pallas kernel, letting XLA do the tiling — used where Pallas isn't
    (CPU) and as the inner per-chunk compute of ring attention."""
    b, sq, nh, d = q.shape
    skv, n_kv = k.shape[1], k.shape[2]
    if n_kv != nh:
        rep = nh // n_kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))
    block_kv = min(block_kv, skv)
    skv_p = int(np.ceil(skv / block_kv)) * block_kv
    nblocks = skv_p // block_kv

    kp = _pad_to(k, skv_p, 1).transpose(0, 2, 1, 3)  # [b,h,skv_p,d]
    vp = _pad_to(v, skv_p, 1).transpose(0, 2, 1, 3)
    valid = jnp.ones((b, skv), bool) if segment_mask is None else segment_mask.astype(bool)
    valid = _pad_to(valid, skv_p, 1)

    qt = q.transpose(0, 2, 1, 3).astype(jnp.float32)  # [b,h,sq,d]
    k_blocks = kp.reshape(b, nh, nblocks, block_kv, d).transpose(2, 0, 1, 3, 4)
    v_blocks = vp.reshape(b, nh, nblocks, block_kv, d).transpose(2, 0, 1, 3, 4)
    m_blocks = valid.reshape(b, nblocks, block_kv).transpose(1, 0, 2)
    q_pos = jnp.arange(sq)

    def body(carry, xs):
        acc, m_run, l_run = carry
        kb, vb, mb, bidx = xs
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, kb.astype(jnp.float32)) * scale
        col_mask = mb[:, None, None, :]  # [b,1,1,bkv]
        if causal:
            kv_pos = bidx * block_kv + jnp.arange(block_kv)
            col_mask = col_mask & (q_pos[:, None] >= kv_pos[None, :])[None, None]
            if window:
                col_mask = col_mask & (kv_pos[None, :] > q_pos[:, None] - window)[None, None]
        s = jnp.where(col_mask, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_run, m_cur)
        # fully-masked rows: m_new == NEG_INF (finite) would give exp(0)=1
        p = jnp.where(m_new[..., None] == NEG_INF, 0.0, jnp.exp(s - m_new[..., None]))
        alpha = jnp.exp(m_run - m_new)
        l_new = alpha * l_run + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32)
        )
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((b, nh, sq, d), jnp.float32)
    m0 = jnp.full((b, nh, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nh, sq), jnp.float32)
    (acc, m_run, l_run), _ = jax.lax.scan(
        body, (acc0, m0, l0), (k_blocks, v_blocks, m_blocks, jnp.arange(nblocks))
    )
    l_safe = jnp.where(l_run == 0.0, 1.0, l_run)
    out = acc / l_safe[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
