"""Ring attention with the Pallas flash kernel as the per-chunk engine.

The einsum ring body (``parallel/context.py``) materialises a
``[b, h, s_loc, s_loc]`` score block per ring step in fp32; this module
replaces that inner compute with the Mosaic flash kernel (O(s) memory,
MXU-tiled) while keeping the ring structure:

* forward — each ring step runs ``_fwd_call`` on (local Q, traveling KV
  chunk) and merges the chunk's (normalised output, LSE) into the running
  pair with the online-softmax rule. Under causal masking, chunks strictly
  in the future are skipped entirely (``lax.cond`` → zero work), the
  diagonal chunk uses the kernel's causal path (local coordinates align),
  and past chunks run full attention.
* backward — a whole-ring ``custom_vjp``: the flash decomposition makes
  each chunk's (dq, dk, dv) computable independently given the FINAL
  (o, lse) and do (``delta = rowsum(do·o)`` — exactly what ``_bwd_call``
  computes), so the bwd is a second ring where dk/dv accumulators travel
  with their KV chunk and arrive home after a full cycle.

Layouts: the public entry takes the ring body's ``[b, s_loc, h, d]``;
kernels run in ``[b, h, s, d]`` with KV/bias padded to block multiples.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention import NEG_INF, _bwd_call, _fit_block, _fwd_call, _pad_to


def _merge(o_run, lse_run, o_c, lse_c):
    """Online-softmax combination of two normalised partial attentions."""
    lse_new = jnp.logaddexp(lse_run, lse_c)
    w_run = jnp.exp(lse_run - lse_new)
    w_c = jnp.exp(lse_c - lse_new)
    return o_run * w_run + o_c.astype(jnp.float32) * w_c, lse_new


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _ring_flash_bhsd(
    q, k, v, bias, idxf, axis_name, scale, causal, block_q, block_kv, interpret
):
    """``idxf``: this shard's ring position as an f32 ``[1]`` DATA array
    (exact for any real ring size). Plumbed as a differentiable arg with a
    zero cotangent because (a) custom_vjp nondiff args must be static and
    (b) ``jax.lax.axis_index`` cannot be used here — inside a nested
    manual region (cp attention in a GPipe stage body) its lowering claims
    the parent's manual axes and the MLIR verifier rejects the program."""
    o, _ = _ring_fwd_impl(
        q, k, v, bias, idxf, axis_name, scale, causal, block_q, block_kv, interpret
    )
    return o


def _chunk_fwd(q, k_cur, v_cur, bias_cur, src, idx, *, scale, causal, bq, bkv, interp):
    """One ring step's (o_c, lse_c) with the causal-class branching."""
    def diag():
        return _fwd_call(q, k_cur, v_cur, bias_cur, scale, True, bq, bkv, interp)

    def full():
        return _fwd_call(q, k_cur, v_cur, bias_cur, scale, False, bq, bkv, interp)

    def skip():
        b, h, sq, d = q.shape
        return (
            jnp.zeros((b, h, sq, d), q.dtype),
            jnp.full((b, h, sq, 1), NEG_INF, jnp.float32),
        )

    if not causal:
        return full()
    return jax.lax.cond(
        src == idx, diag, lambda: jax.lax.cond(src < idx, full, skip)
    )


def _ring_fwd_impl(q, k, v, bias, idxf, axis_name, scale, causal, block_q, block_kv, interpret):
    n = jax.lax.axis_size(axis_name)
    idx = (
        idxf.reshape(()).astype(jnp.int32)
        if idxf is not None
        else jax.lax.axis_index(axis_name)
    )
    b, h, sq, d = q.shape
    o = jnp.zeros((b, h, sq, d), jnp.float32)
    lse = jnp.full((b, h, sq, 1), NEG_INF, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    k_cur, v_cur, bias_cur = k, v, bias
    for step in range(n):
        src = (idx - step) % n
        o_c, lse_c = _chunk_fwd(
            q, k_cur, v_cur, bias_cur, src, idx,
            scale=scale, causal=causal, bq=block_q, bkv=block_kv, interp=interpret,
        )
        o, lse = _merge(o, lse, o_c, lse_c)
        if step != n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            bias_cur = jax.lax.ppermute(bias_cur, axis_name, perm)
    return o.astype(q.dtype), lse


def _ring_flash_fwd(q, k, v, bias, idxf, axis_name, scale, causal, block_q, block_kv, interpret):
    o, lse = _ring_fwd_impl(
        q, k, v, bias, idxf, axis_name, scale, causal, block_q, block_kv, interpret
    )
    return o, (q, k, v, bias, idxf, o, lse)


def _ring_flash_bwd(axis_name, scale, causal, block_q, block_kv, interpret, res, do):
    q, k, v, bias, idxf, o, lse = res
    n = jax.lax.axis_size(axis_name)
    idx = (
        idxf.reshape(()).astype(jnp.int32)
        if idxf is not None
        else jax.lax.axis_index(axis_name)
    )
    b, h, sq, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def zero3():
        return (
            jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(v)
        )

    def chunk_bwd(k_cur, v_cur, bias_cur, src):
        def diag():
            return _bwd_call(
                q, k_cur, v_cur, bias_cur, o, lse, do, scale, True,
                block_q, block_kv, interpret,
            )

        def full():
            return _bwd_call(
                q, k_cur, v_cur, bias_cur, o, lse, do, scale, False,
                block_q, block_kv, interpret,
            )

        if not causal:
            return full()
        return jax.lax.cond(
            src == idx, diag, lambda: jax.lax.cond(src < idx, full, zero3)
        )

    dq = jnp.zeros(q.shape, jnp.float32)
    k_cur, v_cur, bias_cur = k, v, bias
    dk_cur = jnp.zeros(k.shape, jnp.float32)
    dv_cur = jnp.zeros(v.shape, jnp.float32)
    for step in range(n):
        src = (idx - step) % n
        dq_c, dk_c, dv_c = chunk_bwd(k_cur, v_cur, bias_cur, src)
        dq = dq + dq_c.astype(jnp.float32)
        dk_cur = dk_cur + dk_c.astype(jnp.float32)
        dv_cur = dv_cur + dv_c.astype(jnp.float32)
        # accumulators travel WITH their chunk; after the full cycle each
        # chunk's grads are back on its owner
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        bias_cur = jax.lax.ppermute(bias_cur, axis_name, perm)
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
    return (
        dq.astype(q.dtype), dk_cur.astype(k.dtype), dv_cur.astype(v.dtype),
        jnp.zeros_like(bias),
        None if idxf is None else jnp.zeros_like(idxf),
    )


_ring_flash_bhsd.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention_local(
    q: jax.Array,  # [b, s_local, h, d]
    k: jax.Array,
    v: jax.Array,
    kv_valid: jax.Array,  # [b, s_local] bool
    *,
    axis_name: str = "cp",
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 512,
    block_kv: int = 1024,
    interpret: bool | None = None,
    cp_index: jax.Array | None = None,
) -> jax.Array:
    """Ring attention body with flash-kernel chunks (call inside shard_map
    over ``axis_name``; drop-in for ``ring_attention_local``)."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    b, s_loc, h, d = q.shape
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))

    block_q = _fit_block(s_loc, block_q, 8)
    block_kv = _fit_block(s_loc, block_kv, 128)
    sq_p = int(np.ceil(s_loc / block_q)) * block_q
    skv_p = int(np.ceil(s_loc / block_kv)) * block_kv

    qt = _pad_to(q.transpose(0, 2, 1, 3), sq_p, 2)  # [b, h, sq_p, d]
    kt = _pad_to(k.transpose(0, 2, 1, 3), skv_p, 2)
    vt = _pad_to(v.transpose(0, 2, 1, 3), skv_p, 2)
    valid = _pad_to(kv_valid.astype(bool), skv_p, 1)
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)[:, None, None, :]

    idxf = None if cp_index is None else cp_index.astype(jnp.float32)
    o = _ring_flash_bhsd(
        qt, kt, vt, bias, idxf, axis_name, scale, causal, block_q, block_kv, interpret
    )
    return o[:, :, :s_loc, :].transpose(0, 2, 1, 3)
