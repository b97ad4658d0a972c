"""Fused paged attention + quantized KV storage (``ops/paged_attention.py``,
the ``ops/fp8.py`` KV quantize helpers, and the quantizing
``write_paged_kv``).

All ops-level and tier-1: tiny shapes, CPU-cheap. The parity contract is
layered — the fused lax walk must match the gather-then-dense reference to
f32 noise at float storage, and the quantized paths must match the f32
reference within the documented per-dtype tolerances (these same numbers
gate the engine-level matrix in ``tests/test_serving.py`` and are quoted in
``docs/source/usage_guides/serving.md``).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from accelerate_tpu.ops.fp8 import (
    dequantize_kv,
    kv_qmax,
    kv_storage_dtype,
    quantize_kv_rows,
)
from accelerate_tpu.ops.layers import cached_attention, write_paged_kv
from accelerate_tpu.ops.paged_attention import paged_attention

#: ops-level |fused_quantized - f32_reference| ceilings on attention
#: outputs (unit-variance inputs). int8 carries ~0.4% relative error per
#: row (7-bit mantissa + rounding), fp8 e4m3 ~3% (3-bit mantissa).
KV_ATOL = {"int8": 0.05, "fp8": 0.12}


def _filled_pools(rng, *, b=3, n_kv=4, hd=16, bs=4, nb=12, mb=5, idx=(9, 6, 14),
                  dtype=None):
    """Pools written position-by-position through real block tables: the
    f32 pools are ground truth; quantized pools (dtype given) are written
    through the same scatter with scale arrays."""
    bt = np.zeros((b, mb), np.int32)
    used = iter(range(1, nb))
    for i, ix in enumerate(idx):
        for j in range((ix // bs) + 1):
            bt[i, j] = next(used)
    idx = np.asarray(idx, np.int32)
    kpf = jnp.zeros((nb, bs, n_kv, hd), jnp.float32)
    vpf = jnp.zeros_like(kpf)
    q_pools = None
    if dtype is not None:
        kp = jnp.zeros((nb, bs, n_kv, hd), dtype)
        vp = jnp.zeros_like(kp)
        ks = jnp.ones((nb, bs, n_kv), jnp.float32)
        vs = jnp.ones_like(ks)
        q_pools = (kp, vp, ks, vs)
    for p in range(int(idx.max()) + 1):
        k = jnp.asarray(rng.normal(size=(b, 1, n_kv, hd)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, 1, n_kv, hd)).astype(np.float32))
        mask = np.asarray([[p <= ix] for ix in idx])
        pos = np.full((b, 1), p, np.int32)
        kpf, vpf = write_paged_kv(kpf, vpf, k, v, bt, pos, write_mask=mask)
        if q_pools is not None:
            q_pools = write_paged_kv(
                *q_pools[:2], k, v, bt, pos, write_mask=mask,
                k_scale_l=q_pools[2], v_scale_l=q_pools[3],
            )
    return bt, idx, (kpf, vpf), q_pools


def test_fused_lax_matches_gather_reference():
    """The scan-over-blocks online softmax equals the PR 4
    gather-then-``cached_attention`` path to f32 noise — decode (s=1) and
    prefill-chunk (s>1) query shapes, GQA heads."""
    rng = np.random.default_rng(0)
    bt, idx, (kpf, vpf), _ = _filled_pools(rng)
    for s, offs in ((1, 0), (4, 3)):
        q = jnp.asarray(rng.normal(size=(3, s, 8, 16)).astype(np.float32))
        qi = np.maximum(idx - offs, 0)
        ref = paged_attention(q, kpf, vpf, bt, qi, impl="gather")
        fused = paged_attention(q, kpf, vpf, bt, qi, impl="lax")
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_pallas_kernel_matches_gather_reference():
    """The Pallas block-table kernel (in the Pallas interpreter off-TPU)
    computes the same attention as the gather reference — decode and
    prefill-chunk query shapes, GQA heads."""
    rng = np.random.default_rng(1)
    bt, idx, (kpf, vpf), _ = _filled_pools(rng)
    for s, offs in ((1, 0), (4, 3)):
        q = jnp.asarray(rng.normal(size=(3, s, 8, 16)).astype(np.float32))
        qi = np.maximum(idx - offs, 0)
        ref = paged_attention(q, kpf, vpf, bt, qi, impl="gather")
        out = paged_attention(q, kpf, vpf, bt, qi, impl="pallas", interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_pallas_kernel_runs_per_head_shard_under_a_tp_mesh():
    """With the pool's kv heads sharded over ``tp`` the kernel runs under
    ``shard_map`` on each device's own heads (GSPMD cannot partition a
    Mosaic call): same numbers as the gather reference, and the output
    keeps the head sharding instead of coming back replicated."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from accelerate_tpu.mesh import build_mesh
    from accelerate_tpu.ops.attention import attention_context
    from accelerate_tpu.utils.dataclasses import MeshPlugin

    mesh = build_mesh(MeshPlugin(dp=1, tp=4), devices=jax.devices()[:4])
    rng = np.random.default_rng(5)
    dtype, _ = kv_storage_dtype("int8")
    bt, idx, (kpf, vpf), pools = _filled_pools(rng, dtype=dtype)
    q = jnp.asarray(rng.normal(size=(3, 1, 8, 16)).astype(np.float32))
    ref = paged_attention(q, *pools[:2], bt, idx, *pools[2:], impl="gather")
    heads = NamedSharding(mesh, PartitionSpec(None, None, "tp", None))
    scales = NamedSharding(mesh, PartitionSpec(None, None, "tp"))
    placed = [jax.device_put(x, heads) for x in (q, *pools[:2])]
    placed_scales = [jax.device_put(x, scales) for x in pools[2:]]

    @jax.jit
    def run(q, kp, vp, ks, vs):
        return paged_attention(q, kp, vp, bt, idx, ks, vs, impl="pallas",
                               interpret=True)

    with attention_context(mesh=mesh):
        out = run(*placed, *placed_scales)
    assert out.sharding.spec == heads.spec
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", ["int8", "fp8"])
def test_quantized_pool_within_tolerance(name):
    """Quantize-on-scatter + in-register dequantize: every impl agrees
    with the f32 reference within the documented per-dtype ceiling, and
    the quantized impls agree with each other much tighter (same stored
    bytes, same math)."""
    dtype, quantized = kv_storage_dtype(name)
    assert quantized
    rng = np.random.default_rng(2)
    bt, idx, (kpf, vpf), (kp, vp, ks, vs) = _filled_pools(rng, dtype=dtype)
    q = jnp.asarray(rng.normal(size=(3, 1, 8, 16)).astype(np.float32))
    ref = np.asarray(paged_attention(q, kpf, vpf, bt, idx, impl="gather"))
    outs = {}
    for impl in ("lax", "gather", "pallas"):
        out = np.asarray(paged_attention(
            q, kp, vp, bt, idx, k_scale_l=ks, v_scale_l=vs, impl=impl,
            interpret=True,
        ))
        assert np.abs(out - ref).max() < KV_ATOL[name], (
            f"{name}/{impl} exceeded the documented tolerance"
        )
        outs[impl] = out
    np.testing.assert_allclose(outs["lax"], outs["gather"], rtol=1e-4, atol=1e-4)


def test_quantized_write_respects_mask_and_drop():
    """Masked lanes and out-of-range positions drop payload AND scale
    writes — the scale array can never disagree with the pool about which
    rows are real."""
    nb, bs, n_kv, hd = 4, 4, 2, 8
    kp = jnp.zeros((nb, bs, n_kv, hd), jnp.int8)
    vp = jnp.zeros_like(kp)
    ks = jnp.ones((nb, bs, n_kv), jnp.float32)
    vs = jnp.ones_like(ks)
    bt = np.asarray([[1, 2]], np.int32)
    k = jnp.full((1, 2, n_kv, hd), 5.0)
    v = jnp.full((1, 2, n_kv, hd), 5.0)
    # lane 0 real at position 1, lane 1 masked; then a position past the
    # table span (must drop, not clamp)
    kp, vp, ks, vs = write_paged_kv(
        kp, vp, k, v, bt, np.asarray([[1, 2]], np.int32),
        write_mask=np.asarray([[True, False]]), k_scale_l=ks, v_scale_l=vs,
    )
    kp, vp, ks, vs = write_paged_kv(
        kp, vp, k, v, bt, np.asarray([[98, 99]], np.int32),
        write_mask=np.asarray([[True, True]]), k_scale_l=ks, v_scale_l=vs,
    )
    kp_h, ks_h = np.asarray(kp), np.asarray(ks)
    assert kp_h[1, 1].any() and ks_h[1, 1, 0] != 1.0   # the real write landed
    assert not kp_h[1, 2].any() and ks_h[1, 2, 0] == 1.0  # masked lane dropped
    assert not kp_h[2].any() and (ks_h[2] == 1.0).all()   # past-span dropped
    assert not kp_h[0].any() and not kp_h[3].any()


def test_quantize_round_trip_and_zero_rows():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(5, 7, 16)).astype(np.float32)) * 3.0
    for name in ("int8", "fp8"):
        dtype, _ = kv_storage_dtype(name)
        q, scale = quantize_kv_rows(x, dtype)
        back = np.asarray(dequantize_kv(q, scale))
        # per-row amax scaling: relative error bounded by the format's step
        rel = np.abs(back - np.asarray(x)).max() / np.abs(np.asarray(x)).max()
        assert rel < (0.005 if name == "int8" else 0.04)
    # all-zero rows keep scale 1 and dequantize to exactly 0
    z = jnp.zeros((2, 3, 8))
    q, scale = quantize_kv_rows(z, jnp.int8)
    assert (np.asarray(scale) == 1.0).all()
    assert not np.asarray(dequantize_kv(q, scale)).any()


def test_kv_storage_dtype_policy():
    assert kv_storage_dtype("bf16") == (jnp.bfloat16, False)
    assert kv_storage_dtype("f32") == (jnp.float32, False)
    assert kv_storage_dtype("int8") == (jnp.int8, True)
    assert kv_qmax(jnp.int8) == 127.0
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        kv_storage_dtype("int4")
    with pytest.raises(ValueError, match="not a quantized"):
        kv_qmax(jnp.float32)


def test_cached_attention_gqa_grouped_einsum_matches_repeat():
    """The grouped-head einsum equals the materialised ``jnp.repeat``
    formulation to f32 noise (the satellite fix: repeated KV is never
    built). Reference computed inline with explicit repeat."""
    import jax

    rng = np.random.default_rng(4)
    b, s, nh, n_kv, hd, mc = 2, 3, 8, 2, 16, 24
    q = jnp.asarray(rng.normal(size=(b, s, nh, hd)).astype(np.float32))
    kc = jnp.asarray(rng.normal(size=(b, mc, n_kv, hd)).astype(np.float32))
    vc = jnp.asarray(rng.normal(size=(b, mc, n_kv, hd)).astype(np.float32))
    idx = np.asarray([7, 15], np.int32)

    got = cached_attention(q, kc, vc, idx)

    kr = jnp.repeat(kc, nh // n_kv, axis=2)
    vr = jnp.repeat(vc, nh // n_kv, axis=2)
    q_pos = idx[:, None] + np.arange(s)[None, :]
    valid = np.arange(mc)[None, None, :] <= q_pos[:, :, None]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / np.sqrt(float(hd))
    scores = jnp.where(valid[:, None, :, :], scores, jnp.finfo(jnp.float32).min)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), vr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_paged_attention_unknown_impl_raises():
    q = jnp.zeros((1, 1, 2, 4))
    kp = jnp.zeros((3, 2, 1, 4))
    with pytest.raises(ValueError, match="unknown paged attention impl"):
        paged_attention(q, kp, kp, np.zeros((1, 2), np.int32),
                        np.zeros((1,), np.int32), impl="cuda")
