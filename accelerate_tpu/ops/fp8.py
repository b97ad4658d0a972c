"""fp8 matmul policy: scaled float8 projections with a hybrid-format VJP.

Reference: fp8 via TransformerEngine module swaps + ``fp8_autocast``
(``/root/reference/src/accelerate/utils/transformer_engine.py:26,119``) or
MS-AMP (``accelerator.py:2034``). TPU-native equivalent: the model zoo's
dense projections route through :func:`dense` (``ops/layers.py``), and under
:func:`fp8_autocast` that lowers to a per-tensor-scaled float8 matmul —
E4M3 activations/weights forward, E5M2 gradients backward (the
TransformerEngine "HYBRID" recipe) via a ``custom_vjp``.

The quantize→matmul is expressed as f8 casts + a bf16-accumulated dot, so
it runs on every backend; on fp8-capable TPU generations XLA lowers the f8
operand pair onto the native MXU path. The numerics (f8 rounding on every
operand, including the gradients) are recipe-faithful everywhere.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0
E5M2_MAX = 57344.0

_FP8_STATE = {"active": False, "format": "HYBRID"}


def fp8_is_active() -> bool:
    return _FP8_STATE["active"]


@contextlib.contextmanager
def fp8_autocast(enabled: bool = True, fp8_format: str = "HYBRID"):
    """Trace-time switch: :func:`dense` calls inside the context compile to
    fp8 matmuls (reference ``te.fp8_autocast`` shape)."""
    prev = dict(_FP8_STATE)
    _FP8_STATE.update(active=enabled, format=fp8_format.upper())
    try:
        yield
    finally:
        _FP8_STATE.update(prev)


def _quantize(x, dtype, max_val):
    """Per-tensor absmax scaling into the fp8 representable range."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = max_val / jnp.maximum(amax, 1e-12)
    q = (x.astype(jnp.float32) * scale).astype(dtype)
    return q, scale


def _bf16_dot(a8, b8):
    return jnp.matmul(
        a8.astype(jnp.bfloat16), b8.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )


@jax.custom_vjp
def fp8_matmul(x, w):
    """``x [M, K] @ w [K, N]`` with E4M3 forward operands (2-D; the
    :func:`dense` wrapper flattens leading dims)."""
    x8, sx = _quantize(x, jnp.float8_e4m3fn, E4M3_MAX)
    w8, sw = _quantize(w, jnp.float8_e4m3fn, E4M3_MAX)
    return (_bf16_dot(x8, w8) / (sx * sw)).astype(x.dtype)


def _fp8_matmul_fwd(x, w):
    x8, sx = _quantize(x, jnp.float8_e4m3fn, E4M3_MAX)
    w8, sw = _quantize(w, jnp.float8_e4m3fn, E4M3_MAX)
    out = (_bf16_dot(x8, w8) / (sx * sw)).astype(x.dtype)
    # f8 residuals: the activation-memory saving is part of the recipe.
    # The zero-size markers carry (a) the primal dtypes — bwd outputs must
    # match them exactly — and (b) the GRAD dtype, resolved from the recipe
    # HERE at forward-trace time: jax traces the bwd rule later, after
    # fp8_autocast has exited, so _FP8_STATE must not be read there.
    grad_dtype = (
        jnp.float8_e5m2 if _FP8_STATE["format"] == "HYBRID" else jnp.float8_e4m3fn
    )
    markers = (
        jnp.zeros((0,), x.dtype), jnp.zeros((0,), w.dtype), jnp.zeros((0,), grad_dtype)
    )
    return out, (x8, sx, w8, sw, markers)


def _fp8_matmul_bwd(res, g):
    x8, sx, w8, sw, (x_marker, w_marker, g_marker) = res
    grad_max = E5M2_MAX if g_marker.dtype == jnp.float8_e5m2 else E4M3_MAX
    g8, sg = _quantize(g, g_marker.dtype, grad_max)
    dx = (_bf16_dot(g8, w8.T) / (sg * sw)).astype(x_marker.dtype)   # [M, K]
    dw = (_bf16_dot(x8.T, g8) / (sx * sg)).astype(w_marker.dtype)   # [K, N]
    return dx, dw


fp8_matmul.defvjp(_fp8_matmul_fwd, _fp8_matmul_bwd)


def dense(x, w):
    """Dense projection used by the model zoo: plain ``x @ w`` normally,
    the scaled-fp8 matmul inside :func:`fp8_autocast`, and the quantized
    fast paths when ``w`` is a quantized leaf (the streaming offload
    executor feeds segment programs int8/4-bit weights directly —
    ``big_modeling.py`` ``_call_streaming``). ``x [..., K]``, ``w [K, N]``."""
    from ..utils.quantization import (
        Q4DecodedTensor, Q4DecodedTransposed, Q4Transposed, Q4Tensor, QTensor,
        int8_matmul, q4_decoded_matmul, q4_decoded_matmul_t, q4_matmul, q4_matmul_t,
    )

    if isinstance(w, QTensor):
        return int8_matmul(x, w)
    if isinstance(w, Q4Tensor):
        return q4_matmul(x, w)
    if isinstance(w, Q4Transposed):
        return q4_matmul_t(x, w.inner)
    if isinstance(w, Q4DecodedTensor):
        return q4_decoded_matmul(x, w)
    if isinstance(w, Q4DecodedTransposed):
        return q4_decoded_matmul_t(x, w.inner)
    if not _FP8_STATE["active"]:
        return x @ w
    lead = x.shape[:-1]
    out = fp8_matmul(x.reshape(-1, x.shape[-1]), w)
    return out.reshape(*lead, w.shape[-1])


# ---------------------------------------------------------------------------
# Quantized KV-cache storage (serving engine's kv_dtype policy)
#
# The paged block pools can store K/V in int8 or float8_e4m3fn with one
# f32 amax scale per written row (per token position × kv head): decode is
# memory-bandwidth-bound, so halving/quartering the pool's bytes directly
# halves the bytes each decode step moves AND doubles how many blocks fit a
# fixed HBM budget. Scales are quantized-at-write (each scatter quantizes
# only its own rows), so writes are idempotent — no read-modify-write
# requantization of previously written tokens — and a block's payload+scale
# rows travel atomically through copy-on-write, swap-out/in, and radix
# adoption. Dequantize happens in-register inside the fused paged-attention
# kernel (``ops/paged_attention.py``), never as a materialised f32 pool.
# ---------------------------------------------------------------------------

INT8_MAX = 127.0

#: engine ``kv_dtype`` policy names -> jnp storage dtype factory. ``auto``
#: (params dtype) is resolved by the engine, not here.
KV_STORAGE_DTYPES = ("bf16", "f32", "int8", "fp8")
KV_QUANTIZED_DTYPES = ("int8", "fp8")


def kv_storage_dtype(name: str):
    """Resolve a ``kv_dtype`` policy name to ``(jnp dtype, quantized)``.
    Raises on unknown names."""
    if name == "bf16":
        return jnp.bfloat16, False
    if name == "f32":
        return jnp.float32, False
    if name == "int8":
        return jnp.int8, True
    if name == "fp8":
        return jnp.float8_e4m3fn, True
    raise ValueError(
        f"unknown kv_dtype {name!r}: expected one of "
        f"{('auto',) + KV_STORAGE_DTYPES}"
    )


def kv_qmax(dtype) -> float:
    """Largest representable magnitude the amax scale maps onto."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.int8:
        return INT8_MAX
    if dtype == jnp.dtype(jnp.float8_e4m3fn):
        return E4M3_MAX
    raise ValueError(f"{dtype} is not a quantized KV storage dtype")


def quantize_kv_rows(x, dtype):
    """Per-row amax quantization of a K/V chunk ``[..., hd]`` into
    ``dtype``: returns ``(q, scale)`` with ``scale = amax/qmax`` over the
    last axis (shape ``x.shape[:-1]``, f32) and ``q ≈ x / scale``. An
    all-zero row keeps ``scale = 1`` so dequantization is exact for it."""
    qmax = kv_qmax(dtype)
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    scaled = x32 / scale[..., None]
    if jnp.dtype(dtype) == jnp.int8:
        q = jnp.clip(jnp.round(scaled), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    else:
        q = scaled.astype(dtype)  # f8 cast rounds in hardware
    return q, scale


def dequantize_kv(q, scale):
    """Inverse of :func:`quantize_kv_rows`: ``q [..., hd]`` × ``scale
    [...]`` → f32. The fused kernel applies this per gathered block, in
    registers."""
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


@dataclass
class FP8RecipeKwargs:
    """(Reference ``FP8RecipeKwargs`` ``dataclasses.py:283``.) ``margin`` /
    ``amax_history_len`` belong to TE's delayed-scaling bookkeeping — the
    per-tensor just-in-time scaling here needs neither; accepted for
    config parity. ``fp8_format`` selects E4M3-everywhere or HYBRID
    (E5M2 grads)."""

    margin: int = 0
    interval: int = 1
    fp8_format: str = "HYBRID"
    amax_history_len: int = 1024
    amax_compute_algo: str = "most_recent"
    override_linear_precision: tuple = (False, False, False)
    backend: str = "XLA"
