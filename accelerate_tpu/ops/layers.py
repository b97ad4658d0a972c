"""Core transformer ops, written for the MXU/VPU.

No reference analog — the reference delegates all math to torch; these are
the building blocks its model zoo gets from ``transformers``. Design notes:
matmuls stay batched and bf16-friendly (MXU), elementwise chains are left
for XLA to fuse (VPU), and everything is static-shape.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .fp8 import dense, quantize_kv_rows


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm in fp32 accumulation (stability under bf16 compute)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x, g, b, eps):
    """True LayerNorm (GPT-2 centers the mean, unlike llama's RMSNorm)."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32) + b.astype(jnp.float32)
    return out.astype(x.dtype)


def mesh_constrain(x, spec):
    """Sharding constraint that is a no-op outside a mesh context where the
    axes don't exist (keeps the model runnable on a bare single device)."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def residual_spec() -> P:
    """Spec for norm/residual-region activations ``[b, s, h]``: batch over
    dp/fsdp, sequence over cp — and ALSO over tp under Megatron-style
    sequence parallelism (``MegatronLMPlugin(sequence_parallelism=True)``
    with tp>1; reference forwards the flag to Megatron at
    ``utils/dataclasses.py:1916-1919,2112``, where LayerNorm/dropout
    activations shard along sequence within the TP group). Between the
    matmul regions (which are head/ff-sharded on tp, full-sequence) GSPMD
    inserts the all-gather in / reduce-scatter out that Megatron's fused
    kernels code by hand, and per-device activation bytes in the norm
    regions shrink by the tp extent."""
    from .attention import get_attention_context

    if get_attention_context().megatron_sp:
        return P(("dp", "fsdp"), ("cp", "tp"), None)
    return P(("dp", "fsdp"), "cp", None)


def to_nhwc(pixel_values, in_channels: int):
    """Normalise image input to NHWC: append a channel dim to grayscale
    ``[b, h, w]`` and accept torch's NCHW layout (shared by every image
    model in the zoo)."""
    x = jnp.asarray(pixel_values)
    if x.ndim == 3:
        x = x[..., None]
    if x.shape[-1] != in_channels and x.shape[1] == in_channels:
        x = jnp.moveaxis(x, 1, -1)
    return x


@jax.named_scope("head")
def logit_rows(x: jax.Array, logit_positions) -> jax.Array:
    """The hidden rows a paged step's caller reads logits of: ``x [b, s, h]``
    at ``logit_positions [b, r]`` (each row's own positions in ``0..s-1``) is
    ``[b, r, h]``, taken before the final norm and the head, which are
    row-wise: the step's ``logits`` come back ``[b, r, vocab]``. ``None`` is
    every position, and the program without the argument."""
    if logit_positions is None:
        return x
    rows = jnp.asarray(logit_positions, jnp.int32)
    return jnp.take_along_axis(x, rows[:, :, None], axis=1)


def rope_frequencies(head_dim: int, max_seq_len: int, theta: float = 10000.0):
    """Precomputed RoPE cos/sin tables [max_seq, head_dim//2]."""
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    t = np.arange(max_seq_len)
    freqs = np.outer(t, inv_freq)
    return jnp.asarray(np.cos(freqs), dtype=jnp.float32), jnp.asarray(
        np.sin(freqs), dtype=jnp.float32
    )


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, positions: jax.Array) -> jax.Array:
    """Rotate [batch, seq, heads, head_dim] by position-indexed tables.

    The rotation runs in ``x.dtype``: under bf16 compute the q/k operands
    are bf16 on both sides of the rotation anyway (the attention kernel
    consumes bf16), so an f32 round-trip here would only double the HBM
    traffic of one of the hottest elementwise chains — measured +9% train
    step throughput on v5e at seq 1024. The fp32-precision tables are cast
    once per (tiny) gathered slice; fp32 models (CPU tests) still rotate
    in full precision."""
    dtype = x.dtype
    cos = cos[positions][:, :, None, :].astype(dtype)  # [b, s, 1, hd/2]
    sin = sin[positions][:, :, None, :].astype(dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def rotate_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """:func:`apply_rope` without a table of ``max_position_embeddings``
    rows: ``x [b, s, heads, hd]`` rotated by ``positions [b, s]``,
    rotate-half over the whole head, the angles in float32, the rotation in
    ``x``'s dtype."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq, jnp.float32)
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def dot_product_attention(
    q: jax.Array,  # [b, s, n_heads, hd]
    k: jax.Array,  # [b, s_kv, n_kv_heads, hd]
    v: jax.Array,  # [b, s_kv, n_kv_heads, hd]
    mask: jax.Array | None = None,  # broadcastable to [b, n_heads, s, s_kv]
    scale: float | None = None,
) -> jax.Array:
    """Reference (non-Pallas) attention: einsum QK^T → softmax(fp32) → PV.
    GQA handled by repeating KV heads. The Pallas flash kernel in
    ``ops/flash_attention.py`` replaces this on the hot path."""
    b, s, nh, hd = q.shape
    n_kv = k.shape[2]
    if n_kv != nh:
        rep = nh // n_kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def causal_mask(q_len: int, kv_len: int, dtype=jnp.bool_) -> jax.Array:
    return jnp.tril(jnp.ones((q_len, kv_len), dtype=dtype), k=kv_len - q_len)


def causal_attention(q, k, v, segment_mask=None, window: int = 0):
    """Causal self-attention; ``segment_mask`` [b, s] marks valid tokens;
    ``window`` above 0 keeps, for the query at ``p``, the keys ``p - window <
    j <= p`` only."""
    s, skv = q.shape[1], k.shape[1]
    mask = causal_mask(s, skv)[None, None, :, :]
    if window:
        # key j is behind the window of query i (at key position i + skv - s)
        # where j <= i + skv - s - window
        mask = mask & ~jnp.tril(jnp.ones((s, skv), jnp.bool_), k=skv - s - window)[None, None]
    if segment_mask is not None:
        mask = mask & segment_mask[:, None, None, :].astype(bool)
    return dot_product_attention(q, k, v, mask=mask)


def cross_entropy_loss(
    logits: jax.Array,  # [b, s, vocab]
    labels: jax.Array,  # [b, s] int; -100 = ignore
    ignore_index: int = -100,
) -> jax.Array:
    """Token-level CE with ignore mask, fp32 log-softmax."""
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)


def shift_labels(labels: jax.Array, ignore_index: int = -100) -> jax.Array:
    """Causal next-token targets WITHOUT slicing the sequence: position t's
    target is token t+1, and the final position is masked with
    ``ignore_index``. Keeping the sequence length unchanged (vs the
    ``logits[:, :-1] / labels[:, 1:]`` formulation) preserves nice
    power-of-two token counts for :func:`fused_cross_entropy` chunking."""
    b, s = labels.shape
    pad = jnp.full((b, 1), ignore_index, dtype=labels.dtype)
    return jnp.concatenate([labels[:, 1:], pad], axis=1)


#: float32 logits one device may hold for one chunk of the sweep below
_CE_CHUNK_LOGIT_BYTES = 256 << 20


def _constrain(x, sharding):
    """Sharding constraint; ``None`` (no mesh, or nothing to pin) is a no-op."""
    return x if sharding is None else jax.lax.with_sharding_constraint(x, sharding)


def _ce_layout(vocab: int, head_shape: tuple):
    """How the chunk sweep lies on the mesh this trace runs under:
    ``(devices, rows, logits, head, out)``. The vocabulary is spread over
    the mesh's ``fsdp`` and ``tp`` axes (the links a weight's shards
    already cross) and a chunk's rows are gathered over them, staying
    apart over ``dp``: each device multiplies ``[rows, h]`` by the
    ``[h, vocab / n]`` it holds, and neither the head nor its gradient
    crosses a link inside the loop. ``devices`` is what one chunk's logits
    are spread over; the four specs (a chunk of ``x`` in the loop, its
    logits, ``head`` and its gradient, a chunk of ``dx`` on leaving) are
    ``NamedSharding``s on that mesh - the program enters no mesh context, so
    a bare ``PartitionSpec`` would pin nothing - and ``None`` outside a
    mesh, or where ``vocab`` does not divide or ``head`` has no dimension
    of that length."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .attention import get_attention_context

    ctx = get_attention_context()
    sizes = dict(ctx.mesh.shape) if ctx.mesh is not None else {}
    over = tuple(ax for ax in ("fsdp", ctx.head_axis) if sizes.get(ax, 1) > 1)
    batch = tuple(ax for ax in ctx.batch_axes if sizes.get(ax, 1) > 1)
    apart = tuple(ax for ax in batch if ax not in over)
    shards = math.prod(sizes[ax] for ax in over)
    n = shards * math.prod(sizes[ax] for ax in apart)
    if not over or vocab % shards or len(head_shape) != 2 or vocab not in head_shape:
        return n, None, None, None, None
    head_spec = P(None, over) if head_shape[1] == vocab else P(over, None)
    specs = (P(apart or None, None, None), P(apart or None, None, over), head_spec,
             P(batch or None, None, None))
    return (n, *(NamedSharding(ctx.mesh, spec) for spec in specs))


def _ce_chunks(b: int, s: int, vocab: int, devices: int, chunk_tokens: int) -> int:
    """Chunks the sequence is cut into: the fewest that divide ``s`` and
    leave one device at most ``chunk_tokens`` rows of a chunk (fewer where
    their float32 logits would pass ``_CE_CHUNK_LOGIT_BYTES``)."""
    rows = max(1, min(chunk_tokens, _CE_CHUNK_LOGIT_BYTES // (4 * vocab))) * devices
    return min((c for c in range(1, s + 1) if s % c == 0 and b * (s // c) <= rows), default=s)


def _ce_split(x, labels, chunks: int, ignore_index):
    """``x`` and ``labels`` chunk-major, and one over the count of valid labels."""
    b, s = labels.shape
    xc = jnp.moveaxis(x.reshape(b, chunks, s // chunks, x.shape[-1]), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, chunks, s // chunks), 1, 0)
    return xc, lc, 1.0 / jnp.maximum((labels != ignore_index).sum(), 1).astype(jnp.float32)


def _ce_chunk_nll(logits, l_i, ignore_index):
    """``(summed nll, float32 log-softmax, valid, safe labels)`` of a chunk."""
    logits = logits.astype(jnp.float32)
    valid = l_i != ignore_index
    safe = jnp.where(valid, l_i, 0)
    logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    gold = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return -(gold * valid).sum(), logp, valid, safe


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _chunked_cross_entropy(dense_fn, ignore_index, chunks, layout, x, head, labels):
    rows, logits_spec, head_spec, _ = layout
    xc, lc, inv = _ce_split(x, labels, chunks, ignore_index)
    head = _constrain(head, head_spec)

    def body(nll, xs):
        x_i, l_i = xs
        logits = _constrain(dense_fn(_constrain(x_i, rows), head), logits_spec)
        return nll + _ce_chunk_nll(logits, l_i, ignore_index)[0], None

    nll, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
    return nll * inv


def _chunked_cross_entropy_fwd(dense_fn, ignore_index, chunks, layout, x, head, labels):
    """One sweep for value AND gradients: a chunk's ``dlogits`` are made
    where its logits already are and pulled straight back through
    ``dense_fn``, so the backward rule only scales what is kept. The sums
    stay unnormalised (``dlogits`` in [-1, 1], whatever the product's
    dtype) until the backward rule knows the cotangent."""
    rows, logits_spec, head_spec, out = layout
    xc, lc, inv = _ce_split(x, labels, chunks, ignore_index)
    head = _constrain(head, head_spec)

    def body(carry, xs):
        nll, dw = carry
        x_i, l_i = xs
        logits, pull = jax.vjp(dense_fn, _constrain(x_i, rows), head)
        logits = _constrain(logits, logits_spec)
        d_nll, logp, valid, safe = _ce_chunk_nll(logits, l_i, ignore_index)
        onehot = jax.lax.broadcasted_iota(jnp.int32, logp.shape, logp.ndim - 1) == safe[..., None]
        dlogits = jnp.where(valid[..., None], jnp.exp(logp) - onehot, 0.0)
        dx_i, dw_i = pull(_constrain(dlogits.astype(logits.dtype), logits_spec))
        dw = _constrain(dw + dw_i.astype(jnp.float32), head_spec)
        return (nll + d_nll, dw), _constrain(dx_i, out)

    dw0 = _constrain(jnp.zeros(head.shape, jnp.float32), head_spec)
    (nll, dw), dxc = jax.lax.scan(body, (jnp.zeros((), jnp.float32), dw0), (xc, lc))
    dx = jnp.moveaxis(dxc, 0, 1).reshape(x.shape)
    return nll * inv, (dx, dw, inv, jnp.zeros((0,), head.dtype))


def _chunked_cross_entropy_bwd(dense_fn, ignore_index, chunks, layout, kept, g):
    dx, dw, inv, head_like = kept
    scale = g.astype(jnp.float32) * inv
    return (
        (dx.astype(jnp.float32) * scale).astype(dx.dtype),
        (dw * scale).astype(head_like.dtype),
        None,
    )


_chunked_cross_entropy.defvjp(_chunked_cross_entropy_fwd, _chunked_cross_entropy_bwd)


def fused_cross_entropy(
    x: jax.Array,  # [b, s, h] final hidden states (pre-head)
    head: jax.Array,  # what ``dense_fn`` takes beside ``x``: [h, vocab], or a tied [vocab, h]
    labels: jax.Array,  # [b, s] int; -100 = ignore (already shifted)
    ignore_index: int = -100,
    chunk_tokens: int = 1024,
    dense_fn=None,
) -> jax.Array:
    """Token CE computed from pre-head hidden states without ever holding
    the full ``[b, s, vocab]`` logits: one ``lax.scan`` over sequence chunks
    runs the head product (``dense_fn``, default ``jnp.matmul``) and the
    fp32 log-softmax of a chunk.

    Under differentiation the same sweep also makes that chunk's gradients
    (a ``jax.custom_vjp``): ``dlogits = (softmax - onehot) * valid`` where
    the logits already are, pulled back through ``dense_fn`` for the chunk's
    ``dx`` and for ``dW``, which is summed over chunks in float32. The
    backward rule multiplies the kept ``dx`` (the size of ``x``) and ``dW``
    (the size of ``head``) by the cotangent over the count of valid labels.
    Three products a chunk, nothing recomputed; reverse mode only.

    ``chunk_tokens`` is the most rows of one chunk that ONE device gets: a
    chunk is the longest cut of the sequence (a divisor of ``s``) that holds
    at most ``chunk_tokens`` x the devices of the mesh the trace runs under
    (``dp``, ``fsdp``, ``tp``) rows of the global batch, fewer where one
    device's float32 logits of it would pass 256 MiB. Under a mesh the
    vocabulary is spread over ``fsdp`` and ``tp`` (the head is resharded
    once, before the loop, where it is stored otherwise), a chunk's rows
    are gathered over those axes, and ``dW`` is born on the head's
    vocabulary shards: no collective inside the loop touches an array of
    the head's size. A batch no larger than one chunk takes the plain
    single-shot loss.

    Numerically identical to ``cross_entropy_loss(dense_fn(x, head),
    labels)`` (same fp32 log-softmax, same masked mean).
    """
    if dense_fn is None:
        dense_fn = jnp.matmul
    b, s, h = x.shape
    vocab = jax.eval_shape(dense_fn, jax.ShapeDtypeStruct((b, 1, h), x.dtype), head).shape[-1]
    devices, *layout = _ce_layout(vocab, head.shape)
    chunks = _ce_chunks(b, s, vocab, devices, chunk_tokens)
    if chunks == 1:
        return cross_entropy_loss(dense_fn(x, head), labels, ignore_index)
    return _chunked_cross_entropy(dense_fn, ignore_index, chunks, tuple(layout), x, head, labels)


def write_kv_cache(k_cache_l, v_cache_l, k, v, idx, pin_replicated: bool = False):
    """Append a decode chunk's K/V (``[b, s, n_kv, hd]``, ``s >= 1``) at
    each row's own cache positions ``idx[b] .. idx[b]+s-1`` — the single
    owner of the decode scatter every causal family shares (``s == 1`` is
    the plain per-token decode; ``s > 1`` is the speculative-verify chunk).
    ``pin_replicated`` constrains the scatter operands replicated over the
    AUTO mesh axes: under a shard_map manual over ``pp``, GSPMD's scatter
    partitioner check-fails when it tries to tp-shard the cache update,
    and decode tensors are tiny."""
    if pin_replicated:
        from jax.sharding import PartitionSpec

        def _pin(t):
            try:
                return jax.lax.with_sharding_constraint(t, PartitionSpec())
            except Exception:  # no mesh context (bare single device)
                return t

        k, v = _pin(k), _pin(v)
        k_cache_l, v_cache_l = _pin(k_cache_l), _pin(v_cache_l)
    b, s = k.shape[0], k.shape[1]
    rows = jnp.arange(b)[:, None]
    idx = jnp.asarray(idx, jnp.int32).reshape(b)
    pos = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # [b, s]
    # mode="drop": a chunk that overshoots the cache end (speculative verify
    # near an exact-fit budget with a clamped cache) must NOT clamp-scatter —
    # duplicate clamped indices would let an overshoot token overwrite the
    # final legitimate cache slot. Dropped writes belong to tokens past the
    # budget, which are never emitted.
    k_cache_l = k_cache_l.at[rows, pos].set(k, mode="drop")
    v_cache_l = v_cache_l.at[rows, pos].set(v, mode="drop")
    return k_cache_l, v_cache_l


def rope_cached_attention_block(
    layer, x, k_cache_l, v_cache_l, cos, sin, idx,
    n_heads: int, n_kv_heads: int, head_dim: int, eps: float,
    pp_manual: bool = False,
):
    """The decode-step attention sub-block shared by the llama-style
    families (llama, mixtral): RMSNorm → q/k/v projections → RoPE at each
    row's cache position → cache append → cached attention → output
    projection residual. gpt2 keeps its own (LayerNorm, fused QKV, learned
    positions). Returns ``(x + attn_out, kc_l, vc_l)``; ``pp_manual``: see
    :func:`write_kv_cache`."""
    b, s, _ = x.shape
    positions = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # [b, s]
    y = rms_norm(x, layer["attn_norm"], eps)
    q = apply_rope(
        dense(y, layer["wq"]).reshape(b, s, n_heads, head_dim), cos, sin, positions
    )
    k = apply_rope(
        dense(y, layer["wk"]).reshape(b, s, n_kv_heads, head_dim), cos, sin, positions
    )
    v = dense(y, layer["wv"]).reshape(b, s, n_kv_heads, head_dim)
    if pp_manual:
        from jax.sharding import PartitionSpec

        try:
            q = jax.lax.with_sharding_constraint(q, PartitionSpec())
        except Exception:  # no mesh context (bare single device)
            pass
    k_cache_l, v_cache_l = write_kv_cache(
        k_cache_l, v_cache_l, k, v, idx, pin_replicated=pp_manual
    )
    attn = cached_attention(q, k_cache_l, v_cache_l, idx)
    x = x + dense(attn.reshape(b, s, n_heads * head_dim), layer["wo"])
    return x, k_cache_l, v_cache_l


def last_visible(q_pos, block_len: int = 1):
    """The last cache position a query at ``q_pos`` attends. ``block_len``
    1 is the causal rule (itself); a block-diffusion model's sequence is cut
    into blocks of ``block_len`` positions, causal from block to block and
    bidirectional inside one: the end of the query's own block. Static in
    ``block_len``, and at 1 no operation is traced."""
    if block_len == 1:
        return q_pos
    if block_len & (block_len - 1) == 0:
        # a power of two: the block's last position sets the low bits (an
        # ``or`` where Mosaic would else divide a vector of integers)
        return q_pos | (block_len - 1)
    return (q_pos // block_len + 1) * block_len - 1


def cached_attention(q, k_cache, v_cache, idx, block_len: int = 1, window: int = 0):
    """Chunked attention against a KV cache with per-row valid prefix.

    q: ``[b, s, nh, hd]`` (``s == 1``: the token being decoded; ``s > 1``:
    a speculative-verify chunk); caches ``[b, max_cache, n_kv, hd]``
    already containing this chunk's K/V at ``idx[b] .. idx[b]+s-1``. Query
    position ``j`` of row ``b`` attends cache positions ``<= idx[b]+j`` —
    the per-row prefix plus the causal triangle within the chunk (with
    ``block_len`` > 1, every position up to the end of the query's own
    block of that many positions: :func:`last_visible`; with ``window``
    above 0, only the ``window`` positions that end at the query's own). GQA
    handled by repeating KV heads. f32 scores/softmax. Shared by every
    model family's decode step (no per-model drift in the masking or
    dtype policy).
    """
    b, s, nh, hd = q.shape
    n_kv = k_cache.shape[2]
    # GQA by grouped-head einsum — q heads reshaped to [n_kv, rep] groups
    # against the un-expanded KV (head h reads kv head h // rep, matching
    # the old jnp.repeat layout) so repeated KV is never materialised:
    # the einsum batches over the kv-head axis instead of moving
    # rep × the cache bytes through the MXU's operand path
    rep = nh // n_kv
    qg = q.astype(jnp.float32).reshape(b, s, n_kv, rep, hd)
    max_cache = k_cache.shape[1]
    q_pos = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # [b, s]
    valid = (  # [b, s, max]
        jnp.arange(max_cache)[None, None, :] <= last_visible(q_pos, block_len)[:, :, None]
    )
    if window:
        valid = valid & (jnp.arange(max_cache)[None, None, :] > q_pos[:, :, None] - window)
    scores = jnp.einsum(
        "bqnrd,bknd->bnrqk", qg, k_cache.astype(jnp.float32)
    ) / np.sqrt(float(hd))
    scores = jnp.where(
        valid[:, None, None, :, :], scores, jnp.finfo(jnp.float32).min
    )
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnrqk,bknd->bqnrd", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, s, nh, hd).astype(q.dtype)


def yarn_frequencies(dim: int, theta: float, factor: float, original: int,
                     beta_fast: float = 32.0, beta_slow: float = 1.0) -> np.ndarray:
    """The ``dim // 2`` rotary frequencies under YaRN scaling (Peng et al.,
    2023, as DeepSeek-V3 publishes it): lane pair ``i`` turns at ``f_i =
    theta^(-2i/dim)``; the pairs that turn more than ``beta_fast`` times over
    the ``original`` context keep their frequency, those that turn fewer than
    ``beta_slow`` times are stretched ``factor`` x, and a linear ramp over the
    pair index joins the two. Float64 on the host: 32 numbers, no table of
    positions (the angles are made from absolute positions in float32 where
    they are used)."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)

    def pair_that_turns(n):
        return dim * np.log(original / (2 * np.pi * n)) / (2 * np.log(theta))

    lo = max(int(np.floor(pair_that_turns(beta_fast))), 0)
    hi = min(int(np.ceil(pair_that_turns(beta_slow))), dim - 1)
    ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term, ``0.1 * mscale * ln(factor) + 1``
    (1 where nothing is stretched)."""
    return 0.1 * mscale * float(np.log(factor)) + 1.0 if factor > 1 else 1.0


def cached_latent_attention(q, cache, idx, rank: int, scale: float):
    """:func:`cached_attention` for a latent cache: ``q [b, s, nh, width]``
    (each head's query already carried into the cache's coordinates: the
    absorbed form) against ``cache [b, max_cache, width]``, one vector a
    position for every head, whose first ``rank`` entries are also the
    values. Scores ``q . cache * scale``, the same valid-prefix and causal
    mask, float32 softmax; returns ``[b, s, nh, rank]``."""
    b, s = q.shape[:2]
    c = cache.astype(jnp.float32)
    q_pos = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    valid = jnp.arange(c.shape[1])[None, None, :] <= q_pos[:, :, None]      # [b, s, max]
    scores = jnp.einsum("bqhd,bkd->bhqk", q.astype(jnp.float32), c) * scale
    scores = jnp.where(valid[:, None], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkd->bqhd", probs, c[..., :rank]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Block-paged KV cache (serving engine)
#
# The serving engine's cache is ONE stacked pool of fixed-size blocks per
# K and per V, stored lane-folded: ``[layers, num_blocks, block_size,
# n_kv*hd]`` (head ``n`` is the lane slice ``[n*hd, (n+1)*hd)`` — the view
# the Pallas kernel reads is the view that is stored), plus a per-slot
# **block table** mapping each slot's logical block index to a pool block —
# the PagedAttention layout (vLLM, SOSP '23). The pool never moves: the
# step programs take it donated, carry it through the layer loop, write
# new rows at ``(layer, block, offset)`` and read blocks at ``(layer,
# block)``; nothing of a layer's slab size is ever sliced out or written
# back. Block 0 is the reserved *null block*: free slots and unfilled
# table entries point at it, so the static ``[num_slots, 1]`` decode step
# needs no dynamic shapes, and garbage written/read there is always masked
# out by the per-slot valid prefix.
# ---------------------------------------------------------------------------


def _paged_row_scatter(pool, layer, block_tables, positions, write_mask):
    """``put(pool, rows)`` for one step's rows ``[b, s, ...]`` of layer
    ``layer`` at ``positions [b, s]`` through the block tables, under
    :func:`write_paged_kv`'s drop rules; the same indices serve every array
    that shares the pool's ``[layers, num_blocks, block_size, ...]`` shape."""
    nb, bs = pool.shape[1], pool.shape[2]
    b, s = positions.shape
    positions = jnp.asarray(positions, jnp.int32)
    blk = jnp.take_along_axis(
        jnp.asarray(block_tables, jnp.int32), positions // bs, axis=1,
        mode="fill", fill_value=nb,
    )  # [b, s]; fill → a block id past the pool, which the scatter drops
    if write_mask is not None:
        blk = jnp.where(write_mask, blk, nb)  # out of range → dropped
    blk = blk.reshape(b * s)
    off = (positions % bs).reshape(b * s)
    layer = jnp.asarray(layer, jnp.int32)

    def put(pool, rows):
        return pool.at[layer, blk, off].set(
            rows.reshape(b * s, pool.shape[-1]), mode="drop"
        )

    return put


def write_paged_latent(pool, layer, rows, block_tables, positions, write_mask=None,
                       scale=None):
    """A latent layer's twin of :func:`write_paged_kv`: ``rows [b, s, width]``
    (the compressed vector and the rotated key of each token, one for all
    heads) into layer ``layer`` of the ONE pool ``[layers, num_blocks,
    block_size, width]``, same indices, same drop rules. With ``scale``
    (``[layers, num_blocks, block_size, 1]`` float32: a quantized pool) each
    row is amax-quantized into the pool's type and its one scale scattered
    beside it. Returns ``(pool,)`` or ``(pool, scale)``."""
    put = _paged_row_scatter(pool, layer, block_tables, positions, write_mask)
    if scale is None:
        return (put(pool, rows.astype(pool.dtype)),)
    rows, row_scale = quantize_kv_rows(rows, pool.dtype)        # [b, s, width] + [b, s]
    return put(pool, rows), put(scale, row_scale[..., None])


def write_paged_kv(
    k_pool, v_pool, layer, k, v, block_tables, positions, write_mask=None,
    k_scale=None, v_scale=None,
):
    """Scatter a chunk's K/V (``[b, s, n_kv, hd]``) into layer ``layer`` of
    the stacked block-paged pools ``[layers, num_blocks, block_size,
    n_kv*hd]`` at absolute token ``positions`` ``[b, s]`` through each
    row's ``block_tables`` row ``[b, max_blocks]``: row ``(b, s)`` lands at
    ``(layer, block_tables[b, pos // bs], pos % bs)``. ``layer`` may be a
    traced scalar (the layer loop's index); the whole pool is the scatter's
    operand, so a loop that carries the pool updates it in place.

    ``write_mask`` ``[b, s]`` (optional) marks real tokens; masked lanes
    (the padded tail of a final prefill chunk) get an out-of-range block id
    and are dropped — the pool never sees them. Positions past the table
    span (post-budget burst lane-steps at a slot's maximum) gather an
    out-of-range block id via ``mode="fill"`` and are likewise dropped —
    never clamped into the slot's own final block, and never carried into
    another layer's rows (the index is ``(layer, block, offset)``, not a
    flattened row number). Distinct live slots own disjoint blocks, so the
    scatter has no cross-slot collisions; only the null block (0) absorbs
    free-slot writes, and it is never attended.

    **Quantize-on-scatter** (``k_scale``/``v_scale`` given, shape
    ``[layers, num_blocks, bs, n_kv]`` f32): K/V are amax-quantized per
    written row into the pool's storage dtype (int8/fp8 — ``ops/fp8.py``)
    and each row's scale is scattered through the *same* indices, so
    payload and scale stay atomic under the identical drop/masking rules.
    Returns 4 arrays in that case."""
    put = _paged_row_scatter(k_pool, layer, block_tables, positions, write_mask)
    if k_scale is not None:
        k, k_sc = quantize_kv_rows(k, k_pool.dtype)   # [b,s,n_kv,hd] + [b,s,n_kv]
        v, v_sc = quantize_kv_rows(v, v_pool.dtype)
        return put(k_pool, k), put(v_pool, v), put(k_scale, k_sc), put(v_scale, v_sc)
    # e.g. bf16 storage under f32 compute
    return put(k_pool, k.astype(k_pool.dtype)), put(v_pool, v.astype(v_pool.dtype))


def paged_step_frame(rows, cache_positions, write_mask):
    """What every model's step against the paged cache (``*_apply(...,
    paged_kv=, block_tables=, cache_positions=, paged_write_mask=[,
    state_slots=], logit_positions=)``) starts from: ``rows [b, s, ...]`` (the
    step's tokens, or their hidden rows) begin at ``cache_positions [b]``, and
    ``(idx [b], positions [b, s], valid [b, s])`` are each row's first cache
    position, every token's absolute position, and the lanes ``write_mask``
    leaves on (all of them without one). The contract the step keeps: a
    layer's rows are written before its queries attend
    (:func:`paged_write_attend`); a lane that is off leaves the cache as it
    was; the cache dict comes back whole as ``paged_kv``; and the ``logits``
    are those of ``logit_positions`` alone where the caller names them
    (:func:`logit_rows`)."""
    b, s = rows.shape[:2]
    idx = jnp.asarray(cache_positions, jnp.int32).reshape(b)
    positions = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    valid = jnp.ones((b, s), bool) if write_mask is None else jnp.broadcast_to(
        jnp.asarray(write_mask, bool), (b, s))
    return idx, positions, valid


def slot_state_frame(valid, state_slots, num_slots: int):
    """The per-slot-state half of the frame: ``(n_valid [b], slots)`` - how
    many of a row's lanes are on, and the slots ``state_slots [b]`` whose state
    the rows continue, or ``None``: the decode step, row ``i`` is slot ``i``
    of the ``num_slots`` the state arrays hold."""
    b, s = valid.shape
    n_valid = valid.sum(axis=1).astype(jnp.int32)
    if state_slots is not None:
        return n_valid, jnp.asarray(state_slots, jnp.int32).reshape(b)
    if s != 1 or num_slots != b:
        raise ValueError(
            f"a step without state_slots is the decode step of every slot: got "
            f"[{b}, {s}] tokens for {num_slots} slots"
        )
    return n_valid, None


def paged_write_attend(q, k, v, leaves, layer, table, positions, idx, valid,
                       scope: str = "attn_kernel", **visibility):
    """Write the rows, then attend through the table: ``k`` / ``v [b, s, n_kv,
    hd]`` scattered into layer ``layer`` of ONE paged kind's pools ``leaves``
    (``(k_pool, v_pool)``, and ``(k_scale, v_scale)`` behind them where the
    pool is quantized: :func:`write_paged_kv`) under ``kv_write``, then
    :func:`~.paged_attention.paged_attention` of ``q`` over what the kind's
    ``table`` holds, under ``scope`` (the kernel's name in a trace);
    ``visibility`` is that function's ``block_len=`` / ``window=`` /
    ``impl=``. Returns ``(attention [b, s, n_heads, hd], leaves)``, the leaves
    updated, in the order given."""
    from .paged_attention import paged_attention

    with jax.named_scope("kv_write"):
        leaves = write_paged_kv(
            leaves[0], leaves[1], layer, k, v, table, positions, valid, *leaves[2:])
    with jax.named_scope(scope):
        attn = paged_attention(
            q, leaves[0], leaves[1], layer, table, idx, *leaves[2:], **visibility)
    return attn, leaves


def rope_paged_attention_block(
    layer, x, k_pool, v_pool, layer_idx, cos, sin, block_tables, idx,
    n_heads: int, n_kv_heads: int, head_dim: int, eps: float,
    write_mask=None, k_scale=None, v_scale=None, attn_impl=None,
):
    """Paged twin of :func:`rope_cached_attention_block`: RMSNorm → q/k/v →
    RoPE at each slot's absolute position → row scatter into layer
    ``layer_idx`` of the stacked pools through the block table
    (quantize-on-scatter when scale arrays ride along) → **fused paged
    attention** walking the block table directly at ``(layer_idx, block)``
    (:func:`paged_write_attend` — neither the layer's slab nor the gathered
    ``[b, max_blocks*bs, ...]`` span is ever materialised) → output
    projection residual. ``s == 1`` is the engine's decode step; ``s > 1`` a
    prefill chunk (``write_mask`` drops its padded tail). Returns ``(x,
    k_pool, v_pool)`` — the whole pools, updated — and the scale arrays too
    when quantized."""
    b, s, _ = x.shape
    idx, positions, valid = paged_step_frame(x, idx, write_mask)
    # scopes: the same names llama_layer_apply gives the training block
    with jax.named_scope("attn_proj"):
        y = rms_norm(x, layer["attn_norm"], eps)
        q = apply_rope(
            dense(y, layer["wq"]).reshape(b, s, n_heads, head_dim), cos, sin, positions
        )
        k = apply_rope(
            dense(y, layer["wk"]).reshape(b, s, n_kv_heads, head_dim), cos, sin, positions
        )
        v = dense(y, layer["wv"]).reshape(b, s, n_kv_heads, head_dim)
    leaves = (k_pool, v_pool) if k_scale is None else (k_pool, v_pool, k_scale, v_scale)
    attn, leaves = paged_write_attend(
        q, k, v, leaves, layer_idx, block_tables, positions, idx, valid, impl=attn_impl)
    return (attention_out(layer, x, attn), *leaves)


# -- what the served families' files share beside the block --------------------


@jax.named_scope("embed")
def embed_tokens(params, input_ids):
    return params["embed_tokens"][input_ids]


@jax.named_scope("head")
def untied_head(x, lm_head):
    return dense(x, lm_head)


def layer_at(stack, i, but=()):
    """Layer ``i`` (static) of every leaf of a stack but those named in
    ``but`` (the experts' matrices, which stay stacked and are addressed at
    ``(i, expert)``)."""
    return {name: leaf[i] for name, leaf in stack.items() if name not in but}


@jax.named_scope("attn_proj")
def qk_normed_rotary_qkv(layer, x, norm, positions, n_heads: int, n_kv_heads: int,
                         head_dim: int, eps: float, theta: float):
    """q, k (each head normed by ``q_norm`` / ``k_norm``, then rotated) and v
    of ``RMSNorm(x, norm)``: bias-free grouped-query projections."""
    b, s, _ = x.shape
    y = rms_norm(x, norm, eps)
    q = rms_norm(dense(y, layer["wq"]).reshape(b, s, n_heads, head_dim), layer["q_norm"], eps)
    k = rms_norm(dense(y, layer["wk"]).reshape(b, s, n_kv_heads, head_dim), layer["k_norm"], eps)
    v = dense(y, layer["wv"]).reshape(b, s, n_kv_heads, head_dim)
    return rotate_rope(q, positions, theta), rotate_rope(k, positions, theta), v


@jax.named_scope("attn_proj")
def attention_out(layer, x, attn):
    """``x + attn Wo``, the heads of ``attn [b, s, heads, hd]`` folded."""
    b, s = attn.shape[:2]
    return x + dense(attn.reshape(b, s, -1), layer["wo"])
