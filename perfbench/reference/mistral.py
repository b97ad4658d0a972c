"""Mistral-7B's forward pass, plain: RMSNorm, rotary embedding (half-split
rotation, as the published implementation), grouped-query causal attention,
SwiGLU — straightforward ``jax.numpy`` in float32 with matrix products at
``highest`` precision. No kernels, no cache, no batching, and nothing
imported from the program under test. Weights are made from the seed by
``perfbench.weights``, one layer at a time, so the whole model is never
resident.

The training reference (loss, gradients, AdamW) is the same forward under
``jax.value_and_grad`` — see ``perfbench/reference/train.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench import weights

HI = jax.lax.Precision.HIGHEST

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up", "w_down")


def leaf_shapes(cfg: dict) -> dict:
    """Leaf name -> shape, the layout the published model has (projection
    matrices stored ``[in, out]``, layers stacked)."""
    h, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or h // nh
    n = cfg["num_hidden_layers"]
    shapes = {
        "embed_tokens": (v, h),
        "norm": (h,),
        "layers.attn_norm": (n, h),
        "layers.mlp_norm": (n, h),
        "layers.wq": (n, h, nh * hd),
        "layers.wk": (n, h, nkv * hd),
        "layers.wv": (n, h, nkv * hd),
        "layers.wo": (n, nh * hd, h),
        "layers.w_gate": (n, h, ff),
        "layers.w_up": (n, h, ff),
        "layers.w_down": (n, ff, h),
    }
    if not cfg.get("tie_word_embeddings", False):
        shapes["lm_head"] = (h, v)
    return shapes


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """``x [T, heads, hd]``: rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer(cfg: dict, w: dict, x, valid_len):
    """One block on ``x [T, h]`` (positions ``0..T-1``; rows ``>= valid_len``
    are padding and never attended)."""
    t, _ = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // nh
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(t)
    y = rms_norm(x, w["attn_norm"], eps)
    q = jnp.dot(y, w["wq"], precision=HI).reshape(t, nh, hd)
    k = jnp.dot(y, w["wk"], precision=HI).reshape(t, nkv, hd)
    v = jnp.dot(y, w["wv"], precision=HI).reshape(t, nkv, hd)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    g = nh // nkv
    q = q.reshape(t, nkv, g, hd)
    s = jnp.einsum("qngd,knd->ngqk", q, k, precision=HI) / (hd ** 0.5)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < valid_len)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("ngqk,knd->qngd", p, v, precision=HI).reshape(t, nh * hd)
    x = x + jnp.dot(a, w["wo"], precision=HI)
    y = rms_norm(x, w["mlp_norm"], eps)
    gate = jax.nn.silu(jnp.dot(y, w["w_gate"], precision=HI))
    up = jnp.dot(y, w["w_up"], precision=HI)
    return x + jnp.dot(gate * up, w["w_down"], precision=HI)


@functools.lru_cache(maxsize=None)
def _programs(cfg_items: tuple, scale_items: tuple, served_dtype: str):
    """The jitted pieces for one configuration: embed, one layer (weights
    made inside from the key, so they are never all resident), head."""
    cfg = dict(cfg_items)
    scales = dict(scale_items)
    shapes = leaf_shapes(cfg)
    served = jnp.dtype(served_dtype)

    def get(key, name, l=None):
        return weights.leaf(key, name, shapes[name], served, layer=l,
                            scales=scales).astype(jnp.float32)

    @jax.jit
    def embed(key, ids):
        return get(key, "embed_tokens")[ids]

    @jax.jit
    def one_layer(key, l, x, valid_len):
        w = {n: get(key, "layers." + n, l) for n in LAYER_LEAVES}
        return layer(cfg, w, x, valid_len)

    @jax.jit
    def head(key, x, rows):
        x = rms_norm(x[rows], get(key, "norm"), cfg["rms_norm_eps"])
        w = get(key, "lm_head") if "lm_head" in shapes else get(key, "embed_tokens").T
        return jnp.dot(x, w, precision=HI)

    return embed, one_layer, head


def logits_at(cfg: dict, seed: int, ids, valid_len: int, rows,
              served_dtype="bfloat16"):
    """Logits ``[len(rows), vocab]`` of the sequence ``ids [T]`` (padded;
    ``valid_len`` real tokens) at positions ``rows``, layer by layer."""
    items = tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (dict, list))))
    scales = tuple(sorted(cfg.get("weight_scales", {}).items()))
    embed, one_layer, head = _programs(items, scales, str(served_dtype))
    key = weights.root_key(seed)
    x = embed(key, jnp.asarray(ids, jnp.int32))
    for l in range(cfg["num_hidden_layers"]):
        x = one_layer(key, l, x, jnp.int32(valid_len))
    return head(key, x, jnp.asarray(rows, jnp.int32))
