"""LFM2-MoE's forward pass (``lfm2_moe``, LFM2-8B-A1B), plain: gated short
convolutions and a few QK-normed rotary GQA layers in the order
``layer_types`` gives, a dense SwiGLU behind the first ``num_dense_layers``
layers and routed experts behind the rest, the embedding as the head.

Straightforward ``jax.numpy`` in float32 with matrix products at
``highest`` precision. The convolution is three shifted products over the
whole sequence (no tail carried, no chunks); attention is full and causal;
**every expert runs over every token** and a one-hot of the router's choice
picks and weighs what is kept — no grouping, no sort, no grouped product, no
counters. No cache, no batching, nothing imported from the program under
test. Weights are made from the seed by ``perfbench.weights``, one layer at
a time. The module's contract is in ``perfbench/README.md``.

Per layer:

    x <- x + op(RMSNorm(x, operator_norm));   x <- x + ff(RMSNorm(x, ffn_norm))

``conv``: ``[B, C, u] = split3(y W_in)``; ``g = B * u``; ``c_t = sum_j
w[j] * g_{t-(L-1)+j}`` (``g`` zero before the sequence; no activation, no
bias); ``(C * c) W_out``. ``full_attention``: bias-free q, k, v;
``RMSNorm`` over each head's entries of q and k, then rotate-half rotary
over the whole head (``rope_theta``, no scaling); ``softmax(q k^T /
sqrt(head))``, causal, grouped queries. Dense feed-forward:
``W_out(silu(g) * u)``, ``[g, u] = y W_in``. Routed: ``s = sigmoid(y W_g)``;
chosen = ``top_k(s + expert_bias)``; weights ``s[chosen] / (sum + 1e-6) *
routed_scaling_factor`` (the un-biased scores); ``sum_i weight_i *
expert_i(y)``. ``logits = RMSNorm(x, embedding_norm) E^T``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights

HI = jax.lax.Precision.HIGHEST

CONV_LEAVES = ("operator_norm", "in_proj", "conv_w", "out_proj")
ATTENTION_LEAVES = ("operator_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")
DENSE_LEAVES = ("ffn_norm", "w_in", "w_out")
MOE_LEAVES = ("ffn_norm", "gate", "expert_bias", "w_in", "w_out")
LEAVES = {"conv": CONV_LEAVES, "attention": ATTENTION_LEAVES, "dense": DENSE_LEAVES,
          "moe": MOE_LEAVES}


def _sizes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    return {
        "h": h, "v": cfg["vocab_size"], "ff": cfg["intermediate_size"],
        "f": cfg["moe_intermediate_size"], "e": cfg["num_experts"],
        "k": cfg["num_experts_per_tok"], "nh": cfg["num_attention_heads"],
        "nkv": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or h // cfg["num_attention_heads"],
        "taps": cfg.get("conv_L_cache", 3), "eps": cfg.get("norm_eps", 1e-5),
    }


def _plan(cfg: dict) -> list:
    """``[(operator stack, index in it, feed-forward stack, index in it)]``
    in published order: the stacks hold the layers of their kind."""
    seen = {"conv": 0, "attention": 0}
    n_dense = cfg.get("num_dense_layers", 2)
    out = []
    for i, kind in enumerate(cfg["layer_types"][: cfg["num_hidden_layers"]]):
        op = "conv" if kind == "conv" else "attention"
        out.append((op, seen[op], "dense" if i < n_dense else "moe",
                    i if i < n_dense else i - n_dense))
        seen[op] += 1
    return out


def leaf_shapes(cfg: dict) -> dict:
    """Leaf name -> shape, as the program's parameter tree names them: four
    stacks — ``layers.conv.*`` and ``layers.attention.*`` the operators,
    ``layers.dense.*`` and ``layers.moe.*`` the feed-forwards — projections
    ``[in, out]``; the published ``in_proj`` as its columns ``B | C | u``; the
    taps ``[L, channels]``; an expert's ``w1 | w3`` as ``w_in [h, 2f]``."""
    z = _sizes(cfg)
    plan = _plan(cfg)
    n = {kind: sum(1 for p in plan if kind in (p[0], p[2])) for kind in LEAVES}
    h, hd = z["h"], z["hd"]
    return {
        "embed_tokens": (z["v"], h), "embedding_norm": (h,),
        "layers.conv.operator_norm": (n["conv"], h),
        "layers.conv.in_proj": (n["conv"], h, 3 * h),
        "layers.conv.conv_w": (n["conv"], z["taps"], h),
        "layers.conv.out_proj": (n["conv"], h, h),
        "layers.attention.operator_norm": (n["attention"], h),
        "layers.attention.wq": (n["attention"], h, z["nh"] * hd),
        "layers.attention.wk": (n["attention"], h, z["nkv"] * hd),
        "layers.attention.wv": (n["attention"], h, z["nkv"] * hd),
        "layers.attention.wo": (n["attention"], z["nh"] * hd, h),
        "layers.attention.q_norm": (n["attention"], hd),
        "layers.attention.k_norm": (n["attention"], hd),
        "layers.dense.ffn_norm": (n["dense"], h),
        "layers.dense.w_in": (n["dense"], h, 2 * z["ff"]),
        "layers.dense.w_out": (n["dense"], z["ff"], h),
        "layers.moe.ffn_norm": (n["moe"], h),
        "layers.moe.gate": (n["moe"], h, z["e"]),
        "layers.moe.expert_bias": (n["moe"], z["e"]),
        "layers.moe.w_in": (n["moe"], z["e"], h, 2 * z["f"]),
        "layers.moe.w_out": (n["moe"], z["e"], z["f"], h),
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def conv_operator(cfg: dict, w: dict, y):
    """``y [T, h]`` (normed) -> the operator's output ``[T, h]``."""
    z = _sizes(cfg)
    t, taps = y.shape[0], z["taps"]
    b_gate, c_gate, u = jnp.split(jnp.dot(y, w["in_proj"], precision=HI), 3, axis=-1)
    g = b_gate * u
    # causal depthwise convolution: tap L-1 multiplies the current token.
    # The published code calls a conv1d with padding L-1 and cuts the tail
    # off: the same sum
    padded = jnp.concatenate([jnp.zeros((taps - 1, z["h"]), jnp.float32), g])
    conv = sum(padded[j:j + t] * w["conv_w"][j] for j in range(taps))
    return jnp.dot(c_gate * conv, w["out_proj"], precision=HI)


def rope(x, theta: float):
    """``x [T, heads, hd]`` at positions ``0..T-1``, rotate-half."""
    t, _, hd = x.shape
    inv_freq = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_operator(cfg: dict, w: dict, y, valid_len):
    z = _sizes(cfg)
    t = y.shape[0]
    nh, nkv, hd = z["nh"], z["nkv"], z["hd"]
    q = jnp.dot(y, w["wq"], precision=HI).reshape(t, nh, hd)
    k = jnp.dot(y, w["wk"], precision=HI).reshape(t, nkv, hd)
    v = jnp.dot(y, w["wv"], precision=HI).reshape(t, nkv, hd)
    # each head normed over its own entries, BEFORE the rotation
    q = rope(rms_norm(q, w["q_norm"], z["eps"]), cfg["rope_theta"]).reshape(t, nkv, nh // nkv, hd)
    k = rope(rms_norm(k, w["k_norm"], z["eps"]), cfg["rope_theta"])
    s = jnp.einsum("qngd,knd->ngqk", q, k, precision=HI) / np.sqrt(hd)
    pos = jnp.arange(t)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < valid_len)
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("ngqk,knd->qngd", p, v, precision=HI).reshape(t, nh * hd)
    return jnp.dot(a, w["wo"], precision=HI)


def dense_ff(w: dict, y):
    g, u = jnp.split(jnp.dot(y, w["w_in"], precision=HI), 2, axis=-1)
    return jnp.dot(jax.nn.silu(g) * u, w["w_out"], precision=HI)


def routed_ff(cfg: dict, w: dict, y):
    """Every expert over every token; the router's one-hot keeps and weighs
    ``num_experts_per_tok`` of them a token."""
    z = _sizes(cfg)
    scores = jax.nn.sigmoid(jnp.dot(y, w["gate"], precision=HI))          # [T, E]
    # the bias moves the choice; the weights are the scores without it
    _, chosen = jax.lax.top_k(scores + w["expert_bias"], z["k"])           # [T, k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-6)
    picked = picked * cfg.get("routed_scaling_factor", 1.0)
    # [T, E]: a token's weight for each expert, 0 where it was not chosen
    share = (jax.nn.one_hot(chosen, z["e"], dtype=jnp.float32) * picked[..., None]).sum(axis=1)

    def one_expert(acc, inp):
        w_in, w_out, col = inp
        return acc + col[:, None] * dense_ff({"w_in": w_in, "w_out": w_out}, y), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y), (w["w_in"], w["w_out"], share.T))
    return out


def layer(cfg: dict, op: str, ff: str, w_op: dict, w_ff: dict, x, valid_len):
    """One layer on ``x [T, h]`` (positions ``0..T-1``; rows ``>=
    valid_len`` are padding: causality keeps them out of every valid row)."""
    eps = _sizes(cfg)["eps"]
    y = rms_norm(x, w_op["operator_norm"], eps)
    x = x + (conv_operator(cfg, w_op, y) if op == "conv"
             else attention_operator(cfg, w_op, y, valid_len))
    y = rms_norm(x, w_ff["ffn_norm"], eps)
    return x + (dense_ff(w_ff, y) if ff == "dense" else routed_ff(cfg, w_ff, y))


@functools.lru_cache(maxsize=None)
def _programs(cfg_items: tuple, layer_types: tuple, scale_items: tuple, served_dtype: str):
    """The jitted pieces for one configuration: embed, one layer of each
    (operator, feed-forward) pairing with its weights made inside from the
    key (never all resident), head."""
    cfg = dict(cfg_items, layer_types=list(layer_types))
    scales = dict(scale_items)
    shapes = leaf_shapes(cfg)
    served = jnp.dtype(served_dtype)
    eps = _sizes(cfg)["eps"]

    def get(key, name, l=None):
        return weights.leaf(key, name, shapes[name], served, layer=l,
                            scales=scales).astype(jnp.float32)

    @jax.jit
    def embed(key, ids):
        return get(key, "embed_tokens")[ids]

    def one_layer(op, ff):
        @jax.jit
        def run(key, l_op, l_ff, x, valid_len):
            w_op = {n: get(key, f"layers.{op}.{n}", l_op) for n in LEAVES[op]}
            w_ff = {n: get(key, f"layers.{ff}.{n}", l_ff) for n in LEAVES[ff]}
            return layer(cfg, op, ff, w_op, w_ff, x, valid_len)
        return run

    @jax.jit
    def head(key, x, rows):
        x = rms_norm(x[rows], get(key, "embedding_norm"), eps)
        # tied: the published file leaves tie_word_embeddings out; the
        # family's convention and the published parameter count say tied
        return jnp.dot(x, get(key, "embed_tokens").T, precision=HI)

    pairings = {(p[0], p[2]) for p in _plan(cfg)}
    return embed, {pair: one_layer(*pair) for pair in pairings}, head


def logits_at(cfg: dict, seed: int, ids, valid_len: int, rows, served_dtype="bfloat16"):
    """Logits ``[len(rows), vocab]`` of the sequence ``ids [T]`` (padded;
    ``valid_len`` real tokens) at positions ``rows``, layer by layer."""
    items = tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (dict, list))))
    scales = tuple(sorted(cfg.get("weight_scales", {}).items()))
    embed, layers, head = _programs(
        items, tuple(cfg["layer_types"][: cfg["num_hidden_layers"]]), scales, str(served_dtype))
    key = weights.root_key(seed)
    x = embed(key, jnp.asarray(ids, jnp.int32))
    for op, l_op, ff, l_ff in _plan(cfg):
        x = layers[(op, ff)](key, l_op, l_ff, x, jnp.int32(valid_len))
    return head(key, x, jnp.asarray(rows, jnp.int32))
