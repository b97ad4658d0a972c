"""SDAR-MoE's forward pass (``sdar_moe``, SDAR-30B-A3B-Chat), plain, under
the conditioning its block rounds serve a token from.

Straightforward ``jax.numpy`` in float32 with matrix products at
``highest`` precision. Attention is a full masked softmax; **every expert
runs over every token** and a one-hot of the router's choice picks and
weighs what is kept. No cache, no pages, no rounds, no batching, nothing
imported from the program under test. Weights are made from the seed by
``perfbench.weights``, one layer at a time. The module's contract is in
``perfbench/README.md``.

Per layer, for hidden ``x [T, h]``:

    h = RMSNorm(x, attn_norm);  q, k, v = h Wq, h Wk, h Wv   (no bias)
    q, k: RMSNorm over each head's entries (q_norm, k_norm), then rotate-half
          rotary over the whole head at the absolute position (rope_theta)
    a_p = softmax_j(q_p . k_j / sqrt(hd)) v_j   over   j < (p // B + 1) * B
    x = x + a Wo
    h = RMSNorm(x, ffn_norm);  s = softmax(h Wg) over all experts
    chosen = top_k(s);  w = s[chosen] / (sum + 1e-6)        (norm_topk_prob)
    x = x + sum_i w_i * W_out[e_i](silu(g) * u),  [g | u] = h W_in[e_i]

then ``RMSNorm(x, norm)`` and the untied head. ``B`` is ``block_length``:
attention is causal from block to block and bidirectional inside a block.

**What a served token was conditioned on.** A block is generated by filling
it with ``mask_token_id`` and unmasking it over ``T = denoise_steps``
passes; pass ``t`` fixes the still-masked positions of sub-block ``t`` of
the block's ``T`` equal sub-blocks, left to right, each to the best token of
its own logits — the logits AT a masked position are the distribution of
the token at that position (no shift). So the token at position ``p`` saw:
every earlier block clean, as its own clean block-causal forward left its
keys and values; of its own block, the positions before ``p``'s sub-block
(and the prompt's, where the prompt ends inside the block) clean, and ``p``'s
sub-block and everything after it the mask token. That is a function of the
prompt and the tokens before ``p`` alone, which is what lets a teacher-forced
pass check it. Departures from the published description: none in the layer;
``block_length``, ``mask_token_id``, the aligned prediction and the
left-to-right static schedule are the family's convention and not in the
published file (the configuration lists them under ``assumed``).

One layer-by-layer pass over two streams does it: the clean sequence, and
for every asked row one noisy copy of its block. A noisy row attends the
clean stream's keys and values of the blocks before its own, and its own
copy's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights

HI = jax.lax.Precision.HIGHEST

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ffn_norm", "gate",
                "w_in", "w_out")
#: noisy copies attended at a time (bounds the scores' buffer)
COPY_CHUNK = 64


def _sizes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    b = cfg.get("block_length", 4)
    t = cfg.get("denoise_steps") or b
    if b % t:
        raise ValueError(f"denoise_steps {t} does not divide block_length {b}")
    return {
        "h": h, "v": cfg["vocab_size"], "f": cfg["moe_intermediate_size"],
        "e": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
        "nh": cfg["num_attention_heads"], "nkv": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or h // cfg["num_attention_heads"],
        "eps": cfg.get("rms_norm_eps", 1e-6), "n": cfg["num_hidden_layers"],
        "b": b, "sub": b // t, "mask": cfg.get("mask_token_id", 151669),
    }


def leaf_shapes(cfg: dict) -> dict:
    """Leaf name -> shape, as the program's parameter tree names them: one
    stack ``layers.*`` over the layers, projections ``[in, out]``, an
    expert's ``w1 | w3`` as ``w_in [h, 2f]`` and its ``w2`` as ``w_out [f,
    h]``, the untied head ``lm_head [h, v]``."""
    z = _sizes(cfg)
    n, h, hd = z["n"], z["h"], z["hd"]
    return {
        "embed_tokens": (z["v"], h), "norm": (h,), "lm_head": (h, z["v"]),
        "layers.attn_norm": (n, h),
        "layers.wq": (n, h, z["nh"] * hd),
        "layers.wk": (n, h, z["nkv"] * hd),
        "layers.wv": (n, h, z["nkv"] * hd),
        "layers.wo": (n, z["nh"] * hd, h),
        "layers.q_norm": (n, hd),
        "layers.k_norm": (n, hd),
        "layers.ffn_norm": (n, h),
        "layers.gate": (n, h, z["e"]),
        "layers.w_in": (n, z["e"], h, 2 * z["f"]),
        "layers.w_out": (n, z["e"], z["f"], h),
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta: float):
    """``x [..., heads, hd]`` at ``positions [...]``, rotate-half."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def qkv(cfg: dict, w: dict, y, positions):
    """Normed, rotated q ``[..., nkv, group, hd]`` and k, and v ``[..., nkv,
    hd]`` of the normed residual ``y [..., h]`` at ``positions [...]``."""
    z = _sizes(cfg)
    nh, nkv, hd = z["nh"], z["nkv"], z["hd"]
    lead = y.shape[:-1]
    q = jnp.dot(y, w["wq"], precision=HI).reshape(*lead, nh, hd)
    k = jnp.dot(y, w["wk"], precision=HI).reshape(*lead, nkv, hd)
    v = jnp.dot(y, w["wv"], precision=HI).reshape(*lead, nkv, hd)
    # each head normed over its own entries, BEFORE the rotation
    q = rope(rms_norm(q, w["q_norm"], z["eps"]), positions, cfg["rope_theta"])
    k = rope(rms_norm(k, w["k_norm"], z["eps"]), positions, cfg["rope_theta"])
    return q.reshape(*lead, nkv, nh // nkv, hd), k, v


def routed_ff(cfg: dict, w: dict, y):
    """Every expert over every token ``y [T, h]``; the router's one-hot
    keeps and weighs ``num_experts_per_tok`` of them a token."""
    z = _sizes(cfg)
    scores = jax.nn.softmax(jnp.dot(y, w["gate"], precision=HI), axis=-1)   # [T, E]
    picked, chosen = jax.lax.top_k(scores, z["k"])                          # [T, k]
    if cfg.get("norm_topk_prob", True):
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-6)
    # [T, E]: a token's weight for each expert, 0 where it was not chosen
    share = (jax.nn.one_hot(chosen, z["e"], dtype=jnp.float32) * picked[..., None]).sum(axis=1)

    def one_expert(acc, inp):
        w_in, w_out, col = inp
        g, u = jnp.split(jnp.dot(y, w_in, precision=HI), 2, axis=-1)
        return acc + col[:, None] * jnp.dot(jax.nn.silu(g) * u, w_out, precision=HI), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y), (w["w_in"], w["w_out"], share.T))
    return out


def layer(cfg: dict, w: dict, x, valid_len, xn, first):
    """One layer over both streams: the clean sequence ``x [T, h]`` (rows
    ``>= valid_len`` are padding, attended by no valid row of a whole
    block), and the noisy copies ``xn [R, B, h]``, copy ``r`` standing at
    positions ``first[r] .. first[r] + B - 1`` (a block's)."""
    z = _sizes(cfg)
    t, (r, b, h) = x.shape[0], xn.shape
    hd, scale = z["hd"], 1.0 / np.sqrt(z["hd"])
    pos = jnp.arange(t, dtype=jnp.int32)
    pos_n = first[:, None] + jnp.arange(b, dtype=jnp.int32)[None, :]
    q, k, v = qkv(cfg, w, rms_norm(x, w["attn_norm"], z["eps"]), pos)
    qn, kn, vn = qkv(cfg, w, rms_norm(xn, w["attn_norm"], z["eps"]), pos_n)

    # the clean stream: causal from block to block, bidirectional inside one
    block_end = (pos // b + 1) * b
    mask = (pos[None, :] < block_end[:, None]) & (pos[None, :] < valid_len)
    s = jnp.einsum("qngd,knd->ngqk", q, k, precision=HI) * scale
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("ngqk,knd->qngd", p, v, precision=HI).reshape(t, -1)
    # a padded row whose whole block is padding attends nothing: its softmax
    # is not a number, and no valid row reads it
    x = x + jnp.dot(jnp.nan_to_num(a), w["wo"], precision=HI)

    # the noisy copies: the clean keys and values of the blocks before the
    # copy's, and the copy's own
    def attend(chunk):
        qc, kc, vc, fc = chunk
        before = pos[None, :] < fc[:, None]                               # [c, T]
        s_clean = jnp.einsum("cqngd,knd->cngqk", qc, k, precision=HI) * scale
        s_clean = jnp.where(before[:, None, None, None, :], s_clean, -jnp.inf)
        s_own = jnp.einsum("cqngd,cknd->cngqk", qc, kc, precision=HI) * scale
        pc = jax.nn.softmax(jnp.concatenate([s_clean, s_own], axis=-1), axis=-1)
        return (jnp.einsum("cngqk,knd->cqngd", pc[..., :t], v, precision=HI)
                + jnp.einsum("cngqk,cknd->cqngd", pc[..., t:], vc, precision=HI))

    chunks = -(-r // COPY_CHUNK)
    pad = chunks * COPY_CHUNK - r

    def chunked(arr):
        arr = jnp.concatenate([arr, jnp.zeros((pad, *arr.shape[1:]), arr.dtype)])
        return arr.reshape(chunks, COPY_CHUNK, *arr.shape[1:])

    an = jax.lax.map(attend, tuple(chunked(arr) for arr in (qn, kn, vn, first)))
    an = an.reshape(chunks * COPY_CHUNK, b, -1)[:r]
    xn = xn + jnp.dot(an, w["wo"], precision=HI)

    x = x + routed_ff(cfg, w, rms_norm(x, w["ffn_norm"], z["eps"]))
    yn = rms_norm(xn, w["ffn_norm"], z["eps"]).reshape(r * b, h)
    return x, xn + routed_ff(cfg, w, yn).reshape(r, b, h)


def noisy_copies(cfg: dict, ids, rows, prompt_len):
    """For each asked row ``r`` (the served position ``p = r + 1``): the
    block of ``p`` as the denoise pass that fixes ``p`` sees it — the
    positions before ``p``'s sub-block, and the prompt's, hold their
    tokens; the rest hold the mask token. Returns ``(tokens [R, B], first
    position [R], p's place in its block [R])``."""
    z = _sizes(cfg)
    b, sub = z["b"], z["sub"]
    p = rows + 1
    first = p // b * b
    sub_start = first + (p - first) // sub * sub
    clean_until = jnp.maximum(sub_start, prompt_len)                        # exclusive
    at = first[:, None] + jnp.arange(b, dtype=jnp.int32)[None, :]           # [R, B]
    toks = jnp.where(at < clean_until[:, None], ids[jnp.minimum(at, ids.shape[0] - 1)],
                     z["mask"])
    return toks, first, p - first


@functools.lru_cache(maxsize=None)
def _programs(cfg_items: tuple, scale_items: tuple, served_dtype: str):
    """The jitted pieces for one configuration: embed, one layer with its
    weights made inside from the key (never all resident), head."""
    cfg = dict(cfg_items)
    scales = dict(scale_items)
    shapes = leaf_shapes(cfg)
    served = jnp.dtype(served_dtype)
    eps = _sizes(cfg)["eps"]

    def get(key, name, l=None):
        return weights.leaf(key, name, shapes[name], served, layer=l,
                            scales=scales).astype(jnp.float32)

    @jax.jit
    def embed(key, ids, rows, prompt_len):
        toks, first, place = noisy_copies(cfg, ids, rows, prompt_len)
        table = get(key, "embed_tokens")
        return table[ids], table[toks], first, place

    @jax.jit
    def one_layer(key, l, x, valid_len, xn, first):
        w = {n: get(key, f"layers.{n}", l) for n in LAYER_LEAVES}
        return layer(cfg, w, x, valid_len, xn, first)

    @jax.jit
    def head(key, xn, place):
        x = jnp.take_along_axis(xn, place[:, None, None], axis=1)[:, 0]
        x = rms_norm(x, get(key, "norm"), eps)
        return jnp.dot(x, get(key, "lm_head"), precision=HI)

    return embed, one_layer, head


def logits_at(cfg: dict, seed: int, ids, valid_len: int, rows, served_dtype="bfloat16"):
    """Logits ``[len(rows), vocab]`` for the sequence ``ids [T]`` (padded;
    ``valid_len`` real tokens: a prompt and its served tokens but the last).
    Row ``r`` asks for the distribution the token at position ``p = r + 1``
    was served from: the logits AT ``p`` of the denoise pass that fixes ``p``
    (the module's docstring). The prompt's length is ``rows[0] + 1``: that
    is how ``check.sequence_readings`` builds ``rows`` (the first served
    token stands right behind the prompt), and the reference needs it
    because a prompt that ends inside a block leaves its tail there clean in
    every pass."""
    items = tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (dict, list))))
    scales = tuple(sorted(cfg.get("weight_scales", {}).items()))
    embed, one_layer, head = _programs(items, scales, str(served_dtype))
    key = weights.root_key(seed)
    rows = jnp.asarray(rows, jnp.int32)
    x, xn, first, place = embed(key, jnp.asarray(ids, jnp.int32), rows, rows[0] + 1)
    for l in range(cfg["num_hidden_layers"]):
        x, xn = one_layer(key, l, x, jnp.int32(valid_len), xn, first)
    return head(key, xn, place)
