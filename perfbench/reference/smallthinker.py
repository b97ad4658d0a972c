"""SmallThinker's forward pass (``smallthinker``,
SmallThinker-21BA3B-Instruct), plain.

Straightforward ``jax.numpy`` in float32 with matrix products at ``highest``
precision. Attention is a masked softmax over the whole sequence, computed a
block of queries at a time so that a 16,384-token sequence fits (the mask is
made from positions; no key is skipped, no cache, no pages, no kernel);
**every expert runs over every token** and a one-hot of the router's choice
picks and weighs what is kept. Nothing is imported from the program under
test. Weights are made from the seed by ``perfbench.weights``, one layer at a
time. The module's contract is in ``perfbench/README.md``.

For layer ``l`` (from 0), hidden ``x [T, h]``, ``eps = rms_norm_eps``, no
bias anywhere:

    r = x W_r                          float32: the router reads the layer's
                                       INPUT, before the attention norm
    y = rmsnorm(x; attn_norm);  q, k, v = y Wq, y Wk, y Wv     no q/k norm
    rope_layout[l] == 1:  q, k rotated: rotate-half over the whole head
                          (lane i with i + hd/2), rope_theta, no scaling
                     0:  no position term at all
    visible(i, j) = j <= i                        sliding_window_layout[l] == 0
                  = i - sliding_window_size < j <= i                        1
    a = softmax_j(q_i . k_j / sqrt(hd) over visible j) v_j;  head n reads kv head n // rep
    x' = x + a Wo
    m = rmsnorm(x'; ffn_norm)
    e_1..e_k = the k largest of r;  w = softmax(r[e_1..e_k])
    out = x' + sum_i w_i * (relu(m W_g[e_i]) * (m W_u[e_i])) W_d[e_i]

``logits = rmsnorm(x_L; norm) lm_head``, untied; the embedding is a lookup.
Each of ``rope_layout`` and ``sliding_window_layout`` is read for what it
says. Departures from the published modeling file: none in the arithmetic;
the stacks are ``layers.full.*`` and ``layers.window.*`` (the layers of each
cache kind in published order) with ``W_g | W_u`` stored as ``w_in [h, 2f]``,
which with seeded weights is a naming of the draws.

``cfg["_fault"]`` plants a fault by name, for ``perfbench/tests`` and
``tests/test_smallthinker.py`` to show that the tolerance holds a mechanism
out; no configuration file has the key.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights

HI = jax.lax.Precision.HIGHEST

LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm", "gate", "w_in", "w_out")
KINDS = ("full", "window")
#: queries a block of the attention's softmax takes
QUERY_BLOCK = 512
FAULTS = ("window_ignored", "window_plus_one", "full_layers_rotated", "route_after_attention",
          "silu", "last_choice_dropped")


def _sizes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    return {
        "h": h, "v": cfg["vocab_size"], "f": cfg["moe_ffn_hidden_size"],
        "e": cfg["moe_num_primary_experts"], "k": cfg["moe_num_active_primary_experts"],
        "nh": cfg["num_attention_heads"], "nkv": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or h // cfg["num_attention_heads"],
        "eps": cfg.get("rms_norm_eps", 1e-6), "window": cfg["sliding_window_size"],
        "theta": cfg.get("rope_theta", 1.5e6),
    }


def _plan(cfg: dict) -> list:
    """``[(kind, index in the kind's stack, rotated)]`` in published order."""
    n = cfg["num_hidden_layers"]
    seen = dict.fromkeys(KINDS, 0)
    out = []
    for windowed, rotated in zip(cfg["sliding_window_layout"][:n], cfg["rope_layout"][:n]):
        kind = KINDS[int(windowed)]
        out.append((kind, seen[kind], bool(rotated)))
        seen[kind] += 1
    return out


def leaf_shapes(cfg: dict) -> dict:
    """Leaf name -> shape, as the program's parameter tree names them: two
    stacks, ``layers.full.*`` and ``layers.window.*``, projections ``[in,
    out]``, an expert's gate | up as ``w_in [h, 2f]``."""
    z = _sizes(cfg)
    plan = _plan(cfg)
    h, hd, e, f = z["h"], z["hd"], z["e"], z["f"]
    out = {"embed_tokens": (z["v"], h), "norm": (h,), "lm_head": (h, z["v"])}
    for kind in KINDS:
        n = sum(1 for p in plan if p[0] == kind)
        out.update({
            f"layers.{kind}.attn_norm": (n, h),
            f"layers.{kind}.wq": (n, h, z["nh"] * hd),
            f"layers.{kind}.wk": (n, h, z["nkv"] * hd),
            f"layers.{kind}.wv": (n, h, z["nkv"] * hd),
            f"layers.{kind}.wo": (n, z["nh"] * hd, h),
            f"layers.{kind}.ffn_norm": (n, h),
            f"layers.{kind}.gate": (n, h, e),
            f"layers.{kind}.w_in": (n, e, h, 2 * f),
            f"layers.{kind}.w_out": (n, e, f, h),
        })
    return out


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta: float):
    """``x [T, heads, hd]`` at positions ``0..T-1``, rotate-half."""
    t, _, hd = x.shape
    inv_freq = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(cfg: dict, w: dict, y, valid_len, window: int, rotated: bool):
    """``y [T, h]`` (normed) -> ``a Wo [T, h]``; ``window`` 0 sees the whole
    past. A block of queries at a time against every key."""
    z = _sizes(cfg)
    t = y.shape[0]
    nh, nkv, hd = z["nh"], z["nkv"], z["hd"]
    q = jnp.dot(y, w["wq"], precision=HI).reshape(t, nh, hd)
    k = jnp.dot(y, w["wk"], precision=HI).reshape(t, nkv, hd)
    v = jnp.dot(y, w["wv"], precision=HI).reshape(t, nkv, hd)
    if rotated:
        q, k = rope(q, z["theta"]), rope(k, z["theta"])
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, nkv, nh // nkv, hd)
    key_pos = jnp.arange(t)

    def one_block(_, inp):
        q_i, first = inp
        q_pos = first + jnp.arange(block)
        s = jnp.einsum("qngd,knd->ngqk", q_i, k, precision=HI) / np.sqrt(hd)
        mask = (key_pos[None, :] <= q_pos[:, None]) & (key_pos[None, :] < valid_len)
        if window:
            mask = mask & (key_pos[None, :] > q_pos[:, None] - window)
        # a padded query sees no key: its row is not read
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return None, jnp.einsum("ngqk,knd->qngd", p, v, precision=HI).reshape(block, nh * hd)

    _, a = jax.lax.scan(one_block, None, (qb, jnp.arange(qb.shape[0]) * block))
    return jnp.dot(a.reshape(-1, nh * hd)[:t], w["wo"], precision=HI)


def experts(cfg: dict, w: dict, m, r):
    """Every expert over every normed token ``m [T, h]``; the one-hot of the
    ``k`` largest of the router's logits ``r [T, E]`` keeps and weighs them."""
    z = _sizes(cfg)
    fault = cfg.get("_fault")
    top, chosen = jax.lax.top_k(r, z["k"])
    weight = jax.nn.softmax(top, axis=-1)
    if fault == "last_choice_dropped":
        weight = weight.at[:, -1].set(0.0)
    share = (jax.nn.one_hot(chosen, z["e"], dtype=jnp.float32) * weight[..., None]).sum(axis=1)
    act = jax.nn.silu if fault == "silu" else jax.nn.relu

    def one_expert(acc, inp):
        w_in, w_out, col = inp
        g, u = jnp.split(jnp.dot(m, w_in, precision=HI), 2, axis=-1)
        return acc + col[:, None] * jnp.dot(act(g) * u, w_out, precision=HI), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(m), (w["w_in"], w["w_out"], share.T))
    return out


def layer(cfg: dict, kind: str, rotated: bool, w: dict, x, valid_len):
    """One layer on ``x [T, h]`` (positions ``0..T-1``; rows ``>= valid_len``
    are padding: causality keeps them out of every valid row)."""
    z = _sizes(cfg)
    fault = cfg.get("_fault")
    window = z["window"] if kind == "window" else 0
    if window and fault == "window_ignored":
        window = 0
    if window and fault == "window_plus_one":
        window += 1
    if kind == "full" and fault == "full_layers_rotated":
        rotated = True
    r = jnp.dot(x, w["gate"], precision=HI)            # the layer's input, un-normed
    x = x + attention(cfg, w, rms_norm(x, w["attn_norm"], z["eps"]), valid_len, window, rotated)
    m = rms_norm(x, w["ffn_norm"], z["eps"])
    if fault == "route_after_attention":
        r = jnp.dot(m, w["gate"], precision=HI)
    return x + experts(cfg, w, m, r)


@functools.lru_cache(maxsize=None)
def _programs(cfg_items: tuple, window_layout: tuple, rope_layout: tuple, scale_items: tuple,
              served_dtype: str):
    """The jitted pieces for one configuration: embed, one layer of each
    (kind, rotated) pairing with its weights made inside from the key (never
    all resident), head."""
    cfg = dict(cfg_items, sliding_window_layout=list(window_layout),
               rope_layout=list(rope_layout))
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

    def one_layer(kind, rotated):
        @jax.jit
        def run(key, l, x, valid_len):
            w = {n: get(key, f"layers.{kind}.{n}", l) for n in LEAVES}
            return layer(cfg, kind, rotated, w, x, valid_len)
        return run

    @jax.jit
    def head(key, x, rows):
        x = rms_norm(x[rows], get(key, "norm"), eps)
        return jnp.dot(x, get(key, "lm_head"), precision=HI)

    pairings = {(p[0], p[2]) for p in _plan(cfg)}
    return embed, {pair: one_layer(*pair) for pair in pairings}, head


def logits_at(cfg: dict, seed: int, ids, valid_len: int, rows, served_dtype="bfloat16"):
    """Logits ``[len(rows), vocab]`` of the sequence ``ids [T]`` (padded;
    ``valid_len`` real tokens) at positions ``rows``, layer by layer."""
    n = cfg["num_hidden_layers"]
    items = tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (dict, list))))
    scales = tuple(sorted(cfg.get("weight_scales", {}).items()))
    embed, layers, head = _programs(
        items, tuple(cfg["sliding_window_layout"][:n]), tuple(cfg["rope_layout"][:n]), scales,
        str(served_dtype))
    key = weights.root_key(seed)
    x = embed(key, jnp.asarray(ids, jnp.int32))
    for kind, l, rotated in _plan(cfg):
        x = layers[(kind, rotated)](key, l, x, jnp.int32(valid_len))
    return head(key, x, jnp.asarray(rows, jnp.int32))
