"""DeepSeek-V3's forward pass (``deepseek_v3``), plain, in the EXPANDED form:
every head's keys and values are made from the compressed vector through
``W_kvb`` and attended as any multi-head attention is. The program serves
from a latent cache in the absorbed form (the query carried into the
compressed coordinates, the softmax summing the compressed vectors); this
file never does, so that path is held against other arithmetic.

Straightforward ``jax.numpy`` in float32 with matrix products at ``highest``
precision. Attention is a full causal softmax, the queries taken a block at
a time so that a 16k request fits one chip beside its keys and values; **every
held expert runs over every token** and the router's one-hot picks and weighs
what is kept. No cache, no pages, no batching, nothing imported from the
program under test. Weights are made from the seed by ``perfbench.weights``,
one layer at a time. The module's contract is in ``perfbench/README.md``.

Per layer, for hidden ``x [T, h]``, ``eps = rms_norm_eps``, no bias:

    y    = rmsnorm(x; attn_norm)
    c_q  = rmsnorm(y W_qa; q_norm)
    q    = c_q W_qb -> [T, heads, nope + rope] = [q_nope | q_pe]
    [c | k_pe] = y W_kva;  c = rmsnorm(c; kv_norm);  k_pe = rope(k_pe)   one k_pe for all heads
    [k_nope | v] = c W_kvb -> [T, heads, nope + v]
    s_pj = (q_nope_p . k_nope_j + rope(q_pe_p) . k_pe_j) * scale,   j <= p
    x    = x + concat_heads(softmax_j(s_pj) v_j) W_o
    y'   = rmsnorm(x; ffn_norm)
    layers < first_k_dense_replace:  x = x + W_out(silu(g) * u),  [g | u] = y' W_in
    the others:
      s = sigmoid(y' W_g);  b = s + e_bias              (b for the selection only)
      G_g = sum of the top 2 of b inside group g         (n_group runs of consecutive experts)
      keep the topk_group groups of largest G; b = 0 elsewhere; e_1..e_k = top_k(b)
      w_i = s[e_i] / (sum_i s[e_i] + 1e-20) * routed_scaling_factor
      x = x + shared(y') + sum_{i: e_i held here} w_i * expert_{e_i}(y')

then ``rmsnorm(x; norm)`` and the untied head. Rotation: YaRN over the
``rope`` lanes (``yarn_inv_freq``), angles from absolute positions, cos and
sin times ``mscale(mscale) / mscale(mscale_all_dim)`` (1 as published);
``scale = (nope + rope)^-0.5 * mscale(mscale_all_dim)^2``.

**The share.** The configuration holds ``n_routed_experts`` of the router's
``router_experts`` outputs, from ``first_held_expert``; the router scores all
of them, what the experts held elsewhere would have added is left out, and
that partial sum goes on to the next layer. The vocabulary is the
configuration's slice. The program is given the same share.

Departures from the published code, each shared with the program: the
rotation pairs lane ``i`` with lane ``i + rope/2`` (rotate-half) where the
published weights pair adjacent lanes — with seeded weights a relabelling of
``W_qb``'s and ``W_kva``'s rope columns; the dropped groups' biased scores
are set to 0 and not to minus infinity before the top ``k``, AS the published
code does (noted because a reader expects the other); ties are broken
towards the lower index (``lax.top_k``); the multi-token-prediction block
(``num_nextn_predict_layers``) is not computed: the served logits do not
depend on it.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights

HI = jax.lax.Precision.HIGHEST

ATTN_LEAVES = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
               "ffn_norm")
DENSE_LEAVES = ("w_in", "w_out")
MOE_LEAVES = ("gate", "expert_bias", "w_in", "w_out", "shared_in", "shared_out")
#: queries attended at a time (bounds the scores' buffer: heads x this x T)
QUERY_BLOCK = 256


def _sizes(cfg: dict) -> dict:
    held = cfg["n_routed_experts"]
    return {
        "h": cfg["hidden_size"], "v": cfg["vocab_size"], "ff": cfg["intermediate_size"],
        "f": cfg["moe_intermediate_size"], "n": cfg["num_hidden_layers"],
        "nd": cfg["first_k_dense_replace"], "nh": cfg["num_attention_heads"],
        "qr": cfg["q_lora_rank"], "kr": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "vd": cfg["v_head_dim"],
        "held": held, "e": cfg.get("router_experts") or held,
        "first": cfg.get("first_held_expert", 0), "k": cfg["num_experts_per_tok"],
        "groups": cfg.get("n_group", 1), "kept": cfg.get("topk_group", 1),
        "shared": cfg.get("n_shared_experts", 0), "eps": cfg.get("rms_norm_eps", 1e-6),
    }


def leaf_shapes(cfg: dict) -> dict:
    """Leaf name -> shape, as the program's parameter tree names them: the
    attention and both norms of every layer under ``layers.attn.*``, the
    leading dense feed-forwards under ``layers.dense.*``, the routed layers
    under ``layers.moe.*`` (``gate`` and ``expert_bias`` as wide as the
    router, ``w_in`` / ``w_out`` of the experts HELD); projections ``[in,
    out]``, gate | up fused as ``w_in``, ``wkv_b`` with each head's ``k_nope |
    v`` columns together, the untied head ``lm_head [h, v]``."""
    z = _sizes(cfg)
    n, nd, h, nh, f = z["n"], z["nd"], z["h"], z["nh"], z["f"]
    nm = n - nd
    shapes = {
        "embed_tokens": (z["v"], h), "norm": (h,), "lm_head": (h, z["v"]),
        "layers.attn.attn_norm": (n, h),
        "layers.attn.wq_a": (n, h, z["qr"]),
        "layers.attn.q_norm": (n, z["qr"]),
        "layers.attn.wq_b": (n, z["qr"], nh * (z["nope"] + z["rope"])),
        "layers.attn.wkv_a": (n, h, z["kr"] + z["rope"]),
        "layers.attn.kv_norm": (n, z["kr"]),
        "layers.attn.wkv_b": (n, z["kr"], nh * (z["nope"] + z["vd"])),
        "layers.attn.wo": (n, nh * z["vd"], h),
        "layers.attn.ffn_norm": (n, h),
    }
    if nd:
        shapes.update({"layers.dense.w_in": (nd, h, 2 * z["ff"]),
                       "layers.dense.w_out": (nd, z["ff"], h)})
    if nm:
        shapes.update({
            "layers.moe.gate": (nm, h, z["e"]),
            "layers.moe.expert_bias": (nm, z["e"]),
            "layers.moe.w_in": (nm, z["held"], h, 2 * f),
            "layers.moe.w_out": (nm, z["held"], f, h),
        })
        if z["shared"]:
            shapes.update({"layers.moe.shared_in": (nm, h, 2 * f * z["shared"]),
                           "layers.moe.shared_out": (nm, f * z["shared"], h)})
    return shapes


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * float(np.log(factor)) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """The ``rope / 2`` frequencies: ``f_i = theta^(-2i/rope)``; the lane pairs
    that turn more than ``beta_fast`` times over the original context keep
    theirs, those that turn fewer than ``beta_slow`` times are stretched
    ``factor`` x, a linear ramp over the pair index between."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg.get("rope_theta", 10000.0))
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)
    r = cfg.get("rope_scaling")
    if not r:
        return f
    orig = r["original_max_position_embeddings"]

    def pair_that_turns(n):
        return dim * np.log(orig / (2 * np.pi * n)) / (2 * np.log(theta))

    lo = max(int(np.floor(pair_that_turns(r.get("beta_fast", 32)))), 0)
    hi = min(int(np.ceil(pair_that_turns(r.get("beta_slow", 1)))), dim - 1)
    ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return f / r["factor"] * ramp + f * (1.0 - ramp)


def softmax_scale(cfg: dict) -> float:
    scale = float(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    r = cfg.get("rope_scaling")
    if not r:
        return scale
    return scale * yarn_mscale(r["factor"], r.get("mscale_all_dim", 0)) ** 2


def rope(cfg: dict, x, positions):
    """``x [T, ..., rope]`` at ``positions [T]``, rotate-half."""
    r = cfg.get("rope_scaling")
    mag = 1.0 if not r else (yarn_mscale(r["factor"], r.get("mscale", 1))
                             / yarn_mscale(r["factor"], r.get("mscale_all_dim", 0)))
    angles = positions[:, None].astype(jnp.float32) * jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    angles = angles.reshape(angles.shape[0], *(1,) * (x.ndim - 2), angles.shape[-1])
    cos, sin = jnp.cos(angles) * mag, jnp.sin(angles) * mag
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(cfg: dict, w: dict, x, valid_len):
    """``x [T, h]`` -> ``x`` with the attention's output added; the queries
    ``QUERY_BLOCK`` at a time against every head's expanded keys and values."""
    z = _sizes(cfg)
    t, nh, nope, vd = x.shape[0], z["nh"], z["nope"], z["vd"]
    pos = jnp.arange(t, dtype=jnp.int32)
    y = rms_norm(x, w["attn_norm"], z["eps"])
    c_q = rms_norm(jnp.dot(y, w["wq_a"], precision=HI), w["q_norm"], z["eps"])
    ckv = jnp.dot(y, w["wkv_a"], precision=HI)
    c = rms_norm(ckv[:, :z["kr"]], w["kv_norm"], z["eps"])
    k_pe = rope(cfg, ckv[:, z["kr"]:], pos)                                   # [T, rope]
    kv = jnp.dot(c, w["wkv_b"], precision=HI).reshape(t, nh, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = softmax_scale(cfg)

    def block(args):
        cq_b, pos_b = args
        q = jnp.dot(cq_b, w["wq_b"], precision=HI).reshape(-1, nh, nope + z["rope"])
        q_pe = rope(cfg, q[..., nope:], pos_b)
        s = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_nope, precision=HI)
             + jnp.einsum("qhd,kd->hqk", q_pe, k_pe, precision=HI)) * scale
        mask = (pos[None, :] <= pos_b[:, None]) & (pos[None, :] < valid_len)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(-1, nh * vd)
        return jnp.dot(a, w["wo"], precision=HI)

    qb = min(QUERY_BLOCK, t)
    blocks = -(-t // qb)
    pad = blocks * qb - t
    cq_p = jnp.concatenate([c_q, jnp.zeros((pad, c_q.shape[1]), c_q.dtype)])
    pos_p = jnp.concatenate([pos, jnp.full((pad,), t - 1, jnp.int32)])
    out = jax.lax.map(block, (cq_p.reshape(blocks, qb, -1), pos_p.reshape(blocks, qb)))
    return x + out.reshape(blocks * qb, -1)[:t]


def swiglu(y, w_in, w_out):
    g, u = jnp.split(jnp.dot(y, w_in, precision=HI), 2, axis=-1)
    return jnp.dot(jax.nn.silu(g) * u, w_out, precision=HI)


def router(cfg: dict, w: dict, y):
    """``share [T, router_experts]``: a token's weight for each expert of the
    whole router, 0 where it was not chosen."""
    z = _sizes(cfg)
    scores = jax.nn.sigmoid(jnp.dot(y, w["gate"], precision=HI))             # [T, E]
    biased = scores + w["expert_bias"]
    if z["groups"] > 1:
        t = y.shape[0]
        grouped = biased.reshape(t, z["groups"], z["e"] // z["groups"])
        marks = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)
        _, kept = jax.lax.top_k(marks, z["kept"])
        keep = jax.nn.one_hot(kept, z["groups"], dtype=jnp.float32).sum(axis=1) > 0
        # the published code puts 0, not minus infinity, where a group is dropped
        biased = jnp.where(keep[:, :, None], grouped, 0.0).reshape(t, z["e"])
    _, chosen = jax.lax.top_k(biased, z["k"])                                 # [T, k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    picked = picked * cfg.get("routed_scaling_factor", 1.0)
    return (jax.nn.one_hot(chosen, z["e"], dtype=jnp.float32) * picked[..., None]).sum(axis=1)


def routed_ff(cfg: dict, w: dict, y):
    """The held experts' part of the routed sum, and the shared expert."""
    z = _sizes(cfg)
    share = router(cfg, w, y)[:, z["first"]:z["first"] + z["held"]]           # [T, held]

    def one_expert(acc, inp):
        w_in, w_out, col = inp
        return acc + col[:, None] * swiglu(y, w_in, w_out), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y), (w["w_in"], w["w_out"], share.T))
    if z["shared"]:
        out = out + swiglu(y, w["shared_in"], w["shared_out"])
    return out


def layer(cfg: dict, w: dict, x, valid_len, routed: bool):
    z = _sizes(cfg)
    x = attention(cfg, w, x, valid_len)
    y = rms_norm(x, w["ffn_norm"], z["eps"])
    if routed:
        return x + routed_ff(cfg, w, y)
    return x + swiglu(y, w["w_in"], w["w_out"])


def _decode_cfg(cfg_items: tuple) -> dict:
    cfg = dict(cfg_items)
    if cfg.get("rope_scaling"):
        cfg["rope_scaling"] = json.loads(cfg["rope_scaling"])
    return cfg


@functools.lru_cache(maxsize=None)
def _programs(cfg_items: tuple, scale_items: tuple, served_dtype: str):
    """The jitted pieces for one configuration: embed, one layer of either
    kind with its weights made inside from the key (never all resident),
    head."""
    cfg = _decode_cfg(cfg_items)
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

    @functools.partial(jax.jit, static_argnames=("routed",))
    def one_layer(key, l, j, x, valid_len, routed: bool):
        w = {n: get(key, f"layers.attn.{n}", l) for n in ATTN_LEAVES}
        kind, leaves = ("moe", MOE_LEAVES) if routed else ("dense", DENSE_LEAVES)
        w.update({n: get(key, f"layers.{kind}.{n}", j) for n in leaves
                  if f"layers.{kind}.{n}" in shapes})
        return layer(cfg, w, x, valid_len, routed)

    @jax.jit
    def head(key, x, rows):
        x = rms_norm(x[rows], get(key, "norm"), eps)
        return jnp.dot(x, get(key, "lm_head"), precision=HI)

    return embed, one_layer, head


def cfg_items(cfg: dict) -> tuple:
    """The configuration's scalars, and its ``rope_scaling`` group as text, in
    a form a cache can key on."""
    items = {k: v for k, v in cfg.items() if not isinstance(v, (dict, list))}
    if cfg.get("rope_scaling"):
        items["rope_scaling"] = json.dumps(cfg["rope_scaling"], sort_keys=True)
    return tuple(sorted(items.items()))


def logits_at(cfg: dict, seed: int, ids, valid_len: int, rows, served_dtype="bfloat16"):
    """Logits ``[len(rows), vocab]`` for the sequence ``ids [T]`` (padded;
    ``valid_len`` real tokens) at positions ``rows``: the weights are the
    served values upcast (``served_dtype`` then float32), the arithmetic
    float32 at ``highest``."""
    scales = tuple(sorted(cfg.get("weight_scales", {}).items()))
    embed, one_layer, head = _programs(cfg_items(cfg), scales, str(served_dtype))
    key = weights.root_key(seed)
    x = embed(key, jnp.asarray(ids, jnp.int32))
    nd = cfg["first_k_dense_replace"]
    for l in range(cfg["num_hidden_layers"]):
        routed = l >= nd
        x = one_layer(key, l, l - nd if routed else l, x, jnp.int32(valid_len), routed=routed)
    return head(key, x, jnp.asarray(rows, jnp.int32))
