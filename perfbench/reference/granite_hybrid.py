"""Granite-4.0-H's forward pass, plain: ``granitemoehybrid`` with no experts
— Mamba-2 layers and a few attention layers in the order ``layer_types``
gives, every layer followed by the shared gated MLP, the four Granite
multipliers, no position term, the embedding as the head.

Straightforward ``jax.numpy`` in float32 with matrix products at
``highest`` precision. The Mamba-2 layer is the **token-by-token
recurrence** (a ``lax.scan`` over time) — no chunks, no carried state
between calls, no kernel — so that the program's chunked scan, its carry
across prefill chunks and its decode-time state update are each held
against something that has none of them. Attention is full and causal. No
cache, no batching, nothing imported from the program under test. Weights
are made from the seed by ``perfbench.weights``, one layer at a time. The
module's contract is in ``perfbench/README.md``.

Per layer, with ``r`` the ``residual_multiplier``:

    x <- x + r * mixer(RMSNorm(x));   x <- x + r * W_out(silu(g) * u),  [g, u] = W_in RMSNorm(x)

Mamba-2 mixer (one group; ``H`` heads of ``P``, state ``N``):

    [z, xBC] = W_in y, dt = W_dt y (the published in_proj's columns);  xBC <- silu(conv1d_causal_depthwise(xBC) + b);  x | B | C = xBC
    D_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
    S_t = exp(D_t A) S_{t-1} + D_t x_t (outer) B_t;   y_t = S_t C_t + D x_t
    out = W_out(w_norm * RMSNorm_{d_inner}(y * silu(z)))

Attention mixer: ``softmax(q k^T * attention_multiplier)``, causal, grouped
queries, no rotary. ``x0 = embedding_multiplier * E[ids]``; ``logits =
RMSNorm(x) E^T / logits_scaling``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench import weights

HI = jax.lax.Precision.HIGHEST

MLP_LEAVES = ("mlp_norm", "w_in", "w_out")
MAMBA_LEAVES = ("norm", "in_proj", "dt_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                "gate_norm", "out_proj") + MLP_LEAVES
ATTENTION_LEAVES = ("norm", "wq", "wk", "wv", "wo") + MLP_LEAVES


def _sizes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    d_inner = cfg["mamba_expand"] * h
    return {
        "h": h, "ff": cfg["shared_intermediate_size"], "v": cfg["vocab_size"],
        "nh": cfg["num_attention_heads"], "nkv": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or h // cfg["num_attention_heads"],
        "d_inner": d_inner, "n": cfg["mamba_d_state"], "mh": cfg["mamba_n_heads"],
        "p": cfg["mamba_d_head"], "k": cfg["mamba_d_conv"],
        "conv": d_inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"],
    }


def _kinds(cfg: dict) -> list:
    """``[(kind, index among the layers of its kind)]`` in published order."""
    seen = {"mamba": 0, "attention": 0}
    out = []
    for kind in cfg["layer_types"][: cfg["num_hidden_layers"]]:
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


def leaf_shapes(cfg: dict) -> dict:
    """Leaf name -> shape, as the program's parameter tree names them: two
    stacks, ``layers.mamba.*`` and ``layers.attention.*``; projections
    ``[in, out]``; the convolution's taps ``[d_conv, channels]``; the
    published ``in_proj`` ``[h, 2*d_inner + 2N + H]`` as its columns ``z |
    x B C`` (``in_proj``) and ``dt`` (``dt_proj``)."""
    z = _sizes(cfg)
    kinds = [k for k, _ in _kinds(cfg)]
    nm, na = kinds.count("mamba"), kinds.count("attention")
    h, ff = z["h"], z["ff"]
    shapes = {"embed_tokens": (z["v"], h), "norm": (h,)}
    for kind, n in (("mamba", nm), ("attention", na)):
        shapes.update({
            f"layers.{kind}.norm": (n, h),
            f"layers.{kind}.mlp_norm": (n, h),
            f"layers.{kind}.w_in": (n, h, 2 * ff),
            f"layers.{kind}.w_out": (n, ff, h),
        })
    shapes.update({
        "layers.mamba.in_proj": (nm, h, z["d_inner"] + z["conv"]),
        "layers.mamba.dt_proj": (nm, h, z["mh"]),
        "layers.mamba.conv_w": (nm, z["k"], z["conv"]),
        "layers.mamba.conv_b": (nm, z["conv"]),
        "layers.mamba.dt_bias": (nm, z["mh"]),
        "layers.mamba.A_log": (nm, z["mh"]),
        "layers.mamba.D": (nm, z["mh"]),
        "layers.mamba.gate_norm": (nm, z["d_inner"]),
        "layers.mamba.out_proj": (nm, z["d_inner"], h),
        "layers.attention.wq": (na, h, z["nh"] * z["hd"]),
        "layers.attention.wk": (na, h, z["nkv"] * z["hd"]),
        "layers.attention.wv": (na, h, z["nkv"] * z["hd"]),
        "layers.attention.wo": (na, z["nh"] * z["hd"], h),
    })
    return shapes


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mlp(cfg: dict, w: dict, x):
    y = rms_norm(x, w["mlp_norm"], cfg["rms_norm_eps"])
    g, u = jnp.split(jnp.dot(y, w["w_in"], precision=HI), 2, axis=-1)
    out = jnp.dot(jax.nn.silu(g) * u, w["w_out"], precision=HI)
    return x + cfg["residual_multiplier"] * out


def mamba_mixer(cfg: dict, w: dict, y, state_dtype: str = "float32"):
    """``y [T, h]`` (normed) -> the mixer's output ``[T, h]``, token by
    token from a zero state; ``state_dtype`` as :func:`logits_at` has it."""
    z = _sizes(cfg)
    t = y.shape[0]
    gate, xbc = jnp.split(jnp.dot(y, w["in_proj"], precision=HI), [z["d_inner"]], axis=-1)
    dt = jnp.dot(y, w["dt_proj"], precision=HI)
    # causal depthwise convolution: tap k-1 multiplies the current token
    padded = jnp.concatenate([jnp.zeros((z["k"] - 1, z["conv"]), jnp.float32), xbc])
    conv = w["conv_b"] + sum(padded[j:j + t] * w["conv_w"][j] for j in range(z["k"]))
    xbc = jax.nn.silu(conv)
    xs, b_mat, c_mat = jnp.split(xbc, [z["d_inner"], z["d_inner"] + z["n"]], axis=-1)
    xs = xs.reshape(t, z["mh"], z["p"])
    delta = jax.nn.softplus(dt + w["dt_bias"])               # [T, H]
    a = -jnp.exp(w["A_log"])                                 # [H]

    def step(state, inp):
        x_t, b_t, c_t, d_t = inp
        state = (jnp.exp(d_t * a)[:, None, None] * state
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        if state_dtype != "float32":
            # what is carried, as the narrower type holds it (ties to even);
            # an operation of its own, which no compiler folds away as it
            # may a pair of conversions
            fi = jnp.finfo(state_dtype)
            state = jax.lax.reduce_precision(state, fi.nexp, fi.nmant)
        return state, jnp.einsum("hpn,n->hp", state, c_t, precision=HI)

    state0 = jnp.zeros((z["mh"], z["p"], z["n"]), jnp.float32)
    _, ys = jax.lax.scan(step, state0, (xs, b_mat, c_mat, delta))
    ys = (ys + w["D"][:, None] * xs).reshape(t, z["d_inner"])
    gated = ys * jax.nn.silu(gate)
    return jnp.dot(rms_norm(gated, w["gate_norm"], cfg["rms_norm_eps"]), w["out_proj"], precision=HI)


def attention_mixer(cfg: dict, w: dict, y, valid_len):
    z = _sizes(cfg)
    t = y.shape[0]
    nh, nkv, hd = z["nh"], z["nkv"], z["hd"]
    q = jnp.dot(y, w["wq"], precision=HI).reshape(t, nkv, nh // nkv, hd)
    k = jnp.dot(y, w["wk"], precision=HI).reshape(t, nkv, hd)
    v = jnp.dot(y, w["wv"], precision=HI).reshape(t, nkv, hd)
    s = jnp.einsum("qngd,knd->ngqk", q, k, precision=HI) * cfg["attention_multiplier"]
    pos = jnp.arange(t)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < valid_len)
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("ngqk,knd->qngd", p, v, precision=HI).reshape(t, nh * hd)
    return jnp.dot(a, w["wo"], precision=HI)


def layer(cfg: dict, kind: str, w: dict, x, valid_len, state_dtype: str = "float32"):
    """One layer on ``x [T, h]`` (positions ``0..T-1``; rows ``>=
    valid_len`` are padding: causality keeps them out of every valid row)."""
    y = rms_norm(x, w["norm"], cfg["rms_norm_eps"])
    mixed = (mamba_mixer(cfg, w, y, state_dtype) if kind == "mamba"
             else attention_mixer(cfg, w, y, valid_len))
    return mlp(cfg, w, x + cfg["residual_multiplier"] * mixed)


@functools.lru_cache(maxsize=None)
def _programs(cfg_items: tuple, layer_types: tuple, scale_items: tuple, served_dtype: str,
              state_dtype: str = "float32"):
    """The jitted pieces for one configuration and one ``state_dtype``:
    embed, one layer of each kind (weights made inside from the key, never
    all resident), head."""
    cfg = dict(cfg_items, layer_types=list(layer_types))
    scales = dict(scale_items)
    shapes = leaf_shapes(cfg)
    served = jnp.dtype(served_dtype)

    def get(key, name, l=None):
        return weights.leaf(key, name, shapes[name], served, layer=l,
                            scales=scales).astype(jnp.float32)

    @jax.jit
    def embed(key, ids):
        return cfg["embedding_multiplier"] * get(key, "embed_tokens")[ids]

    def one_layer(kind, leaves):
        @jax.jit
        def run(key, l, x, valid_len):
            w = {n: get(key, f"layers.{kind}.{n}", l) for n in leaves}
            return layer(cfg, kind, w, x, valid_len, state_dtype)
        return run

    @jax.jit
    def head(key, x, rows):
        x = rms_norm(x[rows], get(key, "norm"), cfg["rms_norm_eps"])
        return jnp.dot(x, get(key, "embed_tokens").T, precision=HI) / cfg["logits_scaling"]

    return embed, {"mamba": one_layer("mamba", MAMBA_LEAVES),
                   "attention": one_layer("attention", ATTENTION_LEAVES)}, head


def logits_at(cfg: dict, seed: int, ids, valid_len: int, rows, served_dtype="bfloat16",
              state_dtype="float32"):
    """Logits ``[len(rows), vocab]`` of the sequence ``ids [T]`` (padded;
    ``valid_len`` real tokens) at positions ``rows``, layer by layer.
    ``state_dtype`` other than float32 rounds the recurrent state to that
    type once a token, after its update (``y_t`` reads the rounded state),
    and changes nothing else: what a program does that keeps the state
    narrower than the configuration states, on these very tokens. The check
    reads from it which way and how far such a state moves each served
    log-probability (``check.served``: ``narrow_state_share``)."""
    items = tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (dict, list))))
    scales = tuple(sorted(cfg.get("weight_scales", {}).items()))
    embed, layers, head = _programs(
        items, tuple(cfg["layer_types"][: cfg["num_hidden_layers"]]), scales, str(served_dtype),
        str(jnp.dtype(state_dtype)))
    key = weights.root_key(seed)
    x = embed(key, jnp.asarray(ids, jnp.int32))
    for kind, i in _kinds(cfg):
        x = layers[kind](key, i, x, jnp.int32(valid_len))
    return head(key, x, jnp.asarray(rows, jnp.int32))
