"""The plain training reference: Mistral's forward (``mistral.layer``) under
``jax.value_and_grad``, next-token cross entropy, and AdamW written from
its published equations — float32, ``highest`` matrix products, nothing of
the program under test. It follows the run's first steps on the same
batches from the same seeded weights and hands back, per step, the loss
and, per leaf, the gradient's norm (step 1) and the norm of the parameters'
change (after the last step).

It is as large as the program it follows, so it runs the same way a user
would have to: across the chips the cell has, parameters and optimizer
state split over them along their last dimension, the batch along its rows
(plain ``jax.sharding``; the compiler inserts the exchanges). Each block is
recomputed in the backward pass and attention goes query block by query
block, so that float32 scores never exist for a whole sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perfbench import weights
from perfbench.reference import mistral

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _layer_blocked(cfg: dict, w: dict, x):
    """``mistral.layer`` for a long sequence: the same equations, attention
    one block of queries at a time (each block recomputed in the backward
    pass). ``x [T, h]``."""
    t, _ = x.shape
    if t <= Q_BLOCK or t % Q_BLOCK:
        return mistral.layer(cfg, w, x, t)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // nh
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(t)
    y = mistral.rms_norm(x, w["attn_norm"], eps)
    q = jnp.dot(y, w["wq"], precision=HI).reshape(t, nh, hd)
    k = jnp.dot(y, w["wk"], precision=HI).reshape(t, nkv, hd)
    v = jnp.dot(y, w["wv"], precision=HI).reshape(t, nkv, hd)
    q, k = mistral.rope(q, pos, theta), mistral.rope(k, pos, theta)
    g = nh // nkv

    @jax.checkpoint
    def block(qb, pb):
        qb = qb.reshape(Q_BLOCK, nkv, g, hd)
        s = jnp.einsum("qngd,knd->ngqk", qb, k, precision=HI) / (hd ** 0.5)
        s = jnp.where((pos[None, :] <= pb[:, None])[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("ngqk,knd->qngd", p, v, precision=HI).reshape(Q_BLOCK, nh * hd)

    a = jax.lax.map(
        lambda qp: block(*qp),
        (q.reshape(t // Q_BLOCK, Q_BLOCK, nh, hd), pos.reshape(t // Q_BLOCK, Q_BLOCK)),
    ).reshape(t, nh * hd)
    x = x + jnp.dot(a, w["wo"], precision=HI)
    y = mistral.rms_norm(x, w["mlp_norm"], eps)
    gate = jax.nn.silu(jnp.dot(y, w["w_gate"], precision=HI))
    up = jnp.dot(y, w["w_up"], precision=HI)
    return x + jnp.dot(gate * up, w["w_down"], precision=HI)


def loss_fn(cfg: dict, params: dict, ids):
    """Mean next-token cross entropy over ``ids [B, T]`` (position ``t``
    predicts token ``t + 1``; the last position predicts nothing)."""
    x = params["embed_tokens"][ids]
    for l in range(cfg["num_hidden_layers"]):
        w = {n: params["layers"][n][l] for n in mistral.LAYER_LEAVES}
        x = jax.checkpoint(jax.vmap(lambda xs, w=w: _layer_blocked(cfg, w, xs)))(x)
    x = mistral.rms_norm(x, params["norm"], cfg["rms_norm_eps"])
    head = params.get("lm_head")
    if head is None:
        head = params["embed_tokens"].T
    labels = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
    b, t, _ = x.shape

    @jax.checkpoint
    def picked_logp(xc, lc):
        logp = jax.nn.log_softmax(jnp.dot(xc, head, precision=HI), axis=-1)
        return jnp.take_along_axis(logp, lc[..., None], axis=-1)[..., 0]

    if t > Q_BLOCK and t % Q_BLOCK == 0:
        # the vocabulary-wide logits one block of positions at a time
        n = t // Q_BLOCK
        xs = x.reshape(b, n, Q_BLOCK, -1).swapaxes(0, 1)
        ls = labels.reshape(b, n, Q_BLOCK).swapaxes(0, 1)
        picked = jax.lax.map(lambda a: picked_logp(*a), (xs, ls)).swapaxes(0, 1).reshape(b, t)
    else:
        picked = picked_logp(x, labels)
    return -jnp.sum(picked[:, :-1]) / (b * (t - 1))


def adamw(params, grads, m, v, step, lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-4):
    """AdamW (Loshchilov & Hutter): decoupled weight decay, bias-corrected
    moments. ``step`` counts from 1."""
    def one(p, g, m_, v_):
        m2 = b1 * m_ + (1 - b1) * g
        v2 = b2 * v_ + (1 - b2) * g * g
        mh = m2 / (1 - b1 ** step)
        vh = v2 / (1 - b2 ** step)
        return p - lr * (mh / (jnp.sqrt(vh) + eps) + weight_decay * p), m2, v2
    out = jax.tree.map(one, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def sumsq_tree(tree: dict) -> dict:
    """Per leaf, the sum of squares; a stacked leaf ``[L, ...]`` keeps its
    layer axis, so every layer counts as a leaf of its own. Traceable."""
    def one(name, x):
        x = x.astype(jnp.float32)
        if name.startswith(weights.STACKED_PREFIX):
            return jnp.sum(x * x, axis=tuple(range(1, x.ndim)))
        return jnp.sum(x * x)
    return {name: one(name, x) for name, x in weights.flat_names(tree).items()}


def leaf_norms(sumsq: dict) -> dict:
    """``{leaf name or 'layers.<leaf>.<l>': norm}`` from ``sumsq_tree``."""
    out = {}
    for name, ss in sumsq.items():
        ss = np.sqrt(np.asarray(ss, np.float64))
        if ss.ndim:
            for l, val in enumerate(ss):
                out[f"{name}.{l}"] = float(val)
        else:
            out[name] = float(ss)
    return out


def _shardings(abstract: dict, mesh: Mesh) -> dict:
    n = mesh.devices.size

    def spec(a):
        if a.ndim >= 2 and a.shape[-1] % n == 0:
            return NamedSharding(mesh, P(*([None] * (a.ndim - 1)), "x"))
        return NamedSharding(mesh, P())

    return jax.tree.map(spec, abstract)


def follow(cfg: dict, seed: int, batches: list, lr: float, devices=None) -> dict:
    """Train ``len(batches)`` steps from the seeded weights. Returns
    ``{"losses": [...], "grad_norms": {leaf: norm} (step 1),
    "update_norms": {leaf: norm} (after the last step)}``."""
    devices = list(devices or jax.devices())
    rows = batches[0].shape[0]
    while rows % len(devices):
        devices = devices[: len(devices) // 2]
    mesh = Mesh(np.array(devices), ("x",))
    abstract = weights.unflatten({
        name: jax.ShapeDtypeStruct(shape, jnp.float32)
        for name, shape in mistral.leaf_shapes(cfg).items()
    })
    shard = _shardings(abstract, mesh)
    rows_sh = NamedSharding(mesh, P("x", None))

    def step(params, m, v, ids, t):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(cfg, p, ids))(params)
        params2, m2, v2 = adamw(params, grads, m, v, t, lr)
        return loss, sumsq_tree(grads), params2, m2, v2

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p), out_shardings=shard)
    params = weights.make_tree(seed, abstract, out_shardings=shard)
    m, v = zeros(params), zeros(params)
    losses, grad_norms = [], None
    for i, ids in enumerate(batches):
        ids = jax.device_put(jnp.asarray(ids, jnp.int32), rows_sh)
        loss, gss, params, m, v = step(params, m, v, ids, jnp.float32(i + 1))
        losses.append(float(loss))
        if i == 0:
            grad_norms = leaf_norms(gss)
    for leaf in jax.tree.leaves((m, v)):
        leaf.delete()
    params0 = weights.make_tree(seed, abstract, out_shardings=shard)
    delta = jax.jit(lambda a, b: sumsq_tree(jax.tree.map(lambda x, y: x - y, a, b)))(
        params, params0)
    update_norms = leaf_norms(delta)
    for leaf in jax.tree.leaves((params, params0)):
        leaf.delete()
    return {"losses": losses, "grad_norms": grad_norms, "update_norms": update_norms}
