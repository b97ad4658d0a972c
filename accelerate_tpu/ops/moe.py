"""Routed experts without a capacity: a router that scores every expert
(a sigmoid with a selection bias, or a softmax over all of them) and keeps
the top ``k``, and the dropless expert product.

    s = sigmoid(x W_g)   or   softmax(x W_g) over all E    [T, E], float32
    chosen = top_k(s + expert_bias)                      selection only
    weight = s[chosen] / (sum s[chosen] + 1e-6) * scale  the un-biased scores
    y = sum_i weight_i * W_out[e_i](act(g) * u),  [g | u] = x W_in[e_i]
                                                         act: silu, or relu

The (token, choice) pairs are grouped by expert — a stable sort of the
``T * k`` expert ids — and each group is multiplied by its own expert's
matrices in one grouped product (:func:`grouped_matmul`). Shapes are static
(``T * k`` pairs whatever the routing); no pair is dropped, whatever the
imbalance: all tokens to one expert is one group of ``T * k`` rows. A token
that ``live`` switches off routes nowhere: its pairs sort behind every
group, belong to none, add nothing to ``counts`` and cost no expert's
weights a read.

:mod:`..models.mixtral`'s ``moe_ffn`` (capacity-bounded buffers, a softmax
router, the GSPMD ``ep`` layout, trained) is another layer and is left as
it is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import rms_norm

_HI = jax.lax.Precision.HIGHEST

#: rows, contraction and columns of one grid step of the grouped product on
#: the chip: a whole 128-row tile of pairs against ``[k, 512]`` of one
#: expert (2 MB in bfloat16 at k = 2048), so that an expert's matrix passes
#: through VMEM once for every 128 pairs it was given. A contraction the tile
#: does not divide takes the tile halved until it does (k = 7168: 1024): the
#: product masks a part tile's remainder at every step, which read 24 %
#: slower at DeepSeek-V3's decode shape (PERF.md section 6, PR 42)
_GMM_TILING = (128, 2048, 512)


#: what an expert's gate half may go through (``expert_ffn(activation=)``)
_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def default_moe_impl() -> str:
    """The Pallas grouped product on a TPU backend, ``jax.lax.ragged_dot``
    elsewhere — a static choice by platform, like ``default_ssm_impl``."""
    return "gmm" if jax.default_backend() == "tpu" else "ragged"


def route(x, w_gate, expert_bias, top_k: int, norm_topk_prob: bool = True,
          routed_scaling_factor: float = 1.0, scoring: str = "sigmoid",
          n_group: int = 1, topk_group: int = 1, norm_eps: float = 1e-6, logits=None):
    """``x [T, h]`` -> ``(experts [T, k] int32, weights [T, k] float32)``.
    The gate's product, the scores and the top-k run in float32 (in
    bfloat16 two scores tie). ``scoring``: ``"sigmoid"``, each expert scored
    alone, or ``"softmax"`` over all the experts. ``expert_bias [E]`` (or
    ``None`` where the model has none) moves which experts are chosen and
    never the weights of those chosen. ``n_group`` above 1 limits the choice
    to groups (DeepSeek-V3's ``noaux_tc``): the experts are ``n_group``
    groups of consecutive ones, a group's mark is the sum of its two best
    biased scores, and the top ``k`` are taken among the ``topk_group`` best
    groups; the others' biased scores count as 0, not as minus infinity, as
    the published code has it. At ``n_group`` 1 nothing of that is traced.
    ``norm_eps`` guards the renormalisation's sum. ``logits [T, E]``
    (float32), where the caller has made the gate's product itself — from
    another tensor than the one the experts multiply, say — take the place of
    ``x W_g``: ``x`` and ``w_gate`` are then not read (pass ``None``)."""
    f32 = jnp.float32
    if logits is None:
        logits = jnp.dot(x.astype(f32), w_gate.astype(f32), precision=_HI)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}: want sigmoid or softmax")
    biased = scores if expert_bias is None else scores + expert_bias.astype(f32)
    if n_group > 1:
        t, e = biased.shape
        grouped = biased.reshape(t, n_group, e // n_group)
        marks = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)                    # [T, groups]
        _, kept = jax.lax.top_k(marks, topk_group)
        keep = (kept[:, :, None] == jnp.arange(n_group, dtype=kept.dtype)).any(axis=1)
        biased = jnp.where(keep[:, :, None], grouped, 0.0).reshape(t, e)
    _, experts = jax.lax.top_k(biased, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + norm_eps)
    return experts.astype(jnp.int32), weights * routed_scaling_factor


def grouped_matmul(lhs, rhs, group_sizes, impl: str | None = None,
                   interpret: bool = False):
    """``lhs[rows of group e] @ rhs[e]`` for every group: ``lhs [m, k]``
    sorted by group, ``rhs [E, k, n]``, ``group_sizes [E]`` int32 summing to
    at most ``m``. Rows behind the last group are not computed; what comes
    back for them is unspecified."""
    if impl is None:
        impl = default_moe_impl()
    if impl == "ragged":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))
    if impl == "gmm":
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        m, k = lhs.shape
        tm, tk, tn = _GMM_TILING
        tk = min(tk, k)
        while k % tk and tk % 256 == 0:
            tk //= 2
        pad = (-m) % tm
        if pad:
            lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        out = gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype,
                  (tm, tk, min(tn, rhs.shape[-1])), None, None, False, interpret)
        return out[:m] if pad else out
    raise ValueError(f"unknown grouped_matmul impl {impl!r}")


def expert_ffn(x, experts, weights, w_in, w_out, live=None, layer: int | None = None,
               impl: str | None = None, interpret: bool = False,
               held: tuple | None = None, activation: str = "silu"):
    """The dropless expert product. ``x [T, h]``; ``experts`` / ``weights``
    ``[T, k]`` from :func:`route`; ``w_in [E, h, 2f]`` (gate | up),
    ``w_out [E, f, h]``; ``live [T]`` bool (``None``: every token). With
    ``layer`` (a static index) the matrices are a model's stacks ``[layers,
    E, ...]`` and the product addresses ``(layer, expert)`` in them: no
    layer's experts are sliced out to be multiplied. Returns ``(y [T, h],
    counts [E] int32)``: the weighted sum of each token's experts, zero for
    a token that is not live, and the pairs each expert was given.
    ``activation``: what the gate's half goes through before it multiplies
    the up half, ``"silu"`` or ``"relu"``.

    ``held = (first, count)``: this chip's share of an expert-parallel
    layer. The router scored all the experts and ``experts`` names any of
    them; the matrices are those of experts ``first .. first + count - 1``
    alone (``w_in [count, ...]``). A pair whose expert lives elsewhere takes
    the dead lane — it sorts behind every group, reads no weights and adds
    nothing — and ``counts [count]`` is over the experts held: the caller
    has the pairs routed elsewhere as its live pairs less ``counts.sum()``.
    The sum that comes back is this chip's partial one; nothing stands in
    for the other chips or the exchange with them."""
    t, k = experts.shape
    n_experts = w_in.shape[-3]
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unknown expert activation {activation!r}: want one of "
                         f"{', '.join(_ACTIVATIONS)}")
    if held is not None:
        first, count = held
        if count != n_experts:
            raise ValueError(f"held {held}: the matrices are of {n_experts} experts")
        local = experts - first
        experts = jnp.where((local >= 0) & (local < count), local, n_experts)
    if live is not None:
        # a dead token's pairs get an expert id past the last: they sort
        # behind every group and belong to none
        experts = jnp.where(live[:, None], experts, n_experts)
    flat = experts.reshape(t * k)
    order = jnp.argsort(flat, stable=True)                     # pairs by expert
    counts = (flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype)).sum(
        axis=0, dtype=jnp.int32)
    grouped = x[order // k]                                    # [T*k, h]
    sizes = counts
    if impl is None:
        impl = default_moe_impl()
    if layer is not None and impl == "ragged":
        w_in, w_out = w_in[layer], w_out[layer]
    elif layer is not None:
        # every (layer, expert) is a group; only this layer's are given rows
        n_layers = w_in.shape[0]
        sizes = jnp.zeros((n_layers, n_experts), jnp.int32).at[layer].set(counts).reshape(-1)
        w_in = w_in.reshape(n_layers * n_experts, *w_in.shape[2:])
        w_out = w_out.reshape(n_layers * n_experts, *w_out.shape[2:])
    gate, up = jnp.split(grouped_matmul(grouped, w_in, sizes, impl, interpret), 2, axis=-1)
    out = grouped_matmul((_ACTIVATIONS[activation](gate) * up).astype(x.dtype), w_out, sizes,
                         impl, interpret)
    in_a_group = jnp.arange(t * k) < counts.sum()
    out = jnp.where(in_a_group[:, None], out.astype(jnp.float32), 0.0)
    out = out * weights.reshape(t * k)[order][:, None]
    # back to (token, choice) order, then the sum over a token's choices
    back = jnp.argsort(order)
    return out[back].reshape(t, k, -1).sum(axis=1).astype(x.dtype), counts


def routed_ffn(stack, i, x, live, eps: float, *router, **router_kw):
    """A layer's routed feed-forward as LFM2 and SDAR have it, the residual
    added: ``RMSNorm(x, ffn_norm)`` routed by ``gate`` (and ``expert_bias``,
    where the stack has one) under ``moe_router`` - ``router`` /
    ``router_kw`` are :func:`route`'s arguments from ``top_k`` on -, then
    layer ``i``'s experts of the stack under ``moe_experts``. ``x [b, s, h]``;
    ``live [b, s]`` (or ``None``) keeps padding and dead lanes out of every
    expert. Returns ``(x, pairs [E] int32)``."""
    b, s, h = x.shape
    with jax.named_scope("moe_router"):
        y = rms_norm(x, stack["ffn_norm"][i], eps).reshape(b * s, h)
        gate = stack["gate"][i]
        bias = stack["expert_bias"][i] if "expert_bias" in stack else None
        experts, weights = route(y, gate, bias, *router, **router_kw)
    with jax.named_scope("moe_experts"):
        out, pairs = expert_ffn(
            y, experts, weights, stack["w_in"], stack["w_out"],
            live=None if live is None else live.reshape(b * s), layer=i)
        return x + out.reshape(b, s, h), pairs


def step_counter_shapes(moe_layers: int, experts: int, extra=()) -> dict:
    """What a routed model's step against the cache hands back beside its
    logits (``step_counters``), name -> shape (int32): the serving engine sums
    each over the steps it dispatched. ``extra``: a family's further totals."""
    totals = ("moe_dispatches_total", "moe_pairs_routed_total",
              "moe_experts_touched_total", "moe_load_max_total", *extra)
    return {"moe_expert_pairs": (moe_layers, experts), **dict.fromkeys(totals, ())}


def step_counters(pairs) -> dict:
    """``pairs``, :func:`expert_ffn`'s counts ``[E]`` of each routed layer of
    one step -> the counters of :func:`step_counter_shapes`."""
    pairs = jnp.stack(pairs).astype(jnp.int32)
    return {
        "moe_expert_pairs": pairs,
        "moe_dispatches_total": jnp.ones((), jnp.int32),
        "moe_pairs_routed_total": pairs.sum(),
        "moe_experts_touched_total": (pairs > 0).sum(dtype=jnp.int32),
        "moe_load_max_total": pairs.max(axis=1).sum(),
    }
