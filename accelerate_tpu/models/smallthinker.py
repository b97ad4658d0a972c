"""SmallThinker (``smallthinker``, SmallThinker-21BA3B-Instruct): a GQA
transformer whose layers are of two kinds - a few attend the whole context
with no position term, the rest rotate and see a sliding window - with
ReLU-gated routed experts behind every layer, chosen by a router that reads
the layer's INPUT, ahead of attention.

For layer ``l``, hidden ``x [T, h]``, no bias anywhere:

    r = x W_r                                float32: the router reads the
                                             residual stream BEFORE the
                                             attention norm
    y = RMSNorm(x, attn_norm);  q, k, v = y Wq, y Wk, y Wv   (no q/k norm)
    rope_layout[l] == 1:  q, k rotated (rotate-half, the whole head,
                          ``rope_theta``, no scaling);  0: no position term
    sliding_window_layout[l] == 0:  query i sees keys j <= i
                               1:  i - sliding_window_size < j <= i
    x = x + softmax(q k^T / sqrt(hd)) v Wo
    m = RMSNorm(x, ffn_norm)
    e_1..e_k = top_k(r);  w = softmax(r[e_1..e_k])            float32
    x = x + sum_i w_i * W_out[e_i](relu(g) * u),  [g | u] = m W_in[e_i]

then ``RMSNorm(x, norm)`` and the untied head. No shared expert, no dense
layer. ``rope_layout`` and ``sliding_window_layout`` are read each for what
it says; the published model sets them alike.

Two stacks, ``layers.full.<leaf>`` over the layers that see the whole
context and ``layers.window.<leaf>`` over those that see a window, each in
published order; the layer loop is unrolled, a layer's index into its stack
is static, and the expert product addresses ``(layer, expert)`` of the
stacked matrices in place (:mod:`..ops.moe`).

**What a served sequence keeps** (:class:`~.cache.CacheSpec`): two kinds of
paged layer (:class:`~.cache.PagedKind`), a pool and a block table each.
The ``full`` kind keeps every position of a request (pool leaves ``"k"`` /
``"v"``); the ``window`` kind the ``sliding_window_size`` before a
dispatch's first query and the dispatch's own (``"k_window"`` /
``"v_window"``): the engine gives a window block back once it lies wholly
behind the window. ``block_tables`` come in as ``[b, 2, max_blocks]``, the
kinds in the spec's order. A step against the cache hands back the
``step_counters`` of :func:`..ops.moe.step_counter_shapes`.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..modules import Model, ModelOutput
from ..ops.attention import attention
from ..ops import moe
from ..ops.fp8 import dense
from ..ops.layers import (
    attention_out,
    embed_tokens,
    fused_cross_entropy,
    layer_at,
    logit_rows,
    paged_step_frame,
    paged_write_attend,
    rms_norm,
    rotate_rope,
    shift_labels,
    untied_head,
)
from ..parallel.pipeline import remat_wrap
from .cache import CacheSpec, PagedKind, pool_leaf_names

_HI = jax.lax.Precision.HIGHEST

#: the cache kinds, in the order of the spec, the pools and ``block_tables``
KINDS = ("full", "window")


@dataclass
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int | None = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    #: per layer: 1 rotates queries and keys, 0 gives them no position term
    rope_layout: tuple = (0, 1, 1, 1) * 13
    #: per layer: 1 sees ``sliding_window_size`` positions, 0 the whole context
    sliding_window_layout: tuple = (0, 1, 1, 1) * 13
    sliding_window_size: int = 4096
    rope_theta: float = 1500000.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 16384
    tie_word_embeddings: bool = False
    remat: bool | str = False

    def __post_init__(self):
        self.rope_layout = tuple(int(v) for v in self.rope_layout)
        self.sliding_window_layout = tuple(int(v) for v in self.sliding_window_layout)
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        for name in ("rope_layout", "sliding_window_layout"):
            layout = getattr(self, name)
            if len(layout) != self.num_hidden_layers or set(layout) - {0, 1}:
                raise ValueError(
                    f"{name} names {len(layout)} layers with values {sorted(set(layout))}: "
                    f"want a 0 or a 1 for each of the {self.num_hidden_layers} layers")
        if not self.sliding_window_layout or self.sliding_window_layout[0]:
            raise ValueError(
                "sliding_window_layout starts with a window layer: the serving cache's "
                "first kind keeps the whole context (models/cache.py), and the published "
                "model's first layer does")
        if all(v == 0 for v in self.sliding_window_layout):
            raise ValueError(
                "sliding_window_layout names no window layer: that is one cache kind, "
                "which the other served models declare; not built twice")
        if self.sliding_window_size < 1:
            raise ValueError(f"sliding_window_size {self.sliding_window_size}: a window "
                             "holds the query's own position")
        if not 1 <= self.moe_num_active_primary_experts <= self.moe_num_primary_experts:
            raise ValueError(
                f"moe_num_active_primary_experts {self.moe_num_active_primary_experts} of "
                f"moe_num_primary_experts {self.moe_num_primary_experts}: a token picks "
                "distinct experts")
        if not self.moe_primary_router_apply_softmax:
            raise ValueError(
                "moe_primary_router_apply_softmax false (the 4B sibling's sigmoid router): "
                "built as published for SmallThinker-21BA3B-Instruct, whose chosen logits "
                "go through a softmax; the sigmoid's normalisation is not in this file")
        if not self.norm_topk_prob:
            raise ValueError("norm_topk_prob false: the weights are the softmax over the "
                             "chosen logits, which sums to 1 by construction")
        if self.tie_word_embeddings:
            raise ValueError("built as published for SmallThinker-21BA3B-Instruct: the "
                             "head is untied")

    @classmethod
    def tiny(cls, vocab_size=256, hidden_size=64, seq=512, **kw):
        """Five layers (full, window, window, full, window) over 8 experts,
        top 3, a window of 12 - for the CPU tests."""
        base = dict(
            vocab_size=vocab_size, hidden_size=hidden_size, num_hidden_layers=5,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_ffn_hidden_size=32, moe_num_primary_experts=8,
            moe_num_active_primary_experts=3, rope_layout=(0, 1, 1, 0, 1),
            sliding_window_layout=(0, 1, 1, 0, 1), sliding_window_size=12,
            max_position_embeddings=seq,
        )
        base.update(kw)
        return cls(**base)


#: training placement: every matrix over fsdp on its input dimension (the
#: experts themselves are not spread, ROADMAP Reach 2)
SMALLTHINKER_PARTITION_RULES = [
    (r"embed_tokens", P("tp", "fsdp")),
    (r"lm_head", P("fsdp", "tp")),
    (r"layers\.\w+\.(w_in|w_out)", P(None, None, "fsdp", None)),
    (r"layers\.\w+\.(wq|wk|wv|wo|gate)", P(None, "fsdp", None)),
    (r".*", P()),
]


def layer_plan(config: SmallThinkerConfig) -> list:
    """``[(kind, index in the kind's stack, rotated)]`` in published order."""
    seen = dict.fromkeys(KINDS, 0)
    out = []
    for windowed, rotated in zip(config.sliding_window_layout, config.rope_layout):
        kind = KINDS[windowed]
        out.append((kind, seen[kind], bool(rotated)))
        seen[kind] += 1
    return out


def cache_spec(config: SmallThinkerConfig) -> CacheSpec:
    c = config
    n_window = sum(c.sliding_window_layout)
    shape = dict(kv_heads=c.num_key_value_heads, head_dim=c.head_dim)
    return CacheSpec(
        paged_layers=c.num_hidden_layers, **shape,
        kinds=(PagedKind("full", c.num_hidden_layers - n_window, **shape),
               PagedKind("window", n_window, window=c.sliding_window_size, **shape)))


def step_counter_shapes(config: SmallThinkerConfig) -> dict:
    return moe.step_counter_shapes(config.num_hidden_layers, config.moe_num_primary_experts)


def init_smallthinker_params(key, config: SmallThinkerConfig, dtype=jnp.float32):
    c = config
    h, hd = c.hidden_size, c.head_dim
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    e, f = c.moe_num_primary_experts, c.moe_ffn_hidden_size
    keys = iter(jax.random.split(key, 24))

    def mat(*shape):
        scale = 1.0 / np.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def stack(n):
        return {
            "attn_norm": ones(n, h),
            "wq": mat(n, h, nh * hd),
            "wk": mat(n, h, nkv * hd),
            "wv": mat(n, h, nkv * hd),
            "wo": mat(n, nh * hd, h),
            "ffn_norm": ones(n, h),
            "gate": mat(n, h, e),
            # each expert's gate | up columns, and its down projection
            "w_in": mat(n, e, h, 2 * f),
            "w_out": mat(n, e, f, h),
        }

    n_window = sum(c.sliding_window_layout)
    return {
        "embed_tokens": (jax.random.normal(next(keys), (c.vocab_size, h)) * 0.02).astype(dtype),
        "norm": ones(h),
        "lm_head": mat(h, c.vocab_size),
        "layers": {"full": stack(c.num_hidden_layers - n_window), "window": stack(n_window)},
    }


# -- the parts, each under the scope the trace files it by ---------------------


@jax.named_scope("moe_router")
def _route(c, layer, x):
    """The choice of a layer's experts from its INPUT ``x [b, s, h]`` (the
    residual stream, un-normed): the gate's product, the top k and the
    softmax over the chosen logits, in float32. ``([b*s, k], [b*s, k])``."""
    b, s, h = x.shape
    logits = jnp.dot(x.reshape(b * s, h).astype(jnp.float32),
                     layer["gate"].astype(jnp.float32), precision=_HI)
    # softmax over all the experts, the top k of it, renormalised: equal to
    # the softmax over the k chosen logits (the sum is never 0: no guard)
    return moe.route(None, None, None, c.moe_num_active_primary_experts, True,
                 scoring="softmax", norm_eps=0.0, logits=logits)


@jax.named_scope("attn_proj")
def _qkv(c, layer, x, positions, rotated: bool):
    """q, k (rotated where the layer's ``rope_layout`` says so) and v of the
    normed residual; no norm on q or k."""
    b, s, _ = x.shape
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    y = rms_norm(x, layer["attn_norm"], c.rms_norm_eps)
    q = dense(y, layer["wq"]).reshape(b, s, nh, hd)
    k = dense(y, layer["wk"]).reshape(b, s, nkv, hd)
    v = dense(y, layer["wv"]).reshape(b, s, nkv, hd)
    if rotated:
        q, k = rotate_rope(q, positions, c.rope_theta), rotate_rope(k, positions, c.rope_theta)
    return q, k, v


@jax.named_scope("moe_experts")
def _experts(c, stack, i, x, experts, weights, live):
    """The ReLU-gated experts of layer ``i`` of ``stack`` over the normed
    ``x [b, s, h]``, as chosen ahead of attention; ``live [b, s]`` (or
    ``None``) keeps padding and dead lanes out of every expert. Returns
    ``(x + y, pairs [E] int32)``."""
    b, s, h = x.shape
    y = rms_norm(x, stack["ffn_norm"][i], c.rms_norm_eps).reshape(b * s, h)
    out, pairs = moe.expert_ffn(
        y, experts, weights, stack["w_in"], stack["w_out"],
        live=None if live is None else live.reshape(b * s), layer=i, activation="relu")
    return x + out.reshape(b, s, h), pairs


def smallthinker_apply(
    config: SmallThinkerConfig,
    params,
    input_ids,
    attention_mask=None,
    labels=None,
    paged_kv=None,
    block_tables=None,
    cache_positions=None,
    paged_write_mask=None,
    logit_positions=None,
):
    """Forward pass: whole sequences (training / eval / ``generate``), or -
    with ``paged_kv`` - one step against the engine's cache
    (:func:`_paged_step`)."""
    c = config
    if paged_kv is not None:
        return _paged_step(c, params, input_ids, paged_kv, block_tables,
                           cache_positions, paged_write_mask, logit_positions)
    b, s = input_ids.shape
    valid = None if attention_mask is None else attention_mask.astype(bool)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))

    def one_layer(x, kind, i, rotated):
        stack = params["layers"][kind]
        layer = layer_at(stack, i, but=("w_in", "w_out"))
        experts, weights = _route(c, layer, x)
        q, k, v = _qkv(c, layer, x, positions, rotated)
        with jax.named_scope("attn_kernel_" + kind):
            attn = attention(q, k, v, segment_mask=attention_mask, causal=True,
                             window=c.sliding_window_size if kind == "window" else 0)
        x = attention_out(layer, x, attn)
        return _experts(c, stack, i, x, experts, weights, valid)[0]

    x = embed_tokens(params, input_ids)
    with jax.named_scope("layers"):
        for kind, i, rotated in layer_plan(c):
            x = remat_wrap(
                functools.partial(one_layer, kind=kind, i=i, rotated=rotated), c.remat)(x)
    with jax.named_scope("head"):
        x = rms_norm(x, params["norm"], c.rms_norm_eps)
    out = ModelOutput(logits=untied_head(x, params["lm_head"]))
    if labels is not None:
        out["loss"] = fused_cross_entropy(
            x, params["lm_head"], shift_labels(labels),
            dense_fn=untied_head)
    return out


def _paged_step(c, params, input_ids, cache, block_tables, cache_positions, write_mask,
                logit_positions=None):
    """One step against the cache ``{"k", "v", "k_window", "v_window"[, and
    a ``_scale`` beside each]}`` (the contract:
    :func:`~..ops.layers.paged_step_frame`): a prefill chunk of one prompt, or
    one token of every slot; ``block_tables [b, 2, max_blocks]`` the full
    kind's table and the window kind's. A layer writes into its kind's pool
    and attends what its kind lets it see. A lane that is off routes to no
    expert; beside the logits come the step's ``step_counters``."""
    idx, positions, valid = paged_step_frame(input_ids, cache_positions, write_mask)
    tables = jnp.asarray(block_tables, jnp.int32)
    cache = dict(cache)
    names = {kind.name: pool_leaf_names(cache, kind, n == 0)
             for n, kind in enumerate(cache_spec(c).paged_kinds)}
    pairs = []
    x = embed_tokens(params, input_ids)
    with jax.named_scope("layers"):
        for kind, i, rotated in layer_plan(c):
            stack = params["layers"][kind]
            layer = layer_at(stack, i, but=("w_in", "w_out"))
            table = tables[:, KINDS.index(kind)]
            experts, weights = _route(c, layer, x)
            q, k, v = _qkv(c, layer, x, positions, rotated)
            attn, held = paged_write_attend(
                q, k, v, [cache[n] for n in names[kind]], i, table, positions, idx, valid,
                scope="attn_kernel_" + kind,
                window=c.sliding_window_size if kind == "window" else 0)
            cache.update(zip(names[kind], held))
            x = attention_out(layer, x, attn)
            x, layer_pairs = _experts(c, stack, i, x, experts, weights, valid)
            pairs.append(layer_pairs)
    with jax.named_scope("head"):
        x = rms_norm(logit_rows(x, logit_positions), params["norm"], c.rms_norm_eps)
    return ModelOutput(logits=untied_head(x, params["lm_head"]), paged_kv=cache,
                       step_counters=moe.step_counters(pairs))


class SmallThinkerForCausalLM:
    """Factory mirroring the transformers entry point."""

    @staticmethod
    def from_config(config: SmallThinkerConfig, seed: int = 0, dtype=jnp.float32) -> Model:
        from ..big_modeling import is_empty_init

        config = dataclasses.replace(config)  # private copy: apply_fn closes over it

        def make_params(key):
            return init_smallthinker_params(key, config, dtype=dtype)

        if is_empty_init():
            params = jax.eval_shape(make_params, jax.random.PRNGKey(seed))
        else:
            params = make_params(jax.random.PRNGKey(seed))

        def apply_fn(p, input_ids=None, attention_mask=None, labels=None, **kw):
            return smallthinker_apply(config, p, input_ids, attention_mask, labels, **kw)

        model = Model(
            apply_fn, params,
            partition_rules=SMALLTHINKER_PARTITION_RULES,
            name="SmallThinkerForCausalLM",
        )
        model.config = config
        model.stacked_params_prefix = "layers"
        model.supports_paged_kv = True
        model.cache_spec = cache_spec(config)
        model.step_counter_shapes = step_counter_shapes(config)
        model.serve_stats = {
            "moe_layers": config.num_hidden_layers,
            "moe_experts": config.moe_num_primary_experts,
            "moe_top_k": config.moe_num_active_primary_experts,
        }
        return model
