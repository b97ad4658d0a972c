"""DeepSeek-V3 (``deepseek_v3``): multi-head latent attention over a
compressed cache, a few leading dense layers, then routed experts under a
group-limited sigmoid router with a shared expert beside them.

For hidden ``x [T, h]`` (no bias anywhere):

    y    = RMSNorm(x, attn_norm)
    c_q  = RMSNorm(y W_qa, q_norm)
    q    = c_q W_qb -> [T, heads, nope + rope] = [q_nope | q_pe]
    [c | k_pe] = y W_kva;  c = RMSNorm(c, kv_norm);  k_pe = rope(k_pe)
    [k_nope | v] = c W_kvb -> [T, heads, nope + v]
    s_pj = (q_nope_p . k_nope_j + rope(q_pe_p) . k_pe_j) * scale,  j <= p
    x    = x + concat_heads(softmax_j(s_pj) v_j) W_o

``c`` (``kv_lora_rank`` entries) and the one rotated key ``k_pe`` all heads
share are everything a later token needs of this one: the cache keeps that
vector and nothing per head (:class:`.cache.CacheSpec` ``latent_rank``).
Against the cache the step computes the same numbers **absorbed**: ``q_lat =
q_nope W_kvb_k^T`` carries each head's query into ``c``'s coordinates, the
scores are ``(q_lat . c_j + rope(q_pe) . k_pe_j) * scale``, the softmax sums
``c_j`` itself, and ``W_kvb_v`` carries that sum back to the head
(:func:`..ops.paged_attention.latent_attention`). Whole sequences (training,
the tests) take the expanded form above. The rotation is YaRN-scaled
(:func:`..ops.layers.yarn_frequencies`; rotate-half over the ``rope`` lanes,
angles from absolute positions in float32) and ``scale = (nope + rope)^-0.5
* m^2`` with ``m`` :func:`..ops.layers.yarn_mscale` of ``mscale_all_dim``.

Feed-forward: SwiGLU of ``intermediate_size`` in the first
``first_k_dense_replace`` layers; behind them

    s = sigmoid(y' W_g), float32;  b = s + e_bias (selection only)
    keep the topk_group groups whose two best b sum highest; e_1..e_k = top_k(b there)
    w_i = s[e_i] / (sum_i s[e_i] + 1e-20) * routed_scaling_factor
    x = x + shared(y') + sum_i w_i * expert_{e_i}(y')

(:func:`..ops.moe.route` with ``n_group``). **The experts held here may be a
chip's share** of an expert-parallel layer: ``n_routed_experts`` counts the
matrices this model holds, ``router_experts`` (the published
``n_routed_experts``; by default the same) the router's outputs, and
``first_held_expert`` where the held range starts. The router scores them
all, pairs of experts held elsewhere are left out
(:func:`..ops.moe.expert_ffn` ``held=``), the shared expert and everything
else is whole, and the partial sum goes on: nothing stands in for the other
chips or the exchange with them.

Three stacks: ``layers.attn.*`` over every layer (the attention and both
norms), ``layers.dense.*`` over the leading dense layers, ``layers.moe.*``
over the routed ones; the layer loop is unrolled so that the expert product
addresses ``(layer, expert)`` in place. A step against the cache hands back
:mod:`.lfm2`'s ``step_counters`` — over the experts HELD — and
``moe_pairs_elsewhere_total`` beside them.

Not built: the multi-token-prediction block (``num_nextn_predict_layers``
names a further block that drafts one token ahead; the served logits do not
depend on it, the key is read by nothing); the published training losses
(``labels`` give the next-token loss).
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
from ..ops import moe
from ..ops.fp8 import dense
from ..ops.layers import (
    attention_out,
    causal_mask,
    dot_product_attention,
    embed_tokens,
    fused_cross_entropy,
    layer_at,
    logit_rows,
    paged_step_frame,
    rms_norm,
    shift_labels,
    untied_head,
    write_paged_latent,
    yarn_frequencies,
    yarn_mscale,
)
from ..ops.paged_attention import latent_attention
from ..parallel.pipeline import remat_wrap
from .cache import CacheSpec

#: what a YaRN ``rope_scaling`` group has to state (the betas and the two
#: ``mscale`` keys have the published code's defaults)
_YARN_REQUIRED = ("factor", "original_max_position_embeddings")


@dataclass
class DeepseekV3Config:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 3
    moe_layer_freq: int = 1
    #: routed experts whose matrices this model holds (the published 256, or
    #: a chip's share of them)
    n_routed_experts: int = 256
    #: the router's outputs, as published (``None``: ``n_routed_experts``)
    router_experts: int | None = None
    #: the first expert of the held range (a rank of ``ep`` holds
    #: ``[rank * n_routed_experts, (rank + 1) * n_routed_experts)``)
    first_held_expert: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    hidden_act: str = "silu"
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: the published YaRN group, or ``None`` for the plain rotation
    rope_scaling: dict | None = None
    max_position_embeddings: int = 163840
    tie_word_embeddings: bool = False
    remat: bool | str = False

    def __post_init__(self):
        if self.router_experts is None:
            self.router_experts = self.n_routed_experts
        for key, built in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                           ("hidden_act", "silu"), ("attention_bias", False),
                           ("tie_word_embeddings", False), ("moe_layer_freq", 1)):
            if getattr(self, key) != built:
                raise ValueError(
                    f"deepseek_v3 with {key} {getattr(self, key)!r}: built as published "
                    f"for DeepSeek-V3, {key} {built!r}")
        if self.rope_scaling is not None:
            kind = self.rope_scaling.get("type", self.rope_scaling.get("rope_type"))
            if kind != "yarn":
                raise ValueError(
                    f"deepseek_v3 with rope_scaling type {kind!r}: only 'yarn' (or no "
                    "rope_scaling) is built")
            missing = [k for k in _YARN_REQUIRED if k not in self.rope_scaling]
            if missing:
                raise ValueError(f"rope_scaling of type yarn lacks {missing}")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} of "
                f"num_hidden_layers {self.num_hidden_layers}")
        e, g = self.router_experts, self.n_group
        if g < 1 or e % g or not 1 <= self.topk_group <= g:
            raise ValueError(
                f"n_group {g} / topk_group {self.topk_group} of {e} router outputs: the "
                "groups are equal runs of consecutive experts, some of them kept")
        if g > 1 and e // g < 2:
            raise ValueError(f"n_group {g} of {e} experts: a group's mark is its two best")
        if not 1 <= self.num_experts_per_tok <= self.topk_group * (e // g):
            raise ValueError(
                f"num_experts_per_tok {self.num_experts_per_tok}: a token picks distinct "
                f"experts among {self.topk_group} groups of {e // g}")
        if self.n_routed_experts < 1 or self.first_held_expert < 0 or \
                self.first_held_expert + self.n_routed_experts > e:
            raise ValueError(
                f"experts {self.first_held_expert}..{self.first_held_expert + self.n_routed_experts - 1} "
                f"held of a router over {e}")
        if self.n_shared_experts < 0:
            raise ValueError(f"n_shared_experts {self.n_shared_experts}")

    @property
    def n_dense(self) -> int:
        return self.first_k_dense_replace

    @property
    def n_moe(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def held(self) -> tuple | None:
        """``(first, count)`` for :func:`..ops.moe.expert_ffn`, or ``None``
        where every expert the router knows is held."""
        if self.n_routed_experts == self.router_experts:
            return None
        return (self.first_held_expert, self.n_routed_experts)

    @property
    def cache_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        scale = float(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.rope_scaling is None:
            return scale
        m = yarn_mscale(self.rope_scaling["factor"], self.rope_scaling.get("mscale_all_dim", 0))
        return scale * m * m

    @classmethod
    def tiny(cls, vocab_size=256, hidden_size=64, seq=512, **kw):
        """Four layers, the first dense; 8 experts in 2 groups of 4, the
        better group kept and its top 2 taken; YaRN stretching 64 positions
        8 x — for the CPU tests."""
        base = dict(
            vocab_size=vocab_size, hidden_size=hidden_size, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=4, num_attention_heads=4,
            q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, first_k_dense_replace=1, n_routed_experts=8, n_group=2,
            topk_group=1, num_experts_per_tok=2, max_position_embeddings=seq,
            rope_scaling={"type": "yarn", "factor": 8, "original_max_position_embeddings": 64,
                          "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
        )
        base.update(kw)
        return cls(**base)


#: training placement: every matrix over fsdp on its input dimension (the
#: experts themselves are not spread, ROADMAP Reach 2)
DEEPSEEK_PARTITION_RULES = [
    (r"embed_tokens", P("tp", "fsdp")),
    (r"lm_head", P("fsdp", "tp")),
    (r"layers\.moe\.(w_in|w_out)", P(None, None, "fsdp", None)),
    (r"layers\.\w+\.(wq_a|wq_b|wkv_a|wkv_b|wo|w_in|w_out|gate|shared_in|shared_out)",
     P(None, "fsdp", None)),
    (r".*", P()),
]


def cache_spec(config: DeepseekV3Config) -> CacheSpec:
    c = config
    return CacheSpec(paged_layers=c.num_hidden_layers, kv_heads=1, head_dim=c.cache_width,
                     latent_rank=c.kv_lora_rank)


def step_counter_shapes(config: DeepseekV3Config) -> dict:
    """Over the experts held, and the pairs the router sent to experts held
    elsewhere."""
    return moe.step_counter_shapes(config.n_moe, config.n_routed_experts,
                                   extra=("moe_pairs_elsewhere_total",))


def init_deepseek_params(key, config: DeepseekV3Config, dtype=jnp.float32):
    c = config
    h, n, nh = c.hidden_size, c.num_hidden_layers, c.num_attention_heads
    nd, nm, f = c.n_dense, c.n_moe, c.moe_intermediate_size
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    keys = iter(jax.random.split(key, 20))

    def mat(*shape):
        scale = 1.0 / np.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    layers = {
        "attn": {
            "attn_norm": ones(n, h),
            "wq_a": mat(n, h, c.q_lora_rank),
            "q_norm": ones(n, c.q_lora_rank),
            "wq_b": mat(n, c.q_lora_rank, nh * qk),
            "wkv_a": mat(n, h, c.cache_width),
            "kv_norm": ones(n, c.kv_lora_rank),
            # each head's k_nope | v columns
            "wkv_b": mat(n, c.kv_lora_rank, nh * (c.qk_nope_head_dim + c.v_head_dim)),
            "wo": mat(n, nh * c.v_head_dim, h),
            "ffn_norm": ones(n, h),
        },
    }
    if nd:
        layers["dense"] = {
            "w_in": mat(nd, h, 2 * c.intermediate_size),       # gate | up
            "w_out": mat(nd, c.intermediate_size, h),
        }
    if nm:
        layers["moe"] = {
            "gate": mat(nm, h, c.router_experts),
            "expert_bias": jnp.zeros((nm, c.router_experts), dtype),
            "w_in": mat(nm, c.n_routed_experts, h, 2 * f),
            "w_out": mat(nm, c.n_routed_experts, f, h),
        }
        if c.n_shared_experts:
            layers["moe"]["shared_in"] = mat(nm, h, 2 * f * c.n_shared_experts)
            layers["moe"]["shared_out"] = mat(nm, f * c.n_shared_experts, h)
    return {
        "embed_tokens": (jax.random.normal(next(keys), (c.vocab_size, h)) * 0.02).astype(dtype),
        "norm": ones(h),
        "lm_head": mat(h, c.vocab_size),
        "layers": layers,
    }


# -- the parts, each under the scope the trace files it by ---------------------


def _rope(c, x, positions):
    """Rotate ``x [b, s, ..., rope]`` by ``positions [b, s]``: rotate-half
    over the rope lanes, YaRN's frequencies, the angles in float32 and the
    rotation in ``x``'s dtype; cos and sin carry ``mscale / mscale_all_dim``
    (1 as published)."""
    r = c.rope_scaling
    if r is None:
        inv_freq = c.rope_theta ** (-np.arange(0, c.qk_rope_head_dim, 2, dtype=np.float64)
                                    / c.qk_rope_head_dim)
        mag = 1.0
    else:
        inv_freq = yarn_frequencies(
            c.qk_rope_head_dim, c.rope_theta, r["factor"], r["original_max_position_embeddings"],
            r.get("beta_fast", 32), r.get("beta_slow", 1))
        mag = yarn_mscale(r["factor"], r.get("mscale", 1)) / yarn_mscale(
            r["factor"], r.get("mscale_all_dim", 0))
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq, jnp.float32)
    lead = (slice(None), slice(None)) + (None,) * (x.ndim - 3)
    cos = (jnp.cos(angles) * mag)[lead].astype(x.dtype)
    sin = (jnp.sin(angles) * mag)[lead].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@jax.named_scope("attn_proj")
def _latent_qkv(c, layer, x, positions):
    """``q_nope [b, s, heads, nope]``, rotated ``q_pe [b, s, heads, rope]``,
    the normed compressed ``c_kv [b, s, rank]`` and the rotated shared key
    ``k_pe [b, s, rope]`` of the normed residual."""
    b, s, _ = x.shape
    nh = c.num_attention_heads
    y = rms_norm(x, layer["attn_norm"], c.rms_norm_eps)
    c_q = rms_norm(dense(y, layer["wq_a"]), layer["q_norm"], c.rms_norm_eps)
    q = dense(c_q, layer["wq_b"]).reshape(b, s, nh, c.qk_nope_head_dim + c.qk_rope_head_dim)
    q_nope, q_pe = q[..., :c.qk_nope_head_dim], q[..., c.qk_nope_head_dim:]
    ckv = dense(y, layer["wkv_a"])
    c_kv = rms_norm(ckv[..., :c.kv_lora_rank], layer["kv_norm"], c.rms_norm_eps)
    k_pe = _rope(c, ckv[..., c.kv_lora_rank:], positions)
    return q_nope, _rope(c, q_pe, positions), c_kv, k_pe


def _wkv_b(c, layer):
    """``W_kvb`` as ``(k part [rank, heads, nope], v part [rank, heads, v])``."""
    w = layer["wkv_b"].reshape(
        c.kv_lora_rank, c.num_attention_heads, c.qk_nope_head_dim + c.v_head_dim)
    return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]


def _expanded_attention(c, layer, x, positions, attention_mask):
    """Whole sequences: every head's keys and values expanded through
    ``W_kvb``, a causal softmax over ``nope + rope`` wide scores."""
    b, s, _ = x.shape
    nh = c.num_attention_heads
    q_nope, q_pe, c_kv, k_pe = _latent_qkv(c, layer, x, positions)
    with jax.named_scope("mla_expand"):
        w_k, w_v = _wkv_b(c, layer)
        k_nope = jnp.einsum("bsc,chd->bshd", c_kv, w_k)
        v = jnp.einsum("bsc,chd->bshd", c_kv, w_v)
    with jax.named_scope("attn_kernel"):
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (b, s, nh, k_pe.shape[-1]))], axis=-1)
        mask = causal_mask(s, s)[None, None]
        if attention_mask is not None:
            mask = mask & attention_mask[:, None, None, :].astype(bool)
        attn = dot_product_attention(q, k, v, mask=mask, scale=c.softmax_scale)
    return attention_out(layer, x, attn)


def _absorbed_attention(c, layer, i, x, positions, idx, cache, block_tables, valid):
    """One step against the latent pool: this step's rows written, then every
    head's absorbed query against the row's table span. Returns ``(x,
    cache)``."""
    b, s, _ = x.shape
    q_nope, q_pe, c_kv, k_pe = _latent_qkv(c, layer, x, positions)
    pool = cache["k"]
    pad = pool.shape[-1] - c.cache_width
    with jax.named_scope("kv_write"):
        rows = jnp.concatenate([c_kv, k_pe, jnp.zeros((b, s, pad), c_kv.dtype)], axis=-1)
        written = write_paged_latent(pool, i, rows, block_tables, positions,
                                     write_mask=valid, scale=cache.get("k_scale"))
    w_k, w_v = _wkv_b(c, layer)
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w_k)
        q_abs = jnp.concatenate(
            [q_lat, q_pe, jnp.zeros((*q_pe.shape[:-1], pad), q_pe.dtype)], axis=-1)
    with jax.named_scope("attn_kernel"):
        a_lat = latent_attention(
            q_abs, written[0], i, block_tables, idx, rank=c.kv_lora_rank,
            scale=c.softmax_scale, pool_scale=written[1] if len(written) > 1 else None)
    with jax.named_scope("mla_absorb"):
        attn = jnp.einsum("bshc,chd->bshd", a_lat, w_v)
    cache = {**cache, **dict(zip(("k", "k_scale"), written))}
    return attention_out(layer, x, attn), cache


@jax.named_scope("mlp")
def _dense_ff(c, layer, norm, x):
    y = rms_norm(x, norm, c.rms_norm_eps)
    g, u = jnp.split(dense(y, layer["w_in"]), 2, axis=-1)
    return x + dense(jax.nn.silu(g) * u, layer["w_out"])


def _routed_ff(c, stack, norm, i, x, live):
    """The routed feed-forward of layer ``i`` of the ``moe`` stack over ``x
    [b, s, h]``: the held experts' part and the shared expert. ``live [b, s]``
    (or ``None``) keeps padding and dead lanes out of every routed expert.
    Returns ``(x, pairs [held] int32, pairs routed elsewhere)``."""
    b, s, h = x.shape
    k = c.num_experts_per_tok
    with jax.named_scope("moe_router"):
        y = rms_norm(x, norm, c.rms_norm_eps).reshape(b * s, h)
        experts, weights = moe.route(
            y, stack["gate"][i], stack["expert_bias"][i], k, c.norm_topk_prob,
            c.routed_scaling_factor, n_group=c.n_group, topk_group=c.topk_group,
            norm_eps=1e-20)
    with jax.named_scope("moe_experts"):
        flat_live = None if live is None else live.reshape(b * s)
        out, pairs = moe.expert_ffn(y, experts, weights, stack["w_in"], stack["w_out"],
                                live=flat_live, layer=i, held=c.held)
        n_live = b * s if flat_live is None else flat_live.sum(dtype=jnp.int32)
        elsewhere = n_live * k - pairs.sum()
    if c.n_shared_experts:
        with jax.named_scope("moe_shared"):
            g, u = jnp.split(dense(y, stack["shared_in"][i]), 2, axis=-1)
            out = out + dense(jax.nn.silu(g) * u, stack["shared_out"][i])
    return x + out.reshape(b, s, h), pairs, elsewhere


def _feed_forward(c, stacks, i, x, live):
    """Layer ``i``'s feed-forward: ``(x, pairs or None, elsewhere or None)``."""
    norm = stacks["attn"]["ffn_norm"][i]
    if i < c.n_dense:
        return _dense_ff(c, layer_at(stacks["dense"], i), norm, x), None, None
    return _routed_ff(c, stacks["moe"], norm, i - c.n_dense, x, live)


def deepseek_apply(
    config: DeepseekV3Config,
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
    """Forward pass: whole sequences in the expanded form (training / eval),
    or — with ``paged_kv`` — one absorbed step against the engine's latent
    pool (:func:`_paged_step`)."""
    c = config
    if paged_kv is not None:
        return _paged_step(c, params, input_ids, paged_kv, block_tables,
                           cache_positions, paged_write_mask, logit_positions)
    b, s = input_ids.shape
    valid = None if attention_mask is None else attention_mask.astype(bool)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    stacks = params["layers"]

    def one_layer(x, i):
        x = _expanded_attention(c, layer_at(stacks["attn"], i), x, positions, attention_mask)
        return _feed_forward(c, stacks, i, x, valid)[0]

    x = embed_tokens(params, input_ids)
    with jax.named_scope("layers"):
        for i in range(c.num_hidden_layers):
            x = remat_wrap(functools.partial(one_layer, i=i), c.remat)(x)
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
    """One step against the cache ``{"k"[, "k_scale"]}`` — the latent pool
    ``[layers, num_blocks, block_size, pool_width]``, no ``"v"`` — (the
    contract: :func:`~..ops.layers.paged_step_frame`): every slot's one
    token, or a prefill chunk. A lane that is off routes to no expert; beside
    the logits come the step's ``step_counters``."""
    idx, positions, valid = paged_step_frame(input_ids, cache_positions, write_mask)
    stacks = params["layers"]
    cache = dict(cache)
    pairs, elsewhere = [], []
    x = embed_tokens(params, input_ids)
    with jax.named_scope("layers"):
        for i in range(c.num_hidden_layers):
            x, cache = _absorbed_attention(
                c, layer_at(stacks["attn"], i), i, x, positions, idx, cache, block_tables, valid)
            x, layer_pairs, layer_elsewhere = _feed_forward(c, stacks, i, x, valid)
            if layer_pairs is not None:
                pairs.append(layer_pairs)
                elsewhere.append(layer_elsewhere)
    with jax.named_scope("head"):
        x = rms_norm(logit_rows(x, logit_positions), params["norm"], c.rms_norm_eps)
    out = ModelOutput(logits=untied_head(x, params["lm_head"]), paged_kv=cache)
    if pairs:
        out["step_counters"] = {
            **moe.step_counters(pairs),
            "moe_pairs_elsewhere_total": jnp.stack(elsewhere).sum().astype(jnp.int32),
        }
    return out


class DeepseekV3ForCausalLM:
    """Factory mirroring the transformers entry point."""

    @staticmethod
    def from_config(config: DeepseekV3Config, seed: int = 0, dtype=jnp.float32) -> Model:
        from ..big_modeling import is_empty_init

        config = dataclasses.replace(config)  # private copy: apply_fn closes over it

        def make_params(key):
            return init_deepseek_params(key, config, dtype=dtype)

        if is_empty_init():
            params = jax.eval_shape(make_params, jax.random.PRNGKey(seed))
        else:
            params = make_params(jax.random.PRNGKey(seed))

        def apply_fn(p, input_ids=None, attention_mask=None, labels=None, **kw):
            return deepseek_apply(config, p, input_ids, attention_mask, labels, **kw)

        model = Model(
            apply_fn, params,
            partition_rules=DEEPSEEK_PARTITION_RULES,
            name="DeepseekV3ForCausalLM",
        )
        model.config = config
        model.stacked_params_prefix = "layers"
        model.supports_paged_kv = True
        model.cache_spec = cache_spec(config)
        if config.n_moe:
            model.step_counter_shapes = step_counter_shapes(config)
            model.serve_stats = {
                "moe_layers": config.n_moe, "moe_experts": config.n_routed_experts,
                "moe_top_k": config.num_experts_per_tok,
                "moe_router_experts": config.router_experts,
            }
        return model
