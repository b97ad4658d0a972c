"""LFM2-MoE (``lfm2_moe``, LFM2-8B-A1B): gated short convolutions with a
few grouped-query attention layers among them, by the published
``layer_types``, and routed experts behind all but the leading layers.

Every layer makes two residual updates,

    x <- x + op(RMSNorm(x, operator_norm));   x <- x + ff(RMSNorm(x, ffn_norm))

with ``op`` one of two operators and ``ff`` one of two feed-forwards:

* ``conv`` — ``[B, C, u] = split3(y W_in)``; ``g = B * u``; a depthwise
  causal convolution of ``conv_L_cache`` taps over ``g`` (no activation, no
  bias); ``(C * conv) W_out``. No position term.
* ``full_attention`` — bias-free q/k/v, RMSNorm over each head's entries
  of q and of k (``q_norm``, ``k_norm``) **before** the rotary embedding
  (rotate-half, the whole head), causal GQA at ``1/sqrt(head_dim)``.
* dense SwiGLU of width ``intermediate_size`` in the first
  ``num_dense_layers`` layers;
* routed experts after them: a sigmoid router whose ``expert_bias`` moves
  the selection and not the weights, top-k, each expert a SwiGLU of width
  ``moe_intermediate_size``, no shared expert, no capacity
  (:mod:`..ops.moe`).

The head is the embedding, tied, after ``RMSNorm(x, embedding_norm)``.

Four stacks, each over the layers of its kind in published order:
``layers.conv.*`` and ``layers.attention.*`` hold the operators,
``layers.dense.*`` and ``layers.moe.*`` the feed-forwards. The layer loop is
unrolled: a layer's index into each stack is static, which is what lets the
expert product address ``(layer, expert)`` of the stacked matrices in place.

**What a served sequence keeps** (:class:`~.cache.CacheSpec`): the
attention layers hold block-paged K/V; every ``conv`` layer holds, per
slot, the last ``conv_L_cache - 1`` rows of ``g`` (the gated input, not
``x``) in the compute dtype — 8 KB a layer at the published widths, and the
whole of the per-slot state: no leaf has a precision of its own, so
``state_dtype`` has nothing to narrow.

A step against the cache also hands back ``step_counters`` (int32, summed
by the serving engine into ``stats()``): the pairs each expert of each
routed layer was given, and what follows from them.
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
from ..ops.attention import attention
from ..ops.fp8 import dense
from ..ops.layers import (
    attention_out,
    embed_tokens,
    fused_cross_entropy,
    layer_at,
    logit_rows,
    paged_step_frame,
    paged_write_attend,
    qk_normed_rotary_qkv,
    rms_norm,
    shift_labels,
    slot_state_frame,
)
from ..ops.ssm import conv_with_tail
from ..parallel.pipeline import remat_wrap
from .cache import CacheSpec, SlotStateLeaf, pool_leaf_names

_PUBLISHED_ATTENTION = (2, 6, 10, 14, 18, 21)


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 24
    #: ``"conv"`` or ``"full_attention"`` per layer, as published
    layer_types: tuple = tuple(
        "full_attention" if i in _PUBLISHED_ATTENTION else "conv" for i in range(24))
    #: leading layers whose feed-forward is the dense SwiGLU
    num_dense_layers: int = 2
    intermediate_size: int = 7168
    num_experts: int = 32
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1792
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int | None = None
    rope_theta: float = 1000000.0
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 128000
    remat: bool | str = False

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}"
            )
        unknown = sorted(set(self.layer_types) - {"conv", "full_attention"})
        if unknown:
            raise ValueError(
                f"layer_types holds {unknown}: only 'conv' and 'full_attention' are built")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError(
                f"num_dense_layers {self.num_dense_layers} of "
                f"num_hidden_layers {self.num_hidden_layers}"
            )
        if not 1 <= self.num_experts_per_tok <= self.num_experts:
            raise ValueError(
                f"num_experts_per_tok {self.num_experts_per_tok} of "
                f"num_experts {self.num_experts}: a token picks distinct experts"
            )
        if self.conv_bias or not self.tie_word_embeddings or not self.use_expert_bias:
            raise ValueError(
                "built as published for LFM2-8B-A1B: conv_bias false, "
                "tie_word_embeddings true, use_expert_bias true"
            )

    @property
    def n_conv(self) -> int:
        return self.layer_types.count("conv")

    @property
    def n_attention(self) -> int:
        return self.layer_types.count("full_attention")

    @property
    def n_moe(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    def layer_plan(self) -> list:
        """``[(operator kind, index in its stack, feed-forward kind, index in
        its stack)]`` in published order."""
        plan, seen = [], {"conv": 0, "full_attention": 0}
        for i, kind in enumerate(self.layer_types):
            dense_ff = i < self.num_dense_layers
            plan.append((kind, seen[kind], "dense" if dense_ff else "moe",
                         i if dense_ff else i - self.num_dense_layers))
            seen[kind] += 1
        return plan

    @classmethod
    def tiny(cls, vocab_size=256, hidden_size=64, seq=512, **kw):
        """Five layers — ``conv`` (dense), then ``full_attention conv conv
        full_attention`` routed over 8 experts, top 2 — for the CPU tests."""
        base = dict(
            vocab_size=vocab_size, hidden_size=hidden_size, num_hidden_layers=5,
            layer_types=("conv", "full_attention", "conv", "conv", "full_attention"),
            num_dense_layers=1, intermediate_size=128, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=seq,
        )
        base.update(kw)
        return cls(**base)


#: training placement: every matrix over fsdp on its input dimension (an
#: expert's too: the experts themselves are not spread, ROADMAP Reach 2)
LFM2_PARTITION_RULES = [
    (r"embed_tokens", P("tp", "fsdp")),
    (r"layers\.moe\.(w_in|w_out)", P(None, None, "fsdp", None)),
    (r"layers\.\w+\.(wq|wk|wv|wo|in_proj|out_proj|w_in|w_out|gate)", P(None, "fsdp", None)),
    (r".*", P()),
]


def cache_spec(config: Lfm2MoeConfig) -> CacheSpec:
    c = config
    return CacheSpec(
        paged_layers=c.n_attention,
        kv_heads=c.num_key_value_heads,
        head_dim=c.head_dim,
        slot_state={
            "conv": SlotStateLeaf(c.n_conv, (c.conv_L_cache - 1, c.hidden_size), None)},
    )


def step_counter_shapes(config: Lfm2MoeConfig) -> dict:
    return moe.step_counter_shapes(config.n_moe, config.num_experts)


def init_lfm2_params(key, config: Lfm2MoeConfig, dtype=jnp.float32):
    c = config
    h, hd = c.hidden_size, c.head_dim
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    nc, na, nd, nm = c.n_conv, c.n_attention, c.num_dense_layers, c.n_moe
    keys = iter(jax.random.split(key, 16))

    def mat(*shape):
        scale = 1.0 / np.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    return {
        "embed_tokens": (jax.random.normal(next(keys), (c.vocab_size, h)) * 0.02).astype(dtype),
        "embedding_norm": ones(h),
        "layers": {
            "conv": {
                "operator_norm": ones(nc, h),
                # the published in_proj's columns B | C | u
                "in_proj": mat(nc, h, 3 * h),
                # taps [L, channels]: tap L-1 multiplies the current token
                "conv_w": mat(nc, c.conv_L_cache, h),
                "out_proj": mat(nc, h, h),
            },
            "attention": {
                "operator_norm": ones(na, h),
                "wq": mat(na, h, nh * hd),
                "wk": mat(na, h, nkv * hd),
                "wv": mat(na, h, nkv * hd),
                "wo": mat(na, nh * hd, h),
                "q_norm": ones(na, hd),
                "k_norm": ones(na, hd),
            },
            "dense": {
                "ffn_norm": ones(nd, h),
                "w_in": mat(nd, h, 2 * c.intermediate_size),
                "w_out": mat(nd, c.intermediate_size, h),
            },
            "moe": {
                "ffn_norm": ones(nm, h),
                "gate": mat(nm, h, c.num_experts),
                "expert_bias": jnp.zeros((nm, c.num_experts), dtype),
                # each expert's gate | up columns, and its down projection
                "w_in": mat(nm, c.num_experts, h, 2 * c.moe_intermediate_size),
                "w_out": mat(nm, c.num_experts, c.moe_intermediate_size, h),
            },
        },
    }


# -- the parts, each under the scope the trace files it by ---------------------


@jax.named_scope("head")
def _tied_head(x, embed):
    return jnp.einsum("...h,vh->...v", x, embed)


@jax.named_scope("head")
def _final_norm_and_head(c, params, x):
    x = rms_norm(x, params["embedding_norm"], c.norm_eps)
    return x, _tied_head(x, params["embed_tokens"])


@jax.named_scope("mlp")
def _dense_ff(c, layer, x):
    y = rms_norm(x, layer["ffn_norm"], c.norm_eps)
    g, u = jnp.split(dense(y, layer["w_in"]), 2, axis=-1)
    return x + dense(jax.nn.silu(g) * u, layer["w_out"])


def _routed_ff(c, stack, i, x, live):
    """Layer ``i`` of the ``moe`` stack: a sigmoid router whose
    ``expert_bias`` moves the selection. ``(x, pairs [E] int32)``."""
    return moe.routed_ffn(stack, i, x, live, c.norm_eps, c.num_experts_per_tok,
                          c.norm_topk_prob, c.routed_scaling_factor)


@jax.named_scope("conv_proj")
def _conv_in(c, layer, x):
    """``(g = B * u, C)`` of the normed residual."""
    y = rms_norm(x, layer["operator_norm"], c.norm_eps)
    b_gate, c_gate, u = jnp.split(dense(y, layer["in_proj"]), 3, axis=-1)
    with jax.named_scope("conv_mix"):
        return b_gate * u, c_gate


def _conv_mix(layer, g, c_gate, tail, n_valid):
    """The taps over ``g`` continuing from ``tail``, gated by ``C``; the
    new tail is the last valid rows of ``g``."""
    with jax.named_scope("conv_mix"):
        conv, tail = conv_with_tail(g, tail, layer["conv_w"], None, n_valid, activation=None)
        return c_gate * conv, tail


@jax.named_scope("conv_proj")
def _conv_out(layer, x, mixed):
    return x + dense(mixed, layer["out_proj"])


def _qkv(c, layer, x, positions):
    return qk_normed_rotary_qkv(
        layer, x, layer["operator_norm"], positions, c.num_attention_heads,
        c.num_key_value_heads, c.head_dim, c.norm_eps, c.rope_theta)


def lfm2_apply(
    config: Lfm2MoeConfig,
    params,
    input_ids,
    attention_mask=None,
    labels=None,
    paged_kv=None,
    block_tables=None,
    cache_positions=None,
    paged_write_mask=None,
    state_slots=None,
    logit_positions=None,
):
    """Forward pass: whole sequences (training / eval, every convolution
    from an empty tail), or — with ``paged_kv`` — one step against the
    engine's cache (:func:`_paged_step`)."""
    c = config
    if paged_kv is not None:
        return _paged_step(c, params, input_ids, paged_kv, block_tables,
                           cache_positions, paged_write_mask, state_slots, logit_positions)
    b, s = input_ids.shape
    valid = None if attention_mask is None else attention_mask.astype(bool)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    stacks = params["layers"]

    def conv_op(x, layer):
        g, c_gate = _conv_in(c, layer, x)
        tail = jnp.zeros((b, c.conv_L_cache - 1, c.hidden_size), g.dtype)
        mixed, _ = _conv_mix(layer, g, c_gate, tail, jnp.zeros((b,), jnp.int32))
        return _conv_out(layer, x, mixed)

    def attention_op(x, layer):
        q, k, v = _qkv(c, layer, x, positions)
        with jax.named_scope("attn_kernel"):
            attn = attention(q, k, v, segment_mask=attention_mask, causal=True)
        return attention_out(layer, x, attn)

    def one_layer(x, op_kind, op_index, ff_kind, ff_index):
        if op_kind == "conv":
            x = conv_op(x, layer_at(stacks["conv"], op_index))
        else:
            x = attention_op(x, layer_at(stacks["attention"], op_index))
        if ff_kind == "dense":
            return _dense_ff(c, layer_at(stacks["dense"], ff_index), x)
        return _routed_ff(c, stacks["moe"], ff_index, x, valid)[0]

    x = embed_tokens(params, input_ids)
    with jax.named_scope("layers"):
        for op_kind, op_index, ff_kind, ff_index in c.layer_plan():
            layer = functools.partial(one_layer, op_kind=op_kind, op_index=op_index,
                                      ff_kind=ff_kind, ff_index=ff_index)
            x = remat_wrap(layer, c.remat)(x)
    x, logits = _final_norm_and_head(c, params, x)
    out = ModelOutput(logits=logits)
    if labels is not None:
        out["loss"] = fused_cross_entropy(
            x, params["embed_tokens"], shift_labels(labels),
            dense_fn=lambda x_chunk, embed: _tied_head(x_chunk, embed))
    return out


def _paged_step(c, params, input_ids, cache, block_tables, cache_positions,
                write_mask, state_slots, logit_positions=None):
    """One step against the cache ``{"k", "v"[, "k_scale", "v_scale"],
    "conv"}`` (the contract: :func:`~..ops.layers.paged_step_frame`): ``s ==
    1`` token for every slot (``state_slots`` ``None``: row ``i`` is slot
    ``i``), or a prefill chunk of ``s`` tokens for the slots ``state_slots
    [b]``, each continuing from its own tail. A lane that is off leaves the
    tail too as it was and routes to no expert; beside the logits come the
    step's ``step_counters`` (:func:`step_counter_shapes`)."""
    idx, positions, valid = paged_step_frame(input_ids, cache_positions, write_mask)
    n_valid, slots = slot_state_frame(valid, state_slots, cache["conv"].shape[1])
    decode = slots is None
    names = pool_leaf_names(cache)
    stacks = params["layers"]
    cache = dict(cache)
    pairs = []
    x = embed_tokens(params, input_ids)
    with jax.named_scope("layers"):
        for op_kind, op_index, ff_kind, ff_index in c.layer_plan():
            i = op_index
            if op_kind == "conv":
                layer = layer_at(stacks["conv"], i)
                g, c_gate = _conv_in(c, layer, x)
                conv = cache["conv"]
                tail = conv[i] if decode else conv[i, slots]
                mixed, tail = _conv_mix(layer, g, c_gate, tail, n_valid)
                with jax.named_scope("conv_mix"):
                    cache["conv"] = conv.at[i].set(tail) if decode else conv.at[i, slots].set(tail)
                x = _conv_out(layer, x, mixed)
            else:
                layer = layer_at(stacks["attention"], i)
                q, k, v = _qkv(c, layer, x, positions)
                attn, held = paged_write_attend(
                    q, k, v, [cache[n] for n in names], i, block_tables, positions, idx, valid)
                cache.update(zip(names, held))
                x = attention_out(layer, x, attn)
            if ff_kind == "dense":
                x = _dense_ff(c, layer_at(stacks["dense"], ff_index), x)
            else:
                x, layer_pairs = _routed_ff(c, stacks["moe"], ff_index, x, valid)
                pairs.append(layer_pairs)
    _, logits = _final_norm_and_head(c, params, logit_rows(x, logit_positions))
    out = ModelOutput(logits=logits, paged_kv=cache)
    if pairs:
        out["step_counters"] = moe.step_counters(pairs)
    return out


class Lfm2MoeForCausalLM:
    """Factory mirroring the transformers entry point."""

    @staticmethod
    def from_config(config: Lfm2MoeConfig, seed: int = 0, dtype=jnp.float32) -> Model:
        from ..big_modeling import is_empty_init

        config = dataclasses.replace(config)  # private copy: apply_fn closes over it

        def make_params(key):
            return init_lfm2_params(key, config, dtype=dtype)

        if is_empty_init():
            params = jax.eval_shape(make_params, jax.random.PRNGKey(seed))
        else:
            params = make_params(jax.random.PRNGKey(seed))

        def apply_fn(p, input_ids=None, attention_mask=None, labels=None, **kw):
            return lfm2_apply(config, p, input_ids, attention_mask, labels, **kw)

        model = Model(
            apply_fn, params,
            partition_rules=LFM2_PARTITION_RULES,
            name="Lfm2MoeForCausalLM",
        )
        model.config = config
        model.stacked_params_prefix = "layers"
        model.supports_paged_kv = True
        model.cache_spec = cache_spec(config)
        if config.n_moe:
            model.step_counter_shapes = step_counter_shapes(config)
            model.serve_stats = {
                "moe_layers": config.n_moe, "moe_experts": config.num_experts,
                "moe_top_k": config.num_experts_per_tok,
            }
        return model
