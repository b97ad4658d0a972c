"""SDAR-MoE (``sdar_moe``, SDAR-30B-A3B-Chat): a QK-normed rotary GQA
transformer with routed experts behind every layer, which generates by
diffusion over blocks.

Every layer is the same:

    h = RMSNorm(x, attn_norm)
    q, k, v = h Wq, h Wk, h Wv             (no bias); q, k normed head by head
                                           (``q_norm``, ``k_norm``) BEFORE the
                                           rotation (rotate-half, whole head)
    a_p = softmax_j(q_p . k_j / sqrt(hd)) v_j  over  j < (p // B + 1) * B
    x = x + a Wo
    h = RMSNorm(x, ffn_norm)
    s = softmax(h Wg) over all experts, float32;  top k, renormalised
    x = x + sum_i w_i * W_out[e_i](silu(g) * u),  [g | u] = h W_in[e_i]

then ``RMSNorm(x, norm)`` and the untied head. No shared expert, no router
bias, no dense layer (``mlp_only_layers`` empty, ``decoder_sparse_step`` 1).
``B`` is ``block_length``: attention is causal from block to block and
bidirectional inside a block (:func:`..ops.layers.last_visible`), and that
is all the layer knows about blocks; ``B = 1`` is the causal model. How a
block is generated — filled with ``mask_token_id``, unmasked over some
denoise passes, then committed by one pass over the clean block — is the
serving engine's round (``serving/engine.py``), which reads
``model.block_decode`` and nothing else of this file. The logits at a masked
position are the distribution of the token at that position.

One stack ``layers.<leaf>`` over the layers; the layer loop is unrolled so
that the expert product addresses ``(layer, expert)`` of the stacked
matrices in place (:mod:`..ops.moe`). Every layer holds block-paged K/V and
no layer keeps per-slot state. A step against the cache hands back the
``step_counters`` of :func:`..ops.moe.step_counter_shapes`.

The published training objective (masked denoising of noised blocks) is not
built: ``labels`` give the next-token loss of the backbone the family was
adapted from, under this file's attention rule.
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
from ..ops.layers import (
    attention_out,
    dot_product_attention,
    embed_tokens,
    fused_cross_entropy,
    last_visible,
    layer_at,
    logit_rows,
    paged_step_frame,
    paged_write_attend,
    qk_normed_rotary_qkv,
    rms_norm,
    shift_labels,
    untied_head,
)
from ..parallel.pipeline import remat_wrap
from .cache import BlockDecode, CacheSpec, pool_leaf_names


@dataclass
class SdarMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int | None = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    tie_word_embeddings: bool = False
    #: not in the published file: the family's released chat models generate
    #: in blocks of 4 positions and mask with token 151669
    block_length: int = 4
    mask_token_id: int = 151669
    remat: bool | str = False

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if not 1 <= self.num_experts_per_tok <= self.num_experts:
            raise ValueError(
                f"num_experts_per_tok {self.num_experts_per_tok} of "
                f"num_experts {self.num_experts}: a token picks distinct experts"
            )
        if self.tie_word_embeddings:
            raise ValueError("built as published for SDAR-30B-A3B-Chat: the head is untied")
        if self.block_length < 1:
            raise ValueError(f"block_length {self.block_length}: a block holds a position")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                f"mask_token_id {self.mask_token_id} is not a row of the "
                f"{self.vocab_size}-row embedding"
            )

    @classmethod
    def tiny(cls, vocab_size=256, hidden_size=64, seq=512, **kw):
        """Three layers over 8 experts, top 2, blocks of 4 — for the CPU tests."""
        base = dict(
            vocab_size=vocab_size, hidden_size=hidden_size, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32, max_position_embeddings=seq,
            block_length=4, mask_token_id=vocab_size - 1,
        )
        base.update(kw)
        return cls(**base)


#: training placement: every matrix over fsdp on its input dimension (the
#: experts themselves are not spread, ROADMAP Reach 2)
SDAR_PARTITION_RULES = [
    (r"embed_tokens", P("tp", "fsdp")),
    (r"lm_head", P("fsdp", "tp")),
    (r"layers\.(w_in|w_out)", P(None, None, "fsdp", None)),
    (r"layers\.(wq|wk|wv|wo|gate)", P(None, "fsdp", None)),
    (r".*", P()),
]


def cache_spec(config: SdarMoeConfig) -> CacheSpec:
    c = config
    return CacheSpec(paged_layers=c.num_hidden_layers, kv_heads=c.num_key_value_heads,
                     head_dim=c.head_dim)


def block_decode(config: SdarMoeConfig) -> BlockDecode | None:
    """What the engine reads; ``None`` at ``block_length`` 1, the causal
    model, which the one-token decode step serves."""
    if config.block_length == 1:
        return None
    return BlockDecode(config.block_length, config.mask_token_id)


def step_counter_shapes(config: SdarMoeConfig) -> dict:
    return moe.step_counter_shapes(config.num_hidden_layers, config.num_experts)


def init_sdar_params(key, config: SdarMoeConfig, dtype=jnp.float32):
    c = config
    h, hd, n = c.hidden_size, c.head_dim, c.num_hidden_layers
    nh, nkv, e, f = c.num_attention_heads, c.num_key_value_heads, c.num_experts, \
        c.moe_intermediate_size
    keys = iter(jax.random.split(key, 12))

    def mat(*shape):
        scale = 1.0 / np.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    return {
        "embed_tokens": (jax.random.normal(next(keys), (c.vocab_size, h)) * 0.02).astype(dtype),
        "norm": ones(h),
        "lm_head": mat(h, c.vocab_size),
        "layers": {
            "attn_norm": ones(n, h),
            "wq": mat(n, h, nh * hd),
            "wk": mat(n, h, nkv * hd),
            "wv": mat(n, h, nkv * hd),
            "wo": mat(n, nh * hd, h),
            "q_norm": ones(n, hd),
            "k_norm": ones(n, hd),
            "ffn_norm": ones(n, h),
            "gate": mat(n, h, e),
            # each expert's gate | up columns, and its down projection
            "w_in": mat(n, e, h, 2 * f),
            "w_out": mat(n, e, f, h),
        },
    }


# -- the parts, each under the scope the trace files it by ---------------------


def _qkv(c, layer, x, positions):
    return qk_normed_rotary_qkv(
        layer, x, layer["attn_norm"], positions, c.num_attention_heads,
        c.num_key_value_heads, c.head_dim, c.rms_norm_eps, c.rope_theta)


def _routed_ff(c, stack, i, x, live):
    """Layer ``i``'s experts: a softmax router over all of them, no bias.
    ``(x, pairs [E] int32)``."""
    return moe.routed_ffn(stack, i, x, live, c.rms_norm_eps, c.num_experts_per_tok,
                          c.norm_topk_prob, scoring="softmax")


def _block_causal_attention(c, q, k, v, attention_mask):
    """Whole sequences: position ``p`` attends every valid position before
    the end of its own block."""
    if c.block_length == 1:
        return attention(q, k, v, segment_mask=attention_mask, causal=True)
    s = q.shape[1]
    pos = jnp.arange(s, dtype=jnp.int32)
    mask = (pos[None, :] <= last_visible(pos, c.block_length)[:, None])[None, None]
    if attention_mask is not None:
        mask = mask & attention_mask[:, None, None, :].astype(bool)
    return dot_product_attention(q, k, v, mask=mask)


def sdar_apply(
    config: SdarMoeConfig,
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
    """Forward pass: whole sequences (training / eval), or — with
    ``paged_kv`` — one step against the engine's cache (:func:`_paged_step`)."""
    c = config
    if paged_kv is not None:
        return _paged_step(c, params, input_ids, paged_kv, block_tables,
                           cache_positions, paged_write_mask, logit_positions)
    b, s = input_ids.shape
    valid = None if attention_mask is None else attention_mask.astype(bool)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    stack = params["layers"]

    def one_layer(x, i):
        layer = layer_at(stack, i, but=("w_in", "w_out"))
        q, k, v = _qkv(c, layer, x, positions)
        with jax.named_scope("attn_kernel"):
            attn = _block_causal_attention(c, q, k, v, attention_mask)
        x = attention_out(layer, x, attn)
        return _routed_ff(c, stack, i, x, valid)[0]

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
    """One step against the cache ``{"k", "v"[, "k_scale", "v_scale"]}`` (the
    contract: :func:`~..ops.layers.paged_step_frame`): ``s`` tokens a row (a
    prefill chunk of one prompt, or the ``block_length`` positions of every
    slot's open block; ``s == 1`` at ``block_length`` 1), every query
    attending what is written before the end of its own block. A lane that is
    off routes to no expert; beside the logits come the ``step_counters``."""
    idx, positions, valid = paged_step_frame(input_ids, cache_positions, write_mask)
    names = pool_leaf_names(cache)
    stack = params["layers"]
    cache = dict(cache)
    pairs = []
    x = embed_tokens(params, input_ids)
    with jax.named_scope("layers"):
        for i in range(c.num_hidden_layers):
            layer = layer_at(stack, i, but=("w_in", "w_out"))
            q, k, v = _qkv(c, layer, x, positions)
            attn, held = paged_write_attend(
                q, k, v, [cache[n] for n in names], i, block_tables, positions, idx, valid,
                block_len=c.block_length)
            cache.update(zip(names, held))
            x = attention_out(layer, x, attn)
            x, layer_pairs = _routed_ff(c, stack, i, x, valid)
            pairs.append(layer_pairs)
    with jax.named_scope("head"):
        x = rms_norm(logit_rows(x, logit_positions), params["norm"], c.rms_norm_eps)
    return ModelOutput(logits=untied_head(x, params["lm_head"]), paged_kv=cache,
                       step_counters=moe.step_counters(pairs))


class SdarMoeForCausalLM:
    """Factory mirroring the transformers entry point."""

    @staticmethod
    def from_config(config: SdarMoeConfig, seed: int = 0, dtype=jnp.float32) -> Model:
        from ..big_modeling import is_empty_init

        config = dataclasses.replace(config)  # private copy: apply_fn closes over it

        def make_params(key):
            return init_sdar_params(key, config, dtype=dtype)

        if is_empty_init():
            params = jax.eval_shape(make_params, jax.random.PRNGKey(seed))
        else:
            params = make_params(jax.random.PRNGKey(seed))

        def apply_fn(p, input_ids=None, attention_mask=None, labels=None, **kw):
            return sdar_apply(config, p, input_ids, attention_mask, labels, **kw)

        model = Model(
            apply_fn, params,
            partition_rules=SDAR_PARTITION_RULES,
            name="SdarMoeForCausalLM",
        )
        model.config = config
        model.stacked_params_prefix = "layers"
        model.supports_paged_kv = True
        model.cache_spec = cache_spec(config)
        model.block_decode = block_decode(config)
        model.step_counter_shapes = step_counter_shapes(config)
        model.serve_stats = {
            "moe_layers": config.num_hidden_layers, "moe_experts": config.num_experts,
            "moe_top_k": config.num_experts_per_tok,
        }
        return model
