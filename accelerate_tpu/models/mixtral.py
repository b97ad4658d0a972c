"""Mixtral-style sparse-MoE causal LM: expert parallelism over the ``ep``
mesh axis.

The reference's only MoE support is marking DeepSpeed-MoE blocks as ZeRO-3
leaves (``/root/reference/src/accelerate/utils/dataclasses.py:1060-1066``,
applied ``accelerator.py:1772``) — the experts themselves live in other
libraries. Here the framework ships the model family, TPU-first (SURVEY
§2.2 EP row: ``expert`` axis + all-to-all routing):

* **top-k router + capacity-bounded dispatch** (GShard/Switch pattern):
  tokens are dispatched into per-expert buffers ``[E, capacity, h]`` with
  one-hot combine weights. Static shapes throughout — XLA-friendly.
* **expert weights carry a leading ``[E]`` dim sharded over ``ep``**; the
  dispatch einsum reshards tokens → experts, which GSPMD lowers to an
  ``all_to_all`` over the ``ep`` axis of the mesh (ICI), exactly the
  ragged-all-to-all layout a hand-written kernel would use.
* dense parts (attention) reuse the llama block; layers are stacked and
  scanned like :mod:`.llama`.
* auxiliary load-balancing loss (Switch Transformer eq. 4) is returned in
  the output and folded into ``loss`` with ``router_aux_loss_coef``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..modules import Model, ModelOutput
from ..ops.attention import attention
from ..ops.fp8 import dense
from ..ops.layers import (
    apply_rope,
    cross_entropy_loss,
    mesh_constrain as _constrain,
    residual_spec,
    rms_norm,
    rope_frequencies,
)
from ..parallel.pipeline import remat_wrap


@dataclass
class MixtralConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_local_experts: int = 8
    num_experts_per_tok: int = 2
    capacity_factor: float = 2.0
    router_aux_loss_coef: float = 0.02
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    remat: bool | str = True  # False | True | jax.checkpoint_policies name
    #: GPipe microbatch count when the mesh has a pp axis > 1 (0 = auto)
    pipeline_microbatches: int = 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def tiny(cls, vocab_size=256, hidden_size=64, layers=2, heads=4, experts=4, top_k=2, seq=128):
        return cls(
            vocab_size=vocab_size,
            hidden_size=hidden_size,
            intermediate_size=hidden_size * 2,
            num_hidden_layers=layers,
            num_attention_heads=heads,
            num_key_value_heads=heads,
            num_local_experts=experts,
            num_experts_per_tok=top_k,
            max_position_embeddings=seq,
            remat=False,
        )


MIXTRAL_PARTITION_RULES = [
    (r"embed_tokens", P("tp", "fsdp")),
    (r"layers\.(wq|wk|wv)", P(None, "fsdp", "tp")),
    (r"layers\.wo", P(None, "tp", "fsdp")),
    (r"layers\.router", P(None, "fsdp", None)),
    # expert dim over ep; per-expert matmuls shard ff over tp, h over fsdp
    (r"layers\.(e_gate|e_up)", P(None, "ep", "fsdp", "tp")),
    (r"layers\.e_down", P(None, "ep", "tp", "fsdp")),
    (r"norm", P()),
    (r"lm_head", P("fsdp", "tp")),
]


def init_mixtral_params(key: jax.Array, config: MixtralConfig, dtype=jnp.float32):
    c = config
    h, ff, E, L = c.hidden_size, c.intermediate_size, c.num_local_experts, c.num_hidden_layers
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    keys = jax.random.split(key, 12)

    def _init_dense(k, *shape, in_dim):
        return (jax.random.normal(k, shape, dtype=jnp.float32) / np.sqrt(in_dim)).astype(dtype)

    return {
        "embed_tokens": (jax.random.normal(keys[0], (c.vocab_size, h)) * 0.02).astype(dtype),
        "layers": {
            "wq": _init_dense(keys[1], L, h, nh * hd, in_dim=h),
            "wk": _init_dense(keys[2], L, h, nkv * hd, in_dim=h),
            "wv": _init_dense(keys[3], L, h, nkv * hd, in_dim=h),
            "wo": _init_dense(keys[4], L, nh * hd, h, in_dim=nh * hd),
            "router": _init_dense(keys[5], L, h, E, in_dim=h),
            "e_gate": _init_dense(keys[6], L, E, h, ff, in_dim=h),
            "e_up": _init_dense(keys[7], L, E, h, ff, in_dim=h),
            "e_down": _init_dense(keys[8], L, E, ff, h, in_dim=ff),
            "attn_norm": jnp.ones((L, h), dtype=dtype),
            "mlp_norm": jnp.ones((L, h), dtype=dtype),
        },
        "norm": jnp.ones((h,), dtype=dtype),
        "lm_head": _init_dense(keys[9], h, c.vocab_size, in_dim=h),
    }


def moe_ffn(config: MixtralConfig, layer, x):
    """Top-k routed expert FFN on one layer's UNstacked params.

    x: [b, s, h] → (y: [b, s, h], aux_loss: scalar). Capacity-bounded
    one-hot dispatch; the ``[T, h] → [E, C, h]`` einsum is where GSPMD
    inserts the token all-to-all when experts are ``ep``-sharded.
    """
    c = config
    b, s, h = x.shape
    E, k = c.num_local_experts, c.num_experts_per_tok
    tokens = x.reshape(-1, h)  # [T, h]
    T = tokens.shape[0]
    capacity = int(np.ceil(c.capacity_factor * T * k / E))
    capacity = min(capacity, T)

    logits = (tokens.astype(jnp.float32)) @ layer["router"].astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, k)  # [T, k]
    topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)

    # position of each (token, choice) in its expert's buffer
    sel = jax.nn.one_hot(topk_idx, E, dtype=jnp.int32)          # [T, k, E]
    flat_sel = sel.reshape(T * k, E)
    pos = jnp.cumsum(flat_sel, axis=0) * flat_sel - 1            # [T*k, E]
    pos = jnp.max(pos, axis=-1).reshape(T, k)                    # [T, k]
    keep = (pos < capacity) & (pos >= 0)

    # dispatch [T, E, C] one-hot; combine carries the router weight
    onehot_e = jax.nn.one_hot(topk_idx, E, dtype=x.dtype)                        # [T, k, E]
    onehot_c = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity + 1,
                              dtype=x.dtype)[..., :capacity]                     # [T, k, C]
    dispatch = jnp.einsum("tke,tkc->tec", onehot_e, onehot_c)                    # [T, E, C]
    combine = jnp.einsum("tke,tkc,tk->tec", onehot_e, onehot_c, topk_w.astype(x.dtype))

    expert_in = jnp.einsum("tec,th->ech", dispatch, tokens)       # [E, C, h]
    expert_in = _constrain(expert_in, P("ep", None, None))
    g = jax.nn.silu(jnp.einsum("ech,ehf->ecf", expert_in, layer["e_gate"]))
    u = jnp.einsum("ech,ehf->ecf", expert_in, layer["e_up"])
    expert_out = jnp.einsum("ecf,efh->ech", g * u, layer["e_down"])
    expert_out = _constrain(expert_out, P("ep", None, None))
    y = jnp.einsum("tec,ech->th", combine, expert_out).reshape(b, s, h)

    # load-balancing aux loss: E · Σ_e fraction_of_selections(e) ·
    # mean_router_prob(e), counting ALL top-k choices (HF Mixtral's
    # load_balancing_loss_func semantics; ≈1.0 for a uniform router)
    me = jnp.mean(probs, axis=0)                                               # [E]
    ce = jnp.sum(jax.nn.one_hot(topk_idx, E, dtype=jnp.float32), axis=(0, 1)) / (T * k)
    aux = E * jnp.sum(me * ce)
    return y, aux


def mixtral_layer_apply(
    config: MixtralConfig, layer, x, cos, sin, positions, attention_mask,
    return_kv: bool = False,
):
    c = config
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    b, s, h = x.shape
    y = rms_norm(x, layer["attn_norm"], c.rms_norm_eps)
    q = dense(y, layer["wq"]).reshape(b, s, nh, hd)
    k = dense(y, layer["wk"]).reshape(b, s, nkv, hd)
    v = dense(y, layer["wv"]).reshape(b, s, nkv, hd)
    q = apply_rope(q, cos, sin, positions)
    k = apply_rope(k, cos, sin, positions)
    q = _constrain(q, P(("dp", "fsdp"), "cp", "tp", None))
    k = _constrain(k, P(("dp", "fsdp"), "cp", "tp", None))
    attn = attention(q, k, v, segment_mask=attention_mask, causal=True)
    x = x + dense(attn.reshape(b, s, nh * hd), layer["wo"])
    x = _constrain(x, residual_spec())
    y = rms_norm(x, layer["mlp_norm"], c.rms_norm_eps)
    moe_out, aux = moe_ffn(config, layer, y)
    x = x + moe_out
    x = _constrain(x, residual_spec())
    if return_kv:
        return x, aux, (k, v)
    return x, aux


def _mixtral_decode_layer(c, layer, x, k_cache_l, v_cache_l, cos, sin, idx, pp_manual=False):
    """One cached decode block: the shared rope/cache attention sub-block
    (GQA caches store ``n_kv`` heads) + the routed expert FFN on the single
    token. Experts have no state to cache — only attention does."""
    from ..ops.layers import rope_cached_attention_block

    x, k_cache_l, v_cache_l = rope_cached_attention_block(
        layer, x, k_cache_l, v_cache_l, cos, sin, idx,
        c.num_attention_heads, c.num_key_value_heads, c.head_dim,
        c.rms_norm_eps, pp_manual=pp_manual,
    )
    y = rms_norm(x, layer["mlp_norm"], c.rms_norm_eps)
    moe_out, _ = moe_ffn(c, layer, y)
    return x + moe_out, k_cache_l, v_cache_l


def mixtral_apply(
    config: MixtralConfig,
    params,
    input_ids: jax.Array,
    attention_mask: jax.Array | None = None,
    labels: jax.Array | None = None,
    positions: jax.Array | None = None,
    use_cache: bool = False,
    kv_cache=None,  # {"k","v"}: [L, b, max_cache, n_kv, hd] (decode step)
    cache_index: jax.Array | None = None,
    max_cache_len: int | None = None,
):
    c = config
    b, s = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cos, sin = rope_frequencies(c.head_dim, c.max_position_embeddings, c.rope_theta)

    from ..parallel.pipeline import active_pipeline_mesh, pipeline_layer_stack

    pp_mesh = active_pipeline_mesh()
    if kv_cache is not None:
        return _mixtral_decode_step(c, params, input_ids, kv_cache, cache_index, cos, sin)

    x = params["embed_tokens"][input_ids]
    x = _constrain(x, residual_spec())

    caches = None
    if use_cache:
        max_cache = int(max_cache_len or c.max_position_embeddings)
        if not (s <= max_cache <= c.max_position_embeddings):
            raise ValueError(
                f"max_cache_len {max_cache} must be in [{s} (prompt length), "
                f"{c.max_position_embeddings} (max_position_embeddings)]"
            )
        x, aux_total, caches = _mixtral_prefill(
            c, params["layers"], x, cos, sin, positions, attention_mask, max_cache
        )
    elif pp_mesh is not None:
        # GPipe with the aux accumulator: routing/capacity statistics are
        # per-microbatch (standard MoE x pipeline semantics), so aux_loss
        # is the microbatch mean rather than the whole-batch statistic
        x, aux_total = pipeline_layer_stack(
            lambda layer, h, pos_mb, mask_mb, cos_b, sin_b: mixtral_layer_apply(
                c, layer, h, cos_b, sin_b, pos_mb, mask_mb
            ),
            params["layers"], x,
            mesh=pp_mesh,
            remat=c.remat,
            positions=positions,
            mask=attention_mask,
            rope=(cos, sin),
            num_microbatches=c.pipeline_microbatches,
            with_aux=True,
        )
    else:
        def body(carry, layer):
            x, aux_sum = carry
            x, aux = mixtral_layer_apply(c, layer, x, cos, sin, positions, attention_mask)
            return (x, aux_sum + aux), None

        body_fn = remat_wrap(body, c.remat)
        (x, aux_total), _ = jax.lax.scan(
            body_fn, (x, jnp.asarray(0.0, jnp.float32)), params["layers"]
        )

    x = rms_norm(x, params["norm"], c.rms_norm_eps)
    logits = dense(x, params["lm_head"])
    logits = _constrain(logits, P(("dp", "fsdp"), "cp", "tp"))

    if aux_total is None and labels is not None:
        # pp prefill has no aux channel; a silent aux-less "loss" would
        # diverge from the uncached forward on identical inputs
        raise ValueError(
            "use_cache=True with labels over a pp>1 mesh cannot fold the "
            "router aux statistic into the loss; compute the training loss "
            "without use_cache (prefill serves decoding)"
        )
    out = ModelOutput(
        logits=logits,
        aux_loss=(jnp.asarray(0.0, jnp.float32) if aux_total is None
                  else aux_total / c.num_hidden_layers),
    )
    if caches is not None:
        out["kv_cache"] = caches
    if labels is not None:
        lm_loss = cross_entropy_loss(logits[:, :-1, :], labels[:, 1:])
        out["lm_loss"] = lm_loss
        out["loss"] = lm_loss + c.router_aux_loss_coef * out["aux_loss"]
    return out


def _mixtral_prefill(c, layers, x, cos, sin, positions, attention_mask, max_cache):
    """Forward that also fills the attention K/V cache. On a pp=1 mesh the
    plain scan additionally accumulates the router aux statistic (so
    ``loss`` with ``use_cache=True`` matches the uncached forward exactly);
    over a pp mesh the fill rides :func:`parallel.pipeline.prefill_stack`,
    which has no aux channel — ``aux_total`` is returned as None and the
    caller refuses to fold it into a training loss."""
    from ..parallel.pipeline import active_pipeline_mesh

    b, s, _ = x.shape
    pad = ((0, 0), (0, max_cache - s), (0, 0), (0, 0))

    if active_pipeline_mesh() is None:

        def body(carry, layer):
            h, aux_sum = carry
            h, aux, (k, v) = mixtral_layer_apply(
                c, layer, h, cos, sin, positions, attention_mask, return_kv=True
            )
            return (h, aux_sum + aux), (jnp.pad(k, pad), jnp.pad(v, pad))

        (x, aux_total), (kc, vc) = jax.lax.scan(
            body, (x, jnp.asarray(0.0, jnp.float32)), layers
        )
        return x, aux_total, {"k": kc, "v": vc}

    from ..parallel.pipeline import prefill_layer_stack

    def prefill_layer(layer, h, pos_b, mask_b, cos_b, sin_b):
        out, _aux, (k, v) = mixtral_layer_apply(
            c, layer, h, cos_b, sin_b, pos_b, mask_b, return_kv=True
        )
        return out, (jnp.pad(k, pad), jnp.pad(v, pad))

    x, caches = prefill_layer_stack(
        prefill_layer, layers, x,
        (c.num_hidden_layers, b, max_cache, c.num_key_value_heads, c.head_dim),
        positions=positions, mask=attention_mask, rope=(cos, sin),
    )
    return x, None, caches


def _mixtral_decode_step(c, params, input_ids, kv_cache, cache_index, cos, sin):
    """One cached decode step (s == 1 token per row at ``cache_index[b]``);
    the layer loop is owned by :func:`parallel.pipeline.decode_stack`."""
    from ..parallel.pipeline import decode_stack

    b, s = input_ids.shape
    idx = jnp.asarray(cache_index, jnp.int32).reshape(b)
    x = params["embed_tokens"][input_ids]

    x, kv = decode_stack(
        lambda layer, h, kc_l, vc_l, idx_b, cos_b, sin_b, pp_manual: _mixtral_decode_layer(
            c, layer, h, kc_l, vc_l, cos_b, sin_b, idx_b, pp_manual=pp_manual
        ),
        params["layers"], kv_cache, x, broadcast=(idx, cos, sin),
    )
    x = rms_norm(x, params["norm"], c.rms_norm_eps)
    logits = dense(x, params["lm_head"])
    return ModelOutput(logits=logits, kv_cache=kv)


class MixtralForCausalLM:
    @staticmethod
    def from_config(config: MixtralConfig, seed: int = 0, dtype=jnp.float32) -> Model:
        import dataclasses as _dc

        from ..big_modeling import is_empty_init

        # private copy: apply_fn closes over it, so per-model knob
        # changes (e.g. prepare() wiring activation_checkpointing
        # into remat) cannot leak into other models built from the
        # same config object
        config = _dc.replace(config)

        if is_empty_init():
            params = jax.eval_shape(
                lambda k: init_mixtral_params(k, config, dtype=dtype), jax.random.key(0)
            )
        else:
            params = init_mixtral_params(jax.random.key(seed), config, dtype=dtype)

        def apply_fn(p, **kwargs):
            return mixtral_apply(config, p, **kwargs)

        model = Model(
            apply_fn, params,
            partition_rules=MIXTRAL_PARTITION_RULES,
            name="MixtralForCausalLM",
        )
        model.config = config
        model.supports_kv_cache = True
        model.stacked_params_prefix = "layers"
        return model
