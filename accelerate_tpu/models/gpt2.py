"""GPT-2 causal LM: the classic pre-LN transformer with learned positions.

Second decoder family in the zoo (the reference wraps transformers' GPT-2
in its examples, e.g. ``examples/inference/pippy/gpt2.py``). Same TPU-first
recipe as :mod:`.llama` — layer-stacked params + ``lax.scan``, flash
attention routing, partition rules for tp/fsdp — with GPT-2's
architecture: learned absolute position embeddings, true LayerNorm
(mean-centered, with bias), fused-QKV projection, GELU MLP, tied LM head.
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
    cached_attention,
    cross_entropy_loss,
    layer_norm,
    mesh_constrain as _constrain,
    residual_spec,
    write_kv_cache,
)
from ..parallel.pipeline import remat_wrap


@dataclass
class GPT2Config:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 1024
    layer_norm_eps: float = 1e-5
    remat: bool | str = False  # False | True | jax.checkpoint_policies name
    #: GPipe microbatch count when the mesh has a pp axis > 1 (0 = auto)
    pipeline_microbatches: int = 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def intermediate_size(self) -> int:
        return 4 * self.hidden_size

    @classmethod
    def tiny(cls, vocab_size=256, hidden_size=64, layers=2, heads=4, seq=128):
        return cls(
            vocab_size=vocab_size,
            hidden_size=hidden_size,
            num_hidden_layers=layers,
            num_attention_heads=heads,
            max_position_embeddings=seq,
        )


GPT2_PARTITION_RULES = [
    (r"wte", P("tp", "fsdp")),
    (r"wpe", P(None, "fsdp")),
    (r"layers\.w_qkv", P(None, "fsdp", "tp")),
    (r"layers\.b_qkv", P(None, "tp")),
    (r"layers\.w_proj", P(None, "tp", "fsdp")),
    (r"layers\.w_fc", P(None, "fsdp", "tp")),
    (r"layers\.b_fc", P(None, "tp")),
    (r"layers\.w_out", P(None, "tp", "fsdp")),
    (r"layers\.(ln1|ln2)_(g|b)", P()),
    (r"layers\.(b_proj|b_out)", P()),
    (r"ln_f_(g|b)", P()),
]


def init_gpt2_params(key: jax.Array, config: GPT2Config, dtype=jnp.float32):
    c = config
    h, ff, L = c.hidden_size, c.intermediate_size, c.num_hidden_layers
    keys = jax.random.split(key, 8)

    def w(k, *shape):
        # GPT-2's fixed 0.02-std init (no fan-in scaling)
        return (jax.random.normal(k, shape, dtype=jnp.float32) * 0.02).astype(dtype)

    return {
        "wte": w(keys[0], c.vocab_size, h),
        "wpe": w(keys[1], c.max_position_embeddings, h),
        "layers": {
            "ln1_g": jnp.ones((L, h), dtype), "ln1_b": jnp.zeros((L, h), dtype),
            "w_qkv": w(keys[2], L, h, 3 * h),
            "b_qkv": jnp.zeros((L, 3 * h), dtype),
            "w_proj": w(keys[3], L, h, h),
            "b_proj": jnp.zeros((L, h), dtype),
            "ln2_g": jnp.ones((L, h), dtype), "ln2_b": jnp.zeros((L, h), dtype),
            "w_fc": w(keys[4], L, h, ff),
            "b_fc": jnp.zeros((L, ff), dtype),
            "w_out": w(keys[5], L, ff, h),
            "b_out": jnp.zeros((L, h), dtype),
        },
        "ln_f_g": jnp.ones((h,), dtype),
        "ln_f_b": jnp.zeros((h,), dtype),
    }


def gpt2_layer_apply(config: GPT2Config, layer, x, attention_mask, return_kv: bool = False):
    """One pre-LN block on UNstacked layer params (shared by the scan body
    and the streaming executor). ``return_kv`` additionally returns this
    block's (K, V) so prefill caches reuse them."""
    c = config
    nh, hd = c.num_attention_heads, c.head_dim
    b, s, h = x.shape
    y = layer_norm(x, layer["ln1_g"], layer["ln1_b"], c.layer_norm_eps)
    qkv = dense(y, layer["w_qkv"]) + layer["b_qkv"]
    q, k, v = (z.reshape(b, s, nh, hd) for z in jnp.split(qkv, 3, axis=-1))
    q = _constrain(q, P(("dp", "fsdp"), "cp", "tp", None))
    k = _constrain(k, P(("dp", "fsdp"), "cp", "tp", None))
    attn = attention(q, k, v, segment_mask=attention_mask, causal=True)
    x = x + dense(attn.reshape(b, s, h), layer["w_proj"]) + layer["b_proj"]
    x = _constrain(x, residual_spec())
    y = layer_norm(x, layer["ln2_g"], layer["ln2_b"], c.layer_norm_eps)
    x = x + dense(jax.nn.gelu(dense(y, layer["w_fc"]) + layer["b_fc"]), layer["w_out"]) + layer["b_out"]
    x = _constrain(x, residual_spec())
    if return_kv:
        return x, (k, v)
    return x


def gpt2_apply(
    config: GPT2Config,
    params,
    input_ids: jax.Array,
    attention_mask: jax.Array | None = None,
    labels: jax.Array | None = None,
    positions: jax.Array | None = None,
    use_cache: bool = False,
    kv_cache=None,  # {"k","v"}: [L, b, max_cache, nh, hd] (decode step)
    cache_index: jax.Array | None = None,  # [b] per-row write position
    max_cache_len: int | None = None,
):
    c = config
    b, s = input_ids.shape
    if s > c.max_position_embeddings:
        raise ValueError(
            f"sequence length {s} exceeds max_position_embeddings "
            f"{c.max_position_embeddings}: the position-embedding lookup "
            "would silently clamp, producing wrong logits"
        )
    from ..parallel.pipeline import active_pipeline_mesh, pipeline_layer_stack

    pp_mesh = active_pipeline_mesh()
    if kv_cache is not None:
        return _gpt2_decode_step(c, params, input_ids, kv_cache, cache_index)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    x = params["wte"][input_ids] + params["wpe"][positions]
    x = _constrain(x, residual_spec())

    caches = None
    if use_cache:
        max_cache = int(max_cache_len or c.max_position_embeddings)
        if not (s <= max_cache <= c.max_position_embeddings):
            raise ValueError(
                f"max_cache_len {max_cache} must be in [{s} (prompt length), "
                f"{c.max_position_embeddings} (max_position_embeddings)]"
            )

        from ..parallel.pipeline import prefill_layer_stack

        pad = ((0, 0), (0, max_cache - s), (0, 0), (0, 0))

        def prefill_layer(layer, h, pos_b, mask_b):
            out, (k, v) = gpt2_layer_apply(c, layer, h, mask_b, return_kv=True)
            return out, (jnp.pad(k, pad), jnp.pad(v, pad))

        x, caches = prefill_layer_stack(
            prefill_layer, params["layers"], x,
            (c.num_hidden_layers, b, max_cache, c.num_attention_heads, c.head_dim),
            mask=attention_mask,
        )
    elif pp_mesh is not None:
        # GPipe over the pp axis: positions are already folded into x at
        # the embedding, so only the mask rides the microbatch schedule
        x = pipeline_layer_stack(
            lambda layer, h, pos_mb, mask_mb: gpt2_layer_apply(c, layer, h, mask_mb),
            params["layers"], x,
            mesh=pp_mesh,
            remat=c.remat,
            mask=attention_mask,
            num_microbatches=c.pipeline_microbatches,
        )
    else:
        def body(x, layer):
            return gpt2_layer_apply(c, layer, x, attention_mask), None

        body_fn = remat_wrap(body, c.remat)
        x, _ = jax.lax.scan(body_fn, x, params["layers"])

    x = layer_norm(x, params["ln_f_g"], params["ln_f_b"], c.layer_norm_eps)
    logits = dense(x, params["wte"].T)  # tied head
    logits = _constrain(logits, P(("dp", "fsdp"), "cp", "tp"))

    out = ModelOutput(logits=logits)
    if caches is not None:
        out["kv_cache"] = caches
    if labels is not None:
        out["loss"] = cross_entropy_loss(logits[:, :-1, :], labels[:, 1:])
    return out


def _gpt2_decode_layer(c, layer, x, k_cache_l, v_cache_l, idx, pp_manual=False):
    """One cached decode block on UNstacked layer params (mirrors
    ``_llama_decode_layer``, with learned positions and fused QKV;
    ``pp_manual``: see :func:`accelerate_tpu.ops.layers.write_kv_cache`)."""
    b, s, _ = x.shape
    nh, hd = c.num_attention_heads, c.head_dim
    y = layer_norm(x, layer["ln1_g"], layer["ln1_b"], c.layer_norm_eps)
    qkv = dense(y, layer["w_qkv"]) + layer["b_qkv"]
    q, k, v = (z.reshape(b, s, nh, hd) for z in jnp.split(qkv, 3, axis=-1))
    if pp_manual:
        q = _constrain(q, P())
    k_cache_l, v_cache_l = write_kv_cache(
        k_cache_l, v_cache_l, k, v, idx, pin_replicated=pp_manual
    )
    attn = cached_attention(q, k_cache_l, v_cache_l, idx)
    x = x + dense(attn.reshape(b, s, nh * hd), layer["w_proj"]) + layer["b_proj"]
    y = layer_norm(x, layer["ln2_g"], layer["ln2_b"], c.layer_norm_eps)
    x = x + dense(
        jax.nn.gelu(dense(y, layer["w_fc"]) + layer["b_fc"]), layer["w_out"]
    ) + layer["b_out"]
    return x, k_cache_l, v_cache_l


def _gpt2_decode_step(c, params, input_ids, kv_cache, cache_index):
    """One cached decode step: s == 1 token per row appended at
    ``cache_index[b]``; attention is q(1) vs the cache prefix. The layer
    loop is owned by :func:`parallel.pipeline.decode_stack`."""
    from ..parallel.pipeline import decode_stack

    b, s = input_ids.shape
    idx = jnp.asarray(cache_index, jnp.int32).reshape(b)
    pos = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # [b, s]
    x = params["wte"][input_ids] + params["wpe"][pos]

    x, kv = decode_stack(
        lambda layer, h, kc_l, vc_l, idx_b, pp_manual: _gpt2_decode_layer(
            c, layer, h, kc_l, vc_l, idx_b, pp_manual=pp_manual
        ),
        params["layers"], kv_cache, x, broadcast=(idx,),
    )
    x = layer_norm(x, params["ln_f_g"], params["ln_f_b"], c.layer_norm_eps)
    logits = dense(x, params["wte"].T)
    return ModelOutput(logits=logits, kv_cache=kv)


_LAYER_KEYS = (
    "ln1_g", "ln1_b", "w_qkv", "b_qkv", "w_proj", "b_proj",
    "ln2_g", "ln2_b", "w_fc", "b_fc", "w_out", "b_out",
)


def gpt2_segments(config: GPT2Config):
    """Streaming plan (offload/pipeline executors): embed → L× layer →
    final-norm+tied-head (mirrors ``llama_segments``)."""

    def plan(input_ids=None, attention_mask=None, positions=None, labels=None, **kw):
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

        def init():
            return {
                "ids": jnp.asarray(input_ids),
                "mask": None if attention_mask is None else jnp.asarray(attention_mask),
                "pos": positions,
            }

        def embed_fn(seg, carry):
            x = seg["wte"][carry["ids"]] + seg["wpe"][carry["pos"]]
            return {**carry, "x": x}

        def layer_fn(seg, carry):
            layer = {k: seg[f"layers.{k}"] for k in _LAYER_KEYS}
            return {**carry, "x": gpt2_layer_apply(config, layer, carry["x"], carry["mask"])}

        def head_fn(seg, carry):
            x = layer_norm(carry["x"], seg["ln_f_g"], seg["ln_f_b"], config.layer_norm_eps)
            # dense(): a quantized tied head takes the int8-GEMM path
            return {**carry, "logits": dense(x, seg["wte"].T)}

        steps = [("embed", ["wte", "wpe"], embed_fn)]
        for i in range(config.num_hidden_layers):
            steps.append(
                (("layer", i), [(f"layers.{k}", i) for k in _LAYER_KEYS], layer_fn)
            )
        steps.append(("head", ["ln_f_g", "ln_f_b", "wte"], head_fn))

        def finalize(carry):
            out = ModelOutput(logits=carry["logits"])
            if labels is not None:
                out["loss"] = cross_entropy_loss(
                    carry["logits"][:, :-1, :], jnp.asarray(labels)[:, 1:]
                )
            return out

        return {"init": init, "steps": steps, "finalize": finalize}

    return plan


def convert_hf_gpt2_state_dict(flat: dict, config: GPT2Config) -> dict:
    """HF-transformers GPT-2 naming → this model's stacked layout. HF GPT-2
    uses Conv1D (weights already ``[in, out]`` — no transpose needed)."""
    L = config.num_hidden_layers

    def get(name):
        for prefix in ("transformer.", ""):
            if prefix + name in flat:
                return np.asarray(flat[prefix + name])
        raise KeyError(name)

    def stack(fmt):
        return np.stack([get(fmt.format(i)) for i in range(L)])

    return {
        "wte": get("wte.weight"),
        "wpe": get("wpe.weight"),
        "layers": {
            "ln1_g": stack("h.{}.ln_1.weight"), "ln1_b": stack("h.{}.ln_1.bias"),
            "w_qkv": stack("h.{}.attn.c_attn.weight"), "b_qkv": stack("h.{}.attn.c_attn.bias"),
            "w_proj": stack("h.{}.attn.c_proj.weight"), "b_proj": stack("h.{}.attn.c_proj.bias"),
            "ln2_g": stack("h.{}.ln_2.weight"), "ln2_b": stack("h.{}.ln_2.bias"),
            "w_fc": stack("h.{}.mlp.c_fc.weight"), "b_fc": stack("h.{}.mlp.c_fc.bias"),
            "w_out": stack("h.{}.mlp.c_proj.weight"), "b_out": stack("h.{}.mlp.c_proj.bias"),
        },
        "ln_f_g": get("ln_f.weight"),
        "ln_f_b": get("ln_f.bias"),
    }


class GPT2LMHeadModel:
    @staticmethod
    def from_config(config: GPT2Config, seed: int = 0, dtype=jnp.float32) -> Model:
        import dataclasses as _dc

        from ..big_modeling import is_empty_init

        # private copy: apply_fn closes over it, so per-model knob
        # changes (e.g. prepare() wiring activation_checkpointing
        # into remat) cannot leak into other models built from the
        # same config object
        config = _dc.replace(config)

        if is_empty_init():
            params = jax.eval_shape(
                lambda k: init_gpt2_params(k, config, dtype=dtype), jax.random.key(0)
            )
        else:
            params = init_gpt2_params(jax.random.key(seed), config, dtype=dtype)

        def apply_fn(p, **kwargs):
            return gpt2_apply(config, p, **kwargs)

        model = Model(
            apply_fn, params,
            partition_rules=GPT2_PARTITION_RULES,
            name="GPT2LMHeadModel",
        )
        model.config = config
        model.supports_kv_cache = True
        model.stacked_params_prefix = "layers"
        model.segments = gpt2_segments(config)
        model.tied_parameters = []
        model.convert_state_dict = lambda flat: {
            jax.tree_util.keystr(path, simple=True, separator="."): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                convert_hf_gpt2_state_dict(flat, config))[0]}
        return model
