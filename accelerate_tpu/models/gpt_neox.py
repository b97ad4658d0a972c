"""GPT-NeoX causal LM: parallel-residual transformer with partial rotary
position embeddings.

GPT-NeoX-20B and GPT-J-6B are rows of the reference's big-model-inference
benchmark (reference ``benchmarks/big_model_inference/README.md:31-34``);
this family makes both instantiable by name. The two published
architectures share the block (parallel residual ``x + attn(...) + mlp(...)``,
rotary applied to the first ``rotary_dim`` dims of each head, GELU MLP,
untied LM head); they differ only in whether the attention and MLP
branches read separate LayerNorms (NeoX) or one shared LayerNorm (GPT-J,
``shared_layernorm=True``) and whether the QKV/output projections carry
biases (NeoX yes, GPT-J no). Same TPU-first recipe as :mod:`.gpt2`:
layer-stacked params + ``lax.scan``, flash attention routing, partition
rules for tp/fsdp.

HF-name conversion covers the ``gpt_neox`` naming scheme (fused QKV stored
``[heads, 3, head_dim]``-interleaved, rotate-half rotary — the same
rotation this module computes). GPT-J *checkpoints* use rotate-every-two
rotary ordering; loading one requires an even/odd permutation of the
q/k projection columns, applied in :func:`convert_hf_gptj_state_dict`.
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
    cached_attention,
    cross_entropy_loss,
    layer_norm,
    mesh_constrain as _constrain,
    residual_spec,
    rope_frequencies,
    write_kv_cache,
)
from ..parallel.pipeline import remat_wrap


@dataclass
class GPTNeoXConfig:
    vocab_size: int = 50432
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 2048
    rotary_pct: float = 0.25  # fraction of head_dim that rotates
    rope_theta: float = 10000.0
    layer_norm_eps: float = 1e-5
    #: False (e.g. StableLM-style NeoX checkpoints): sequential residual
    #: ``x += attn(ln1(x)); x += mlp(ln2(x))`` instead of the parallel sum
    use_parallel_residual: bool = True
    #: GPT-J: one LayerNorm feeds both the attn and MLP branches
    shared_layernorm: bool = False
    #: GPT-J: no biases on the q/k/v and attn-output projections
    attention_bias: bool = True
    #: MLP GELU flavor: None resolves by family — GPT-NeoX checkpoints use
    #: exact (erf) GELU (HF ``hidden_act="gelu"``) while GPT-J uses the tanh
    #: approximation (``gelu_new``); True/False force tanh/exact.
    gelu_approximate: bool | None = None
    remat: bool | str = False  # False | True | jax.checkpoint_policies name
    #: GPipe microbatch count when the mesh has a pp axis > 1 (0 = auto)
    pipeline_microbatches: int = 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rotary_dim(self) -> int:
        # published configs always produce an even rotary_dim
        return int(self.head_dim * self.rotary_pct)

    @classmethod
    def tiny(cls, vocab_size=256, hidden_size=64, layers=2, heads=4, seq=128, **kw):
        return cls(
            vocab_size=vocab_size,
            hidden_size=hidden_size,
            intermediate_size=4 * hidden_size,
            num_hidden_layers=layers,
            num_attention_heads=heads,
            max_position_embeddings=seq,
            **kw,
        )

    @classmethod
    def neox_20b(cls):
        return cls(
            vocab_size=50432, hidden_size=6144, intermediate_size=24576,
            num_hidden_layers=44, num_attention_heads=64, rotary_pct=0.25,
        )

    @classmethod
    def pythia_1_4b(cls):
        return cls(
            vocab_size=50304, hidden_size=2048, intermediate_size=8192,
            num_hidden_layers=24, num_attention_heads=16, rotary_pct=0.25,
        )

    @classmethod
    def gptj_6b(cls):
        return cls(
            vocab_size=50400, hidden_size=4096, intermediate_size=16384,
            num_hidden_layers=28, num_attention_heads=16,
            rotary_pct=0.25,  # rotary_dim 64 of head_dim 256
            shared_layernorm=True, attention_bias=False,
        )


GPT_NEOX_PARTITION_RULES = [
    (r"wte", P("tp", "fsdp")),
    (r"layers\.w_qkv", P(None, "fsdp", "tp")),
    (r"layers\.b_qkv", P(None, "tp")),
    (r"layers\.w_proj", P(None, "tp", "fsdp")),
    (r"layers\.w_fc", P(None, "fsdp", "tp")),
    (r"layers\.b_fc", P(None, "tp")),
    (r"layers\.w_out", P(None, "tp", "fsdp")),
    (r"layers\.(ln1|ln2)_(g|b)", P()),
    (r"layers\.(b_proj|b_out)", P()),
    (r"ln_f_(g|b)", P()),
    (r"lm_head_b", P("tp")),  # before lm_head: rules match by first search hit
    (r"lm_head", P(None, "tp")),
]


def init_gpt_neox_params(key: jax.Array, config: GPTNeoXConfig, dtype=jnp.float32):
    c = config
    h, ff, L = c.hidden_size, c.intermediate_size, c.num_hidden_layers
    keys = jax.random.split(key, 8)

    def w(k, *shape):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * 0.02).astype(dtype)

    params = {
        "wte": w(keys[0], c.vocab_size, h),
        "layers": {
            "ln1_g": jnp.ones((L, h), dtype), "ln1_b": jnp.zeros((L, h), dtype),
            "w_qkv": w(keys[1], L, h, 3 * h),
            "w_proj": w(keys[2], L, h, h),
            "w_fc": w(keys[3], L, h, ff),
            "b_fc": jnp.zeros((L, ff), dtype),
            "w_out": w(keys[4], L, ff, h),
            "b_out": jnp.zeros((L, h), dtype),
        },
        "ln_f_g": jnp.ones((h,), dtype),
        "ln_f_b": jnp.zeros((h,), dtype),
        "lm_head": w(keys[5], h, c.vocab_size),  # untied (NeoX embed_out)
    }
    if not c.shared_layernorm:
        params["layers"]["ln2_g"] = jnp.ones((L, h), dtype)
        params["layers"]["ln2_b"] = jnp.zeros((L, h), dtype)
    if c.attention_bias:
        params["layers"]["b_qkv"] = jnp.zeros((L, 3 * h), dtype)
        params["layers"]["b_proj"] = jnp.zeros((L, h), dtype)
    else:
        params["lm_head_b"] = jnp.zeros((c.vocab_size,), dtype)  # GPT-J head bias
    return params


def _gelu(c: GPTNeoXConfig, x):
    """Family-resolved GELU: exact erf for NeoX, tanh for GPT-J (which is
    identified by its shared LayerNorm) unless ``gelu_approximate`` forces
    one. The tanh/erf gap is ~1e-3 at |x|≈2 — above checkpoint-parity
    tolerance, so the flavor must match the published architecture."""
    approx = c.gelu_approximate
    if approx is None:
        approx = c.shared_layernorm  # GPT-J
    return jax.nn.gelu(x, approximate=approx)


def _partial_rope(x, cos, sin, positions, rotary_dim):
    """Rotate the first ``rotary_dim`` dims of each head, pass the rest."""
    x_rot, x_pass = x[..., :rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([apply_rope(x_rot, cos, sin, positions), x_pass], axis=-1)


def gpt_neox_layer_apply(
    config: GPTNeoXConfig, layer, x, attention_mask, rope, positions,
    return_kv: bool = False,
):
    """One parallel-residual block on UNstacked layer params (shared by the
    scan body and the streaming executor): both branches read the *input*
    hidden state, so ``x + attn(ln1(x)) + mlp(ln2(x))`` — one residual add,
    not two sequential ones. ``return_kv`` additionally returns this
    block's (K, V) so prefill caches reuse them."""
    c = config
    cos, sin = rope
    nh, hd = c.num_attention_heads, c.head_dim
    b, s, h = x.shape
    y = layer_norm(x, layer["ln1_g"], layer["ln1_b"], c.layer_norm_eps)
    qkv = dense(y, layer["w_qkv"])
    if c.attention_bias:
        qkv = qkv + layer["b_qkv"]
    q, k, v = (z.reshape(b, s, nh, hd) for z in jnp.split(qkv, 3, axis=-1))
    q = _partial_rope(q, cos, sin, positions, c.rotary_dim)
    k = _partial_rope(k, cos, sin, positions, c.rotary_dim)
    q = _constrain(q, P(("dp", "fsdp"), "cp", "tp", None))
    k = _constrain(k, P(("dp", "fsdp"), "cp", "tp", None))
    attn = attention(q, k, v, segment_mask=attention_mask, causal=True)
    attn_out = dense(attn.reshape(b, s, h), layer["w_proj"])
    if c.attention_bias:
        attn_out = attn_out + layer["b_proj"]
    if not c.use_parallel_residual:
        x = x + attn_out
        attn_out = 0.0  # folded in already; the final add below is mlp-only
    if c.shared_layernorm:
        y2 = y  # GPT-J: the MLP branch reads the same normed input
    else:
        y2 = layer_norm(x, layer["ln2_g"], layer["ln2_b"], c.layer_norm_eps)
    mlp_out = dense(
        _gelu(c, dense(y2, layer["w_fc"]) + layer["b_fc"]), layer["w_out"]
    ) + layer["b_out"]
    x = x + attn_out + mlp_out
    x = _constrain(x, residual_spec())
    if return_kv:
        return x, (k, v)
    return x


def gpt_neox_apply(
    config: GPTNeoXConfig,
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
            f"{c.max_position_embeddings}: the RoPE table gather would "
            "silently clamp, producing wrong logits"
        )
    from ..parallel.pipeline import active_pipeline_mesh, pipeline_layer_stack

    pp_mesh = active_pipeline_mesh()
    if kv_cache is not None:
        return _gpt_neox_decode_step(c, params, input_ids, kv_cache, cache_index)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cos, sin = rope_frequencies(c.rotary_dim, c.max_position_embeddings, c.rope_theta)

    x = params["wte"][input_ids]
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
            out, (k, v) = gpt_neox_layer_apply(
                c, layer, h, mask_b, (cos, sin), pos_b, return_kv=True
            )
            return out, (jnp.pad(k, pad), jnp.pad(v, pad))

        x, caches = prefill_layer_stack(
            prefill_layer, params["layers"], x,
            (c.num_hidden_layers, b, max_cache, c.num_attention_heads, c.head_dim),
            positions=positions, mask=attention_mask,
        )
    elif pp_mesh is not None:
        x = pipeline_layer_stack(
            lambda layer, h, pos_mb, mask_mb: gpt_neox_layer_apply(
                c, layer, h, mask_mb, (cos, sin), pos_mb
            ),
            params["layers"], x,
            mesh=pp_mesh,
            remat=c.remat,
            positions=positions,
            mask=attention_mask,
            num_microbatches=c.pipeline_microbatches,
        )
    else:
        def body(x, layer):
            return gpt_neox_layer_apply(
                c, layer, x, attention_mask, (cos, sin), positions
            ), None

        body_fn = remat_wrap(body, c.remat)
        x, _ = jax.lax.scan(body_fn, x, params["layers"])

    x = layer_norm(x, params["ln_f_g"], params["ln_f_b"], c.layer_norm_eps)
    logits = dense(x, params["lm_head"])
    if "lm_head_b" in params:
        logits = logits + params["lm_head_b"]
    logits = _constrain(logits, P(("dp", "fsdp"), "cp", "tp"))

    out = ModelOutput(logits=logits)
    if caches is not None:
        out["kv_cache"] = caches
    if labels is not None:
        out["loss"] = cross_entropy_loss(logits[:, :-1, :], labels[:, 1:])
    return out


def _gpt_neox_decode_layer(c, layer, x, k_cache_l, v_cache_l, idx, rope, pp_manual=False):
    """One cached decode block on UNstacked layer params: the parallel
    residual with partial rotary at each row's cache position
    (``pp_manual``: see :func:`accelerate_tpu.ops.layers.write_kv_cache`)."""
    cos, sin = rope
    b, s, _ = x.shape
    nh, hd = c.num_attention_heads, c.head_dim
    positions = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # [b, s]
    y = layer_norm(x, layer["ln1_g"], layer["ln1_b"], c.layer_norm_eps)
    qkv = dense(y, layer["w_qkv"])
    if c.attention_bias:
        qkv = qkv + layer["b_qkv"]
    q, k, v = (z.reshape(b, s, nh, hd) for z in jnp.split(qkv, 3, axis=-1))
    q = _partial_rope(q, cos, sin, positions, c.rotary_dim)
    k = _partial_rope(k, cos, sin, positions, c.rotary_dim)
    if pp_manual:
        q = _constrain(q, P())
    k_cache_l, v_cache_l = write_kv_cache(
        k_cache_l, v_cache_l, k, v, idx, pin_replicated=pp_manual
    )
    attn = cached_attention(q, k_cache_l, v_cache_l, idx)
    attn_out = dense(attn.reshape(b, s, nh * hd), layer["w_proj"])
    if c.attention_bias:
        attn_out = attn_out + layer["b_proj"]
    if not c.use_parallel_residual:
        x = x + attn_out
        attn_out = 0.0  # folded in already; the final add below is mlp-only
    y2 = y if c.shared_layernorm else layer_norm(
        x, layer["ln2_g"], layer["ln2_b"], c.layer_norm_eps
    )
    mlp_out = dense(
        _gelu(c, dense(y2, layer["w_fc"]) + layer["b_fc"]), layer["w_out"]
    ) + layer["b_out"]
    return x + attn_out + mlp_out, k_cache_l, v_cache_l


def _gpt_neox_decode_step(c, params, input_ids, kv_cache, cache_index):
    """One cached decode step: s == 1 token per row appended at
    ``cache_index[b]``; the layer loop is owned by
    :func:`parallel.pipeline.decode_stack`."""
    from ..parallel.pipeline import decode_stack

    b, s = input_ids.shape
    idx = jnp.asarray(cache_index, jnp.int32).reshape(b)
    cos, sin = rope_frequencies(c.rotary_dim, c.max_position_embeddings, c.rope_theta)
    x = params["wte"][input_ids]

    x, kv = decode_stack(
        lambda layer, h, kc_l, vc_l, idx_b, pp_manual: _gpt_neox_decode_layer(
            c, layer, h, kc_l, vc_l, idx_b, (cos, sin), pp_manual=pp_manual
        ),
        params["layers"], kv_cache, x, broadcast=(idx,),
    )
    x = layer_norm(x, params["ln_f_g"], params["ln_f_b"], c.layer_norm_eps)
    logits = dense(x, params["lm_head"])
    if "lm_head_b" in params:
        logits = logits + params["lm_head_b"]
    return ModelOutput(logits=logits, kv_cache=kv)


def _layer_keys(config: GPTNeoXConfig):
    keys = ["ln1_g", "ln1_b", "w_qkv", "w_proj", "w_fc", "b_fc", "w_out", "b_out"]
    if not config.shared_layernorm:
        keys += ["ln2_g", "ln2_b"]
    if config.attention_bias:
        keys += ["b_qkv", "b_proj"]
    return keys


def gpt_neox_segments(config: GPTNeoXConfig):
    """Streaming plan (offload/pipeline executors): embed → L× layer →
    final-norm+head (mirrors ``gpt2_segments``)."""
    layer_keys = _layer_keys(config)

    def plan(input_ids=None, attention_mask=None, positions=None, labels=None, **kw):
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        rope = rope_frequencies(
            config.rotary_dim, config.max_position_embeddings, config.rope_theta
        )

        def init():
            return {
                "ids": jnp.asarray(input_ids),
                "mask": None if attention_mask is None else jnp.asarray(attention_mask),
                "pos": positions,
            }

        def embed_fn(seg, carry):
            return {**carry, "x": seg["wte"][carry["ids"]]}

        def layer_fn(seg, carry):
            layer = {k: seg[f"layers.{k}"] for k in layer_keys}
            return {
                **carry,
                "x": gpt_neox_layer_apply(
                    config, layer, carry["x"], carry["mask"], rope, carry["pos"]
                ),
            }

        head_leaves = ["ln_f_g", "ln_f_b", "lm_head"]
        if not config.attention_bias:
            head_leaves.append("lm_head_b")

        def head_fn(seg, carry):
            x = layer_norm(carry["x"], seg["ln_f_g"], seg["ln_f_b"], config.layer_norm_eps)
            logits = dense(x, seg["lm_head"])
            if "lm_head_b" in seg:
                logits = logits + seg["lm_head_b"]
            return {**carry, "logits": logits}

        steps = [("embed", ["wte"], embed_fn)]
        for i in range(config.num_hidden_layers):
            steps.append(
                (("layer", i), [(f"layers.{k}", i) for k in layer_keys], layer_fn)
            )
        steps.append(("head", head_leaves, head_fn))

        def finalize(carry):
            out = ModelOutput(logits=carry["logits"])
            if labels is not None:
                out["loss"] = cross_entropy_loss(
                    carry["logits"][:, :-1, :], jnp.asarray(labels)[:, 1:]
                )
            return out

        return {"init": init, "steps": steps, "finalize": finalize}

    return plan


def convert_hf_gpt_neox_state_dict(flat: dict, config: GPTNeoXConfig) -> dict:
    """HF-transformers GPT-NeoX naming → this model's stacked layout.

    HF fuses QKV as ``[3*h, h]`` with rows interleaved per head
    ``[head0-q, head0-k, head0-v, head1-q, ...]``; ours splits Q|K|V
    contiguously on the output dim, so the rows are de-interleaved before
    the transpose. HF rotary is rotate-half over the first ``rotary_dim``
    dims — identical to :func:`apply_rope` — so no column permutation."""
    c = config
    L, nh, hd, h = c.num_hidden_layers, c.num_attention_heads, c.head_dim, c.hidden_size

    def get(name):
        for prefix in ("gpt_neox.", ""):
            if prefix + name in flat:
                return np.asarray(flat[prefix + name])
        raise KeyError(name)

    def split_qkv_w(w_hf):  # [3h, h] interleaved → [h, 3h] contiguous
        w = w_hf.reshape(nh, 3, hd, h)
        return np.concatenate(
            [w[:, j].reshape(nh * hd, h).T for j in range(3)], axis=1
        )

    def split_qkv_b(b_hf):  # [3h] interleaved → [3h] contiguous
        b = b_hf.reshape(nh, 3, hd)
        return np.concatenate([b[:, j].reshape(nh * hd) for j in range(3)])

    def stack(fmt, f=lambda a: a):
        return np.stack([f(get(fmt.format(i))) for i in range(L)])

    layers = {
        "ln1_g": stack("layers.{}.input_layernorm.weight"),
        "ln1_b": stack("layers.{}.input_layernorm.bias"),
        "w_qkv": stack("layers.{}.attention.query_key_value.weight", split_qkv_w),
        "b_qkv": stack("layers.{}.attention.query_key_value.bias", split_qkv_b),
        "w_proj": stack("layers.{}.attention.dense.weight", lambda a: a.T),
        "b_proj": stack("layers.{}.attention.dense.bias"),
        "ln2_g": stack("layers.{}.post_attention_layernorm.weight"),
        "ln2_b": stack("layers.{}.post_attention_layernorm.bias"),
        "w_fc": stack("layers.{}.mlp.dense_h_to_4h.weight", lambda a: a.T),
        "b_fc": stack("layers.{}.mlp.dense_h_to_4h.bias"),
        "w_out": stack("layers.{}.mlp.dense_4h_to_h.weight", lambda a: a.T),
        "b_out": stack("layers.{}.mlp.dense_4h_to_h.bias"),
    }
    return {
        "wte": get("embed_in.weight"),
        "layers": layers,
        "ln_f_g": get("final_layer_norm.weight"),
        "ln_f_b": get("final_layer_norm.bias"),
        "lm_head": np.asarray(flat["embed_out.weight"]).T,
    }


def convert_hf_gptj_state_dict(flat: dict, config: GPTNeoXConfig) -> dict:
    """HF-transformers GPT-J naming → this model's stacked layout
    (``shared_layernorm=True``, ``attention_bias=False`` config).

    GPT-J checkpoints use rotate-every-two rotary ordering (pairs
    ``(x0,x1),(x2,x3),...``) while :func:`apply_rope` rotates halves
    (``(x_i, x_{i+rd/2})``); permuting the q/k projection columns within
    the rotary span — even columns first, then odd — makes the two
    orderings compute identical attention scores."""
    c = config
    L, rd, h = c.num_hidden_layers, c.rotary_dim, c.hidden_size
    nh, hd = c.num_attention_heads, c.head_dim
    # even/odd permutation within each head's rotary span
    perm_head = np.concatenate(
        [np.arange(0, rd, 2), np.arange(1, rd, 2), np.arange(rd, hd)]
    )
    perm = np.concatenate([perm_head + i * hd for i in range(nh)])

    def get(name):
        for prefix in ("transformer.", ""):
            if prefix + name in flat:
                return np.asarray(flat[prefix + name])
        raise KeyError(name)

    def stack(fmt, f=lambda a: a):
        return np.stack([f(get(fmt.format(i))) for i in range(L)])

    def qk(w_hf):  # [h, h] HF [out,in] → ours [in,out], rotary-permuted
        return w_hf.T[:, perm]

    return {
        "wte": get("wte.weight"),
        "layers": {
            "ln1_g": stack("h.{}.ln_1.weight"),
            "ln1_b": stack("h.{}.ln_1.bias"),
            "w_qkv": np.concatenate(
                [
                    stack("h.{}.attn.q_proj.weight", qk),
                    stack("h.{}.attn.k_proj.weight", qk),
                    stack("h.{}.attn.v_proj.weight", lambda a: a.T),
                ],
                axis=2,
            ),
            "w_proj": stack("h.{}.attn.out_proj.weight", lambda a: a.T),
            "w_fc": stack("h.{}.mlp.fc_in.weight", lambda a: a.T),
            "b_fc": stack("h.{}.mlp.fc_in.bias"),
            "w_out": stack("h.{}.mlp.fc_out.weight", lambda a: a.T),
            "b_out": stack("h.{}.mlp.fc_out.bias"),
        },
        "ln_f_g": get("ln_f.weight"),
        "ln_f_b": get("ln_f.bias"),
        "lm_head": np.asarray(flat["lm_head.weight"]).T,
        "lm_head_b": np.asarray(flat["lm_head.bias"]),
    }


class GPTNeoXForCausalLM:
    @staticmethod
    def from_config(config: GPTNeoXConfig, seed: int = 0, dtype=jnp.float32) -> Model:
        import dataclasses as _dc

        from ..big_modeling import is_empty_init

        # private copy: apply_fn closes over it (see GPT2LMHeadModel)
        config = _dc.replace(config)

        if is_empty_init():
            params = jax.eval_shape(
                lambda k: init_gpt_neox_params(k, config, dtype=dtype),
                jax.random.key(0),
            )
        else:
            params = init_gpt_neox_params(jax.random.key(seed), config, dtype=dtype)

        def apply_fn(p, **kwargs):
            return gpt_neox_apply(config, p, **kwargs)

        convert = (
            convert_hf_gptj_state_dict if config.shared_layernorm
            else convert_hf_gpt_neox_state_dict
        )
        model = Model(
            apply_fn, params,
            partition_rules=GPT_NEOX_PARTITION_RULES,
            name="GPTNeoXForCausalLM",
        )
        model.config = config
        model.supports_kv_cache = True
        model.stacked_params_prefix = "layers"
        model.segments = gpt_neox_segments(config)
        model.tied_parameters = []
        model.convert_state_dict = lambda flat: {
            jax.tree_util.keystr(path, simple=True, separator="."): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(convert(flat, config))[0]}
        return model
