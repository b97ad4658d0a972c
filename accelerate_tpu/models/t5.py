"""T5 encoder-decoder: relative-position-bias transformer (Raffel et al.).

Completes the reference's example model set — its PiPPy inference examples
cover {bert, gpt2, llama, t5} (``/root/reference/examples/inference/pippy/
t5.py``) and this zoo now covers the same four plus mixtral. Same TPU-first
recipe as the other families: layer-stacked params + ``lax.scan``,
partition rules over the (fsdp, tp) axes, f32 softmax.

T5 quirks faithfully kept:

* RMSNorm without mean-centering or bias (same as llama's);
* **no** ``1/sqrt(d)`` attention scaling — the initializer compensates;
* bucketed relative-position bias, computed once per stack and shared by
  every layer (HF stores it on block 0), added to self-attention scores —
  encoder bidirectional, decoder causal; cross-attention carries no bias;
* dense layers have no biases; v1.0 ReLU FFN or v1.1 gated-GELU FFN
  (``feed_forward_proj="gated-gelu"``);
* tied embedding with ``1/sqrt(d)`` output rescaling when
  ``tie_word_embeddings`` (v1.0), untied ``lm_head`` otherwise (v1.1).

The additive score bias rules out the flash kernel (it takes only a
segment mask), so attention here is the einsum formulation — T5 workloads
are short-sequence seq2seq, where the f32-softmax einsum is HBM-fine.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..modules import Model, ModelOutput
from ..ops.fp8 import dense
from ..ops.layers import cross_entropy_loss, mesh_constrain as _constrain, rms_norm
from ..parallel.pipeline import remat_wrap


@dataclass
class T5Config:
    vocab_size: int = 32128
    hidden_size: int = 512  # d_model
    d_kv: int = 64  # per-head dim (T5 decouples it from d_model/heads)
    d_ff: int = 2048
    num_layers: int = 6  # encoder depth
    num_decoder_layers: int = 6
    num_heads: int = 8
    relative_attention_num_buckets: int = 32
    relative_attention_max_distance: int = 128
    layer_norm_epsilon: float = 1e-6
    feed_forward_proj: str = "relu"  # "relu" (v1.0) | "gated-gelu" (v1.1)
    tie_word_embeddings: bool = True
    decoder_start_token_id: int = 0
    remat: bool | str = False  # False | True | jax.checkpoint_policies name
    #: GPipe microbatch count when the mesh has a pp axis > 1 (0 = auto)
    pipeline_microbatches: int = 0

    @classmethod
    def t5_small(cls):
        return cls()

    @classmethod
    def t5_base(cls):
        return cls(hidden_size=768, d_ff=3072, num_layers=12, num_decoder_layers=12, num_heads=12)

    @classmethod
    def t5_11b(cls):
        return cls(
            hidden_size=1024, d_kv=128, d_ff=65536,
            num_layers=24, num_decoder_layers=24, num_heads=128,
        )

    @classmethod
    def tiny(cls, vocab_size=256, hidden_size=64, layers=2, heads=4):
        return cls(
            vocab_size=vocab_size, hidden_size=hidden_size, d_kv=hidden_size // heads,
            d_ff=hidden_size * 3, num_layers=layers, num_decoder_layers=layers,
            num_heads=heads,
        )


#: stacked leaves carry a leading [layers] dim; rel_bias is per-stack
T5_PARTITION_RULES = [
    (r"shared", P("tp", "fsdp")),
    (r"lm_head", P("fsdp", "tp")),
    (r"(encoder|decoder)\.rel_bias", P(None, "tp")),
    (r"layers\.(wq|wk|wv|cq|ck|cv)", P(None, "fsdp", "tp")),
    (r"layers\.(wo|co)$", P(None, "tp", "fsdp")),
    (r"layers\.(wi|wi_0|wi_1)", P(None, "fsdp", "tp")),
    (r"layers\.wo_ffn", P(None, "tp", "fsdp")),
    (r"layers\..*_norm", P()),
    (r"final_norm", P()),
]


def relative_position_bucket(
    relative_position: jax.Array, bidirectional: bool, num_buckets: int, max_distance: int
) -> jax.Array:
    """T5's log-bucketed relative positions (HF
    ``T5Attention._relative_position_bucket`` semantics)."""
    ret = jnp.zeros_like(relative_position)
    n = -relative_position
    if bidirectional:
        num_buckets //= 2
        ret = ret + (n < 0).astype(jnp.int32) * num_buckets
        n = jnp.abs(n)
    else:
        n = jnp.maximum(n, 0)
    max_exact = num_buckets // 2
    is_small = n < max_exact
    log_ratio = jnp.log(n.astype(jnp.float32) / max_exact + 1e-6) / np.log(
        max_distance / max_exact
    )
    large = max_exact + (log_ratio * (num_buckets - max_exact)).astype(jnp.int32)
    large = jnp.minimum(large, num_buckets - 1)
    return ret + jnp.where(is_small, n, large)


def compute_position_bias(
    rel_bias: jax.Array,  # [num_buckets, num_heads]
    q_len: int,
    k_len: int,
    bidirectional: bool,
    num_buckets: int,
    max_distance: int,
) -> jax.Array:
    """[1, num_heads, q_len, k_len] additive score bias."""
    ctx = jnp.arange(q_len, dtype=jnp.int32)[:, None]
    mem = jnp.arange(k_len, dtype=jnp.int32)[None, :]
    buckets = relative_position_bucket(mem - ctx, bidirectional, num_buckets, max_distance)
    bias = rel_bias[buckets]  # [q, k, heads]
    return bias.transpose(2, 0, 1)[None]


def init_t5_params(key: jax.Array, config: T5Config, dtype=jnp.float32):
    c = config
    h, kv, ff, nh = c.hidden_size, c.d_kv, c.d_ff, c.num_heads
    inner = nh * kv
    keys = iter(jax.random.split(key, 24))

    def w(*shape, scale):
        return (
            jax.random.normal(next(keys), shape, dtype=jnp.float32) * scale
        ).astype(dtype)

    def stack_ffn(L):
        # T5's scaled init: factor 1/sqrt(fan_in)
        if c.feed_forward_proj == "gated-gelu":
            ffn = {
                "wi_0": w(L, h, ff, scale=h**-0.5),
                "wi_1": w(L, h, ff, scale=h**-0.5),
            }
        else:
            ffn = {"wi": w(L, h, ff, scale=h**-0.5)}
        ffn["wo_ffn"] = w(L, ff, h, scale=ff**-0.5)
        return ffn

    def attn_stack(L, prefix):
        # T5 init: q gets (d_model*d_kv)^-0.5, k/v/o get d_model^-0.5
        names = {"q": (h, inner), "k": (h, inner), "v": (h, inner), "o": (inner, h)}
        scales = {"q": (h * kv) ** -0.5, "k": h**-0.5, "v": h**-0.5, "o": inner**-0.5}
        return {
            f"{prefix}{n}": w(L, *shape, scale=scales[n]) for n, shape in names.items()
        }

    def norm(L, *shape):
        return jnp.ones((L, *shape) if L else shape, dtype=dtype)

    L_e, L_d = c.num_layers, c.num_decoder_layers
    params = {
        "shared": w(c.vocab_size, h, scale=1.0),
        "encoder": {
            # T5's scaled init applies to the bias table too (std d_model^-0.5)
            "rel_bias": w(c.relative_attention_num_buckets, nh, scale=h**-0.5),
            "layers": {
                "attn_norm": norm(L_e, h),
                **attn_stack(L_e, "w"),
                "ffn_norm": norm(L_e, h),
                **stack_ffn(L_e),
            },
            "final_norm": norm(0, h),
        },
        "decoder": {
            "rel_bias": w(c.relative_attention_num_buckets, nh, scale=h**-0.5),
            "layers": {
                "attn_norm": norm(L_d, h),
                **attn_stack(L_d, "w"),
                "cross_norm": norm(L_d, h),
                **attn_stack(L_d, "c"),
                "ffn_norm": norm(L_d, h),
                **stack_ffn(L_d),
            },
            "final_norm": norm(0, h),
        },
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = w(h, c.vocab_size, scale=h**-0.5)
    return params


def _t5_attention(q, k, v, bias, mask):
    """T5 attention: unscaled QK^T + additive bias, f32 softmax.

    q: [b, sq, nh, kv]; k/v: [b, sk, nh, kv]; bias broadcastable to
    [b, nh, sq, sk] (or None); mask: [b, sk] validity of the keys (or None).
    """
    b, sq, nh, hd = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :].astype(bool), scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _split_heads(x, nh, kv):
    b, s, _ = x.shape
    return x.reshape(b, s, nh, kv)


def t5_self_attention(c, layer, x, bias, mask, prefix="w"):
    nh, kv = c.num_heads, c.d_kv
    q = _split_heads(dense(x, layer[f"{prefix}q"]), nh, kv)
    k = _split_heads(dense(x, layer[f"{prefix}k"]), nh, kv)
    v = _split_heads(dense(x, layer[f"{prefix}v"]), nh, kv)
    q = _constrain(q, P(("dp", "fsdp"), None, "tp", None))
    k = _constrain(k, P(("dp", "fsdp"), None, "tp", None))
    attn = _t5_attention(q, k, v, bias, mask)
    b, s = x.shape[:2]
    return dense(attn.reshape(b, s, nh * kv), layer[f"{prefix}o"])


def t5_cross_attention(c, layer, x, enc_out, enc_mask):
    nh, kv = c.num_heads, c.d_kv
    q = _split_heads(dense(x, layer["cq"]), nh, kv)
    k = _split_heads(dense(enc_out, layer["ck"]), nh, kv)
    v = _split_heads(dense(enc_out, layer["cv"]), nh, kv)
    attn = _t5_attention(q, k, v, None, enc_mask)
    b, s = x.shape[:2]
    return dense(attn.reshape(b, s, nh * kv), layer["co"])


def _t5_ffn(c, layer, x):
    y = rms_norm(x, layer["ffn_norm"], c.layer_norm_epsilon)
    if c.feed_forward_proj == "gated-gelu":
        z = jax.nn.gelu(dense(y, layer["wi_0"])) * dense(y, layer["wi_1"])
    else:
        z = jax.nn.relu(dense(y, layer["wi"]))
    return x + dense(z, layer["wo_ffn"])


def t5_encoder_layer_apply(c, layer, x, bias, mask):
    y = rms_norm(x, layer["attn_norm"], c.layer_norm_epsilon)
    x = x + t5_self_attention(c, layer, y, bias, mask)
    x = _t5_ffn(c, layer, x)
    return _constrain(x, P(("dp", "fsdp"), None, None))


def t5_decoder_layer_apply(c, layer, x, bias, dec_mask, enc_out, enc_mask):
    y = rms_norm(x, layer["attn_norm"], c.layer_norm_epsilon)
    x = x + t5_self_attention(c, layer, y, bias, dec_mask)
    y = rms_norm(x, layer["cross_norm"], c.layer_norm_epsilon)
    x = x + t5_cross_attention(c, layer, y, enc_out, enc_mask)
    x = _t5_ffn(c, layer, x)
    return _constrain(x, P(("dp", "fsdp"), None, None))


def _causal_bias(bias, s):
    """Merge the decoder's relative bias with the causal mask."""
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]
    return jnp.where(causal, bias, -1e9)


def shift_right(labels: jax.Array, decoder_start_token_id: int, pad_id: int = 0):
    """Teacher-forcing decoder inputs from labels (HF ``_shift_right``):
    prepend the start token, drop the last position, replace -100 with pad."""
    shifted = jnp.roll(labels, 1, axis=-1).at[:, 0].set(decoder_start_token_id)
    return jnp.where(shifted == -100, pad_id, shifted)


def t5_encode(c, params, input_ids, attention_mask):
    x = params["shared"][input_ids]
    x = _constrain(x, P(("dp", "fsdp"), None, None))
    s = input_ids.shape[1]
    bias = compute_position_bias(
        params["encoder"]["rel_bias"], s, s, True,
        c.relative_attention_num_buckets, c.relative_attention_max_distance,
    )

    from ..parallel.pipeline import active_pipeline_mesh, pipeline_layer_stack

    pp_mesh = active_pipeline_mesh()
    if pp_mesh is not None:
        x = pipeline_layer_stack(
            lambda layer, h, pos_mb, mask_mb, bias_b: t5_encoder_layer_apply(
                c, layer, h, bias_b, mask_mb
            ),
            params["encoder"]["layers"], x,
            mesh=pp_mesh,
            remat=c.remat,
            mask=attention_mask,
            rope=(bias,),
            num_microbatches=c.pipeline_microbatches,
        )
    else:
        def body(x, layer):
            return t5_encoder_layer_apply(c, layer, x, bias, attention_mask), None

        body_fn = remat_wrap(body, c.remat)
        x, _ = jax.lax.scan(body_fn, x, params["encoder"]["layers"])
    return rms_norm(x, params["encoder"]["final_norm"], c.layer_norm_epsilon)


def t5_decode(c, params, decoder_input_ids, decoder_attention_mask, enc_out, enc_mask):
    x = params["shared"][decoder_input_ids]
    x = _constrain(x, P(("dp", "fsdp"), None, None))
    s = decoder_input_ids.shape[1]
    bias = _causal_bias(
        compute_position_bias(
            params["decoder"]["rel_bias"], s, s, False,
            c.relative_attention_num_buckets, c.relative_attention_max_distance,
        ),
        s,
    )

    from ..parallel.pipeline import active_pipeline_mesh, pipeline_layer_stack

    pp_mesh = active_pipeline_mesh()
    if pp_mesh is not None:
        # enc_out (and its mask) are batch-aligned: each microbatch's rows
        # cross-attend their own encoder output slice
        has_enc_mask = enc_mask is not None

        def dec_layer_fn(layer, h, pos_mb, mask_mb, *ops):
            enc_out_mb = ops[0]
            enc_mask_mb = ops[1] if has_enc_mask else None
            bias_b = ops[-1]
            return t5_decoder_layer_apply(
                c, layer, h, bias_b, mask_mb, enc_out_mb, enc_mask_mb
            )

        x = pipeline_layer_stack(
            dec_layer_fn,
            params["decoder"]["layers"], x,
            mesh=pp_mesh,
            remat=c.remat,
            mask=decoder_attention_mask,
            extra_aligned=(enc_out,) + ((enc_mask,) if has_enc_mask else ()),
            rope=(bias,),
            num_microbatches=c.pipeline_microbatches,
        )
    else:
        def body(x, layer):
            return (
                t5_decoder_layer_apply(c, layer, x, bias, decoder_attention_mask, enc_out, enc_mask),
                None,
            )

        body_fn = remat_wrap(body, c.remat)
        x, _ = jax.lax.scan(body_fn, x, params["decoder"]["layers"])
    return rms_norm(x, params["decoder"]["final_norm"], c.layer_norm_epsilon)


def t5_apply(
    config: T5Config,
    params,
    input_ids: jax.Array,  # [b, s_enc]
    attention_mask: jax.Array | None = None,  # [b, s_enc] 1 = real
    decoder_input_ids: jax.Array | None = None,  # [b, s_dec]
    decoder_attention_mask: jax.Array | None = None,
    labels: jax.Array | None = None,  # [b, s_dec]; -100 ignored
    encoder_outputs: jax.Array | None = None,  # [b, s_enc, h] reuse (generation)
):
    """Seq2seq forward. If ``labels`` is given without ``decoder_input_ids``
    the decoder inputs are the shifted-right labels (HF contract), and the
    loss is UNshifted CE — decoder position t predicts label t.
    ``encoder_outputs`` skips the encoder (the HF kwarg generation uses so
    the fixed prompt is encoded once)."""
    c = config
    if decoder_input_ids is None:
        if labels is None:
            raise ValueError("t5_apply needs decoder_input_ids or labels")
        decoder_input_ids = shift_right(labels, c.decoder_start_token_id)

    if encoder_outputs is not None:
        enc_out = encoder_outputs
    else:
        enc_out = t5_encode(c, params, input_ids, attention_mask)
    x = t5_decode(
        c, params, decoder_input_ids, decoder_attention_mask, enc_out, attention_mask
    )

    head = params.get("lm_head")
    if head is None:
        # tied v1.0 head rescales by d_model^-1/2
        head = params["shared"].T * (c.hidden_size**-0.5)
    logits = dense(x, head)
    logits = _constrain(logits, P(("dp", "fsdp"), None, "tp"))

    out = ModelOutput(logits=logits, encoder_last_hidden_state=enc_out)
    if labels is not None:
        out["loss"] = cross_entropy_loss(logits, labels)  # no shift: seq2seq
    return out


_ENC_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm", "wo_ffn")
_DEC_EXTRA = ("cross_norm", "cq", "ck", "cv", "co")


def _ffn_keys(c):
    return ("wi_0", "wi_1") if c.feed_forward_proj == "gated-gelu" else ("wi",)


def t5_segments(config: T5Config):
    """Streaming plan for the offload/pipeline executors: encoder embed →
    L_e× enc layer → enc norm → decoder embed → L_d× dec layer → norm+head
    (mirrors ``llama_segments``; the carry holds the encoder output for
    cross-attention)."""
    c = config
    enc_keys = _ENC_KEYS + _ffn_keys(c)
    dec_keys = _ENC_KEYS + _DEC_EXTRA + _ffn_keys(c)

    def plan(input_ids=None, attention_mask=None, decoder_input_ids=None,
             decoder_attention_mask=None, labels=None, **kw):
        if decoder_input_ids is None:
            if labels is None:
                raise ValueError("t5 needs decoder_input_ids or labels")
            decoder_input_ids = shift_right(jnp.asarray(labels), c.decoder_start_token_id)
        s_enc = input_ids.shape[1]
        s_dec = decoder_input_ids.shape[1]

        def init():
            return {
                "ids": jnp.asarray(input_ids),
                "mask": None if attention_mask is None else jnp.asarray(attention_mask),
                "dec_ids": jnp.asarray(decoder_input_ids),
                "dec_mask": (
                    None if decoder_attention_mask is None
                    else jnp.asarray(decoder_attention_mask)
                ),
            }

        def enc_embed_fn(seg, carry):
            bias = compute_position_bias(
                seg["encoder.rel_bias"], s_enc, s_enc, True,
                c.relative_attention_num_buckets, c.relative_attention_max_distance,
            )
            return {**carry, "x": seg["shared"][carry["ids"]], "enc_bias": bias}

        def enc_layer_fn(seg, carry):
            layer = {k: seg[f"encoder.layers.{k}"] for k in enc_keys}
            x = t5_encoder_layer_apply(c, layer, carry["x"], carry["enc_bias"], carry["mask"])
            return {**carry, "x": x}

        def enc_final_fn(seg, carry):
            enc_out = rms_norm(carry["x"], seg["encoder.final_norm"], c.layer_norm_epsilon)
            return {**carry, "enc_out": enc_out}

        def dec_embed_fn(seg, carry):
            bias = _causal_bias(
                compute_position_bias(
                    seg["decoder.rel_bias"], s_dec, s_dec, False,
                    c.relative_attention_num_buckets, c.relative_attention_max_distance,
                ),
                s_dec,
            )
            return {**carry, "x": seg["shared"][carry["dec_ids"]], "dec_bias": bias}

        def dec_layer_fn(seg, carry):
            layer = {k: seg[f"decoder.layers.{k}"] for k in dec_keys}
            x = t5_decoder_layer_apply(
                c, layer, carry["x"], carry["dec_bias"], carry["dec_mask"],
                carry["enc_out"], carry["mask"],
            )
            return {**carry, "x": x}

        def head_fn(seg, carry):
            x = rms_norm(carry["x"], seg["decoder.final_norm"], c.layer_norm_epsilon)
            head = seg.get("lm_head")
            if head is None:
                # scale x instead of the table: (x*s) @ W == x @ (W*s), and
                # a quantized tied head stays a QTensor for dense()'s
                # int8-GEMM path
                x = x * (c.hidden_size**-0.5)
                head = seg["shared"].T
            return {**carry, "logits": dense(x, head)}

        steps = [("enc_embed", ["shared", "encoder.rel_bias"], enc_embed_fn)]
        for i in range(c.num_layers):
            steps.append(
                (("enc_layer", i), [(f"encoder.layers.{k}", i) for k in enc_keys], enc_layer_fn)
            )
        steps.append(("enc_final", ["encoder.final_norm"], enc_final_fn))
        steps.append(("dec_embed", ["shared", "decoder.rel_bias"], dec_embed_fn))
        for i in range(c.num_decoder_layers):
            steps.append(
                (("dec_layer", i), [(f"decoder.layers.{k}", i) for k in dec_keys], dec_layer_fn)
            )
        head_leaves = ["decoder.final_norm"] + (
            ["shared"] if c.tie_word_embeddings else ["lm_head"]
        )
        steps.append(("head", head_leaves, head_fn))

        def finalize(carry):
            out = ModelOutput(logits=carry["logits"])
            if labels is not None:
                out["loss"] = cross_entropy_loss(carry["logits"], jnp.asarray(labels))
            return out

        return {"init": init, "steps": steps, "finalize": finalize}

    return plan


def convert_hf_t5_state_dict(flat: dict, config: T5Config) -> dict:
    """HF-transformers T5 naming → this stacked layout. HF stores dense
    weights as ``[out, in]`` (torch Linear) — transpose to ``[in, out]``."""
    c = config

    def get(name, transpose=False):
        arr = np.asarray(flat[name])
        return arr.T if transpose else arr

    def stack(fmt, transpose=True):
        return np.stack(
            [get(fmt.format(i), transpose=transpose) for i in range(count)]
        )

    out = {"shared": get("shared.weight")}
    for side, prefix in (("encoder", "encoder"), ("decoder", "decoder")):
        count = c.num_layers if side == "encoder" else c.num_decoder_layers
        sa = f"{prefix}.block.{{}}.layer.0"
        layers = {
            "attn_norm": stack(sa + ".layer_norm.weight", transpose=False),
            "wq": stack(sa + ".SelfAttention.q.weight"),
            "wk": stack(sa + ".SelfAttention.k.weight"),
            "wv": stack(sa + ".SelfAttention.v.weight"),
            "wo": stack(sa + ".SelfAttention.o.weight"),
        }
        ffn_idx = 1 if side == "encoder" else 2
        ff = f"{prefix}.block.{{}}.layer.{ffn_idx}"
        if c.feed_forward_proj == "gated-gelu":
            layers["wi_0"] = stack(ff + ".DenseReluDense.wi_0.weight")
            layers["wi_1"] = stack(ff + ".DenseReluDense.wi_1.weight")
        else:
            layers["wi"] = stack(ff + ".DenseReluDense.wi.weight")
        layers["wo_ffn"] = stack(ff + ".DenseReluDense.wo.weight")
        layers["ffn_norm"] = stack(ff + ".layer_norm.weight", transpose=False)
        if side == "decoder":
            ca = f"{prefix}.block.{{}}.layer.1"
            layers.update({
                "cross_norm": stack(ca + ".layer_norm.weight", transpose=False),
                "cq": stack(ca + ".EncDecAttention.q.weight"),
                "ck": stack(ca + ".EncDecAttention.k.weight"),
                "cv": stack(ca + ".EncDecAttention.v.weight"),
                "co": stack(ca + ".EncDecAttention.o.weight"),
            })
        out[side] = {
            "rel_bias": get(
                f"{prefix}.block.0.layer.0.SelfAttention"
                ".relative_attention_bias.weight"
            ),
            "layers": layers,
            "final_norm": get(f"{prefix}.final_layer_norm.weight"),
        }
    if not c.tie_word_embeddings and "lm_head.weight" in flat:
        out["lm_head"] = get("lm_head.weight", transpose=True)
    return out


class T5ForConditionalGeneration:
    @staticmethod
    def from_config(config: T5Config, seed: int = 0, dtype=jnp.float32) -> Model:
        import dataclasses as _dc

        from ..big_modeling import is_empty_init

        # private copy: apply_fn closes over it, so per-model knob
        # changes (e.g. prepare() wiring activation_checkpointing
        # into remat) cannot leak into other models built from the
        # same config object
        config = _dc.replace(config)

        if is_empty_init():
            params = jax.eval_shape(
                lambda k: init_t5_params(k, config, dtype=dtype), jax.random.key(0)
            )
        else:
            params = init_t5_params(jax.random.key(seed), config, dtype=dtype)

        def apply_fn(p, **kwargs):
            return t5_apply(config, p, **kwargs)

        model = Model(
            apply_fn, params,
            partition_rules=T5_PARTITION_RULES,
            name="T5ForConditionalGeneration",
        )
        model.config = config
        model.is_encoder_decoder = True
        model.stacked_params_prefix = ("encoder.layers", "decoder.layers")
        model.segments = t5_segments(config)
        # the tied v1.0 head reuses "shared" directly (never materialised),
        # so there is no multi-path tied group to declare
        model.tied_parameters = []
        model.convert_state_dict = lambda flat: _flatten_tree(
            convert_hf_t5_state_dict(flat, config)
        )
        return model


def _flatten_tree(tree) -> dict:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = ".".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        flat[key] = leaf
    return flat
