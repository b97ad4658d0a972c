"""BERT-style bidirectional encoder for sequence(-pair) classification.

The examples' model (BASELINE config #1 is BERT-base on GLUE/MRPC via the
reference's ``examples/nlp_example.py``; the reference itself pulls the
model from transformers — this zero-egress build ships its own). TPU-first
design, same recipe as :mod:`.llama`:

* layer-stacked params + ``lax.scan`` — one compiled block program;
* bidirectional (non-causal) attention through :func:`ops.attention`, so
  the flash kernel / context parallelism route the same way as the LMs;
* learned absolute position + token-type embeddings (sentence pairs);
* ``[CLS]``-token pooling + linear head.
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
from ..ops.layers import mesh_constrain as _constrain, residual_spec, rms_norm
from ..parallel.pipeline import remat_wrap


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    num_labels: int = 2
    norm_eps: float = 1e-12
    remat: bool | str = False  # False | True | jax.checkpoint_policies name
    #: GPipe microbatch count when the mesh has a pp axis > 1 (0 = auto)
    pipeline_microbatches: int = 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def tiny(cls, vocab_size=512, hidden_size=64, layers=2, heads=4, seq=64, num_labels=2):
        return cls(
            vocab_size=vocab_size,
            hidden_size=hidden_size,
            intermediate_size=hidden_size * 4,
            num_hidden_layers=layers,
            num_attention_heads=heads,
            max_position_embeddings=seq,
            num_labels=num_labels,
        )


BERT_PARTITION_RULES = [
    (r"embed_tokens", P("tp", "fsdp")),
    (r"embed_positions", P(None, "fsdp")),
    (r"embed_types", P(None, "fsdp")),
    (r"layers\.(wq|wk|wv)", P(None, "fsdp", "tp")),
    (r"layers\.wo", P(None, "tp", "fsdp")),
    (r"layers\.w_in", P(None, "fsdp", "tp")),
    (r"layers\.w_out", P(None, "tp", "fsdp")),
    (r"norm", P()),
    (r"classifier\.w", P("fsdp", None)),
    (r"classifier\.b", P()),
]


def init_bert_params(key: jax.Array, config: BertConfig, dtype=jnp.float32):
    c = config
    h, ff, L = c.hidden_size, c.intermediate_size, c.num_hidden_layers
    keys = jax.random.split(key, 12)

    def _init_dense(k, *shape, in_dim):
        return (jax.random.normal(k, shape, dtype=jnp.float32) / np.sqrt(in_dim)).astype(dtype)

    return {
        "embed_tokens": (jax.random.normal(keys[0], (c.vocab_size, h)) * 0.02).astype(dtype),
        "embed_positions": (jax.random.normal(keys[1], (c.max_position_embeddings, h)) * 0.02).astype(dtype),
        "embed_types": (jax.random.normal(keys[2], (c.type_vocab_size, h)) * 0.02).astype(dtype),
        "emb_norm": jnp.ones((h,), dtype=dtype),
        "layers": {
            "wq": _init_dense(keys[3], L, h, h, in_dim=h),
            "wk": _init_dense(keys[4], L, h, h, in_dim=h),
            "wv": _init_dense(keys[5], L, h, h, in_dim=h),
            "wo": _init_dense(keys[6], L, h, h, in_dim=h),
            "w_in": _init_dense(keys[7], L, h, ff, in_dim=h),
            "w_out": _init_dense(keys[8], L, ff, h, in_dim=ff),
            "attn_norm": jnp.ones((L, h), dtype=dtype),
            "mlp_norm": jnp.ones((L, h), dtype=dtype),
        },
        "norm": jnp.ones((h,), dtype=dtype),
        "classifier": {
            "w": _init_dense(keys[9], h, c.num_labels, in_dim=h),
            "b": jnp.zeros((c.num_labels,), dtype=dtype),
        },
    }


def bert_layer_apply(config: BertConfig, layer, x, attention_mask):
    """One post-embedding encoder block on UNstacked layer params (shared
    by the scan body and the streaming/pipeline executors)."""
    c = config
    nh, hd = c.num_attention_heads, c.head_dim
    b, s, h = x.shape
    y = rms_norm(x, layer["attn_norm"], c.norm_eps)
    q = dense(y, layer["wq"]).reshape(b, s, nh, hd)
    k = dense(y, layer["wk"]).reshape(b, s, nh, hd)
    v = dense(y, layer["wv"]).reshape(b, s, nh, hd)
    q = _constrain(q, P(("dp", "fsdp"), "cp", "tp", None))
    k = _constrain(k, P(("dp", "fsdp"), "cp", "tp", None))
    attn = attention(q, k, v, segment_mask=attention_mask, causal=False)
    x = x + dense(attn.reshape(b, s, nh * hd), layer["wo"])
    x = _constrain(x, residual_spec())
    y = rms_norm(x, layer["mlp_norm"], c.norm_eps)
    x = x + dense(jax.nn.gelu(dense(y, layer["w_in"])), layer["w_out"])
    return _constrain(x, residual_spec())


def _bert_block(config: BertConfig, attention_mask):
    def body(x, layer):
        return bert_layer_apply(config, layer, x, attention_mask), None

    return remat_wrap(body, config.remat)


def bert_apply(
    config: BertConfig,
    params,
    input_ids: jax.Array,                      # [b, s] int32
    attention_mask: jax.Array | None = None,   # [b, s] 1 = real token
    token_type_ids: jax.Array | None = None,   # [b, s] sentence-pair segments
    labels: jax.Array | None = None,           # [b] class index
):
    c = config
    b, s = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s), dtype=jnp.int32)
    if token_type_ids is None:
        token_type_ids = jnp.zeros((b, s), dtype=jnp.int32)

    pos = jnp.arange(s, dtype=jnp.int32)
    x = (
        params["embed_tokens"][input_ids]
        + params["embed_positions"][pos][None, :, :]
        + params["embed_types"][token_type_ids]
    )
    x = rms_norm(x, params["emb_norm"], c.norm_eps)
    x = _constrain(x, residual_spec())

    from ..parallel.pipeline import active_pipeline_mesh, pipeline_layer_stack

    pp_mesh = active_pipeline_mesh()
    if pp_mesh is not None:
        x = pipeline_layer_stack(
            lambda layer, h, pos_mb, mask_mb: bert_layer_apply(c, layer, h, mask_mb),
            params["layers"], x,
            mesh=pp_mesh,
            remat=c.remat,
            mask=attention_mask,
            num_microbatches=c.pipeline_microbatches,
        )
    else:
        x, _ = jax.lax.scan(_bert_block(c, attention_mask), x, params["layers"])
    x = rms_norm(x, params["norm"], c.norm_eps)

    pooled = x[:, 0, :]  # [CLS]
    logits = pooled @ params["classifier"]["w"] + params["classifier"]["b"]

    out = ModelOutput(logits=logits)
    if labels is not None:
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        out["loss"] = -jnp.mean(
            jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)
        )
    return out


_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_in", "w_out", "attn_norm", "mlp_norm")


def bert_segments(config: BertConfig):
    """Streaming plan (offload/pipeline executors): embed → L× layer →
    norm+classifier (mirrors ``gpt2_segments``; the reference's pippy
    example set includes BERT, ``examples/inference/pippy/bert.py``)."""
    c = config

    def plan(input_ids=None, attention_mask=None, token_type_ids=None, labels=None, **kw):
        b, s = input_ids.shape

        def init():
            return {
                "ids": jnp.asarray(input_ids),
                "mask": (
                    jnp.ones((b, s), jnp.int32) if attention_mask is None
                    else jnp.asarray(attention_mask)
                ),
                "types": (
                    jnp.zeros((b, s), jnp.int32) if token_type_ids is None
                    else jnp.asarray(token_type_ids)
                ),
            }

        def embed_fn(seg, carry):
            pos = jnp.arange(s, dtype=jnp.int32)
            x = (
                seg["embed_tokens"][carry["ids"]]
                + seg["embed_positions"][pos][None, :, :]
                + seg["embed_types"][carry["types"]]
            )
            return {**carry, "x": rms_norm(x, seg["emb_norm"], c.norm_eps)}

        def layer_fn(seg, carry):
            layer = {k: seg[f"layers.{k}"] for k in _LAYER_KEYS}
            return {**carry, "x": bert_layer_apply(c, layer, carry["x"], carry["mask"])}

        def head_fn(seg, carry):
            x = rms_norm(carry["x"], seg["norm"], c.norm_eps)
            logits = x[:, 0, :] @ seg["classifier.w"] + seg["classifier.b"]
            return {**carry, "logits": logits}

        steps = [
            ("embed", ["embed_tokens", "embed_positions", "embed_types", "emb_norm"], embed_fn)
        ]
        for i in range(c.num_hidden_layers):
            steps.append(
                (("layer", i), [(f"layers.{k}", i) for k in _LAYER_KEYS], layer_fn)
            )
        steps.append(("head", ["norm", "classifier.w", "classifier.b"], head_fn))

        def finalize(carry):
            out = ModelOutput(logits=carry["logits"])
            if labels is not None:
                logp = jax.nn.log_softmax(carry["logits"].astype(jnp.float32), axis=-1)
                out["loss"] = -jnp.mean(
                    jnp.take_along_axis(
                        logp, jnp.asarray(labels)[:, None].astype(jnp.int32), axis=-1
                    )
                )
            return out

        return {"init": init, "steps": steps, "finalize": finalize}

    return plan


class BertForSequenceClassification:
    """Factory mirroring :class:`LlamaForCausalLM`'s interface."""

    @staticmethod
    def from_config(config: BertConfig, seed: int = 0, dtype=jnp.float32) -> Model:
        import dataclasses as _dc

        from ..big_modeling import is_empty_init

        # private copy: apply_fn closes over it, so per-model knob
        # changes (e.g. prepare() wiring activation_checkpointing
        # into remat) cannot leak into other models built from the
        # same config object
        config = _dc.replace(config)

        if is_empty_init():
            params = jax.eval_shape(
                lambda k: init_bert_params(k, config, dtype=dtype), jax.random.key(0)
            )
        else:
            params = init_bert_params(jax.random.key(seed), config, dtype=dtype)

        def apply_fn(p, **kwargs):
            return bert_apply(config, p, **kwargs)

        model = Model(
            apply_fn, params,
            partition_rules=BERT_PARTITION_RULES,
            name="BertForSequenceClassification",
        )
        model.config = config
        model.stacked_params_prefix = "layers"
        model.segments = bert_segments(config)
        model.tied_parameters = []
        return model
