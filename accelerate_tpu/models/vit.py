"""Vision Transformer image classifier, TPU-first (timm's
``vit_base_patch16_224`` — the standard CV transformer users of the
reference bring via timm, like the cv example's ``create_model`` at
``/root/reference/examples/cv_example.py:121``).

Design:

* **patch embedding as ONE matmul** — images reshape to
  ``[B, N_patches, P·P·C]`` and hit a single ``[P·P·C, D]`` projection;
  the MXU sees a large dense matmul instead of a small-window conv.
* pre-LN encoder blocks (true LayerNorm, GELU MLP, biases everywhere —
  timm layout, so the parameter count matches vit_base exactly),
  layer-stacked + ``lax.scan`` like the rest of the zoo.
* CLS-token classification head; learned position embeddings.
* partition rules: QKV/MLP project out on ``tp``, proj/fc2 in on ``tp``;
  batch activations pin to ``('dp','fsdp')``.
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
from ..ops.layers import cross_entropy_loss, layer_norm, mesh_constrain as _constrain, to_nhwc


@dataclass
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    in_channels: int = 3
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    num_classes: int = 1000
    layer_norm_eps: float = 1e-6
    #: False | True | a jax.checkpoint_policies name
    remat: bool | str = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @classmethod
    def vit_b16(cls, num_classes: int = 1000):
        return cls(num_classes=num_classes)

    @classmethod
    def tiny(cls, num_classes: int = 3):
        return cls(
            image_size=32, patch_size=8, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128, num_classes=num_classes,
        )


VIT_PARTITION_RULES = [
    (r"patch_embed\.w", P(None, "tp")),
    (r"pos_embed|cls_token", P()),
    (r"layers\.w_qkv", P(None, "fsdp", "tp")),
    (r"layers\.b_qkv", P(None, "tp")),
    (r"layers\.w_proj", P(None, "tp", "fsdp")),
    (r"layers\.w_fc1", P(None, "fsdp", "tp")),
    (r"layers\.b_fc1", P(None, "tp")),
    (r"layers\.w_fc2", P(None, "tp", "fsdp")),
    (r"layers\.(ln1|ln2)_(g|b)|layers\.(b_proj|b_fc2)", P()),
    (r"head\.w", P("fsdp", None)),
    (r"(ln_f_|head\.b|patch_embed\.b)", P()),
]


def init_vit_params(key, config: ViTConfig):
    c = config
    d, ff, L = c.hidden_size, c.intermediate_size, c.num_hidden_layers
    patch_dim = c.patch_size * c.patch_size * c.in_channels
    keys = jax.random.split(key, 8)

    def w(k, *shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(jnp.float32)

    return {
        "patch_embed": {"w": w(keys[0], patch_dim, d), "b": jnp.zeros((d,))},
        "cls_token": w(keys[1], 1, 1, d),
        "pos_embed": w(keys[2], 1, c.num_patches + 1, d),
        "layers": {
            "ln1_g": jnp.ones((L, d)), "ln1_b": jnp.zeros((L, d)),
            "w_qkv": w(keys[3], L, d, 3 * d),
            "b_qkv": jnp.zeros((L, 3 * d)),
            "w_proj": w(keys[4], L, d, d),
            "b_proj": jnp.zeros((L, d)),
            "ln2_g": jnp.ones((L, d)), "ln2_b": jnp.zeros((L, d)),
            "w_fc1": w(keys[5], L, d, ff),
            "b_fc1": jnp.zeros((L, ff)),
            "w_fc2": w(keys[6], L, ff, d),
            "b_fc2": jnp.zeros((L, d)),
        },
        "ln_f_g": jnp.ones((d,)),
        "ln_f_b": jnp.zeros((d,)),
        "head": {"w": w(keys[7], d, c.num_classes), "b": jnp.zeros((c.num_classes,))},
    }


def _vit_block(config: ViTConfig, layer, x):
    c = config
    nh, hd = c.num_attention_heads, c.head_dim
    b, n, d = x.shape
    y = layer_norm(x, layer["ln1_g"], layer["ln1_b"], c.layer_norm_eps)
    qkv = dense(y, layer["w_qkv"]) + layer["b_qkv"]
    q, k, v = (z.reshape(b, n, nh, hd) for z in jnp.split(qkv, 3, axis=-1))
    q = _constrain(q, P(("dp", "fsdp"), None, "tp", None))
    k = _constrain(k, P(("dp", "fsdp"), None, "tp", None))
    attn = attention(q, k, v, causal=False)
    x = x + dense(attn.reshape(b, n, d), layer["w_proj"]) + layer["b_proj"]
    y = layer_norm(x, layer["ln2_g"], layer["ln2_b"], c.layer_norm_eps)
    h = jax.nn.gelu(dense(y, layer["w_fc1"]) + layer["b_fc1"])
    x = x + dense(h, layer["w_fc2"]) + layer["b_fc2"]
    return _constrain(x, P(("dp", "fsdp"), None, None))


def _patchify(x, patch: int):
    """[B, H, W, C] → [B, N, P·P·C] (row-major patches, channel-last inside
    each patch — matches a ``Conv(P, stride=P)`` + flatten)."""
    b, h, w, ch = x.shape
    gh, gw = h // patch, w // patch
    x = x.reshape(b, gh, patch, gw, patch, ch)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # [B, gh, gw, P, P, C]
    return x.reshape(b, gh * gw, patch * patch * ch)


def vit_apply(config: ViTConfig, params, pixel_values=None, labels=None, **kw):
    c = config
    x = to_nhwc(pixel_values, c.in_channels)
    patches = _patchify(x, c.patch_size)
    h = dense(patches, params["patch_embed"]["w"]) + params["patch_embed"]["b"]
    b = h.shape[0]
    cls = jnp.broadcast_to(params["cls_token"], (b, 1, c.hidden_size))
    h = jnp.concatenate([cls, h], axis=1) + params["pos_embed"]
    h = _constrain(h, P(("dp", "fsdp"), None, None))

    def body(carry, layer):
        return _vit_block(c, layer, carry), None

    from ..parallel.pipeline import remat_wrap

    h, _ = jax.lax.scan(remat_wrap(body, c.remat), h, params["layers"])
    h = layer_norm(h, params["ln_f_g"], params["ln_f_b"], c.layer_norm_eps)
    logits = h[:, 0, :] @ params["head"]["w"] + params["head"]["b"]
    out = ModelOutput(logits=logits)
    if labels is not None:
        out["loss"] = cross_entropy_loss(logits[:, None, :], jnp.asarray(labels)[:, None])
    return out


class ViTForImageClassification:
    """Factory mirroring the timm entry point (``vit_base_patch16_224``)."""

    @staticmethod
    def from_config(config: ViTConfig, seed: int = 0) -> Model:
        import dataclasses as _dc

        from ..big_modeling import is_empty_init

        config = _dc.replace(config)

        def make_params(key):
            return init_vit_params(key, config)

        if is_empty_init():
            params = jax.eval_shape(make_params, jax.random.PRNGKey(seed))
        else:
            params = make_params(jax.random.PRNGKey(seed))

        def apply_fn(p, pixel_values=None, labels=None, **kw):
            return vit_apply(config, p, pixel_values=pixel_values, labels=labels, **kw)

        model = Model(
            apply_fn, params,
            partition_rules=VIT_PARTITION_RULES,
            name="ViTForImageClassification",
        )
        model.config = config
        model.stacked_params_prefix = "layers"
        return model
