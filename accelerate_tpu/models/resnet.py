"""ResNet image classifier, TPU-first (the reference's canonical CV model:
``create_model("resnet50d", ...)`` at ``/root/reference/examples/cv_example.py:121``).

Implements the "-d" variant faithfully (deep 3×3 stem, stride on the 3×3
bottleneck conv, average-pool shortcut downsampling — the timm resnet50d
architecture), as pure functions over an explicit parameter pytree:

* **NHWC layout + HWIO kernels** — the layouts XLA:TPU tiles onto the MXU
  without transposes; convolutions lower to ``lax.conv_general_dilated``.
* **BatchNorm normalises with the current batch's statistics** in both
  train and eval (functional purity: no running-stats side channel; eval
  parity with torch's running averages is traded for a pure step — the
  train-throughput BASELINE row this model serves is unaffected).
* **partition rules** — kernels shard input channels on ``fsdp`` and
  output channels on ``tp``; activations pin batch to ``('dp','fsdp')``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..modules import Model, ModelOutput
from ..ops.layers import cross_entropy_loss, mesh_constrain as _constrain, to_nhwc


@dataclass
class ResNetConfig:
    depths: tuple = (3, 4, 6, 3)  # resnet50
    base_width: int = 64
    num_classes: int = 1000
    in_channels: int = 3
    bn_eps: float = 1e-5
    #: False | True | a jax.checkpoint_policies name (remat per stage)
    remat: bool | str = False

    @classmethod
    def resnet50d(cls, num_classes: int = 1000):
        return cls(num_classes=num_classes)

    @classmethod
    def tiny(cls, num_classes: int = 3):
        return cls(depths=(1, 1), base_width=8, num_classes=num_classes)


RESNET_PARTITION_RULES = [
    (r"conv", P(None, None, "fsdp", "tp")),  # HWIO kernels
    (r"(gamma|beta)", P()),
    (r"fc\.w", P("fsdp", "tp")),
    (r"fc\.b", P()),
]


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w,
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _bn(x, gamma, beta, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(0, 1, 2), keepdims=True)
    var = jnp.var(x32, axis=(0, 1, 2), keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (out * gamma + beta).astype(x.dtype)


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return (
        jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
        * np.sqrt(2.0 / fan_in)
    ).astype(jnp.float32)


def _bn_init(c):
    return jnp.ones((c,), jnp.float32), jnp.zeros((c,), jnp.float32)


def init_resnet_params(key, config: ResNetConfig):
    c = config
    keys = iter(jax.random.split(key, 256))
    w = c.base_width
    params = {
        # resnet-d deep stem: three 3x3 convs (32, 32, 64 for width 64)
        "stem": {
            "conv1": _conv_init(next(keys), 3, 3, c.in_channels, w // 2),
            "conv2": _conv_init(next(keys), 3, 3, w // 2, w // 2),
            "conv3": _conv_init(next(keys), 3, 3, w // 2, w),
        },
        "stages": [],
    }
    for name, ch in (("g1", w // 2), ("g2", w // 2), ("g3", w)):
        params["stem"][f"{name}_gamma"], params["stem"][f"{name}_beta"] = _bn_init(ch)

    cin = w
    for i, depth in enumerate(c.depths):
        planes = w * (2**i)
        cout = planes * 4
        blocks = []
        for b in range(depth):
            stride = 2 if (b == 0 and i > 0) else 1
            block = {
                "conv1": _conv_init(next(keys), 1, 1, cin, planes),
                "conv2": _conv_init(next(keys), 3, 3, planes, planes),
                "conv3": _conv_init(next(keys), 1, 1, planes, cout),
            }
            for j, ch in (("1", planes), ("2", planes), ("3", cout)):
                block[f"g{j}_gamma"], block[f"g{j}_beta"] = _bn_init(ch)
            if cin != cout:
                block["conv_proj"] = _conv_init(next(keys), 1, 1, cin, cout)
                block["gp_gamma"], block["gp_beta"] = _bn_init(cout)
            blocks.append(block)
            cin = cout
        params["stages"].append(blocks)
    params["fc"] = {
        "w": (
            jax.random.normal(next(keys), (cin, c.num_classes), jnp.float32)
            * np.sqrt(1.0 / cin)
        ),
        "b": jnp.zeros((c.num_classes,), jnp.float32),
    }
    return params


def _bottleneck_d(config, block, x, stride):
    """resnet-d bottleneck: stride lives on the 3×3; the shortcut
    downsamples with avg-pool + 1×1 (never a strided 1×1)."""
    c = config
    y = _conv(x, block["conv1"])
    y = jax.nn.relu(_bn(y, block["g1_gamma"], block["g1_beta"], c.bn_eps))
    y = _conv(y, block["conv2"], stride=stride)
    y = jax.nn.relu(_bn(y, block["g2_gamma"], block["g2_beta"], c.bn_eps))
    y = _conv(y, block["conv3"])
    y = _bn(y, block["g3_gamma"], block["g3_beta"], c.bn_eps)

    shortcut = x
    if stride > 1:
        shortcut = jax.lax.reduce_window(
            shortcut, 0.0, jax.lax.add, (1, stride, stride, 1),
            (1, stride, stride, 1), "SAME",
        ) / (stride * stride)
    if "conv_proj" in block:
        shortcut = _conv(shortcut, block["conv_proj"])
        shortcut = _bn(shortcut, block["gp_gamma"], block["gp_beta"], c.bn_eps)
    out = jax.nn.relu(y + shortcut)
    return _constrain(out, P(("dp", "fsdp"), None, None, "tp"))


def resnet_apply(config: ResNetConfig, params, pixel_values=None, labels=None, **kw):
    c = config
    x = to_nhwc(pixel_values, c.in_channels)
    s = params["stem"]
    x = _conv(x, s["conv1"], stride=2)
    x = jax.nn.relu(_bn(x, s["g1_gamma"], s["g1_beta"], c.bn_eps))
    x = _conv(x, s["conv2"])
    x = jax.nn.relu(_bn(x, s["g2_gamma"], s["g2_beta"], c.bn_eps))
    x = _conv(x, s["conv3"])
    x = jax.nn.relu(_bn(x, s["g3_gamma"], s["g3_beta"], c.bn_eps))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )

    def run_stage(x, blocks, stage_idx):
        for b, block in enumerate(blocks):
            stride = 2 if (b == 0 and stage_idx > 0) else 1
            x = _bottleneck_d(c, block, x, stride)
        return x

    for i, blocks in enumerate(params["stages"]):
        stage = lambda x, blocks=blocks, i=i: run_stage(x, blocks, i)
        if c.remat:
            policy = None
            if isinstance(c.remat, str):
                policy = getattr(jax.checkpoint_policies, c.remat)
            stage = jax.checkpoint(stage, policy=policy)
        x = stage(x)

    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))  # global average pool
    logits = x @ params["fc"]["w"] + params["fc"]["b"]
    out = ModelOutput(logits=logits)
    if labels is not None:
        out["loss"] = cross_entropy_loss(logits[:, None, :], jnp.asarray(labels)[:, None])
    return out


class ResNetForImageClassification:
    """Factory mirroring the timm entry point the reference's cv example
    brings to ``prepare()`` (``cv_example.py:121``)."""

    @staticmethod
    def from_config(config: ResNetConfig, seed: int = 0) -> Model:
        import dataclasses as _dc

        from ..big_modeling import is_empty_init

        config = _dc.replace(config)

        def make_params(key):
            return init_resnet_params(key, config)

        if is_empty_init():
            params = jax.eval_shape(make_params, jax.random.PRNGKey(seed))
        else:
            params = make_params(jax.random.PRNGKey(seed))

        def apply_fn(p, pixel_values=None, labels=None, **kw):
            return resnet_apply(config, p, pixel_values=pixel_values, labels=labels, **kw)

        model = Model(
            apply_fn, params,
            partition_rules=RESNET_PARTITION_RULES,
            name="ResNetForImageClassification",
        )
        model.config = config
        return model
