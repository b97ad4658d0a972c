"""Granite-4.0-H (``granitemoehybrid`` without experts): Mamba-2 layers with
a few attention layers among them, by the published ``layer_types``.

Every layer makes two residual updates, ``x <- x + r * f(RMSNorm(x))``: the
mixer — Mamba-2 (:mod:`..ops.ssm`) or grouped-query attention with **no
position term** and the published score multiplier — then the shared gated
MLP. ``x0 = embedding_multiplier * E[ids]``; the head is the embedding,
tied, and the logits are divided by ``logits_scaling``.

Two kinds of layer are stacked apart, ``layers.mamba.*`` ``[n_mamba, ...]``
and ``layers.attention.*`` ``[n_attention, ...]``, and the layer loop runs
the published order in one program: a ``lax.scan`` over each run of Mamba
layers (the layer's weights and its row of the state picked by index
inside the body), each attention layer in its place between them.

**What a served sequence keeps** (:class:`~.cache.CacheSpec`): the
attention layers hold block-paged K/V, ``n_kv * head_dim`` lanes a token;
every Mamba layer holds, per slot, the recurrent state ``ssm [H, P, N]``
in **float32** (assumed — the published file does not say; the engine's
``state_dtype`` policy may store it narrower) and the convolution's
last ``d_conv - 1`` inputs ``conv [d_conv - 1, d_inner + 2N]`` in the
compute dtype. The engine owns the arrays, stacked ``[layers, slots,
...]``; the step programs carry them through the layer loop and update them
in place at ``(layer, slot)``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..modules import Model, ModelOutput
from ..ops.attention import attention
from ..ops.fp8 import dense
from ..ops.layers import (
    fused_cross_entropy,
    logit_rows,
    paged_step_frame,
    paged_write_attend,
    rms_norm,
    shift_labels,
    slot_state_frame,
)
from ..ops.ssm import conv_with_tail, live_slots, ssd_chunk_scan, ssm_state_update
from ..parallel.pipeline import remat_wrap
from .cache import CacheSpec, SlotStateLeaf, pool_leaf_names

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    #: ``"mamba"`` or ``"attention"`` per layer, as published
    layer_types: tuple = _PERIOD * 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int | None = None
    shared_intermediate_size: int = 8192
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 131072
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
        unknown = sorted(set(self.layer_types) - {"mamba", "attention"})
        if unknown:
            raise ValueError(f"layer_types holds {unknown}: only 'mamba' and 'attention' are built")
        if self.mamba_n_groups != 1:
            raise ValueError(
                f"mamba_n_groups {self.mamba_n_groups}: one group of B and C shared by "
                "every head is the only layout built here"
            )
        if self.d_inner != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(
                f"mamba_expand * hidden_size = {self.d_inner} is not mamba_n_heads * "
                f"mamba_d_head = {self.mamba_n_heads * self.mamba_d_head}"
            )
        if self.mamba_proj_bias or not self.mamba_conv_bias or not self.tie_word_embeddings:
            raise ValueError(
                "built as published for Granite-4.0-H: mamba_proj_bias false, "
                "mamba_conv_bias true, tie_word_embeddings true"
            )

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def n_attention(self) -> int:
        return self.layer_types.count("attention")

    @classmethod
    def tiny(cls, vocab_size=256, hidden_size=64, seq=512, **kw):
        """Four layers, ``mamba mamba attention mamba``, for the CPU tests."""
        base = dict(
            vocab_size=vocab_size, hidden_size=hidden_size, num_hidden_layers=4,
            layer_types=("mamba", "mamba", "attention", "mamba"),
            num_attention_heads=4, num_key_value_heads=2, shared_intermediate_size=128,
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=16,
            max_position_embeddings=seq,
        )
        base.update(kw)
        return cls(**base)


#: training placement: every matrix over fsdp on its input dimension; the
#: Mamba projections' output is a concatenation (z | x B C | dt), which a
#: tp split would cut across
GRANITE_HYBRID_PARTITION_RULES = [
    (r"embed_tokens", P("tp", "fsdp")),
    (r"layers\.\w+\.(wq|wk|wv|wo|w_in|w_out|in_proj|dt_proj|out_proj)", P(None, "fsdp", None)),
    (r".*", P()),
]


def cache_spec(config: GraniteHybridConfig) -> CacheSpec:
    c = config
    return CacheSpec(
        paged_layers=c.n_attention,
        kv_heads=c.num_key_value_heads,
        head_dim=c.head_dim,
        slot_state={
            "ssm": SlotStateLeaf(
                c.n_mamba, (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state), "float32"),
            "conv": SlotStateLeaf(c.n_mamba, (c.mamba_d_conv - 1, c.conv_dim), None),
        },
    )


def init_granite_hybrid_params(key, config: GraniteHybridConfig, dtype=jnp.float32):
    c = config
    h, ff, di, cd = c.hidden_size, c.shared_intermediate_size, c.d_inner, c.conv_dim
    nh, nkv, hd, mh = c.num_attention_heads, c.num_key_value_heads, c.head_dim, c.mamba_n_heads
    nm, na = c.n_mamba, c.n_attention
    keys = iter(jax.random.split(key, 16))

    def mat(*shape):
        scale = 1.0 / np.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def mlp(n):
        return {"mlp_norm": ones(n, h), "w_in": mat(n, h, 2 * ff), "w_out": mat(n, ff, h)}

    # dt_bias so that softplus(dt_bias) spans 1e-3 .. 1e-1, A in -[1, 16]: the
    # initialisation the Mamba-2 paper gives
    dt = jnp.exp(jax.random.uniform(next(keys), (nm, mh)) * (np.log(0.1) - np.log(1e-3))
                 + np.log(1e-3))
    return {
        "embed_tokens": (jax.random.normal(next(keys), (c.vocab_size, h)) * 0.02).astype(dtype),
        "norm": ones(h),
        "layers": {
            "mamba": {
                "norm": ones(nm, h),
                # the published in_proj [h, 2*d_inner + 2N + H], stored as its
                # lane-aligned part (z | x B C) and the H columns of dt
                "in_proj": mat(nm, h, di + cd),
                "dt_proj": mat(nm, h, mh),
                "conv_w": mat(nm, c.mamba_d_conv, cd),
                "conv_b": jnp.zeros((nm, cd), dtype),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                "A_log": jnp.log(
                    jax.random.uniform(next(keys), (nm, mh), minval=1.0, maxval=16.0)
                ).astype(dtype),
                "D": ones(nm, mh),
                "gate_norm": ones(nm, di),
                "out_proj": mat(nm, di, h),
                **mlp(nm),
            },
            "attention": {
                "norm": ones(na, h),
                "wq": mat(na, h, nh * hd),
                "wk": mat(na, h, nkv * hd),
                "wv": mat(na, h, nkv * hd),
                "wo": mat(na, nh * hd, h),
                **mlp(na),
            },
        },
    }


# -- the parts, each under the scope the trace files it by ---------------------


@jax.named_scope("embed")
def _embed(c, params, input_ids):
    x = params["embed_tokens"][input_ids]
    return x * jnp.asarray(c.embedding_multiplier, x.dtype)


@jax.named_scope("head")
def _tied_head(c, x, embed):
    """``x @ embed.T / logits_scaling`` without a transposed copy of the
    embedding (the vocabulary product, wherever it is traced)."""
    logits = jnp.einsum("...h,vh->...v", x, embed)
    return logits / jnp.asarray(c.logits_scaling, logits.dtype)


@jax.named_scope("head")
def _final_norm_and_head(c, params, x):
    x = rms_norm(x, params["norm"], c.rms_norm_eps)
    return x, _tied_head(c, x, params["embed_tokens"])


@jax.named_scope("mlp")
def _shared_mlp(c, layer, x):
    y = rms_norm(x, layer["mlp_norm"], c.rms_norm_eps)
    g, u = jnp.split(dense(y, layer["w_in"]), 2, axis=-1)
    out = dense(jax.nn.silu(g) * u, layer["w_out"])
    return x + out * jnp.asarray(c.residual_multiplier, out.dtype)


@jax.named_scope("ssm_proj")
def _mamba_in(c, layer, x):
    """``(z, xBC, dt)`` of the normed residual."""
    y = rms_norm(x, layer["norm"], c.rms_norm_eps)
    z, xbc = jnp.split(dense(y, layer["in_proj"]), [c.d_inner], axis=-1)
    return z, xbc, dense(y, layer["dt_proj"])


@jax.named_scope("ssm_proj")
def _mamba_out(c, layer, x, y, z):
    """Gate, then the norm over all of ``d_inner`` (one group), then out."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    normed = rms_norm(gated, layer["gate_norm"], c.rms_norm_eps).astype(x.dtype)
    out = dense(normed, layer["out_proj"])
    return x + out * jnp.asarray(c.residual_multiplier, out.dtype)


def _split_xbc(c, xbc):
    b, s, _ = xbc.shape
    xs, b_mat, c_mat = jnp.split(xbc, [c.d_inner, c.d_inner + c.mamba_d_state], axis=-1)
    return xs.reshape(b, s, c.mamba_n_heads, c.mamba_d_head), b_mat, c_mat


def _dt_and_a(layer, dt_raw, valid):
    """``(softplus(dt + dt_bias)`` zeroed on padding, ``-exp(A_log))``."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + layer["dt_bias"].astype(jnp.float32))
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    return dt, -jnp.exp(layer["A_log"].astype(jnp.float32))


def _skip(layer, y, xs):
    """``y + D x`` in float32, back in the compute dtype, heads folded."""
    y = y + layer["D"].astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
    return y.reshape(*y.shape[:-2], -1).astype(xs.dtype)


def mamba_layer_apply(c, layer, x, valid=None):
    """One Mamba-2 layer over whole sequences ``x [b, s, h]`` from a zero
    state (training / eval); ``valid [b, s]`` keeps right padding out."""
    b = x.shape[0]
    z, xbc, dt_raw = _mamba_in(c, layer, x)
    with jax.named_scope("ssm_conv"):
        tail = jnp.zeros((b, c.mamba_d_conv - 1, c.conv_dim), xbc.dtype)
        xbc, _ = conv_with_tail(xbc, tail, layer["conv_w"], layer["conv_b"],
                                jnp.zeros((b,), jnp.int32))
    with jax.named_scope("ssm_scan"):
        xs, b_mat, c_mat = _split_xbc(c, xbc)
        dt, a = _dt_and_a(layer, dt_raw, valid)
        if valid is not None:
            xs = jnp.where(valid[..., None, None], xs, 0)
        state = jnp.zeros((b, c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state), jnp.float32)
        y, _ = ssd_chunk_scan(xs, dt, a, b_mat, c_mat, state, c.mamba_chunk_size)
        y = _skip(layer, y, xs)
    return _shared_mlp(c, layer, _mamba_out(c, layer, x, y, z))


def attention_layer_apply(c, layer, x, attention_mask=None):
    """One attention layer over whole sequences: no position term, scores
    times ``attention_multiplier``, causal."""
    b, s, _ = x.shape
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with jax.named_scope("attn_proj"):
        y = rms_norm(x, layer["norm"], c.rms_norm_eps)
        q = dense(y, layer["wq"]).reshape(b, s, nh, hd)
        k = dense(y, layer["wk"]).reshape(b, s, nkv, hd)
        v = dense(y, layer["wv"]).reshape(b, s, nkv, hd)
    with jax.named_scope("attn_kernel"):
        attn = attention(q, k, v, segment_mask=attention_mask, causal=True,
                         scale=c.attention_multiplier)
    with jax.named_scope("attn_proj"):
        out = dense(attn.reshape(b, s, nh * hd), layer["wo"])
        x = x + out * jnp.asarray(c.residual_multiplier, out.dtype)
    return _shared_mlp(c, layer, x)


def layer_runs(layer_types) -> list:
    """The published order as runs: ``("mamba", first, count)`` with
    ``first`` the run's first index among the Mamba layers, and
    ``("attention", index, 1)`` with its index among the attention ones."""
    runs, seen = [], {"mamba": 0, "attention": 0}
    for kind in layer_types:
        if kind == "mamba" and runs and runs[-1][0] == "mamba":
            runs[-1] = ("mamba", runs[-1][1], runs[-1][2] + 1)
        else:
            runs.append((kind, seen[kind], 1))
        seen[kind] += 1
    return runs


def _layer_at(stack, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)


def _run_layers(c, params, carry, mamba_body, attention_body):
    """The layer loop: ``mamba_body(carry, layer, index)`` scanned over each
    run of Mamba layers, ``attention_body`` called in between, in the
    published order."""
    stacks = params["layers"]
    for kind, first, count in layer_runs(c.layer_types):
        if kind == "attention":
            carry = attention_body(carry, _layer_at(stacks["attention"], first), first)
            continue

        def body(carry, i):
            return mamba_body(carry, _layer_at(stacks["mamba"], i), i), None

        carry, _ = jax.lax.scan(
            body, carry, jnp.arange(first, first + count, dtype=jnp.int32))
    return carry


def granite_hybrid_apply(
    config: GraniteHybridConfig,
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
    """Forward pass: whole sequences (training / eval, every layer from a
    zero state), or — with ``paged_kv`` — one step against the engine's
    cache (:func:`_paged_step`)."""
    c = config
    if paged_kv is not None:
        return _paged_step(c, params, input_ids, paged_kv, block_tables,
                           cache_positions, paged_write_mask, state_slots, logit_positions)
    valid = None if attention_mask is None else attention_mask.astype(bool)
    x = _embed(c, params, input_ids)
    mamba = remat_wrap(lambda x, layer: (mamba_layer_apply(c, layer, x, valid), None), c.remat)
    attn = remat_wrap(
        lambda x, layer: (attention_layer_apply(c, layer, x, attention_mask), None), c.remat)
    with jax.named_scope("layers"):
        x = _run_layers(
            c, params, x,
            lambda x, layer, i: mamba(x, layer)[0],
            lambda x, layer, i: attn(x, layer)[0],
        )
    x, logits = _final_norm_and_head(c, params, x)
    out = ModelOutput(logits=logits)
    if labels is not None:
        out["loss"] = fused_cross_entropy(
            x, params["embed_tokens"], shift_labels(labels),
            dense_fn=lambda x_chunk, embed: _tied_head(c, x_chunk, embed))
    return out


def _paged_step(c, params, input_ids, cache, block_tables, cache_positions,
                write_mask, state_slots, logit_positions=None):
    """One step against the cache ``{"k", "v"[, "k_scale", "v_scale"],
    "ssm", "conv"}`` (the contract: :func:`~..ops.layers.paged_step_frame`):
    ``s == 1`` token for every slot (``state_slots`` ``None``: row ``i`` is
    slot ``i``, and the recurrence is the :func:`~..ops.ssm.ssm_state_update`
    kernel on the stacked state), or a prefill chunk of ``s`` tokens for the
    slots ``state_slots [b]`` (the chunked scan from the slot's own state,
    left with the outgoing state and the last valid inputs of the
    convolution). A lane that is off leaves state and tail too as they were.
    The cache travels in the layer loop's carry."""
    b, s = input_ids.shape
    idx, positions, valid = paged_step_frame(input_ids, cache_positions, write_mask)
    n_valid, slots = slot_state_frame(valid, state_slots, cache["ssm"].shape[1])
    decode = slots is None
    # what the state kernel walks: one mask a step, so one list for every layer
    live = live_slots(valid[:, 0]) if decode else None
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    names = pool_leaf_names(cache)
    x = _embed(c, params, input_ids)

    def mamba_body(carry, layer, i):
        x, cache = carry
        z, xbc, dt_raw = _mamba_in(c, layer, x)
        with jax.named_scope("ssm_conv"):
            conv = cache["conv"]
            tail = conv[i] if decode else conv[i, slots]
            # Mamba-2's convolution: bias and silu (conv_with_tail's defaults)
            xbc, tail = conv_with_tail(xbc, tail, layer["conv_w"], layer["conv_b"], n_valid)
            conv = conv.at[i].set(tail) if decode else conv.at[i, slots].set(tail)
        with jax.named_scope("ssm_scan"):
            xs, b_mat, c_mat = _split_xbc(c, xbc)
            dt, a = _dt_and_a(layer, dt_raw, valid)
            ssm = cache["ssm"]
            if decode:
                ssm, y = ssm_state_update(
                    ssm, i, xs[:, 0], dt[:, 0], a, b_mat[:, 0], c_mat[:, 0], valid[:, 0],
                    live=live)
                y = y[:, None]
            else:
                xs_in = jnp.where(valid[..., None, None], xs, 0)
                y, state = ssd_chunk_scan(
                    xs_in, dt, a, b_mat, c_mat, ssm[i, slots], c.mamba_chunk_size)
                ssm = ssm.at[i, slots].set(state.astype(ssm.dtype))
            y = _skip(layer, y, xs)
        x = _shared_mlp(c, layer, _mamba_out(c, layer, x, y, z))
        return x, {**cache, "ssm": ssm, "conv": conv}

    def attention_body(carry, layer, i):
        x, cache = carry
        with jax.named_scope("attn_proj"):
            y = rms_norm(x, layer["norm"], c.rms_norm_eps)
            # the paged kernel divides by sqrt(head_dim); the published
            # multiplier takes its place (0.015625 * 8: a power of two)
            q = dense(y, layer["wq"]) * jnp.asarray(
                c.attention_multiplier * np.sqrt(float(hd)), x.dtype)
            q = q.reshape(b, s, nh, hd)
            k = dense(y, layer["wk"]).reshape(b, s, nkv, hd)
            v = dense(y, layer["wv"]).reshape(b, s, nkv, hd)
        attn, held = paged_write_attend(
            q, k, v, [cache[n] for n in names], i, block_tables, positions, idx, valid)
        with jax.named_scope("attn_proj"):
            out = dense(attn.reshape(b, s, nh * hd), layer["wo"])
            x = x + out * jnp.asarray(c.residual_multiplier, out.dtype)
        return _shared_mlp(c, layer, x), {**cache, **dict(zip(names, held))}

    with jax.named_scope("layers"):
        x, cache = _run_layers(c, params, (x, dict(cache)), mamba_body, attention_body)
    _, logits = _final_norm_and_head(c, params, logit_rows(x, logit_positions))
    return ModelOutput(logits=logits, paged_kv=cache)


class GraniteHybridForCausalLM:
    """Factory mirroring the transformers entry point
    (``GraniteMoeHybridForCausalLM`` with ``num_local_experts`` 0)."""

    @staticmethod
    def from_config(config: GraniteHybridConfig, seed: int = 0, dtype=jnp.float32) -> Model:
        from ..big_modeling import is_empty_init

        config = dataclasses.replace(config)  # private copy: apply_fn closes over it

        def make_params(key):
            return init_granite_hybrid_params(key, config, dtype=dtype)

        if is_empty_init():
            params = jax.eval_shape(make_params, jax.random.PRNGKey(seed))
        else:
            params = make_params(jax.random.PRNGKey(seed))

        def apply_fn(p, input_ids=None, attention_mask=None, labels=None, **kw):
            return granite_hybrid_apply(config, p, input_ids, attention_mask, labels, **kw)

        model = Model(
            apply_fn, params,
            partition_rules=GRANITE_HYBRID_PARTITION_RULES,
            name="GraniteHybridForCausalLM",
        )
        model.config = config
        model.stacked_params_prefix = "layers"
        model.supports_paged_kv = True
        model.cache_spec = cache_spec(config)
        return model
