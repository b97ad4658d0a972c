"""Llama-family causal LM, TPU-first.

The flagship model (BASELINE config #3: Llama-2-7B FSDP finetune). Design,
per the scaling-book recipe rather than the reference's torch model zoo
(the reference itself ships no models — it wraps ``transformers``):

* **layer-stacked params + ``lax.scan``** — every block's weights carry a
  leading ``[n_layers]`` dim and one scan body applies the stack. Compile
  time is O(1) in depth and XLA sees one fused block program.
* **explicit partition rules** — q/k/v/gate/up project *out* along ``tp``,
  o/down project *in* along ``tp`` (one psum per block, rides ICI);
  everything else shards its largest dim on ``fsdp`` (ZeRO-3-style).
* **activation sharding constraints** — hidden states pinned to
  ``P(('dp','fsdp'), 'cp', None)`` so sequence/context parallelism composes.
* bf16 matmuls / fp32 norms+softmax; ``jax.checkpoint`` on the block for
  rematerialised backward.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..modules import Model, ModelOutput
from ..ops.attention import attention
from ..parallel.pipeline import remat_wrap
from ..ops.fp8 import dense
from ..ops.layers import (
    apply_rope,
    cached_attention,
    cross_entropy_loss,
    fused_cross_entropy,
    logit_rows,
    mesh_constrain as _constrain,
    residual_spec,
    rms_norm,
    rope_cached_attention_block,
    rope_frequencies,
    rope_paged_attention_block,
    shift_labels,
)
from .cache import pool_leaf_names


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    #: False | True (full recompute) | a jax.checkpoint_policies name
    remat: bool | str = True
    #: GPipe microbatch count when the mesh has a pp axis > 1
    #: (0 = auto: smallest batch divisor >= number of stages)
    pipeline_microbatches: int = 0
    #: a published ``head_dim``; None = hidden_size // num_attention_heads
    head_dim: int | None = None

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads

    @classmethod
    def llama2_7b(cls):
        return cls()

    @classmethod
    def flagship_700m(cls, max_position_embeddings: int = 1024, remat: bool | str = False):
        """The ~700M flagship slice (hidden 1536, 12 heads × 128,
        ff 4h, 16 layers) — the largest credible-aspect-ratio shape whose
        fp32 adam state fits one v5e chip. Single source of truth for
        ``chip_smoke.py``, ``benchmarks/serve_bench.py`` and the serve CLI's
        ``--preset flagship`` so they run one model."""
        return cls(
            vocab_size=32000,
            hidden_size=1536,
            intermediate_size=6144,
            num_hidden_layers=16,
            num_attention_heads=12,
            num_key_value_heads=12,
            max_position_embeddings=max_position_embeddings,
            remat=remat,
        )

    @classmethod
    def tiny(cls, vocab_size=256, hidden_size=64, layers=2, heads=4, seq=128):
        return cls(
            vocab_size=vocab_size,
            hidden_size=hidden_size,
            intermediate_size=hidden_size * 3,
            num_hidden_layers=layers,
            num_attention_heads=heads,
            num_key_value_heads=heads,
            max_position_embeddings=seq,
            remat=False,
        )


#: path-regex → PartitionSpec. Layer-stacked leaves have a leading [layers]
#: dim (never sharded — it's the scan axis).
LLAMA_PARTITION_RULES = [
    (r"embed_tokens", P("tp", "fsdp")),
    (r"layers\.(wq|wk|wv)", P(None, "fsdp", "tp")),
    (r"layers\.wo", P(None, "tp", "fsdp")),
    (r"layers\.(w_gate|w_up)", P(None, "fsdp", "tp")),
    (r"layers\.w_down", P(None, "tp", "fsdp")),
    (r"norm", P()),
    (r"lm_head", P("fsdp", "tp")),
]


def init_llama_params(key: jax.Array, config: LlamaConfig, dtype=jnp.float32):
    """Initialise the layer-stacked parameter pytree."""
    c = config
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    h, ff, nh, nkv, hd = (
        c.hidden_size,
        c.intermediate_size,
        c.num_attention_heads,
        c.num_key_value_heads,
        c.head_dim,
    )
    L = c.num_hidden_layers

    def norm_init(*shape):
        return jnp.ones(shape, dtype=dtype)

    def dense_init(key, *shape, in_dim):
        scale = 1.0 / np.sqrt(in_dim)
        return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)

    ks = jax.random.split(k_layers, 8)
    params = {
        "embed_tokens": (
            jax.random.normal(k_embed, (c.vocab_size, h), dtype=jnp.float32) * 0.02
        ).astype(dtype),
        "layers": {
            "wq": dense_init(ks[0], L, h, nh * hd, in_dim=h),
            "wk": dense_init(ks[1], L, h, nkv * hd, in_dim=h),
            "wv": dense_init(ks[2], L, h, nkv * hd, in_dim=h),
            "wo": dense_init(ks[3], L, nh * hd, h, in_dim=nh * hd),
            "w_gate": dense_init(ks[4], L, h, ff, in_dim=h),
            "w_up": dense_init(ks[5], L, h, ff, in_dim=h),
            "w_down": dense_init(ks[6], L, ff, h, in_dim=ff),
            "attn_norm": norm_init(L, h),
            "mlp_norm": norm_init(L, h),
        },
        "norm": norm_init(h),
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = dense_init(k_head, h, c.vocab_size, in_dim=h)
    return params


@jax.named_scope("mlp")
def _swiglu_mlp(c, layer, x):
    """The block's SwiGLU MLP with its residual."""
    y = rms_norm(x, layer["mlp_norm"], c.rms_norm_eps)
    gated = jax.nn.silu(dense(y, layer["w_gate"])) * dense(y, layer["w_up"])
    return x + dense(gated, layer["w_down"])


#: the vocabulary product, under ``head`` wherever it is traced (the fused
#: cross-entropy calls it once per sequence chunk)
_head_dense = jax.named_scope("head")(dense)


@jax.named_scope("head")
def _final_norm_and_head(c, params, x):
    """``(normed hidden states, head matrix, logits)``."""
    x = rms_norm(x, params["norm"], c.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed_tokens"].T
    return x, head, dense(x, head)


@jax.named_scope("embed")
def _embed(params, input_ids):
    return params["embed_tokens"][input_ids]


def llama_layer_apply(
    config: LlamaConfig, layer, x, cos, sin, positions, attention_mask,
    return_kv: bool = False,
):
    """One transformer block on UNstacked layer params — shared by the
    training scan body and the streaming (offload) executor.
    ``return_kv`` additionally returns the (rotated K, V) this block just
    computed, so the prefill cache reuses them instead of re-projecting."""
    c = config
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    b, s, h = x.shape
    # attention (the scope names are the trace vocabulary of
    # docs/source/usage_guides/monitoring.md, shared with the paged step)
    with jax.named_scope("attn_proj"):
        y = rms_norm(x, layer["attn_norm"], c.rms_norm_eps)
        q = dense(y, layer["wq"]).reshape(b, s, nh, hd)
        k = dense(y, layer["wk"]).reshape(b, s, nkv, hd)
        v = dense(y, layer["wv"]).reshape(b, s, nkv, hd)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        q = _constrain(q, P(("dp", "fsdp"), "cp", "tp", None))
        k = _constrain(k, P(("dp", "fsdp"), "cp", "tp", None))
    with jax.named_scope("attn_kernel"):
        attn = attention(q, k, v, segment_mask=attention_mask, causal=True)
    with jax.named_scope("attn_proj"):
        x = x + dense(attn.reshape(b, s, nh * hd), layer["wo"])
        x = _constrain(x, residual_spec())
    x = _swiglu_mlp(c, layer, x)
    x = _constrain(x, residual_spec())
    if return_kv:
        return x, (k, v)
    return x


def _block(config: LlamaConfig, cos, sin, positions, attention_mask):
    """One transformer block as a scan body over stacked layer params."""

    def body(x, layer):
        return llama_layer_apply(config, layer, x, cos, sin, positions, attention_mask), None

    return remat_wrap(body, config.remat)


def _pipeline_mesh():
    from ..parallel.pipeline import active_pipeline_mesh

    return active_pipeline_mesh()


def _pipeline_stack(c, layers, x, cos, sin, positions, attention_mask, mesh):
    """Run the transformer stack as a GPipe pipeline over the pp axis
    (layer-stacked params split into contiguous stages)."""
    from ..parallel.pipeline import pipeline_layer_stack

    return pipeline_layer_stack(
        lambda layer, h, pos_mb, mask_mb, cos_b, sin_b: llama_layer_apply(
            c, layer, h, cos_b, sin_b, pos_mb, mask_mb
        ),
        layers, x,
        mesh=mesh,
        remat=c.remat,
        positions=positions,
        mask=attention_mask,
        rope=(cos, sin),
        num_microbatches=c.pipeline_microbatches,
    )


def llama_apply(
    config: LlamaConfig,
    params,
    input_ids: jax.Array,  # [b, s] int32
    attention_mask: jax.Array | None = None,  # [b, s] 1=real
    labels: jax.Array | None = None,  # [b, s]; -100 ignored
    positions: jax.Array | None = None,
    use_cache: bool = False,
    kv_cache=None,  # {"k","v"}: [L, b, max_cache, n_kv, hd] (decode step)
    cache_index: jax.Array | None = None,  # [b] per-row write position
    max_cache_len: int | None = None,
    paged_kv=None,  # {"k","v"}: [L, num_blocks, block_size, n_kv*hd]
    block_tables: jax.Array | None = None,  # [b, max_blocks] pool block ids
    cache_positions: jax.Array | None = None,  # [b] first new token position
    paged_write_mask: jax.Array | None = None,  # [b, s] real-token mask
    logit_positions: jax.Array | None = None,  # [b, r] of 0..s-1 (paged step)
):
    """Forward pass; four modes:

    * training/eval (default) — full causal attention;
    * **prefill** (``use_cache=True``) — same, plus the per-layer K/V
      written into a ``[L, b, max_cache_len, n_kv, hd]`` cache returned as
      ``out.kv_cache``;
    * **decode** (``kv_cache=`` + ``cache_index=``) — ``input_ids`` is one
      token per row; K/V append at each row's own position (ragged-batch
      safe) and attention runs token-vs-cache in O(max_cache) — the KV-cache
      inference path (the reference gets this from transformers' generate);
    * **paged decode/prefill-chunk** (``paged_kv=`` + ``block_tables=`` +
      ``cache_positions=``) — the serving engine's block-paged cache path
      (``supports_paged_kv``): K/V scatter through each slot's block table
      into the stacked pool, which the step addresses in place by layer
      and block (:func:`_llama_paged_step`).
      One compiled ``[num_slots, 1]`` program serves every decode iteration
      for the lifetime of the engine; ``s > 1`` with a ``paged_write_mask``
      is a chunked-prefill slice of one prompt. ``logit_positions`` names
      the positions of each row whose logits the caller reads: the head runs
      on those rows alone (:func:`~..ops.layers.logit_rows`).
    """
    c = config
    b, s = input_ids.shape
    if s > c.max_position_embeddings:
        raise ValueError(
            f"sequence length {s} exceeds max_position_embeddings "
            f"{c.max_position_embeddings}: RoPE position tables would "
            "silently clamp, producing wrong logits"
        )
    cos, sin = rope_frequencies(c.head_dim, c.max_position_embeddings, c.rope_theta)

    # over a pp>1 mesh, prefill/decode run through the stage-local-cache
    # pipeline engine (parallel.pipeline.pipeline_cached_stack via the
    # prefill_stack/decode_stack drivers), so stage-split weights and
    # caches stay put instead of the plain scans all-gathering them
    if paged_kv is not None:
        return _llama_paged_step(
            c, params, input_ids, paged_kv, block_tables, cache_positions,
            paged_write_mask, cos, sin, logit_positions,
        )
    if kv_cache is not None:
        return _llama_decode_step(c, params, input_ids, kv_cache, cache_index, cos, sin)

    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    x = _constrain(_embed(params, input_ids), residual_spec())

    if use_cache:
        max_cache = int(max_cache_len or c.max_position_embeddings)
        if not (s <= max_cache <= c.max_position_embeddings):
            raise ValueError(
                f"max_cache_len {max_cache} must be in [{s} (prompt length), "
                f"{c.max_position_embeddings} (max_position_embeddings)] — "
                "above it RoPE tables would silently clamp"
            )

        from ..parallel.pipeline import prefill_layer_stack

        pad = ((0, 0), (0, max_cache - s), (0, 0), (0, 0))

        def prefill_layer(layer, h, pos_b, mask_b, cos_b, sin_b):
            out, (k, v) = llama_layer_apply(
                c, layer, h, cos_b, sin_b, pos_b, mask_b, return_kv=True
            )
            return out, (jnp.pad(k, pad), jnp.pad(v, pad))

        with jax.named_scope("layers"):
            x, caches = prefill_layer_stack(
                prefill_layer, params["layers"], x,
                (c.num_hidden_layers, b, max_cache, c.num_key_value_heads, c.head_dim),
                positions=positions, mask=attention_mask, rope=(cos, sin),
            )
    else:
        pp_mesh = _pipeline_mesh()
        with jax.named_scope("layers"):
            if pp_mesh is not None:
                x = _pipeline_stack(c, params["layers"], x, cos, sin, positions,
                                    attention_mask, pp_mesh)
            else:
                body = _block(c, cos, sin, positions, attention_mask)
                x, _ = jax.lax.scan(body, x, params["layers"])

    x, head, logits = _final_norm_and_head(c, params, x)
    logits = _constrain(logits, P(("dp", "fsdp"), "cp", "tp"))

    out = ModelOutput(logits=logits)
    if use_cache:
        out["kv_cache"] = caches
    if labels is not None:
        # predict token t+1 from prefix ≤ t. The loss is computed straight
        # from the pre-head hidden states (NOT from `logits` above): when a
        # training step only forces `loss`, XLA dead-code-eliminates the
        # full [b, s, vocab] logits buffer and the fused path holds one
        # sequence chunk of logits at a time — the memory headroom is what
        # lets the bench run larger per-chip batches. Under cp the sequence
        # dim is sharded, so chunking it would cut across shards; the plain
        # whole-sequence loss stays on that path.
        from ..ops.attention import get_attention_context

        ctx_mesh = get_attention_context().mesh
        cp_active = ctx_mesh is not None and dict(ctx_mesh.shape).get("cp", 1) > 1
        if cp_active:
            out["loss"] = cross_entropy_loss(logits[:, :-1, :], labels[:, 1:])
        else:
            out["loss"] = fused_cross_entropy(
                x, head, shift_labels(labels), dense_fn=_head_dense)
    return out


def _llama_decode_layer(c, layer, x, k_cache_l, v_cache_l, cos, sin, idx, pp_manual=False):
    """One cached decode block on UNstacked layer params: the shared
    rope/cache attention sub-block + llama's SwiGLU MLP."""
    x, k_cache_l, v_cache_l = rope_cached_attention_block(
        layer, x, k_cache_l, v_cache_l, cos, sin, idx,
        c.num_attention_heads, c.num_key_value_heads, c.head_dim,
        c.rms_norm_eps, pp_manual=pp_manual,
    )
    return _swiglu_mlp(c, layer, x), k_cache_l, v_cache_l


def _llama_decode_step(c, params, input_ids, kv_cache, cache_index, cos, sin):
    """One cached decode step: s == 1 token per row, appended at
    ``cache_index[b]``; attention is q(1) against the cache prefix. The
    layer loop (plain scan vs pp stage pipeline) is owned by
    :func:`parallel.pipeline.decode_stack`."""
    from ..parallel.pipeline import decode_stack

    b, s = input_ids.shape
    idx = jnp.asarray(cache_index, jnp.int32).reshape(b)
    x = _embed(params, input_ids)

    with jax.named_scope("layers"):
        x, kv = decode_stack(
            lambda layer, h, kc_l, vc_l, idx_b, cos_b, sin_b, pp_manual: _llama_decode_layer(
                c, layer, h, kc_l, vc_l, cos_b, sin_b, idx_b, pp_manual=pp_manual
            ),
            params["layers"], kv_cache, x, broadcast=(idx, cos, sin),
        )
    _, _, logits = _final_norm_and_head(c, params, x)
    return ModelOutput(logits=logits, kv_cache=kv)


def _llama_paged_step(
    c, params, input_ids, paged_kv, block_tables, cache_positions,
    paged_write_mask, cos, sin, logit_positions=None,
):
    """One step against the block-paged KV pool: ``s == 1`` token per slot
    (the engine's single compiled decode program) or an ``s``-token prefill
    chunk of one prompt. ``paged_kv`` holds the stacked pools ``k`` / ``v``
    (``[pool_layers, num_blocks, block_size, n_kv*hd]``, heads folded into
    lanes) and, quantized, ``k_scale`` / ``v_scale`` (``[pool_layers,
    num_blocks, block_size, n_kv]``).

    **The pool stays where it is.** The layer loop scans over the layer
    weights and a layer index only; the pools travel in the carry. K/V land
    as a row scatter at ``(layer, block, offset)`` through each slot's
    block table (:func:`ops.layers.write_paged_kv` — quantize-on-scatter
    when the scale arrays ride along, the engine's ``kv_dtype`` policy);
    attention is the fused block-table walk at ``(layer, block)``
    (:mod:`ops.paged_attention`), never a materialised span gather, and
    never a slice of one layer's slab. The loop runs over the layers of
    the ``params`` it was given, which may be fewer than the pool's: the
    early-exit draft (:func:`llama_early_exit_apply`) reads and writes
    layers ``0..N-1`` of the target's own pool by index. The layer loop is
    a plain scan — the serving engine is a single-host path (no pp stage
    pipeline)."""
    x = _embed(params, input_ids)
    names = pool_leaf_names(paged_kv)
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]

    def body(carry, layer_and_index):
        x, pools = carry
        layer, layer_idx = layer_and_index
        x, *pools = rope_paged_attention_block(
            layer, x, pools[0], pools[1], layer_idx, cos, sin, block_tables, cache_positions,
            c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            c.rms_norm_eps, write_mask=paged_write_mask,
            **dict(zip(("k_scale", "v_scale"), pools[2:])),
        )
        return (_swiglu_mlp(c, layer, x), tuple(pools)), None

    with jax.named_scope("layers"):
        (x, pools), _ = jax.lax.scan(
            body, (x, tuple(paged_kv[n] for n in names)),
            (params["layers"], jnp.arange(n_layers, dtype=jnp.int32)),
        )
    _, _, logits = _final_norm_and_head(c, params, logit_rows(x, logit_positions))
    return ModelOutput(logits=logits, paged_kv=dict(zip(names, pools)))


def llama_early_exit_apply(config: LlamaConfig, draft_layers: int):
    """Early-exit draft for speculative decoding: an apply fn running only
    the target's first ``draft_layers`` transformer blocks, closed with the
    target's own final norm + head — the cheapest draft that shares the
    target's representation space, as a factory the serving engine arms via
    ``EngineConfig(draft="early_exit:N")``.

    The returned fn takes the FULL model's params and slices the stacked
    layer leaves **in-trace** (``a[:draft_layers]``), so no persistent
    draft copy of the weights exists — the slice is a transient buffer of
    the compiled program (shard-check prices it as the ``draft_params``
    tier). Because the draft's layers are byte-identical to the target's
    prefix, its K/V at any cached position equal the target's for those
    layers: the serving engine exploits this by handing the draft the
    target's own paged pool, whole — the paged step indexes the pool by
    layer, so the draft reads and writes layers ``0..draft_layers-1`` of it
    in place, with no ``pool[:N]`` slice — no separate draft cache, and
    prefix sharing / CoW / swap maintain the draft state for free."""
    if not 1 <= draft_layers < config.num_hidden_layers:
        raise ValueError(
            f"early-exit draft needs 1 <= layers < {config.num_hidden_layers} "
            f"(the target's depth), got {draft_layers}"
        )
    import dataclasses as _dc

    draft_config = _dc.replace(config, num_hidden_layers=draft_layers)

    def early_exit_apply(params, **kw):
        draft_params = {
            "embed_tokens": params["embed_tokens"],
            "layers": jax.tree.map(lambda a: a[:draft_layers], params["layers"]),
            "norm": params["norm"],
        }
        if "lm_head" in params:
            draft_params["lm_head"] = params["lm_head"]
        return llama_apply(draft_config, draft_params, **kw)

    return early_exit_apply


_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "attn_norm", "mlp_norm")


def llama_segments(config: LlamaConfig):
    """Streaming plan for :class:`accelerate_tpu.big_modeling.DispatchedModel`:
    embed → L× layer (one compiled fn reused) → norm+head. Layer params are
    addressed as ``("layers.wq", i)`` slices of the stacked leaves so
    host/disk tiers stream one layer at a time."""

    def plan(input_ids=None, attention_mask=None, positions=None, labels=None, **kw):
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        cos, sin = rope_frequencies(config.head_dim, config.max_position_embeddings, config.rope_theta)

        def init():
            return {
                "ids": jnp.asarray(input_ids),
                "mask": None if attention_mask is None else jnp.asarray(attention_mask),
                "pos": positions,
            }

        def embed_fn(seg, carry):
            x = seg["embed_tokens"][carry["ids"]]
            return {**carry, "x": x}

        def layer_fn(seg, carry):
            layer = {k: seg[f"layers.{k}"] for k in _LAYER_KEYS}
            x = llama_layer_apply(
                config, layer, carry["x"], cos, sin, carry["pos"], carry["mask"]
            )
            return {**carry, "x": x}

        def head_fn(seg, carry):
            x = rms_norm(carry["x"], seg["norm"], config.rms_norm_eps)
            head = seg.get("lm_head")
            if head is None:
                head = seg["embed_tokens"].T
            # dense(): quantized heads take the int8-GEMM / fused-LUT path
            return {**carry, "logits": dense(x, head)}

        steps = [("embed", ["embed_tokens"], embed_fn)]
        for i in range(config.num_hidden_layers):
            steps.append(
                (("layer", i), [(f"layers.{k}", i) for k in _LAYER_KEYS], layer_fn)
            )
        head_paths = ["norm"] + ([] if config.tie_word_embeddings else ["lm_head"])
        if config.tie_word_embeddings:
            head_paths.append("embed_tokens")
        steps.append(("head", head_paths, head_fn))

        def finalize(carry):
            out = ModelOutput(logits=carry["logits"])
            if labels is not None:
                out["loss"] = cross_entropy_loss(
                    carry["logits"][:, :-1, :], jnp.asarray(labels)[:, 1:]
                )
            return out

        return {"init": init, "steps": steps, "finalize": finalize}

    return plan


def convert_hf_llama_state_dict(flat: dict, config: LlamaConfig) -> dict:
    """HF-transformers llama naming → this model's stacked layout.
    torch ``nn.Linear`` stores ``[out, in]``; ours are ``[in, out]`` —
    hence the transposes. Enables loading Llama-2 checkpoints directly
    (reference users get this via transformers; SURVEY §7 pins keeping
    torch-format checkpoint compatibility)."""
    import numpy as np

    L = config.num_hidden_layers

    def get(name):
        for prefix in ("model.", ""):
            if prefix + name in flat:
                return np.asarray(flat[prefix + name])
        raise KeyError(name)

    mapping = {
        "wq": "self_attn.q_proj.weight",
        "wk": "self_attn.k_proj.weight",
        "wv": "self_attn.v_proj.weight",
        "wo": "self_attn.o_proj.weight",
        "w_gate": "mlp.gate_proj.weight",
        "w_up": "mlp.up_proj.weight",
        "w_down": "mlp.down_proj.weight",
        "attn_norm": "input_layernorm.weight",
        "mlp_norm": "post_attention_layernorm.weight",
    }
    out = {"embed_tokens": get("embed_tokens.weight"), "norm": get("norm.weight")}
    for ours, theirs in mapping.items():
        per_layer = [get(f"layers.{i}.{theirs}") for i in range(L)]
        stacked = np.stack(per_layer)
        if "norm" not in ours:
            stacked = stacked.swapaxes(-1, -2)  # torch [out,in] → ours [in,out]
        out[f"layers.{ours}"] = stacked
    if not config.tie_word_embeddings:
        out["lm_head"] = np.asarray(flat["lm_head.weight"]).T
    return out


class LlamaForCausalLM:
    """Factory mirroring the transformers entry point the reference's users
    bring to ``prepare()``."""

    @staticmethod
    def from_config(config: LlamaConfig, seed: int = 0, dtype=jnp.float32) -> Model:
        import dataclasses as _dc

        from ..big_modeling import is_empty_init

        # private copy: apply_fn closes over it, so per-model knob
        # changes (e.g. prepare() wiring activation_checkpointing
        # into remat) cannot leak into other models built from the
        # same config object
        config = _dc.replace(config)

        def make_params(key):
            return init_llama_params(key, config, dtype=dtype)

        if is_empty_init():
            params = jax.eval_shape(make_params, jax.random.PRNGKey(seed))
        else:
            params = make_params(jax.random.PRNGKey(seed))

        def apply_fn(p, input_ids=None, attention_mask=None, labels=None, positions=None, **kw):
            return llama_apply(config, p, input_ids, attention_mask, labels, positions, **kw)

        model = Model(
            apply_fn,
            params,
            partition_rules=LLAMA_PARTITION_RULES,
            name="LlamaForCausalLM",
        )
        model.config = config
        model.segments = llama_segments(config)
        model.stacked_params_prefix = "layers"
        model.supports_kv_cache = True
        model.supports_paged_kv = True  # serving engine's block-paged decode
        # speculative decoding's early-exit draft factory (EngineConfig(
        # spec_k=..., draft="early_exit:N")): first-N-layers apply over the
        # FULL params, sliced in-trace
        model.early_exit_apply = lambda n: llama_early_exit_apply(config, n)
        model.convert_state_dict = lambda flat: convert_hf_llama_state_dict(flat, config)
        # tied embeddings are a single leaf in this functional design (no
        # separate lm_head param exists), so no tie group is declared
        return model
