"""Pipeline-parallel training: a GPipe schedule over the ``pp`` mesh axis.

The reference delegates pipeline-parallel *training* to Megatron
(``pp_degree``/``num_micro_batches``, reference ``utils/dataclasses.py:1836,1912``)
and covers *inference* pipelining with PiPPy (``inference.py:31-184``; our
analog is :mod:`accelerate_tpu.inference`). This module is the TPU-native
training analog: instead of per-stage processes exchanging activations over
NCCL P2P, the whole pipeline is ONE jitted SPMD program —

* layer-stacked parameters (leading ``[layers]`` axis, the same layout the
  training scan uses) are sharded over the ``pp`` mesh axis, so each device
  group holds ``layers/num_stages`` contiguous layers;
* a ``jax.shard_map`` manual only over ``pp`` (every other mesh axis stays
  GSPMD-auto, so dp/fsdp/tp sharding *composes* with pipelining) runs the
  classic GPipe tick loop as a ``lax.scan``: at tick ``t`` stage ``s``
  processes microbatch ``t - s``, then hands its activation to stage
  ``s + 1`` via ``jax.lax.ppermute``;
* forward + backward through the schedule is plain ``jax.grad`` — ppermute
  transposes to the reverse permutation, so the backward pipeline falls out
  of autodiff instead of a hand-written 1F1B runtime.

Bubble fraction is the textbook ``(S-1)/(M+S-1)`` for ``S`` stages and
``M`` microbatches — choose ``M >= 4*S`` to keep it under ~20%.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

P = PartitionSpec


def set_default_microbatches(n: int) -> None:
    """Set the session default for the GPipe microbatch count (0 = auto).

    The default rides the parallelism context (``AttentionContext``) set by
    ``Accelerator.__init__`` from ``MegatronLMPlugin.num_micro_batches``
    (reference field ``utils/dataclasses.py:1912``), so it shares the mesh's
    lifecycle instead of living in a module global. Model configs that set
    their own ``pipeline_microbatches`` take precedence.
    """
    from ..ops.attention import get_attention_context, set_attention_context
    from dataclasses import replace

    set_attention_context(replace(get_attention_context(), pipeline_microbatches=int(n)))


def remat_wrap(body, remat):
    """Apply the configured rematerialisation to a scan body.

    ``remat`` is False (save everything), True (full recompute), or a
    ``jax.checkpoint_policies`` name — e.g. ``"dots_saveable"`` keeps
    matmul outputs resident and recomputes only elementwise work, trading
    a fraction of full-remat's FLOPs for most of its memory win (the
    activation_checkpointing knob of the FSDP plugin maps here; reference
    wires torch's ``checkpoint_wrapper`` at ``accelerator.py:1523``)."""
    if not remat:
        return body
    policy = None
    if isinstance(remat, str):
        policy = getattr(jax.checkpoint_policies, remat, None)
        if policy is None:
            raise ValueError(
                f"unknown remat policy {remat!r}: expected a "
                "jax.checkpoint_policies name, e.g. 'dots_saveable' or "
                "'dots_with_no_batch_dims_saveable'"
            )
    return jax.checkpoint(body, prevent_cse=False, policy=policy)


def validate_pipeline_axes(mesh_shape: dict) -> None:
    """pp×cp compose since round 4: the cp attention's shard_map claims
    only its own axes (``parallel/context.py`` passes ``axis_names``), so
    it nests inside the GPipe stage body whose shard_map is manual over
    ``pp`` alone. Kept as the single owner of any future composition
    rule; currently every combination is accepted."""


def active_pipeline_mesh():
    """The active mesh when GPipe pipeline training is configured (``pp``
    axis extent > 1), else None. The mesh comes from the parallelism
    context ``Accelerator.prepare`` sets for attention routing."""
    from ..ops.attention import get_attention_context

    mesh = get_attention_context().mesh
    if mesh is None or dict(mesh.shape).get("pp", 1) <= 1:
        return None
    validate_pipeline_axes(dict(mesh.shape))
    return mesh


def ensure_no_pipeline_axis(model_name: str) -> None:
    """Guard for models without a GPipe execution path: a ``pp`` axis > 1
    would otherwise silently run un-pipelined while the sharding planner
    still splits their stacked layers across stages."""
    if active_pipeline_mesh() is not None:
        raise NotImplementedError(
            f"pipeline-parallel execution is not implemented for "
            f"{model_name}; use a mesh with pp=1 (every built-in family "
            f"implements the GPipe path via parallel.pipeline_layer_stack)"
        )


def pipeline_microbatches(batch: int, num_microbatches: int, num_stages: int) -> int:
    """Validate/resolve the microbatch count for a GPipe run.

    ``num_microbatches == 0`` means auto: the session default from
    :func:`set_default_microbatches` if set AND it divides ``batch``
    (an inherited default that doesn't divide falls through to auto
    resolution rather than raising at trace time), else the smallest
    divisor of ``batch`` that is >= ``num_stages``, so the schedule always
    has at least one microbatch in flight per stage (falls back to
    ``batch`` itself).
    """
    if num_microbatches == 0:
        from ..ops.attention import get_attention_context

        inherited = get_attention_context().pipeline_microbatches
        if inherited < 0:
            raise ValueError(f"num_microbatches must be >= 1, got {inherited}")
        if inherited >= 1:
            if batch % inherited == 0:
                num_microbatches = inherited
            else:
                import logging

                logging.getLogger(__name__).warning(
                    "configured num_micro_batches=%d does not divide global "
                    "batch %d; falling back to auto microbatch resolution",
                    inherited,
                    batch,
                )
    if num_microbatches:
        if num_microbatches < 1:
            raise ValueError(f"num_microbatches must be >= 1, got {num_microbatches}")
        if batch % num_microbatches != 0:
            raise ValueError(
                f"global batch {batch} is not divisible by "
                f"num_microbatches={num_microbatches}"
            )
        return num_microbatches
    for m in range(num_stages, batch + 1):
        if batch % m == 0:
            return m
    return batch


def pipeline_layer_stack(
    layer_fn: Callable,
    stage_params,
    x: jax.Array,
    *,
    mesh: Mesh,
    remat=False,
    positions: jax.Array | None = None,
    mask: jax.Array | None = None,
    extra_aligned: tuple = (),
    rope: tuple = (),
    num_microbatches: int = 0,
    with_aux: bool = False,
):
    """Run a transformer layer stack as a GPipe pipeline — the one owner of
    the operand convention every model family shares.

    ``layer_fn(layer, x_mb, positions_mb, mask_mb, *extra_mb, *rope) ->
    y_mb`` (or ``(y_mb, aux_scalar)`` with ``with_aux``) applies ONE
    unstacked layer. ``positions``/``mask`` are per-example ``[batch, ...]``
    operands that ride the microbatch schedule (either may be None), as do
    ``extra_aligned`` operands (e.g. t5's encoder output for
    cross-attention); ``rope`` tables are broadcast to every stage call.
    The scan over each stage's local layers (with ``remat`` applied per
    block) is built here so models don't duplicate the aligned/broadcast
    packing or the aux carry.
    """
    aligned = tuple(a for a in (positions, mask) if a is not None) + tuple(extra_aligned)
    has_pos = positions is not None
    has_mask = mask is not None

    def stage_fn(local_layers, x_mb, *ops):
        pos_mb = ops[0] if has_pos else None
        mask_mb = ops[int(has_pos)] if has_mask else None
        extra_mb = ops[int(has_pos) + int(has_mask) : len(aligned)]
        rope_ops = ops[len(aligned):]
        if with_aux:
            def body(carry, layer):
                h, aux_sum = carry
                h, aux = layer_fn(layer, h, pos_mb, mask_mb, *extra_mb, *rope_ops)
                return (h, aux_sum + aux), None

            (y, aux), _ = jax.lax.scan(
                remat_wrap(body, remat),
                (x_mb, jnp.asarray(0.0, jnp.float32)),
                local_layers,
            )
            return y, aux

        def body(h, layer):
            return layer_fn(layer, h, pos_mb, mask_mb, *extra_mb, *rope_ops), None

        y, _ = jax.lax.scan(remat_wrap(body, remat), x_mb, local_layers)
        return y

    return gpipe(
        stage_fn, stage_params, x,
        mesh=mesh,
        aligned=aligned,
        broadcast=rope,
        num_microbatches=num_microbatches,
        with_aux=with_aux,
    )


def _validate_layer_stack(stage_params, nstages: int, axis: str) -> None:
    """Stacked-layer shape agreement + stage divisibility (shared by the
    training and generation pipeline engines)."""
    layer_lens = {leaf.shape[0] for leaf in jax.tree.leaves(stage_params)}
    if len(layer_lens) > 1:
        raise ValueError(
            f"stage_params leaves disagree on the stacked layer axis "
            f"(leading dims {sorted(layer_lens)}); every leaf must share "
            f"the same [layers] leading axis"
        )
    for n_layers in layer_lens:
        if n_layers % nstages != 0:
            raise ValueError(
                f"stacked layer axis of length {n_layers} must divide "
                f"evenly into {axis}={nstages} pipeline stages"
            )


def pipeline_cached_stack(
    stage_fn: Callable,
    stage_params,
    kv_cache: tuple,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "pp",
    broadcast: tuple = (),
):
    """Run a layer stack with STAGE-LOCAL KV caches over the ``pp`` axis —
    the generation (prefill/decode) counterpart of :func:`gpipe`.

    Training pipelining wants microbatch overlap; cached generation wants
    the cache to stay where its layers live. This engine runs the classic
    single-microbatch tick chain: every stage applies its local layers each
    tick, activations hop forward over ``ppermute``, and each stage commits
    its cache update only at ITS tick (``t == stage``), when the activation
    reaching it is the real one. The K/V cache never leaves its stage —
    decode moves one ``[b, 1, h]`` activation across ICI per hop instead of
    all-gathering ``layers/S`` weight shards per token (reference-side
    analog: PiPPy serves generation by feeding microbatches through stages,
    ``inference.py:99-122``).

    Args:
      stage_fn: ``(local_layers, local_k, local_v, x, *broadcast) ->
        (y, new_local_k, new_local_v)`` — applies this stage's layer slice,
        returning updated local caches (same shapes).
      stage_params: ``[L, ...]`` pytree split over ``axis`` like gpipe.
      kv_cache: ``(k, v)`` arrays ``[L, b, ...]`` split over ``axis`` on
        dim 0 (zeros for prefill).
      x: activations entering stage 0 (already embedded).
      broadcast: operands handed to every stage call unchanged.

    Returns ``(y, (k, v))``: last-stage output replicated over ``axis``,
    caches still split over it.
    """
    nstages = dict(mesh.shape).get(axis, 1)
    k_cache, v_cache = kv_cache
    if nstages <= 1:
        y, k2, v2 = stage_fn(stage_params, k_cache, v_cache, x, *broadcast)
        return y, (k2, v2)
    _validate_layer_stack(stage_params, nstages, axis)

    fwd_perm = [(i, i + 1) for i in range(nstages - 1)]
    back_perm = [(i + 1, i) for i in range(nstages - 1)]
    # On TPU, skip the ticks where this stage's activation hasn't arrived
    # yet (lax.cond): the predicate is uniform across the auto axes (tp/dp
    # peers share the pp coordinate), so auto-axis collectives inside the
    # branch stay uniform, and inactive stages idle instead of computing
    # discarded work. XLA:CPU's collective rendezvous stalls on the
    # branch-gated collectives, so the CPU debug backend computes every
    # tick and masks with `where` — same results, correctness-only backend.
    use_cond = jax.devices()[0].platform != "cpu"

    def body(local_params, kc, vc, x, *broadcast_ops):
        stage = jax.lax.axis_index(axis)

        def tick(carry, t):
            state, kc, vc, out = carry
            active = t == stage

            def run(args):
                state, kc, vc = args
                return stage_fn(local_params, kc, vc, state, *broadcast_ops)

            def skip(args):
                return args

            if use_cond:
                y, kc, vc = jax.lax.cond(active, run, skip, (state, kc, vc))
            else:
                y, kc_new, vc_new = run((state, kc, vc))
                kc = jnp.where(active, kc_new, kc)
                vc = jnp.where(active, vc_new, vc)
            out = jnp.where(active & (stage == nstages - 1), y, out)
            state = jax.lax.ppermute(y, axis, fwd_perm)
            return (state, kc, vc, out), None

        (_, kc, vc, out), _ = jax.lax.scan(
            tick, (x, kc, vc, jnp.zeros_like(x)), jnp.arange(nstages)
        )
        # replicate the last stage's output backward (same ppermute chain
        # rationale as gpipe: psum's reduction region trips XLA:CPU's
        # AllReducePromotion under check_vma=False)
        for _ in range(nstages - 1):
            incoming = jax.lax.ppermute(out, axis, back_perm)
            out = jnp.where(stage == nstages - 1, out, incoming)
        return out, kc, vc

    n_b = len(broadcast)
    y, k2, v2 = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()) + (P(),) * n_b,
        out_specs=(P(), P(axis), P(axis)),
        axis_names={axis},
        check_vma=False,
    )(stage_params, k_cache, v_cache, x, *broadcast)
    return y, (k2, v2)


def decode_stack(decode_layer_fn: Callable, layers, kv_cache: dict, x: jax.Array,
                 *, broadcast: tuple = ()):
    """Run a per-layer cached decode over the whole stack — plain
    ``lax.scan`` on a pp=1 mesh, :func:`pipeline_cached_stack` otherwise.
    The one owner of the "scan decode layer over (layers, k, v)" wrapper
    every causal family shares.

    ``decode_layer_fn(layer, h, kc_l, vc_l, *broadcast, pp_manual=...) ->
    (h, kc_l, vc_l)`` applies one UNstacked layer; ``pp_manual`` tells it
    the call runs inside the pp-manual shard_map (see the models'
    ``write_kv_cache`` pinning). Returns ``(h, {"k": ..., "v": ...})``.
    """
    mesh = active_pipeline_mesh()
    if mesh is None:

        def body(h, xs):
            layer, kc_l, vc_l = xs
            h, kc_l, vc_l = decode_layer_fn(layer, h, kc_l, vc_l, *broadcast, pp_manual=False)
            return h, (kc_l, vc_l)

        x, (kc, vc) = jax.lax.scan(body, x, (layers, kv_cache["k"], kv_cache["v"]))
        return x, {"k": kc, "v": vc}

    def stage_fn(local_layers, kc, vc, h, *ops):
        def body(carry, xs):
            layer, kc_l, vc_l = xs
            h2, kc_l, vc_l = decode_layer_fn(layer, carry, kc_l, vc_l, *ops, pp_manual=True)
            return h2, (kc_l, vc_l)

        y, (kc2, vc2) = jax.lax.scan(body, h, (local_layers, kc, vc))
        return y, kc2, vc2

    x, (kc, vc) = pipeline_cached_stack(
        stage_fn, layers, (kv_cache["k"], kv_cache["v"]), x, mesh=mesh, broadcast=broadcast
    )
    return x, {"k": kc, "v": vc}


def prefill_stack(prefill_layer_fn: Callable, layers, x: jax.Array,
                  cache_shape: tuple, *, broadcast: tuple = ()):
    """Forward the stack while collecting each layer's (padded) K/V — the
    prefill counterpart of :func:`decode_stack`.

    ``prefill_layer_fn(layer, h, *broadcast) -> (h, (k_pad, v_pad))``
    applies one UNstacked layer and returns its cache row already padded
    to ``cache_shape[2:]``. Returns ``(h, {"k": ..., "v": ...})`` with
    caches ``cache_shape`` = ``[L, b, max_cache, n_kv, hd]``.
    """
    mesh = active_pipeline_mesh()
    if mesh is None:

        def body(h, layer):
            return prefill_layer_fn(layer, h, *broadcast)

        x, (kc, vc) = jax.lax.scan(body, x, layers)
        return x, {"k": kc, "v": vc}

    cache0 = jnp.zeros(cache_shape, x.dtype)

    def stage_fn(local_layers, kc, vc, h, *ops):
        def body(h, layer):
            return prefill_layer_fn(layer, h, *ops)

        y, (knew, vnew) = jax.lax.scan(body, h, local_layers)
        return y, knew, vnew

    x, (kc, vc) = pipeline_cached_stack(
        stage_fn, layers, (cache0, cache0), x, mesh=mesh, broadcast=broadcast
    )
    return x, {"k": kc, "v": vc}


def prefill_layer_stack(layer_fn: Callable, layers, x: jax.Array,
                        cache_shape: tuple, *, positions=None, mask=None,
                        rope: tuple = ()):
    """Convention-owning wrapper over :func:`prefill_stack` (the prefill
    analog of :func:`pipeline_layer_stack`): models hand over their
    operands once and ``layer_fn(layer, h, positions, mask, *rope) ->
    (h, (k_pad, v_pad))`` receives them positionally inside any backend —
    no per-family packing/unpacking of the broadcast tuple to keep in
    sync. ``positions``/``mask`` may be None."""
    has_pos = positions is not None
    has_mask = mask is not None
    ops = tuple(o for o in (positions, mask) if o is not None) + tuple(rope)

    def fn(layer, h, *rest):
        pos_b = rest[0] if has_pos else None
        mask_b = rest[int(has_pos)] if has_mask else None
        rope_ops = rest[int(has_pos) + int(has_mask):]
        return layer_fn(layer, h, pos_b, mask_b, *rope_ops)

    return prefill_stack(fn, layers, x, cache_shape, broadcast=ops)


def gpipe(
    stage_fn: Callable,
    stage_params,
    x: jax.Array,
    *,
    mesh: Mesh,
    aligned: tuple = (),
    broadcast: tuple = (),
    num_microbatches: int = 0,
    axis: str = "pp",
    with_aux: bool = False,
):
    """Run ``stage_fn`` as a GPipe pipeline over ``mesh`` axis ``axis``.

    Args:
      stage_fn: ``(local_stage_params, x_mb, *aligned_mb, *broadcast) ->
        y_mb`` — applies this stage's slice of the layer stack to one
        microbatch. Called inside a ``shard_map`` that is manual over
        ``axis`` only; sharding constraints over other axes inside are
        legal (they stay auto).
      stage_params: pytree whose leaves have a leading ``[layers]`` axis
        divisible by the ``pp`` extent. The leading axis is split across
        stages (stage ``s`` gets layers ``[s*L/S, (s+1)*L/S)``).
      x: ``[batch, ...]`` activations entering the first stage.
      aligned: per-example operands ``[batch, ...]`` (attention mask,
        positions) — microbatched like ``x``; at tick ``t`` stage ``s``
        receives the slice for the microbatch it is processing (``t - s``).
      broadcast: operands passed to every stage call unchanged (rope
        tables, scalars).
      num_microbatches: GPipe microbatch count (0 = auto, see
        :func:`pipeline_microbatches`).
      with_aux: ``stage_fn`` additionally returns a f32 scalar per call
        (e.g. an MoE load-balancing statistic); gpipe returns
        ``(outputs, aux)`` where aux is the mean over microbatches of the
        per-stage sums, psum'd over the pipeline — i.e. the same
        "sum over layers, averaged over the batch it was computed on"
        contract the dense scan has, computed per microbatch (standard
        MoE×GPipe semantics: routing statistics are per-microbatch).

    Returns ``[batch, ...]`` activations out of the last stage, replicated
    over ``axis`` (other-axis sharding untouched); with ``with_aux``,
    ``(outputs, aux_scalar)``.
    """
    nstages = dict(mesh.shape).get(axis, 1)
    if nstages <= 1:
        return stage_fn(stage_params, x, *aligned, *broadcast)
    _validate_layer_stack(stage_params, nstages, axis)
    b = x.shape[0]
    m = pipeline_microbatches(b, num_microbatches, nstages)
    mb = b // m

    # XLA:CPU hardening: shard_map's check_vma=False transpose inserts
    # psums over the manual axis whose reduction regions are copy-rooted;
    # AllReducePromotion then check-fails on any that are bf16 ("Invalid
    # binary instruction opcode copy"). Keep every value crossing the
    # shard_map boundary (and the inter-stage ppermute traffic) f32 on the
    # CPU backend; stage compute still runs in the original dtype. On TPU
    # the pass doesn't run and bf16 rides the ICI links natively.
    _narrow = (jnp.bfloat16, jnp.float16)
    cpu_widen = jax.devices()[0].platform == "cpu" and (
        x.dtype in _narrow
        or any(a.dtype in _narrow for a in aligned)
        or any(b.dtype in _narrow for b in broadcast)
    )
    compute_dtype = x.dtype
    # original dtypes of the other operands — differentiable bf16 operands
    # (t5's rel-bias tables, encoder output) must also cross the boundary
    # in f32 or their cotangent psums hit the same XLA:CPU crash
    aligned_dtypes = tuple(a.dtype for a in aligned)
    broadcast_dtypes = tuple(b.dtype for b in broadcast)

    def _widen(v):
        return v.astype(jnp.float32) if v.dtype in _narrow else v

    if cpu_widen:
        x = x.astype(jnp.float32)
        aligned = tuple(_widen(a) for a in aligned)
        broadcast = tuple(_widen(b) for b in broadcast)

    x_mb = x.reshape(m, mb, *x.shape[1:])
    aligned_mb = tuple(a.reshape(m, mb, *a.shape[1:]) for a in aligned)

    fwd_perm = [(i, i + 1) for i in range(nstages - 1)]

    def body(local_params, x_mb, *rest):
        aligned_ops = rest[: len(aligned_mb)]
        broadcast_ops = rest[len(aligned_mb) :]
        stage = jax.lax.axis_index(axis)
        state0 = jnp.zeros_like(x_mb[0])
        outputs0 = jnp.zeros_like(x_mb)
        aux0 = jnp.asarray(0.0, jnp.float32)

        def tick(carry, t):
            state_in, outputs, aux_acc = carry
            inject = x_mb[jnp.clip(t, 0, m - 1)]
            state_in = jnp.where(stage == 0, inject, state_in)
            # microbatch id this stage is processing at tick t (clipped:
            # out-of-range ticks compute on garbage whose output is masked)
            mb_idx = jnp.clip(t - stage, 0, m - 1)
            valid = (t - stage >= 0) & (t - stage < m)
            aligned_t = tuple(
                jax.lax.dynamic_index_in_dim(a, mb_idx, axis=0, keepdims=False)
                for a in aligned_ops
            )
            if cpu_widen:
                # stage compute still runs at the original dtypes; only the
                # boundary crossing (and its transpose psums) is f32
                state_arg = state_in.astype(compute_dtype)
                aligned_t = tuple(
                    a.astype(d) for a, d in zip(aligned_t, aligned_dtypes)
                )
                broadcast_args = tuple(
                    b.astype(d) for b, d in zip(broadcast_ops, broadcast_dtypes)
                )
            else:
                state_arg = state_in
                broadcast_args = broadcast_ops
            res = stage_fn(local_params, state_arg, *aligned_t, *broadcast_args)
            if with_aux:
                y, aux = res
                aux_acc = aux_acc + jnp.where(valid, aux.astype(jnp.float32), 0.0)
            else:
                y = res
            if cpu_widen:
                y = y.astype(jnp.float32)
            out_idx = t - (nstages - 1)
            emit = (stage == nstages - 1) & (out_idx >= 0)
            idx = jnp.clip(out_idx, 0, m - 1)
            prev = jax.lax.dynamic_index_in_dim(outputs, idx, axis=0, keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(emit, y, prev), idx, axis=0
            )
            # hand activation to the next stage; stage 0 receives zeros
            # (no wraparound edge) and overwrites them with its injection
            state_out = jax.lax.ppermute(y, axis, fwd_perm)
            return (state_out, outputs, aux_acc), None

        (_, outputs, aux_acc), _ = jax.lax.scan(
            tick, (state0, outputs0, aux0), jnp.arange(m + nstages - 1)
        )
        # Replicate the last stage's outputs to every stage so downstream
        # (final norm / lm head / loss) runs replicated over pp. Done as a
        # backward ppermute chain rather than a masked psum: the psum's
        # reduction region acquires a copy-rooted computation under
        # check_vma=False, and XLA CPU's AllReducePromotion pass
        # check-fails cloning it ("Invalid binary instruction opcode
        # copy"); collective-permutes sidestep the pass, and the chain has
        # the same S-1 hop latency the psum ring would.
        back_perm = [(i + 1, i) for i in range(nstages - 1)]
        for _ in range(nstages - 1):
            incoming = jax.lax.ppermute(outputs, axis, back_perm)
            outputs = jnp.where(stage == nstages - 1, outputs, incoming)
        if with_aux:
            # total over stages (each stage summed its own layers' aux over
            # its m valid ticks), averaged over microbatches; stays f32 so
            # the psum never enters XLA:CPU's bf16 promotion pass
            aux_total = jax.lax.psum(aux_acc, axis) / m
            return outputs, aux_total
        return outputs

    n_rest = len(aligned_mb) + len(broadcast)
    out_specs = (P(), P()) if with_aux else P()
    res = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()) + (P(),) * n_rest,
        out_specs=out_specs,
        axis_names={axis},
        check_vma=False,
    )(stage_params, x_mb, *aligned_mb, *broadcast)
    y_mb, aux = res if with_aux else (res, None)
    y = y_mb.reshape(b, *x.shape[1:]).astype(compute_dtype)
    return (y, aux) if with_aux else y
