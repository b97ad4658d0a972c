"""Autoregressive generation over any framework model wrapper.

The reference delegates generation to ``transformers.generate`` running on
its hooked/offloaded modules — what its big-model-inference benchmark
measures as s/token (``benchmarks/big_model_inference/README.md:27-37``).
This build ships its own loop so the same measurement exists for zoo
models behind any executor: a plain :class:`Model`, a prepared model, a
:class:`DispatchedModel` streaming from host/disk, or a pipelined model.

Design for XLA: the token buffer has a STATIC shape ``[b, prompt+max_new]``
(right-padded, mask-tracked), so every decode step reuses one compiled
forward; the step index only changes mask values and the gather position.
With a causal model, logits at position ``cur-1`` are unaffected by the
padded tail, so full-length forwards are exact. (For offload-tier models
the weight streaming dominates decode time, which is precisely the
benchmarked regime; a resident-model KV cache is a latency optimisation,
not a correctness one.)
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _logits_of(out):
    logits = out["logits"] if isinstance(out, dict) else out.logits
    if hasattr(logits, "force"):  # deferred (prepared model)
        logits = logits.force()
    return logits


def _cache_backend(model):
    """(apply_fn, params) when the model supports the KV-cache decode path.

    Only plain :class:`Model`s and :class:`PreparedModel`s qualify — a
    DispatchedModel's ``params`` property would MATERIALISE the whole
    offloaded model, defeating the tiering (those models use the streaming
    full-forward path, where weight movement dominates anyway). A prepared
    model's compute-dtype policy is applied around the raw apply."""
    from .modules import Model, PreparedModel, _cast_floats

    if isinstance(model, PreparedModel):
        inner = model._model
        if not getattr(inner, "supports_kv_cache", False):
            return None
        # the wrapping closures are cached on the PreparedModel — a fresh
        # closure per call would carry a fresh jit cache and recompile
        # prefill/decode on every generate(). Keyed by the CURRENT
        # compute_dtype: autocast(enabled=False) islands mutate it, and a
        # stale snapshot would make generation blind to the policy.
        cache = getattr(model, "_cached_generation_apply", None)
        if cache is None:
            cache = {}
            model._cached_generation_apply = cache
        dtype = model.compute_dtype
        apply = cache.get(dtype)
        if apply is None:

            def apply(p, **kw):
                if dtype is not None:
                    p = _cast_floats(p, dtype)
                return inner.apply_fn(p, **kw)

            cache[dtype] = apply
        return apply, model.params
    if isinstance(model, Model) and getattr(model, "supports_kv_cache", False):
        return model.apply_fn, model.params
    return None


#: the temperature floor every sampling path divides by — ONE constant,
#: so `generate()`, the serving engine, and the per-slot lane path can
#: never disagree about what "temperature ~ 0" means
TEMPERATURE_FLOOR = 1e-6


def scale_logits(logits, temperature):
    """Temperature scaling with the shared floor. ``temperature`` may be a
    scalar or a per-row array (the serving engine's per-slot lanes
    broadcast a ``[num_slots, 1]`` column against ``[num_slots, vocab]``
    logits) — the floor applies elementwise either way."""
    return logits / jnp.maximum(temperature, TEMPERATURE_FLOOR)


@jax.named_scope("sample")
def pick_next_token(logits, key, finished, eos_id, temperature, do_sample, has_eos):
    """THE decode-step token pick (temperature floor, categorical key-split
    order, eos masking) — the single source of sampling semantics. Every
    decode path calls it: ``generate()``'s compiled scan, the host-side
    full-forward/seq2seq loops (via :func:`_pick_next`, which is now a thin
    numpy shim over this), the serving engine's decode/prefill executables,
    and the per-slot lane path in :mod:`~accelerate_tpu.serving.sampling`
    (which reuses :func:`scale_logits` and this greedy branch, adding only
    the per-slot key derivation and top-k/top-p filters on top). Change it
    here or nowhere."""
    if do_sample:
        key, sub = jax.random.split(key)
        tok = jax.random.categorical(sub, scale_logits(logits, temperature), axis=-1)
    else:
        tok = jnp.argmax(logits, axis=-1)
    tok = tok.astype(jnp.int32)
    if has_eos:
        tok = jnp.where(finished, eos_id, tok)
        finished = finished | (tok == eos_id)
    return tok, key, finished


#: legacy alias — the serving engine and the compiled scans imported the
#: picker under this name before it was single-sourced
_pick_traced = pick_next_token


def _pick_next(logits, do_sample, temperature, key, finished, eos_token_id):
    """Host-side shim over :func:`pick_next_token` for the full-forward and
    seq2seq loops: same rule, numpy in/out. Delegating (instead of keeping
    a host twin) is what makes the `use_cache` paths incapable of
    diverging."""
    logits = jnp.asarray(logits)
    has_eos = eos_token_id is not None
    if not has_eos:
        tok, key, _ = pick_next_token(
            logits, key, jnp.zeros(logits.shape[:-1], bool),
            jnp.int32(0), temperature, do_sample, has_eos,
        )
        return np.asarray(tok), key, finished
    tok, key, fin = pick_next_token(
        logits, key, jnp.asarray(finished), jnp.int32(eos_token_id),
        temperature, do_sample, has_eos,
    )
    return np.asarray(tok), key, np.asarray(fin)


def _jitted_for(apply_fn, total: int):
    """Per-apply-fn compile cache: generate() may be called many times in a
    serving loop; the prefill and decode-loop programs must compile once.
    The entry holds the prefill jit plus a nested cache of whole-decode
    scan programs (keyed by step count / sampling / eos flags)."""
    cache = getattr(apply_fn, "_generation_jit_cache", None)
    if cache is None:
        cache = {}
        try:
            apply_fn._generation_jit_cache = cache
        except AttributeError:  # non-function callable; fall back per call
            pass
    entry = cache.get(total)
    if entry is None:
        prefill = jax.jit(
            lambda p, i, m: apply_fn(
                p, input_ids=i, attention_mask=m, use_cache=True, max_cache_len=total
            )
        )
        entry = (prefill, {})
        cache[total] = entry
    return entry


#: decode-scan chunk length when an eos can end generation early: the loop
#: syncs the finished flag with the host once per chunk, so wasted forwards
#: after every row finishes are bounded by one chunk
_EOS_CHUNK = 64


def _pick0_for(scan_cache, do_sample: bool, has_eos: bool):
    """Compiled first-token pick from the prefill logits."""
    key_ = ("pick0", do_sample, has_eos)
    runner = scan_cache.get(key_)
    if runner is None:
        def pick0(logits0, key, eos_id, temperature):
            finished0 = jnp.zeros(logits0.shape[:1], bool)
            return _pick_traced(
                logits0, key, finished0, eos_id, temperature, do_sample, has_eos
            )

        runner = jax.jit(pick0)
        scan_cache[key_] = runner
    return runner


def _scan_decode_for(apply_fn, scan_cache, chunk_len: int, do_sample: bool, has_eos: bool):
    """One decode CHUNK as a compiled program: a ``lax.scan`` of
    ``chunk_len`` steps with the model forward, the token pick
    (:func:`_pick_traced`), eos masking, and the KV append all on device.
    The per-token host round trip of a Python decode loop is pure latency,
    and batching the loop into chunked dispatches removes it. With an eos the caller checks the finished flag
    between chunks (one small sync per ``_EOS_CHUNK`` steps) so early
    completion stops the loop; rows that finish keep emitting ``eos``
    inside the trace, and the caller trims to the step where every row
    finished — outputs match a per-step loop token for token."""
    key_ = (chunk_len, do_sample, has_eos)
    runner = scan_cache.get(key_)
    if runner is not None:
        return runner

    def run_chunk(params, carry, eos_id, temperature):
        def step(carry, _):
            kv_cache, tok, pos, key, finished = carry
            out = apply_fn(
                params, input_ids=tok[:, None], kv_cache=kv_cache, cache_index=pos
            )
            nxt, key, finished = _pick_traced(
                out["logits"][:, 0, :], key, finished, eos_id, temperature,
                do_sample, has_eos,
            )
            return (out["kv_cache"], nxt, pos + 1, key, finished), nxt

        return jax.lax.scan(step, carry, None, length=chunk_len)

    # donate the carry (the KV buffers ride in it): without aliasing the
    # program transiently holds TWO full [L, b, total, n_kv, hd] caches
    runner = jax.jit(run_chunk, donate_argnums=(1,))
    scan_cache[key_] = runner
    return runner


def generate(
    model,
    input_ids,
    max_new_tokens: int = 20,
    do_sample: bool = False,
    temperature: float = 1.0,
    eos_token_id: int | None = None,
    seed: int = 0,
    attention_mask=None,
    use_cache: bool = False,
    draft_model=None,
    num_draft_tokens: int = 5,
):
    """Greedy / temperature-sampled decoding. Returns ``[b, prompt+new]``
    int32 token ids (right-padded with ``eos`` after a sequence finishes).

    ``use_cache=True`` runs prefill-then-decode with a per-layer KV cache
    (O(cache) per token instead of O(n²) re-forwards) when the model
    declares ``supports_kv_cache``; other models silently use the
    full-forward path, which is equally correct — and for offload-streamed
    models equally fast, since weight movement dominates there anyway.

    Encoder-decoder models (``model.is_encoder_decoder``, e.g. t5) decode
    into growing ``decoder_input_ids`` against the fixed encoder prompt
    (the reference gets this from transformers' seq2seq ``generate``);
    the returned ids are the DECODER sequence including the start token.
    """
    from .telemetry import get_active_recorder

    tel = get_active_recorder()
    _t0 = time.perf_counter()
    if _is_encoder_decoder(model):
        out = _generate_seq2seq(
            model, input_ids, max_new_tokens, do_sample, temperature,
            eos_token_id, seed, attention_mask,
        )
        if tel:
            tel.record_generation(
                mode="seq2seq",
                new_tokens=int(out.shape[0]) * (int(out.shape[1]) - 1),
                seconds=time.perf_counter() - _t0,
            )
        return out
    if draft_model is not None:
        if do_sample:
            raise NotImplementedError(
                "speculative decoding is greedy-only: rejection sampling for "
                "do_sample=True is not implemented (pass do_sample=False)"
            )
        if int(num_draft_tokens) < 1:
            raise ValueError(
                f"num_draft_tokens must be >= 1, got {num_draft_tokens}: the "
                "speculative loop drafts k tokens per verify round — k < 1 "
                "would verify nothing and never advance"
            )
        target = _cache_backend(model)
        draft = _cache_backend(draft_model)
        if target is None or draft is None:
            raise ValueError(
                "draft_model decoding needs KV-cache support on both models "
                "(supports_kv_cache on a Model/PreparedModel); got "
                f"target={'ok' if target else 'unsupported'}, "
                f"draft={'ok' if draft else 'unsupported'}"
            )
        config = getattr(model, "config", None) or getattr(
            getattr(model, "_model", None), "config", None
        )
        return _generate_speculative(
            target, draft, input_ids, max_new_tokens, int(num_draft_tokens),
            eos_token_id, attention_mask,
            max_positions=getattr(config, "max_position_embeddings", None),
        )
    if use_cache:
        backend = _cache_backend(model)
        if backend is not None:
            out = _generate_cached(
                backend, input_ids, max_new_tokens, do_sample, temperature,
                eos_token_id, seed, attention_mask,
            )
            if tel:
                prompt_len = np.atleast_2d(np.asarray(input_ids)).shape[1]
                tel.record_generation(
                    mode="kv_cache",
                    new_tokens=int(out.shape[0]) * max(int(out.shape[1]) - prompt_len, 0),
                    seconds=time.perf_counter() - _t0,
                )
            return out
    ids = np.asarray(input_ids)
    if ids.ndim == 1:
        ids = ids[None, :]
    b, prompt_len = ids.shape
    total = prompt_len + max_new_tokens
    buf = np.zeros((b, total), np.int32)
    buf[:, :prompt_len] = ids
    mask = np.zeros((b, total), np.int32)
    if attention_mask is not None:
        mask[:, :prompt_len] = np.asarray(attention_mask)
    else:
        mask[:, :prompt_len] = 1
    # per-row decode position: right-padded shorter prompts continue from
    # THEIR last real token, not the batch-uniform column
    lengths = mask.sum(axis=1).astype(np.int64)

    key = jax.random.PRNGKey(seed)
    finished = np.zeros((b,), bool)
    rows = np.arange(b)
    for _ in range(max_new_tokens):
        out = model(input_ids=jnp.asarray(buf), attention_mask=jnp.asarray(mask))
        all_logits = np.asarray(jax.device_get(_logits_of(out)))
        logits = all_logits[rows, lengths - 1, :]
        next_tok, key, finished = _pick_next(
            logits, do_sample, temperature, key, finished, eos_token_id
        )
        buf[rows, lengths] = next_tok
        mask[rows, lengths] = 1
        lengths += 1
        if eos_token_id is not None and finished.all():
            break
    out = buf[:, : int(lengths.max())]
    if tel:
        tel.record_generation(
            mode="full_forward",
            new_tokens=int(b) * max(int(out.shape[1]) - prompt_len, 0),
            seconds=time.perf_counter() - _t0,
        )
    return out


def _is_encoder_decoder(model) -> bool:
    """The flag lives on the raw :class:`Model`; prepared/dispatched
    wrappers hold it at ``_model`` (same unwrapping ``_cache_backend``
    does for ``supports_kv_cache``)."""
    return bool(
        getattr(model, "is_encoder_decoder", False)
        or getattr(getattr(model, "_model", None), "is_encoder_decoder", False)
    )


def _generate_seq2seq(
    model, input_ids, max_new_tokens, do_sample, temperature,
    eos_token_id, seed, attention_mask,
):
    """Greedy/sampled seq2seq decoding: the encoder prompt is fixed, tokens
    fill a fixed-size ``decoder_input_ids`` buffer (starting from the
    config's ``decoder_start_token_id``). Decoder self-attention is causal
    and cross-attention is per-position, so the not-yet-written buffer
    tail cannot influence the position being read — one compiled shape
    serves every step. For raw Models the encoder runs ONCE (its output is
    re-fed via ``encoder_outputs``) and the per-step decoder forward is
    jitted; wrapper models (prepared/dispatched) run their own
    compiled/streamed full forward per step."""
    config = getattr(model, "config", None) or getattr(
        getattr(model, "_model", None), "config", None
    )
    start_id = int(getattr(config, "decoder_start_token_id", 0) or 0)
    ids = np.asarray(input_ids)
    if ids.ndim == 1:
        ids = ids[None, :]
    b = ids.shape[0]
    mask = (
        np.asarray(attention_mask, np.int32)
        if attention_mask is not None
        else np.ones_like(ids, np.int32)
    )
    total = 1 + max_new_tokens

    apply = model.apply_fn if hasattr(model, "apply_fn") else None
    params = getattr(model, "params", None)

    enc_out = None
    step_fn = None
    if apply is not None and params is not None:
        cache = getattr(apply, "_generation_jit_cache", None)
        if cache is None:
            cache = {}
            try:
                apply._generation_jit_cache = cache
            except AttributeError:
                pass
        entry = cache.get(("seq2seq", total))
        if entry is None:
            encode = jax.jit(
                lambda p, i, m: apply(
                    p, input_ids=i, attention_mask=m,
                    decoder_input_ids=jnp.zeros((i.shape[0], 1), jnp.int32),
                )["encoder_last_hidden_state"]
            )
            decode = jax.jit(
                lambda p, i, m, e, d: _logits_of(
                    apply(
                        p, input_ids=i, attention_mask=m, encoder_outputs=e,
                        decoder_input_ids=d,
                    )
                )
            )
            entry = (encode, decode)
            cache[("seq2seq", total)] = entry
        encode, decode = entry
        enc_out = encode(params, jnp.asarray(ids), jnp.asarray(mask))

        def step_fn(dec):
            return decode(params, jnp.asarray(ids), jnp.asarray(mask), enc_out, dec)

    else:

        def step_fn(dec):
            return _logits_of(
                model(
                    input_ids=jnp.asarray(ids), attention_mask=jnp.asarray(mask),
                    decoder_input_ids=dec,
                )
            )

    dec = np.full((b, total), start_id, np.int32)
    key = jax.random.PRNGKey(seed)
    finished = np.zeros((b,), bool)
    n_written = 0
    for t in range(max_new_tokens):
        logits = np.asarray(jax.device_get(step_fn(jnp.asarray(dec))))[:, t, :]
        next_tok, key, finished = _pick_next(
            logits, do_sample, temperature, key, finished, eos_token_id
        )
        dec[:, t + 1] = next_tok
        n_written = t + 1
        if eos_token_id is not None and finished.all():
            break
    return jnp.asarray(dec[:, : 1 + n_written])


def _generate_cached(
    backend, input_ids, max_new_tokens, do_sample, temperature,
    eos_token_id, seed, attention_mask,
):
    """Prefill + per-token cached decode (see ``llama_apply``'s decode
    mode). Each decode step appends K/V at every row's own position, so
    ragged right-padded prompts behave exactly like the full-forward path."""
    apply_fn, params = backend
    ids = np.asarray(input_ids)
    if ids.ndim == 1:
        ids = ids[None, :]
    b, prompt_len = ids.shape
    total = prompt_len + max_new_tokens
    mask = (
        np.atleast_2d(np.asarray(attention_mask, np.int32))
        if attention_mask is not None
        else np.ones((b, prompt_len), np.int32)
    )
    if mask.shape != (b, prompt_len):
        raise ValueError(
            f"attention_mask shape {mask.shape} does not match input_ids {(b, prompt_len)}"
        )
    lengths = mask.sum(axis=1).astype(np.int64)
    buf = np.zeros((b, total), np.int32)
    buf[:, :prompt_len] = ids

    if max_new_tokens <= 0:
        return buf[:, : int(lengths.max())] if lengths.size else buf

    prefill, scan_cache = _jitted_for(apply_fn, total)
    out = prefill(params, jnp.asarray(ids), jnp.asarray(mask))
    rows = np.arange(b)
    logits0 = out["logits"][jnp.asarray(rows), jnp.asarray(lengths - 1), :]

    has_eos = eos_token_id is not None
    eos_dev = jnp.int32(eos_token_id if has_eos else 0)
    temp_dev = jnp.float32(temperature)
    tok0, key, finished = _pick0_for(scan_cache, do_sample, has_eos)(
        logits0, jax.random.PRNGKey(seed), eos_dev, temp_dev
    )

    carry = (out["kv_cache"], tok0, jnp.asarray(lengths, jnp.int32), key, finished)
    pieces = [tok0[None, :]]
    steps_left = max_new_tokens - 1
    while steps_left > 0:
        # no eos → nothing can stop early: one chunk for the whole decode
        chunk = min(_EOS_CHUNK, steps_left) if has_eos else steps_left
        runner = _scan_decode_for(apply_fn, scan_cache, chunk, do_sample, has_eos)
        carry, toks_chunk = runner(params, carry, eos_dev, temp_dev)
        pieces.append(toks_chunk)
        steps_left -= chunk
        if has_eos and steps_left > 0 and bool(np.asarray(jax.device_get(carry[4])).all()):
            break
    toks = np.asarray(jax.device_get(jnp.concatenate(pieces, axis=0)))  # [n, b]

    # trim to the step where every row had finished — the same stopping
    # point a per-step loop with an all-finished break produces
    if has_eos:
        finished_by = np.cumsum(toks == eos_token_id, axis=0) > 0
        all_fin = finished_by.all(axis=1)
        n_emit = int(np.argmax(all_fin)) + 1 if all_fin.any() else toks.shape[0]
    else:
        n_emit = toks.shape[0]
    for s in range(n_emit):
        buf[rows, lengths] = toks[s]
        lengths += 1
    return buf[:, : int(lengths.max())]


def spec_accept_tokens(d, preds):
    """Greedy speculative acceptance — the SINGLE source for the
    accept/emit token math, shared by the batch ``generate()`` spec loop
    (:func:`_spec_loop_for`) and the serving engine's compiled spec-decode
    step (``serving/engine.py``). Change it in one place or the two paths'
    acceptance semantics diverge.

    ``d`` ``[b, k]`` are the draft's proposed tokens; ``preds`` ``[b, k+1]``
    the target's greedy picks at each position of the verify chunk
    ``[pending, d_1 .. d_k]``. Returns ``(accept, tok_seq)``:

    * ``accept`` ``[b]`` int32 in ``0..k`` — the longest prefix of ``d``
      agreeing with the target's own greedy choices;
    * ``tok_seq`` ``[b, k+1]`` int32 — the round's emittable tokens: the
      accepted draft prefix, then the target's correction at index
      ``accept``, zeros after (callers emit ``tok_seq[:, : accept + 1]``).

    Greedy acceptance is exact for ANY draft: every emitted token equals
    what plain greedy decoding of the target would have produced, so the
    draft only changes how many target forwards a sequence costs."""
    b, k = d.shape
    match = preds[:, :k] == d
    accept = jnp.where(
        match.all(axis=1), k, jnp.argmin(match, axis=1)
    ).astype(jnp.int32)  # [b]
    j = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    corr = jnp.take_along_axis(preds, accept[:, None], axis=1)  # [b, 1]
    d_ext = jnp.concatenate([d, jnp.zeros((b, 1), jnp.int32)], axis=1)
    tok_seq = jnp.where(
        j < accept[:, None], d_ext, jnp.where(j == accept[:, None], corr, 0)
    )
    return accept, tok_seq


def _spec_loop_for(apply_fn, draft_apply, cache_len: int, k: int, has_eos: bool):
    """The WHOLE speculative loop as one compiled program — draft scan,
    feed-only push of the last draft token (so the draft cache never
    develops a hole), target verify chunk, vectorised accept/emit, and the
    round-to-round state threading all live inside a ``lax.while_loop``,
    so a full generation is ONE dispatch regardless of round count (the
    same move that made the plain decode loop dispatch-latency-proof).
    Cached per (target apply, cache_len); the draft apply is part of the
    key — the same target can be paired with different drafts, and a stale
    closure would run one draft's apply_fn with another's params."""
    _, scan_cache = _jitted_for(apply_fn, cache_len)
    key_ = ("specloop", k, id(draft_apply), has_eos)
    runner = scan_cache.get(key_)
    if runner is not None:
        return runner

    def spec_loop(
        params_t, params_d, kv_t, kv_d, buf, lengths, emitted, pending,
        pos, finished, eos_id, max_new,
    ):
        b, total = buf.shape
        rows = jnp.arange(b, dtype=jnp.int32)
        cache_limit = jnp.int32(cache_len - k - 2)

        def round_done(state):
            _, _, _, _, emitted, _, _, finished, _ = state
            return ~(finished | (emitted >= max_new)).all()

        def round_body(state):
            kv_t, kv_d, buf, lengths, emitted, pending, pos, finished, rounds = state

            # draft k tokens greedily from the pending one
            def dstep(c, _):
                kv, tok, p = c
                out = draft_apply(
                    params_d, input_ids=tok[:, None], kv_cache=kv, cache_index=p
                )
                nxt = jnp.argmax(out["logits"][:, 0, :], axis=-1).astype(jnp.int32)
                return (out["kv_cache"], nxt, p + 1), nxt

            (kv_d, d_last, d_pos), d = jax.lax.scan(
                dstep, (kv_d, pending, pos), None, length=k
            )
            # feed-only: d_k's K/V must land so the draft cache has no hole
            kv_d = draft_apply(
                params_d, input_ids=d_last[:, None], kv_cache=kv_d, cache_index=d_pos
            )["kv_cache"]
            d = d.T.astype(jnp.int32)  # [b, k]

            # one target forward over [pending, d_1 .. d_k]
            chunk = jnp.concatenate([pending[:, None], d], axis=1)
            out_t = apply_fn(
                params_t, input_ids=chunk, kv_cache=kv_t, cache_index=pos
            )
            kv_t = out_t["kv_cache"]
            preds = jnp.argmax(out_t["logits"], axis=-1).astype(jnp.int32)  # [b, k+1]

            # greedy accept: longest agreeing prefix + the target's own
            # token — the shared helper (also the serving engine's rule)
            accept, tok_seq = spec_accept_tokens(d, preds)
            j = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
            corr = jnp.take_along_axis(tok_seq, accept[:, None], axis=1)  # [b, 1]

            # emit semantics identical to the sequential rule: skip finished
            # rows, cut a run at its first eos, cap at the token budget
            base = j <= accept[:, None]
            if has_eos:
                is_eos = (tok_seq == eos_id).astype(jnp.int32)
                prior_eos = jnp.cumsum(is_eos, axis=1) - is_eos
                base = base & (prior_eos == 0) & (~finished)[:, None]
            cnt_before = jnp.cumsum(base.astype(jnp.int32), axis=1) - base.astype(jnp.int32)
            valid = base & (emitted[:, None] + cnt_before < max_new)
            write_pos = jnp.where(valid, lengths[:, None] + cnt_before, total)
            buf = buf.at[rows[:, None], write_pos].set(tok_seq, mode="drop")
            n_row = valid.astype(jnp.int32).sum(axis=1)
            emitted = emitted + n_row
            lengths = lengths + n_row
            if has_eos:
                finished = finished | (valid & (tok_seq == eos_id)).any(axis=1)

            pending = corr[:, 0]
            pos = pos + accept + 1
            # done rows keep riding the batch; pin their write position
            # inside the cache margin so their (ignored) chunks never clip
            done = finished | (emitted >= max_new)
            pos = jnp.where(done, jnp.minimum(pos, cache_limit), pos)
            return kv_t, kv_d, buf, lengths, emitted, pending, pos, finished, rounds + 1

        state = (kv_t, kv_d, buf, lengths, emitted, pending, pos, finished, jnp.int32(0))
        state = jax.lax.while_loop(round_done, round_body, state)
        kv_t, kv_d, buf, lengths, emitted, _, _, _, rounds = state
        # the caches ride back in the outputs ONLY so the donation can
        # alias them (unreturned donated buffers force a transient second
        # copy of both caches and a per-compile warning); callers drop them.
        # ``rounds`` (verify-forward count) feeds the telemetry accept-rate.
        return buf, lengths, emitted, rounds, kv_t, kv_d

    runner = jax.jit(spec_loop, donate_argnums=(2, 3, 4))
    scan_cache[key_] = runner
    return runner


def _generate_speculative(
    target, draft, input_ids, max_new_tokens, k, eos_token_id, attention_mask,
    max_positions: int | None = None,
):
    """Greedy speculative decoding (the reference has no analog): a cheap
    draft model proposes ``k`` tokens autoregressively, the target model
    scores all of them in ONE chunked decode forward (s = k+1 — the
    multi-token `cached_attention` path), and the longest matching prefix
    plus the target's own next token are accepted. Greedy acceptance is
    exact: the emitted sequence equals plain greedy decoding of the target
    for ANY draft — the draft only changes how many target forwards it
    takes. Per round the target reads its weights once for up to ``k+1``
    emitted tokens, which is the win in the memory-bound decode regime.

    Cache rollback is free by construction: `cached_attention` masks every
    position past each row's own index, so rejected draft entries are
    simply never attended and are overwritten by later appends.
    """
    _t_start = time.perf_counter()
    apply_t, params_t = target
    apply_d, params_d = draft
    ids = np.asarray(input_ids)
    if ids.ndim == 1:
        ids = ids[None, :]
    b, prompt_len = ids.shape
    mask = (
        np.atleast_2d(np.asarray(attention_mask, np.int32))
        if attention_mask is not None
        else np.ones((b, prompt_len), np.int32)
    )
    if mask.shape != (b, prompt_len):
        raise ValueError(
            f"attention_mask shape {mask.shape} does not match input_ids {(b, prompt_len)}"
        )
    lengths = mask.sum(axis=1).astype(np.int64)
    total = prompt_len + max_new_tokens
    # verify chunks may overshoot a row's budget by up to k; both caches
    # carry the margin so the scatter never clips a live row. Near an
    # exact-fit budget (total == max_position_embeddings) the margin is
    # clamped — overshoot writes past the cache end are DROPPED by the
    # write scatter (ops.layers.write_kv_cache mode="drop") and belong to
    # tokens past the budget, which are never emitted, so the clamp only
    # removes the pre-allocated slack, not correctness.
    cache_len = total + k + 1
    if max_positions is not None:
        if total > int(max_positions):
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
                f"= {total} exceeds max_position_embeddings {max_positions}: "
                "emitted tokens would fall past the position table"
            )
        cache_len = min(cache_len, int(max_positions))
    buf = np.zeros((b, total), np.int32)
    buf[:, :prompt_len] = ids
    if max_new_tokens <= 0:
        return buf[:, : int(lengths.max())] if lengths.size else buf

    has_eos = eos_token_id is not None
    prefill_t, _ = _jitted_for(apply_t, cache_len)
    prefill_d, _ = _jitted_for(apply_d, cache_len)
    spec_loop = _spec_loop_for(apply_t, apply_d, cache_len, k, has_eos)

    out_t = prefill_t(params_t, jnp.asarray(ids), jnp.asarray(mask))
    out_d = prefill_d(params_d, jnp.asarray(ids), jnp.asarray(mask))
    rows = np.arange(b)
    logits0 = out_t["logits"][jnp.asarray(rows), jnp.asarray(lengths - 1), :]
    pending = np.asarray(jax.device_get(jnp.argmax(logits0, axis=-1))).astype(np.int32)

    # next cache slot == count of CACHED tokens: the prompt only — the
    # pending pick is not yet fed, its K/V lands in the first draft step
    pos = lengths.copy()

    # the prefill pick is the first emitted token (each round inside the
    # compiled loop emits its accepted drafts plus the correction, which
    # becomes the next round's pending — so only this one is host-emitted)
    emitted = np.zeros((b,), np.int32)
    finished = np.zeros((b,), bool)
    for row in rows:
        buf[row, lengths[row]] = pending[row]
        lengths[row] += 1
        emitted[row] += 1
        if has_eos and pending[row] == eos_token_id:
            finished[row] = True

    buf_dev, lengths_dev, emitted_dev, rounds_dev, _, _ = spec_loop(
        params_t, params_d, out_t["kv_cache"], out_d["kv_cache"],
        jnp.asarray(buf), jnp.asarray(lengths, jnp.int32),
        jnp.asarray(emitted), jnp.asarray(pending),
        jnp.asarray(pos, jnp.int32), jnp.asarray(finished),
        jnp.int32(eos_token_id if has_eos else 0), jnp.int32(max_new_tokens),
    )
    buf = np.array(jax.device_get(buf_dev))  # copy: device_get views are read-only
    lengths = np.asarray(jax.device_get(lengths_dev)).astype(np.int64)
    emitted = np.array(jax.device_get(emitted_dev))

    from .telemetry import get_active_recorder

    tel = get_active_recorder()
    if tel:
        rounds = int(np.asarray(jax.device_get(rounds_dev)))
        loop_tokens = int(emitted.sum()) - b  # first token was host-emitted
        tel.record_generation(
            mode="speculative",
            new_tokens=int(emitted.sum()),
            seconds=time.perf_counter() - _t_start,
            # aggregate acceptance: fraction of the k+1 tokens each verify
            # round could emit that were actually emitted (rows that finish
            # early drag it down — it is a fleet-level utilisation number)
            accept_rate=(loop_tokens / (rounds * b * (k + 1))) if rounds else None,
            verify_rounds=rounds,
        )

    # eos-finished rows pad with eos to the step the LAST row stopped at —
    # the same column the all-finished break of the plain loops produces
    if has_eos:
        n_emit = int(emitted.max())
        for row in rows:
            while emitted[row] < n_emit and lengths[row] < total:
                buf[row, lengths[row]] = eos_token_id
                lengths[row] += 1
                emitted[row] += 1
    return buf[:, : int(lengths.max())]
