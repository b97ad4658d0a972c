"""Per-slot sampling for the serving engine.

The continuous-batching engine compiles ONE decode executable and keeps it
for the life of the process (``decode_compiles == 1`` is pin-tested).  That
rules out the obvious way to support per-request sampling params — baking
them into the trace — so everything a request can vary rides in as *traced
lane inputs*: fixed-shape ``[num_slots]`` arrays (plus one ``[num_slots,
rep_window]`` ring for the repetition penalty) whose abstract signature
never changes no matter which requests occupy the slots.

Randomness is derived, never threaded: the per-slot key for output
position ``pos`` is ``fold_in(fold_in(fold_in(base_key, tag), seed),
pos)``.  Because the key depends only on (request seed, output position,
draw kind) — not on the slot index, the batch composition, or how many
bursts it took to get there — identical ``(seed, prompt)`` pairs reproduce
the same completion across admission orders and across preempt/swap/resume
cycles.  The ``tag`` separates the independent draws a speculative round
makes at the same position (draft proposal, accept/reject uniform,
residual resample).

A stage of the pick runs only when a live lane asks for it, decided
inside the program from the lanes it is handed.  The filters, the argmax
and — when the engine harvests log-probabilities — the float32
``log_softmax`` with its ``top_k`` are a few passes over ``[slots, vocab]``
and run on every step.  The sampler (:func:`dist_logprobs`' whole-vocabulary
sort, cumulative sum and scatter, and the per-slot random draw) sits under
a ``lax.cond`` on ``any(lanes["sample"])``: a batch in which nobody samples
pays for none of it, whether or not it harvests, and is served the token a
pick by argmax alone serves (``tests/test_sampling_serving.py`` holds them
recorded, and holds the pick bit-equal to the unstaged composition).  What
the pick costs on the chip is ``scope.sample_pct.chat`` in ``PERF.md``.

Host-side bookkeeping (stop sequences, min/max tokens, the authoritative
DFA state) lives on the request object; this module only supplies the
pure helpers (:func:`match_stop`, :class:`SamplingParams`).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..generation import scale_logits

__all__ = [
    "NEG",
    "SamplingParams",
    "resolve_sampling",
    "blank_lanes",
    "set_slot_lane",
    "clear_slot_lane",
    "match_stop",
    "slot_keys",
    "categorical_per_slot",
    "uniform_per_slot",
    "apply_filters",
    "dist_logprobs",
    "pick_tokens",
    "rejection_accept",
    "TAG_SAMPLE",
    "TAG_DRAFT",
    "TAG_ACCEPT",
    "TAG_RESAMPLE",
]

#: large-but-finite mask fill.  Not -inf: a fully-masked row (a grammar's
#: terminal state, sampled only on discarded burst tails) must softmax to
#: uniform garbage, not NaN.
NEG = -1e30

# Draw kinds folded into the per-slot key so a speculative round's
# independent draws at the same output position don't collide.
TAG_SAMPLE = 0  # plain decode / prefill token pick
TAG_DRAFT = 1  # speculative draft proposal
TAG_ACCEPT = 2  # accept/reject uniform in the verify round
TAG_RESAMPLE = 3  # residual resample / bonus token


# --------------------------------------------------------------------------
# host-side request params
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs, validated once at admission.

    ``stop`` is a tuple of token-id tuples — the engine works in token
    ids; the OpenAI layer encodes string stops with the byte vocabulary
    before they get here.  ``logprobs`` asks for the top-N per-step
    logprobs and must be ≤ the engine's static ``logprobs_topn`` cap
    (the cap shapes the compiled harvest, the request only opts in).
    """

    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    repetition_penalty: float = 1.0
    seed: int = 0
    min_tokens: int = 0
    stop: tuple = ()
    logprobs: int = 0

    def validate(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if self.repetition_penalty <= 0:
            raise ValueError(
                f"repetition_penalty must be > 0, got {self.repetition_penalty}"
            )
        if self.min_tokens < 0:
            raise ValueError(f"min_tokens must be >= 0, got {self.min_tokens}")
        if self.logprobs < 0:
            raise ValueError(f"logprobs must be >= 0, got {self.logprobs}")
        for seq in self.stop:
            if not seq:
                raise ValueError("stop sequences must be non-empty")
        return self

    @property
    def inert(self):
        """True when this request is indistinguishable from bare greedy —
        a batch of such requests is handed the engine's cached blank lanes."""
        return (
            not self.do_sample
            and self.repetition_penalty == 1.0
            and self.min_tokens == 0
            and self.logprobs == 0
        )


def resolve_sampling(obj, default=None):
    """Coerce ``None`` / dict / :class:`SamplingParams` into validated
    params.  ``None`` inherits the engine default (itself derived from the
    engine-wide ``do_sample``/``temperature``/``seed`` config)."""
    if obj is None:
        return default if default is not None else SamplingParams()
    if isinstance(obj, SamplingParams):
        return obj.validate()
    if isinstance(obj, dict):
        allowed = {f.name for f in dataclasses.fields(SamplingParams)}
        unknown = set(obj) - allowed
        if unknown:
            raise ValueError(
                f"unknown sampling params {sorted(unknown)} (allowed: {sorted(allowed)})"
            )
        kw = dict(obj)
        if "stop" in kw:
            stops = kw["stop"]
            if isinstance(stops, (list, tuple)) and stops and isinstance(
                stops[0], (int, np.integer)
            ):
                stops = [stops]  # one bare token-id sequence
            kw["stop"] = tuple(tuple(int(t) for t in s) for s in (stops or ()))
        return SamplingParams(**kw).validate()
    raise ValueError(f"sampling must be a dict or SamplingParams, got {type(obj)!r}")


# --------------------------------------------------------------------------
# lanes: the fixed-shape traced inputs
# --------------------------------------------------------------------------

_LANE_SPECS = (
    # name, dtype, inert default
    ("sample", np.bool_, False),
    ("temp", np.float32, 1.0),
    ("top_k", np.int32, 0),
    ("top_p", np.float32, 1.0),
    ("rep", np.float32, 1.0),
    ("seed", np.int32, 0),
    ("pos", np.int32, 0),
    ("min_tokens", np.int32, 0),
    ("grammar_row", np.int32, 0),
    ("dfa_state", np.int32, 0),
)


def blank_lanes(num_slots, rep_window):
    """All-inert lanes: every slot picks by a bare argmax until
    :func:`set_slot_lane` arms it."""
    lanes = {
        name: np.full((num_slots,), default, dtype=dtype)
        for name, dtype, default in _LANE_SPECS
    }
    lanes["rep_ring"] = np.full((num_slots, rep_window), -1, dtype=np.int32)
    return lanes


def set_slot_lane(lanes, slot, params, pos, grammar_row=0, dfa_state=0, recent=()):
    """Arm one slot from its request state.  ``pos`` is the number of
    output tokens already emitted — the key-derivation position of the
    NEXT token, recomputed from the request on every dispatch so
    preemption/swap cannot desynchronise it.  ``recent`` is the tail of
    the output tokens feeding the repetition-penalty ring."""
    lanes["sample"][slot] = bool(params.do_sample)
    lanes["temp"][slot] = float(params.temperature)
    lanes["top_k"][slot] = int(params.top_k)
    lanes["top_p"][slot] = float(params.top_p)
    lanes["rep"][slot] = float(params.repetition_penalty)
    lanes["seed"][slot] = np.int32(np.uint32(int(params.seed) & 0xFFFFFFFF))
    lanes["pos"][slot] = int(pos)
    lanes["min_tokens"][slot] = int(params.min_tokens)
    lanes["grammar_row"][slot] = int(grammar_row)
    lanes["dfa_state"][slot] = int(dfa_state)
    ring = lanes["rep_ring"]
    ring[slot, :] = -1
    if recent is not None and params.repetition_penalty != 1.0:
        tail = list(recent)[-ring.shape[1] :]
        if tail:
            ring[slot, : len(tail)] = tail


def clear_slot_lane(lanes, slot):
    for name, dtype, default in _LANE_SPECS:
        lanes[name][slot] = dtype(default)
    lanes["rep_ring"][slot, :] = -1


def match_stop(tokens, stop_seqs):
    """Return the length of the stop sequence matched at the tail of
    ``tokens`` (so the caller can trim it), or 0."""
    for seq in stop_seqs:
        n = len(seq)
        if n and len(tokens) >= n and tuple(tokens[-n:]) == tuple(seq):
            return n
    return 0


# --------------------------------------------------------------------------
# traced helpers
# --------------------------------------------------------------------------


def slot_keys(base_key, seed_lane, pos_lane, tag):
    """Per-slot keys for one draw kind: fold the tag (static), then each
    slot's request seed, then its output position."""
    tagged = jax.random.fold_in(base_key, tag)

    def one(seed, pos):
        return jax.random.fold_in(jax.random.fold_in(tagged, seed), pos)

    return jax.vmap(one)(seed_lane, pos_lane)


def categorical_per_slot(keys, logits):
    """One categorical draw per slot, each under its own key (``logits``
    may be unnormalised log-probs)."""
    return jax.vmap(lambda k, l: jax.random.categorical(k, l))(keys, logits).astype(
        jnp.int32
    )


def uniform_per_slot(keys):
    return jax.vmap(lambda k: jax.random.uniform(k, ()))(keys)


def apply_filters(logits, lanes, dfa_state, pos, gmask, eos_id):
    """Everything that reshapes the distribution *before* temperature:
    repetition penalty, grammar allow-mask, min-token eos suppression.
    Greedy slots argmax the result, sampled slots feed it to
    :func:`dist_logprobs`, and the reported logprobs are its plain
    log-softmax — one definition of "the filtered distribution" shared by
    all three consumers.

    ``dfa_state`` is passed separately from ``lanes['dfa_state']``
    because mid-burst / mid-draft steps advance it in-trace; ``pos`` is
    likewise the per-step effective position (``lanes['pos'] + step``).
    """
    num_slots, vocab = logits.shape
    rows = jnp.arange(num_slots)[:, None]
    ring = lanes["rep_ring"]
    present = (
        jnp.zeros((num_slots, vocab), bool)
        .at[rows, jnp.clip(ring, 0, vocab - 1)]
        .max(ring >= 0)
    )
    rep = lanes["rep"][:, None]
    penalized = jnp.where(logits > 0, logits / rep, logits * rep)
    logits = jnp.where(present & (rep != 1.0), penalized, logits)

    mask = gmask[lanes["grammar_row"], dfa_state]
    logits = jnp.where(mask, logits, NEG)

    if eos_id is not None:
        suppress = pos < lanes["min_tokens"]
        logits = logits.at[:, eos_id].add(jnp.where(suppress, NEG, 0.0))
    return logits


def dist_logprobs(filtered, lanes):
    """Per-slot temperature + top-k + top-p over already-filtered logits,
    returned as log-probs in original token order (``NEG`` where cut).
    Both the plain sampled pick and the speculative p/q distributions go
    through here, so draft and target probabilities are filtered by the
    exact same rule — a requirement for the rejection-sampling identity
    to hold."""
    num_slots, vocab = filtered.shape
    scaled = scale_logits(filtered, lanes["temp"][:, None])
    vals, idx = jax.lax.top_k(scaled, vocab)  # full descending sort
    k_eff = jnp.where(lanes["top_k"] <= 0, vocab, lanes["top_k"])
    keep_k = jnp.arange(vocab)[None, :] < k_eff[:, None]
    probs_sorted = jax.nn.softmax(vals, axis=-1)
    csum = jnp.cumsum(probs_sorted, axis=-1)
    # keep every token whose preceding cumulative mass is < top_p — the
    # highest-prob token always survives (its preceding mass is 0)
    keep_p = (csum - probs_sorted) < lanes["top_p"][:, None]
    keep = keep_k & keep_p
    kept = jnp.where(keep, vals, NEG)
    logp_sorted = jax.nn.log_softmax(kept, axis=-1)
    rows = jnp.arange(num_slots)[:, None]
    return (
        jnp.full((num_slots, vocab), NEG, filtered.dtype)
        .at[rows, idx]
        .set(jnp.where(keep, logp_sorted, NEG))
    )


@jax.named_scope("sample")  # the pick and the log-probability harvest
def pick_tokens(logits, lanes, dfa_state, step, gmask, base_key, *, eos_id, logprobs_topn):
    """The per-slot decode-step pick.  Returns ``(tok [S], logp_tok [S],
    top_vals [S,N], top_ids [S,N])`` with ``N = max(logprobs_topn, 1)``
    (zeros when harvesting is off — the shapes must be static).

    One body, three stages.  The filters and the argmax run on every call
    (with inert lanes :func:`apply_filters` changes no value an argmax or
    a softmax can see).  The sampler — :func:`dist_logprobs` and the
    per-slot draw — runs only when some lane samples: free slots hold the
    inert default, so "any lane" is "any live lane", and with one lane
    sampling the whole batch takes it.  The harvest runs when the engine
    was built with ``logprobs_topn > 0``; it reads the filtered logits and
    the served token, never the sampler's distribution, so asking for a
    log-probability does not bring the sampler with it.
    """
    num_slots = logits.shape[0]
    n = max(int(logprobs_topn), 1)
    pos = lanes["pos"] + step

    filtered = apply_filters(logits, lanes, dfa_state, pos, gmask, eos_id)
    greedy = jnp.argmax(filtered, axis=-1).astype(jnp.int32)

    def draw():
        logp_dist = dist_logprobs(filtered, lanes)
        keys = slot_keys(base_key, lanes["seed"], pos, TAG_SAMPLE)
        return categorical_per_slot(keys, logp_dist)

    sampled = jax.lax.cond(jnp.any(lanes["sample"]), draw, lambda: greedy)
    tok = jnp.where(lanes["sample"], sampled, greedy).astype(jnp.int32)
    if logprobs_topn <= 0:
        return (
            tok,
            jnp.zeros((num_slots,), jnp.float32),
            jnp.zeros((num_slots, n), jnp.float32),
            jnp.zeros((num_slots, n), jnp.int32),
        )
    # reported logprobs are the filtered distribution at temperature 1
    # (OpenAI semantics: the model's distribution, not the sampler's)
    lp = jax.nn.log_softmax(jnp.asarray(filtered, jnp.float32), axis=-1)
    logp_tok = jnp.take_along_axis(lp, tok[:, None], axis=1)[:, 0]
    top_vals, top_ids = jax.lax.top_k(lp, n)
    return tok, logp_tok, top_vals, top_ids.astype(jnp.int32)


# --------------------------------------------------------------------------
# speculative rejection sampling
# --------------------------------------------------------------------------


def rejection_accept(d, p, q, u, base_key, seed_lane, pos_lane):
    """Standard speculative-sampling acceptance for the sampled slots of a
    verify round.

    ``d [S, k]`` are the draft tokens, ``p [k+1, S, V]`` the target-model
    probabilities at each draft position (plus the bonus position), ``q
    [k, S, V]`` the draft-model probabilities the tokens were drawn from,
    ``u [S, k]`` the per-position accept uniforms.  Draft token ``j`` is
    accepted while ``u_j < min(1, p_j(d_j) / q_j(d_j))``; the first
    rejection resamples from the clamped residual ``max(p - q, 0)``, and a
    fully-accepted row draws its bonus token from ``p_k``.  The resample /
    bonus draw is keyed at the output position it lands on
    (``pos_lane + accept``, ``TAG_RESAMPLE``), so it is as
    admission-order- and preemption-independent as every other draw.
    Returns ``(accept [S], tok_seq [S, k+1])`` shaped exactly like
    :func:`accelerate_tpu.generation.spec_accept_tokens` so the engine can
    ``where`` the two per slot.

    Grammar masks are already inside ``p`` and ``q`` (both come out of
    :func:`dist_logprobs` over filtered logits), which is what makes the
    verify round re-check the mask: an out-of-language draft has target
    probability 0 and is rejected with certainty, and the residual is
    itself in-language.
    """
    num_slots, k = d.shape
    rows = jnp.arange(num_slots)

    p_d = jnp.stack([p[j, rows, d[:, j]] for j in range(k)], axis=1)
    q_d = jnp.stack([q[j, rows, d[:, j]] for j in range(k)], axis=1)
    ok = u < jnp.minimum(1.0, p_d / jnp.maximum(q_d, 1e-20))
    accept = jnp.where(
        ok.all(axis=1), k, jnp.argmin(ok.astype(jnp.int32), axis=1)
    ).astype(jnp.int32)

    p_a = p[accept, rows]  # [S, V] target dist at the first-reject position
    q_a = q[jnp.minimum(accept, k - 1), rows]
    resid = jnp.clip(p_a - q_a, 0.0, None)
    bonus = (accept == k)[:, None]
    dist = jnp.where(bonus, p_a, resid)
    degenerate = dist.sum(axis=-1, keepdims=True) <= 0.0
    dist = jnp.where(degenerate, p_a, dist)
    resample_keys = slot_keys(base_key, seed_lane, pos_lane + accept, TAG_RESAMPLE)
    corr = categorical_per_slot(resample_keys, jnp.log(dist + 1e-30))

    d_ext = jnp.concatenate([d, jnp.zeros((num_slots, 1), d.dtype)], axis=1)
    j = jnp.arange(k + 1)[None, :]
    a_col = accept[:, None]
    tok_seq = jnp.where(
        j < a_col, d_ext, jnp.where(j == a_col, corr[:, None], 0)
    ).astype(jnp.int32)
    return accept, tok_seq
