"""Continuous-batching inference engine: one compiled decode step, forever.

The static-batch ``generate()`` path compiles a prefill + decode program per
call and every request in the batch waits for the slowest one. This engine
inverts the design for serving (Orca-style iteration scheduling over a
vLLM-style block-paged cache):

* the decode step is **one** pjit-compiled program of static shape
  ``[num_slots, 1]`` against a block-paged KV pool — admitting, evicting,
  or resizing requests never recompiles (asserted by ``stats()``'s
  ``decode_compiles`` counter, which increments only when JAX re-traces);
* prompts are **chunk-prefilled**: ``prefill_chunk`` tokens of one prompt
  per engine iteration, interleaved with the decode step, so a long prompt
  bounds every in-flight request's inter-token latency by one chunk's
  forward instead of a whole prefill;
* KV memory is allocated in ``block_size``-token blocks from a freelist
  (:mod:`.blocks`) — padding waste is bounded by block granularity, and a
  finished short completion's blocks are serving a new request on the next
  iteration;
* **speculative decoding** (``spec_k > 0``): each dispatch becomes one
  compiled spec round — every active slot drafts ``k`` tokens from the
  early-exit draft (the target's own first layers, sharing the target
  pool's leading layers), ONE ``[num_slots, k+1]`` verify forward scores
  all drafts through the fused paged kernel, and the longest agreeing
  prefix + correction emit. Rollback is position bookkeeping only, so the
  one-executable contract and greedy token parity both survive;
* **double-buffered dispatch** (``async_dispatch``, the default): the
  decode round handed off at iteration *i* is harvested at iteration
  *i+1*, so admission, block growth, radix lookups, deadline sweeps, and
  lane edits run WHILE the device computes — the host leaves the
  per-token critical path (ROADMAP item 5) and ``device_wait`` shrinks to
  the residual sync the host could not hide. Dispatch *i+1* still happens
  strictly after harvest *i*, so output is token-identical to the
  synchronous loop (``async_dispatch=False`` / ``serve --sync-engine``);
* **block rounds** (a model that declares ``block_decode``, one that
  generates by diffusion over blocks): the one decode executable is the
  round — each slot's next block of ``B`` positions is filled with the
  mask token, unmasked over ``denoise_steps`` forwards of static shape
  ``[num_slots, B]`` and committed by one more over the clean block, so a
  slot advances by whole blocks and a round emits up to ``B`` tokens a
  slot (:meth:`InferenceEngine._build_block_decode_fn`);
* **per-slot sampling + constrained decoding**: temperature / top-k /
  top-p / repetition penalty / seed / grammar-DFA state ride as
  fixed-shape *lane inputs* of the same ONE decode executable
  (:mod:`.sampling`, :mod:`.grammar`) — per-request
  variation never recompiles, the sampler runs under a ``lax.cond`` only
  when some slot samples (greedy slots are served the bit-identical
  argmax either way), and the spec verify round accepts sampled slots by
  rejection sampling.

Sampling/eos semantics share one traced picker with ``generation.py``
(:func:`accelerate_tpu.generation.pick_next_token`), so greedy engine
output is token-for-token identical to ``generate(use_cache=True)`` — and
the spec round's greedy acceptance reuses
:func:`accelerate_tpu.generation.spec_accept_tokens`, so greedy slots of
the spec-armed engine stay token-identical to the non-spec engine.
"""

from __future__ import annotations

import itertools
import time
import warnings
from collections import OrderedDict, deque
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.sanitizer import get_active_sanitizer as _get_sanitizer
from ..diagnostics.tracing import (
    ensure_trace_id,
    get_tracer,
    span_enter,
    span_exit,
    trace_span,
    valid_trace_id,
)
from ..metrics.ingest import observe_flight
from ..metrics.registry import get_active_registry
from ..models.cache import cache_spec_of
from ..ops.paged_attention import (
    default_paged_attention_impl,
    tile_entries,
    tiles_walked,
    window_walk,
)
from ..telemetry import get_active_recorder
from .blocks import NULL_BLOCK, BlockAllocator, blocks_needed
from .flight import ITERATION_PHASES, PART_NAMES, FlightRecorder, set_active_flight_recorder
from .grammar import compile_grammar
from .radix import RadixCache, SwapPool
from .sampling import (
    TAG_ACCEPT,
    TAG_DRAFT,
    SamplingParams,
    apply_filters,
    blank_lanes,
    categorical_per_slot,
    dist_logprobs,
    match_stop,
    pick_tokens,
    rejection_accept,
    resolve_sampling,
    set_slot_lane,
    slot_keys,
    uniform_per_slot,
)
from .scheduler import Request, RequestState, SlotScheduler, priority_rank
from .usage import UsageLedger, normalize_tenant


@dataclass
class EngineConfig:
    """Engine geometry. ``num_blocks`` defaults to full residency
    (``num_slots`` × the per-slot maximum + the null block) — set it lower
    to exercise freelist contention."""

    num_slots: int = 8
    block_size: int = 16
    #: per-request cap on prompt + generated tokens; also sizes the block
    #: table width (``ceil(max_seq_len / block_size)`` entries per slot)
    max_seq_len: int = 512
    num_blocks: int | None = None
    prefill_chunk: int = 32
    eos_token_id: int | None = None
    do_sample: bool = False
    temperature: float = 1.0
    seed: int = 0
    #: default budget for add_request(max_new_tokens=None)
    max_new_tokens: int = 64
    #: decode steps per dispatch of the (single) compiled decode program —
    #: a ``lax.scan`` of this many ``[num_slots, 1]`` steps. Amortises the
    #: per-dispatch host round trip (the same move generation.py's
    #: ``_EOS_CHUNK`` makes) at the cost of scheduling granularity:
    #: admission/prefill interleave every ``decode_burst`` tokens, and a
    #: request finishing mid-burst wastes at most ``decode_burst - 1``
    #: lane-steps. 1 = schedule every token.
    decode_burst: int = 8
    #: double-buffered dispatch (ROADMAP item 5, the async engine core):
    #: ``step()`` hands round *i* to the device and returns WITHOUT
    #: waiting; round *i*'s tokens are harvested at iteration *i+1*'s
    #: harvest point, AFTER the host has already run iteration *i+1*'s
    #: scheduling work (admission, block growth/CoW, radix lookups,
    #: deadline sweeps, sampling-lane edits) under the in-flight round.
    #: Output stays token-identical to the synchronous loop — dispatch
    #: *i+1* still happens strictly after harvest *i*, so every decode
    #: input (fed token, position, lanes, DFA rows) is byte-identical;
    #: only the host's position relative to the device moves. ``False``
    #: restores the fully synchronous loop (``serve --sync-engine`` /
    #: ``ACCELERATE_SYNC_ENGINE=1``) — the escape hatch and the baseline
    #: ``benchmarks/async_smoke.py`` compares against.
    async_dispatch: bool = True
    #: emit a telemetry "serving" row every N iterations (0 disables)
    stats_interval: int = 32
    #: per-iteration flight recorder ring size (0 disables): every
    #: iteration's wall time decomposed into exclusive phases (schedule /
    #: prefill / dispatch / device_wait / harvest) whose durations are
    #: asserted to sum to the measured wall time — the host-vs-device
    #: attribution ``stats()['host_fraction']``, ``trace tail
    #: --iterations``, ``/profile`` windows, and HANG_REPORT forensics
    #: all read. One perf_counter read per phase boundary (and one
    #: ``time.time_ns()`` per iteration, the anchor on the profiler's
    #: clock); a named part of a phase (``prefill/operands``,
    #: ``dispatch/call``, ...: ``flight.ITERATION_PARTS``) is one more read
    #: where it opens or closes between two phase boundaries; disabled, the
    #: boundaries read no clock and only open the ``serve/<phase>`` and
    #: ``serve/<phase>/<part>`` spans.
    flight_history: int = 256
    #: finished :class:`Request` objects retained for ``stats()``
    #: percentiles — a *ring*, not a list: a long-lived serve process must
    #: not leak every completed request (nor rescan an unbounded history
    #: O(n) per stats() call). Cumulative counts stay exact through
    #: ``completed_total``; the percentile window is the newest this-many
    #: completions.
    completed_history: int = 4096
    #: per-device HBM budget in GiB; when set, the engine runs the
    #: shard-check pre-flight BEFORE allocating anything and refuses to
    #: start (ValueError naming SP004) if params + the paged pools exceed
    #: it — the capacity-planning contract: fail at bring-up, not OOM
    #: mid-request
    hbm_budget_gb: float | None = None
    #: radix prefix sharing (:mod:`.radix`): admission maps a request's
    #: longest cached prompt prefix into its block table at refcount+1 and
    #: chunk-prefills only the tail; finished prompts' full blocks stay
    #: cached (LRU-evicted under pool pressure). Sharing edits only block
    #: tables and refcounts — the one-compiled-executable contract holds.
    prefix_cache: bool = True
    #: host-DRAM swap tier in GiB (0 disables): under pool exhaustion the
    #: lowest-priority victim's unshared blocks are device_get-swapped to
    #: a :class:`~.radix.SwapPool` and the request re-queues at the front
    #: of its class; ``finish_reason="out_of_blocks"`` truncation becomes
    #: the last resort for when even swap capacity is gone.
    swap_gb: float = 0.0
    #: KV pool storage policy — decode is memory-bandwidth-bound, so the
    #: pool's dtype is the direct lever on both bytes-per-decode-step and
    #: how many blocks (slots) an HBM budget holds. ``"auto"`` stores in
    #: the params' compute dtype (the PR 4 behaviour); ``"bf16"``/``"f32"``
    #: force a float width; ``"int8"``/``"fp8"`` quantize on scatter with
    #: per-row amax scales riding beside the pool (``ops/fp8.py``) and
    #: dequantize in-register inside the fused paged-attention kernel.
    #: Scale arrays follow every pool edit — copy-on-write, swap-out/in,
    #: radix adoption — and the one-compiled-decode-executable contract
    #: holds at every setting (scales are just two more donated pool
    #: operands of the same single executable).
    kv_dtype: str = "auto"
    #: storage policy of the per-slot state of a model that keeps one
    #: (``models/cache.py``): ``"auto"`` stores every leaf as the model's
    #: spec declares it; ``"bf16"`` stores the leaves the spec keeps at a
    #: precision of their own (a recurrent state) at that width — half the
    #: bytes a slot holds and a decode step streams, at the cost
    #: of one more rounding of the state per decoded token (the arithmetic
    #: stays float32). Refused for a model that keeps no such state.
    state_dtype: str = "auto"
    #: speculative decoding (0 = off, the plain burst decode). ``spec_k > 0``
    #: replaces the decode step with ONE compiled spec round per dispatch:
    #: every active slot drafts ``spec_k`` tokens from the cheap draft, a
    #: single ``[num_slots, spec_k+1]`` verify forward scores all drafts
    #: through the fused paged-attention kernel, and the longest agreeing
    #: prefix + the target's correction are emitted (greedy acceptance is
    #: exact — output stays token-identical to the non-spec engine).
    #: Rejected drafts are rolled back purely by position bookkeeping: the
    #: next round re-writes those pool rows and attention never reads past
    #: each slot's valid prefix, so no pool edit beyond the normal scatter
    #: happens at any kv_dtype. ``decode_burst`` is ignored while armed —
    #: one spec round already amortises the host round trip over up to
    #: ``spec_k + 1`` tokens. Sampled slots verify by rejection sampling
    #: (accept draft token with prob min(1, p_target/p_draft), resample
    #: the clamped residual otherwise) while greedy slots keep the exact
    #: longest-agreeing-prefix path — so speculation composes with
    #: ``do_sample``.
    spec_k: int = 0
    #: denoise passes of a block round (``None``: the block's length, one
    #: token a pass), for a model that declares ``block_decode``
    #: (``models/cache.py:BlockDecode``): pass ``t`` fixes the still-masked
    #: positions of sub-block ``t`` of the ``denoise_steps`` equal sub-blocks
    #: of a block, left to right, each to the pick of its own logits; one
    #: more forward over the clean block then commits it. Fewer passes are
    #: fewer forwards a token and a coarser conditioning (a sub-block's
    #: tokens are picked without seeing each other). Must divide the block's
    #: length; refused for a model that decodes one token a step.
    denoise_steps: int | None = None
    #: draft policy when ``spec_k > 0`` (see :mod:`.spec`):
    #: ``"early_exit:N"`` runs the target's own first N layers (+ its final
    #: norm/head) as the draft, reading/writing the FIRST N LAYERS of the
    #: target's paged pool — identical weights make the draft's K/V a
    #: strict subset of the target's, so prefix sharing, copy-on-write and
    #: swap preemption maintain the draft state with zero extra machinery.
    draft: str = "early_exit:2"
    #: top-N per-step logprobs harvested through the existing device_get
    #: (0 disables — the harvest shape is static, so this is engine
    #: geometry; requests opt in *up to* this cap). Unsupported with
    #: ``spec_k > 0``.
    logprobs_topn: int = 0
    #: concurrent distinct grammars resident in the device mask/transition
    #: tables (+1 internal row for the unconstrained sentinel). Rows are
    #: refcounted per live request and LRU-cached when idle; admission
    #: with every row held by a live request raises.
    grammar_slots: int = 4
    #: DFA state budget per grammar — sizes the device tables; a grammar
    #: compiling to more states refuses at add_request
    grammar_states: int = 64
    #: repetition-penalty window: the last this-many generated tokens ride
    #: the ``[num_slots, rep_window]`` ring lane
    rep_window: int = 32
    #: per-request resource attribution (:mod:`.usage`): every request
    #: accrues measured decode/prefill device-seconds, KV block-seconds,
    #: swap bytes, spec and grammar counts, rolled up by tenant and
    #: priority class with conservation asserted against the engine's own
    #: ``device_wait`` and pool-occupancy totals. ``False`` removes the
    #: ledger entirely — the disabled path is one truthiness check per
    #: iteration (the telemetry/flight discipline).
    usage_accounting: bool = True

    @property
    def blocks_per_slot(self) -> int:
        return blocks_needed(self.max_seq_len, self.block_size)


@dataclass
class _InFlightRound:
    """One dispatched-but-unharvested decode round (double-buffered
    dispatch). Holds the device *futures* the dispatch returned — nothing
    here has been device_get: the harvest's single blocking transfer is
    deferred until the next iteration's harvest point (or a fence). The
    ``live`` list is the dispatch-order request batch; slots cannot be
    reassigned while a round is in flight (eviction only touches FINISHED
    requests, and members only finish at harvest), so ``req.slot`` still
    indexes the result arrays when the harvest lands."""

    kind: str  # "burst" | "spec" | "block"
    live: list
    #: [burst, slots] next-token future; [slots, k+1] of a spec round;
    #: [burst, slots, B] of block rounds
    toks: object
    accept: object = None  # [slots] accepted-prefix lengths (spec only)
    logps: object = None
    tvals: object = None
    tids: object = None
    harvest_lp: bool = False
    #: the model's step counters of this round, ``{name: [burst, ...]}``
    counters: object = None
    #: block rounds: ``[slots]``, the positions of each slot's open block that
    #: were known (a prompt's tail) when the dispatch was built: no round's to emit
    known: object = None


#: what the engine counts of its block rounds (``_block_stats`` derives the
#: forwards from the rounds: a round is ``denoise_steps`` + 1 of them)
_BLOCK_TOTALS = (
    "block_rounds_total", "block_slot_forwards_total",
    "block_positions_committed_total", "block_tokens_emitted_total",
)

#: keys of a flight entry that place it on a clock; the ``serve/flight``
#: Chrome instant carries the rest (its own ``ts`` places it, and the
#: phases are ``serve/<phase>`` events of their own in that file)
_FLIGHT_CLOCK_KEYS = frozenset({"t_start", "t_start_unix_ns", "intervals", "parts"})

#: the span names of an iteration and of its phases (children of it), as a
#: profiler capture and the Chrome trace show them
_ITERATION_SPAN = "serve/iteration"
_PHASE_SPANS = {phase: "serve/" + phase for phase in ITERATION_PHASES}
#: and of the named parts of a phase (``flight.ITERATION_PARTS``), children of
#: the phase's span
_PART_SPANS = {name: "serve/" + name for name in PART_NAMES}


def _under_mesh(apply_fn, mesh):
    """``apply_fn`` traced with ``mesh`` on the attention context: that is
    where the paged Pallas kernel finds the head axis it must be
    partitioned over (``ops/paged_attention.py`` — GSPMD cannot split a
    Mosaic call, and JAX refuses to lower a bare one on a sharded mesh)."""
    if mesh is None:
        return apply_fn
    from ..ops.attention import attention_context

    def apply(params, **kw):
        with attention_context(mesh=mesh):
            return apply_fn(params, **kw)

    return apply


def _abstract(x):
    """Shape, dtype and sharding of one dispatched operand."""
    return jax.ShapeDtypeStruct(
        np.shape(x), x.dtype, sharding=getattr(x, "sharding", None)
    )


class InferenceEngine:
    """Slot-scheduled continuous-batching engine over a paged-KV model.

    ``add_request()`` enqueues; ``step()`` runs one scheduler iteration
    (evict → admit → one prefill chunk → one decode step) and returns the
    requests that finished; ``run_until_idle()`` drains; ``stream()`` is a
    per-request generator. The model must declare ``supports_paged_kv``
    (the block-table decode path in its apply fn).

    ``mesh=`` shards the ONE decode executable over the named mesh with
    GSPMD ``NamedSharding`` rules (the same planner training uses): params
    by the model's partition rules + FSDP policy, the paged block pool by
    kv-head over ``tp``, scheduler state replicated. Host-side scheduling
    is untouched — sharding is a placement decision, never a different
    program, so greedy output stays token-identical to the single-device
    engine and the one-executable contract keeps holding."""

    def __init__(self, model, config: EngineConfig | None = None, mesh=None):
        self.config = cfg = config or EngineConfig()
        inner = getattr(model, "_model", None) or model
        if not getattr(inner, "supports_paged_kv", False):
            raise ValueError(
                f"model {getattr(inner, 'name', type(inner).__name__)!r} does not "
                "declare supports_paged_kv: the engine needs the block-table "
                "KV decode path (models/llama.py _llama_paged_step)"
            )
        self._apply_fn = _under_mesh(inner.apply_fn, mesh)
        self._params = model.params
        mcfg = inner.config
        if cfg.max_seq_len > mcfg.max_position_embeddings:
            raise ValueError(
                f"max_seq_len {cfg.max_seq_len} exceeds the model's "
                f"max_position_embeddings {mcfg.max_position_embeddings}"
            )
        if min(cfg.prefill_chunk, cfg.block_size, cfg.num_slots, cfg.decode_burst) < 1:
            raise ValueError(
                "prefill_chunk, block_size, num_slots, decode_burst must be >= 1"
            )

        # a model that keeps per-slot state beside its blocks: what assumes
        # that blocks are the whole of a request's past is refused here,
        # like every other geometry error (the prefix cache, which defaults
        # to on, is left out below with its reason in stats())
        spec = cache_spec_of(inner).with_state_dtype(cfg.state_dtype)
        if spec.slot_state:
            name = getattr(inner, "name", type(inner).__name__)
            kept = f"per-slot state ({', '.join(spec.slot_state)}) in {spec.state_layers} layers"
            for armed, what, missing in (
                (cfg.swap_gb and cfg.swap_gb > 0, f"swap_gb={cfg.swap_gb}",
                 "_swap_out moves blocks only, and a request swapped back in would "
                 "resume from a zeroed state"),
                (cfg.spec_k, f"spec_k={cfg.spec_k}",
                 "a rejected draft token is rolled back by position alone, and the "
                 "state has already moved past it"),
                (mesh is not None, "mesh=",
                 "_place_on_mesh has no placement for the slot state"),
            ):
                if armed:
                    raise ValueError(
                        f"{what} is not supported for {name!r}, which keeps {kept}: "
                        f"{missing} (ROADMAP Reach 5)"
                    )

        # a latent pool (one vector a token for every head, models/cache.py):
        # blocks are still the whole of a request's past, so the prefix cache,
        # copy-on-write and preemption by recompute work on them as they are;
        # what reads a K and a V row per kv head is refused with its reason
        if spec.latent_rank:
            name = getattr(inner, "name", type(inner).__name__)
            kept = (f"one latent vector of {spec.head_dim} values a token and layer "
                    f"(stored {spec.pool_width} wide) for all heads")
            for armed, what, missing in (
                (cfg.swap_gb and cfg.swap_gb > 0, f"swap_gb={cfg.swap_gb}",
                 "the host swap pool mirrors a K and a V row per kv head, and this pool "
                 "has neither; a preempted request is recomputed instead"),
                (mesh is not None, "mesh=",
                 "the pool has no kv head to shard over tp, and a replicated latent pool "
                 "under tp-sharded query heads is not placed yet (ROADMAP Reach 3)"),
                (cfg.kv_dtype == "int8", "kv_dtype=int8",
                 "one amax scale a row would cover the compressed entries and the rotated "
                 "key together in 255 steps; fp8 (ops/fp8.py, one scale a row) is the "
                 "quantized latent pool that is built"),
            ):
                if armed:
                    raise ValueError(f"{what} is not supported for {name!r}, which keeps "
                                     f"{kept}: {missing}")

        # layers that keep a window of the past beside layers that keep all of
        # it (models/cache.py:PagedKind): a request's blocks are no longer its
        # whole past in every layer, and what assumes they are is refused
        # here (the prefix cache is left out below with its reason in stats())
        if spec.window_kinds:
            name = getattr(inner, "name", type(inner).__name__)
            kept = ", ".join(f"{k.layers} layers that keep a window of {k.window} positions"
                             for k in spec.window_kinds)
            for armed, what, missing in (
                (cfg.swap_gb and cfg.swap_gb > 0, f"swap_gb={cfg.swap_gb}",
                 "_swap_out mirrors one pool's blocks, and a request swapped back in would "
                 "find its window kind's blocks gone; a preempted request is recomputed "
                 "instead"),
                (cfg.spec_k, f"spec_k={cfg.spec_k}",
                 "the early-exit draft reads the first layers of ONE pool, and a rejected "
                 "draft's rollback by position does not give back the window blocks the "
                 "round's lookahead freed behind it"),
                (mesh is not None, "mesh=",
                 "_place_on_mesh places one pool's leaves; the window kind's pool and its "
                 "table have no placement yet (ROADMAP Reach 4)"),
            ):
                if armed:
                    raise ValueError(f"{what} is not supported for {name!r}, which has "
                                     f"{kept}: {missing}")

        # a model that generates by diffusion over blocks: the decode
        # executable is the block round, and what cannot hold under parallel
        # unmasking is refused here or at add_request, the reasons in stats()
        blk = getattr(inner, "block_decode", None)
        self._block = blk if blk is not None and blk.block_length > 1 else None
        self._denoise_steps = 0
        self.block_round_refuses: dict = {}
        if self._block is None and cfg.denoise_steps is not None:
            raise ValueError(
                f"denoise_steps={cfg.denoise_steps} for a model that decodes one token "
                "a step: only a model that declares block_decode has denoise passes"
            )
        if self._block is not None:
            self._init_block_rounds(inner, mesh)

        # speculative decoding (spec_k > 0): parse the draft policy and
        # bind the early-exit draft apply BEFORE anything allocates — a bad
        # spec must refuse at bring-up, like every other geometry error
        self._spec = None
        self._draft_apply = None
        if cfg.spec_k:
            if cfg.spec_k < 1:
                raise ValueError("spec_k must be >= 1 (0 disables speculation)")
            if cfg.logprobs_topn:
                raise ValueError(
                    "logprobs_topn with spec_k > 0 is not supported: the "
                    "verify round emits a variable accepted prefix, so there "
                    "is no per-step harvest to ride — set spec_k=0 for "
                    "logprobs"
                )
            from .spec import parse_draft_spec

            self._spec = parse_draft_spec(cfg.draft, mcfg.num_hidden_layers)
            factory = getattr(inner, "early_exit_apply", None)
            if factory is None:
                raise ValueError(
                    f"model {getattr(inner, 'name', type(inner).__name__)!r} "
                    "declares no early_exit_apply factory: the spec_k engine "
                    "needs the early-exit draft path (models/llama.py "
                    "llama_early_exit_apply)"
                )
            self._draft_apply = _under_mesh(factory(self._spec.layers), mesh)
        #: cache positions one decode dispatch may write past context_len,
        #: which is the block-growth lookahead. The burst writes the fed
        #: token's K/V at context_len and each later step's one further on:
        #: decode_burst positions. A spec round writes the pending token and
        #: its k drafts: k + 1 positions, of which the next round writes the
        #: rejected tail again. Block rounds write whole blocks from
        #: context_len (a block boundary: every known token is committed but
        #: the open block's own): decode_burst rounds of block_length each
        if self._spec:
            self._decode_lookahead = cfg.spec_k + 1
        elif self._block is not None:
            self._decode_lookahead = cfg.decode_burst * self._block.block_length
        else:
            self._decode_lookahead = cfg.decode_burst

        # per-slot sampling + grammar state (the lanes). The engine-wide
        # do_sample/temperature/seed are the DEFAULT SamplingParams a
        # request inherits when it supplies none.
        if min(cfg.logprobs_topn, cfg.grammar_slots, cfg.rep_window - 1,
               cfg.grammar_states - 1) < 0:
            raise ValueError(
                "logprobs_topn/grammar_slots must be >= 0; "
                "rep_window/grammar_states must be >= 1"
            )
        self._default_sampling = SamplingParams(
            do_sample=cfg.do_sample, temperature=cfg.temperature,
            seed=cfg.seed,
        ).validate()
        if cfg.do_sample:
            warnings.warn(
                "EngineConfig(do_sample=True) + temperature are superseded by "
                "per-request sampling params: they now only set the default "
                "SamplingParams a request inherits when it supplies none "
                "(every sampled draw uses a key derived per slot from the "
                "request's seed and output position)",
                stacklevel=2,
            )
        self._vocab_size = int(mcfg.vocab_size)
        self._sampled_greedy = 0
        self._sampled_sample = 0
        self._grammar_masked_steps = 0
        self._rej_drafted = 0
        self._rej_accepted = 0
        # grammar row table: row 0 is the permanently-pinned unconstrained
        # sentinel (mask all-True, transitions all-0); rows 1..G-1 are
        # refcounted per live request, cached under their grammar hash
        # when idle, LRU-evicted when a new grammar needs a row
        self._grammar_rows: dict[str, int] = {}
        self._row_refs = [0] * (cfg.grammar_slots + 1)
        self._row_grammar: dict[int, object] = {}
        self._row_lru: OrderedDict[str, int] = OrderedDict()

        self._mb = cfg.blocks_per_slot  # block-table width
        # explicit is-None test: an explicit num_blocks=0 must reach the
        # allocator's >= 2 guard, not be silently rewritten to full residency
        num_blocks = (
            cfg.num_blocks if cfg.num_blocks is not None
            else cfg.num_slots * self._mb + 1
        )

        # device state, as the model's cache spec declares it (models/cache.py):
        # the stacked page pools of the layers that attend, in the kv_dtype
        # policy's storage dtype ("auto" = the params' compute dtype, the PR 4
        # behaviour; int8/fp8 add per-row amax scale arrays beside them),
        # stored lane-folded — [paged layers, num_blocks, block_size,
        # n_kv*hd], the view the paged kernel reads, so no step program
        # relayouts it — and, where the model keeps one, a fixed-size state
        # per slot ([layers, num_slots, ...]: a recurrent state, a
        # convolution's tail), which has no blocks
        self._cache_spec = spec
        n_kv = spec.kv_heads
        self._kv_heads = n_kv
        #: query heads: with the kv heads, the stacked rows of a paged call
        self._query_heads = int(getattr(mcfg, "num_attention_heads", n_kv))
        embed = jax.tree.leaves(self._params)[0]
        dtype = embed.dtype if jnp.issubdtype(embed.dtype, jnp.floating) else jnp.float32
        if cfg.kv_dtype in (None, "auto"):
            store_dtype, quantized = dtype, False
        else:
            from ..ops.fp8 import kv_storage_dtype

            store_dtype, quantized = kv_storage_dtype(cfg.kv_dtype)
        self._quantized = quantized
        self.kv_dtype = str(np.dtype(store_dtype))
        #: the paged kinds (models/cache.py): the first is the one num_blocks
        #: counts, the scheduler admits by and every model of one kind has;
        #: a kind that keeps a window gets a pool of its own, sized so that
        #: every slot's window is resident (num_slots x its blocks a slot +
        #: the null block: nothing to admit by, nothing to preempt for)
        self._kinds = spec.paged_kinds
        self._window_kinds = spec.window_kinds
        lookahead = max(cfg.prefill_chunk, self._decode_lookahead)
        #: ``{kind name: blocks}``: a window kind's most blocks a slot, and its pool's
        pools = spec.window_pools(cfg.num_slots, cfg.max_seq_len, cfg.block_size, lookahead)
        self.window_blocks_per_slot = {name: n[0] for name, n in pools.items()}
        self.window_num_blocks = {name: n[1] for name, n in pools.items()}
        shape = (self._kinds[0].layers, num_blocks, cfg.block_size, spec.pool_width)
        scale_shape = (shape[0], num_blocks, cfg.block_size, n_kv)
        #: bytes one cached token costs across the layers that hold paged KV
        #: (K + V payload, or a latent pool's one stored row, plus the f32
        #: scales when quantized) — the decode-bandwidth and slot-capacity
        #: headline number
        self.kv_bytes_per_token = spec.bytes_per_token(store_dtype, quantized)
        #: bytes of the window kinds' pools: a fixed cost beside the
        #: parameters (priced by what a slot keeps resident, not by num_blocks)
        self.window_pool_bytes = spec.window_pool_bytes(
            cfg.num_slots, cfg.max_seq_len, cfg.block_size, lookahead, store_dtype, quantized)
        #: bytes of per-slot state one slot costs, whatever its sequence's
        #: length (0 for a model whose blocks are all of a request's past)
        self.state_bytes_per_slot = spec.state_bytes_per_slot(dtype)
        #: max-length requests the pool holds concurrently (num_blocks is
        #: fixed for the engine's lifetime — computed once, reported by
        #: stats() and every telemetry step row); a slot-state model also
        #: holds no more requests than it has slots of state
        self.kv_slot_capacity = (num_blocks - 1) // cfg.blocks_per_slot
        if spec.slot_state:
            self.kv_slot_capacity = min(self.kv_slot_capacity, cfg.num_slots)
        self.hbm_preflight: dict | None = None
        if cfg.hbm_budget_gb is not None:
            self._hbm_preflight(inner, shape, n_kv, store_dtype, mesh)

        self.allocator = BlockAllocator(num_blocks)
        #: why no prefix cache was built although one was asked for (None:
        #: it was built, or not asked for)
        self.prefix_cache_off_reason = None
        if cfg.prefix_cache and spec.window_kinds:
            self.prefix_cache_off_reason = (
                f"{sum(k.layers for k in spec.window_kinds)} layers keep a window of the "
                "past and give back the blocks behind it: a block-boundary hit would map "
                "the full kind's K/V and nothing for the window kind (a prefix cache over "
                "freed window blocks is not built, ROADMAP Reach 4)"
            )
        elif cfg.prefix_cache and spec.slot_state:
            self.prefix_cache_off_reason = (
                f"the model keeps per-slot state ({', '.join(spec.slot_state)}) in "
                f"{spec.state_layers} layers: a block-boundary hit would map K/V for "
                f"{spec.paged_layers} layers and no state for the rest (state "
                "snapshots at block boundaries are not built, ROADMAP Reach 5)"
            )
        self.radix = (
            RadixCache(self.allocator, cfg.block_size)
            if cfg.prefix_cache and not spec.slot_state and not spec.window_kinds else None
        )
        #: ``{kind name: allocator}`` of the window kinds' pools
        self.window_allocators = {
            name: BlockAllocator(n) for name, n in self.window_num_blocks.items()}
        self._swap = (
            SwapPool(
                num_layers=shape[0], block_size=cfg.block_size,
                num_kv_heads=n_kv, head_dim=spec.head_dim,
                dtype=store_dtype, capacity_gb=cfg.swap_gb,
                quantized=quantized,
            )
            if cfg.swap_gb and cfg.swap_gb > 0
            else None
        )
        #: per-request usage ledger (None = disabled: every hot-path hook
        #: site pays one truthiness check and nothing else)
        self.usage = UsageLedger() if cfg.usage_accounting else None
        self.scheduler = SlotScheduler(
            cfg.num_slots, self.allocator, cfg.block_size, cfg.max_seq_len,
            radix=self.radix, usage=self.usage,
            prefix_granule=self._block.block_length if self._block else 1,
            window_allocators=self.window_allocators,
        )
        #: everything the step programs keep for the sequences, in ONE dict
        #: that every one of them takes donated and hands back whole: "k" /
        #: "v" (a latent spec: "k" alone), and "k_scale" / "v_scale" beside
        #: them (all-ones so that a never-written row dequantizes to exactly
        #: 0), then the model's slot-state leaves
        self._cache = {name: jnp.zeros(shape, store_dtype) for name in spec.pool_leaves}
        if quantized:
            self._cache.update({name + "_scale": jnp.ones(scale_shape, jnp.float32)
                                for name in spec.pool_leaves})
        #: the pool's arrays by name, payload and scales: what a block-granular
        #: edit (copy-on-write) has to touch, all of it
        self._pool_arrays = tuple(self._cache)
        # a further kind's pool (and scales) under its own names, beside the
        # first's: the model's step reads each by name
        for n, kind in enumerate(self._kinds[1:], start=1):
            k_shape = (kind.layers, self.window_num_blocks[kind.name], cfg.block_size,
                       kind.kv_heads * kind.head_dim)
            for leaf in ("k", "v"):
                self._cache[kind.pool_leaf(leaf, False)] = jnp.zeros(k_shape, store_dtype)
                if quantized:
                    self._cache[kind.pool_leaf(leaf + "_scale", False)] = jnp.ones(
                        (*k_shape[:3], kind.kv_heads), jnp.float32)
        for name, leaf in spec.slot_state.items():
            self._cache[name] = jnp.zeros(
                leaf.array_shape(cfg.num_slots), leaf.dtype or dtype)
        self._state_resets = 0
        #: small int32 counters a model's step hands back beside its logits
        #: (``ModelOutput.step_counters``, declared by name and shape as
        #: ``model.step_counter_shapes``; a routed model's per-expert pairs):
        #: fetched in the harvest's one transfer and summed here, by name.
        #: A prefill chunk's wait, as device futures, for the next harvest;
        #: only the engine's thread touches either (``stats()`` just reads)
        self._step_counters = {
            name: np.zeros(shape, np.int64)
            for name, shape in (getattr(inner, "step_counter_shapes", None) or {}).items()}
        self._pending_counters: list = []
        #: what the model says of itself for ``stats()`` (fixed numbers)
        self._model_stats = dict(getattr(inner, "serve_stats", None) or {})
        #: per-slot draw root: never split/threaded — every draw derives
        #: from it by fold_in(tag, request seed, output position), which is
        #: what makes (seed, prompt) reproducible across admission orders
        #: and preempt/swap/resume (sampling.slot_keys)
        self._base_key = jax.random.PRNGKey(cfg.seed)
        g = cfg.grammar_slots + 1
        self._gmask = jnp.ones((g, cfg.grammar_states, self._vocab_size), bool)
        self._gtrans = jnp.zeros(
            (g, cfg.grammar_states, self._vocab_size), jnp.int32
        )
        #: device-committed all-inert lane dict, built lazily: the
        #: all-greedy dispatch fast path reuses these buffers verbatim, so
        #: plain traffic never pays the per-iteration lane rebuild/upload
        self._lanes_idle = None
        self.mesh = mesh
        if mesh is not None:
            self._place_on_mesh(inner)

        # host mirrors the compiled step reads every iteration. A program
        # is handed a COPY of the block tables, never the mirror: the CPU
        # backend takes an aligned numpy operand without copying it, and a
        # chunk or a round still in flight would read the rows the host
        # rewrites for the next one (_sync_block_table zeroes a row first)
        # (a table a kind where the model has more than one: [slots, kinds, mb])
        self._block_tables = np.zeros(
            (cfg.num_slots, self._mb) if len(self._kinds) == 1
            else (cfg.num_slots, len(self._kinds), self._mb), np.int32)
        self._pending_tok = np.zeros((cfg.num_slots,), np.int32)

        # counters (the *_traces counters increment inside the traced
        # bodies, i.e. only on a jit cache miss — the "exactly one decode
        # executable" acceptance bar reads decode_compiles)
        self._decode_traces = 0
        self._prefill_traces = 0
        # one-executable watchdog state: the abstract signature of every
        # decode dispatch, so a second trace can NAME the argument whose
        # shape/dtype drifted (analysis/compiled.py fingerprint diff) —
        # with the sanitizer armed the re-trace raises immediately
        self._decode_sig: tuple | None = None
        self._decode_traces_seen = 0
        self.retrace_report: str | None = None
        self._iterations = 0
        self._tokens_emitted = 0
        self._occupancy_sum = 0.0
        self._start_time: float | None = None
        # bounded completion history (percentile window) + exact totals:
        # the ring caps memory and stats() cost on a long-lived server
        # while completed_total keeps counting past the cap
        self._completed: deque[Request] = deque(
            maxlen=max(1, int(cfg.completed_history))
        )
        self._completed_total = 0
        #: per-iteration request tracer (None when tracing is disabled —
        #: refreshed by ONE get_tracer() read at the top of step())
        self._tr = None
        self._last_stats_t: float | None = None
        self._last_stats_tokens = 0
        # sharing / preemption counters (reset_stats zeroes them with the
        # rest of the measurement state; the radix cache itself stays warm)
        self._preemptions = 0
        self._swapped_out_blocks = 0
        self._swapped_in_blocks = 0
        self._out_of_blocks_total = 0
        self._deadline_expired = 0
        # speculative accounting (accept rate = accepted / drafted):
        # drafted counts spec_k per live lane per round, accepted the
        # verify-agreed prefix length (the correction token is free and
        # counted in neither)
        self._spec_drafted = 0
        self._spec_accepted = 0
        # per-iteration flight recorder (None = disabled: step() pays one
        # `is None` check and nothing else). Registered process-globally
        # so the watchdog's HANG_REPORT and the /profile dump can reach
        # the ring without holding an engine reference.
        self._flight = (
            FlightRecorder(cfg.flight_history) if cfg.flight_history else None
        )
        if self._flight is not None:
            set_active_flight_recorder(self._flight)
        # double-buffered dispatch state: the round handed to the device
        # last iteration and not yet harvested (None = nothing in flight),
        # plus the parking list a mid-schedule fence (swap-out) harvests
        # into — drained into the SAME step's finished list at its harvest
        # point, so a fenced finish is still returned exactly once
        self._inflight: _InFlightRound | None = None
        self._harvest_backlog: list[Request] = []
        # flight phase accumulator (replaces fixed telescoping stamps —
        # the async loop re-enters phases, e.g. "harvest" both at the
        # harvest point and for end-of-step bookkeeping): _fl_switch
        # closes the open interval into its phase bucket; an interval
        # additionally accrues into overlap_hidden when it OPENED with a
        # round in flight — the device was busy under the whole interval,
        # so that host time is off the critical path. The open-time rule
        # makes sync-mode overlap exactly 0.0 (dispatch opens with
        # nothing in flight) and keeps device_wait pure residual sync.
        # The same switch is the ONE stamper of a phase boundary: it also
        # closes the open ``serve/<phase>`` span and opens the next (the
        # Tracer's Chrome events and the profiler's host rows are both fed
        # from there, children of one ``serve/iteration`` span) and keeps
        # the iteration's (phase, start, end) intervals for the recorder.
        # _fl_part stamps a second level on the same stream: the named part
        # of the open phase (a child span, a ``parts`` row), closed by the
        # next part boundary or by the phase switch's own read.
        self._fl_t0 = 0.0
        self._fl_last = 0.0
        self._fl_unix_ns = 0
        self._fl_cur = "idle"
        self._fl_phases: dict | None = None
        self._fl_intervals: list = []
        self._fl_overlap = 0.0
        self._fl_hidden = False
        self._fl_span = None
        self._fl_iter_span = None
        self._fl_part_span = None
        self._fl_part_at: tuple = ("", 0.0)  # the open part's (name, stamp)
        self._fl_parts: list = []
        # time-to-first-token, decomposed at the boundaries a request
        # crosses (monotone totals, reset with the measurement window):
        # arrival -> admission (queue), time inside its own prefill chunks,
        # and the iterations those took; what remains of ttft_sum_s is time
        # admitted but waiting behind decode rounds and other prompts
        self._first_tokens_total = 0
        self._ttft_sum_s = 0.0
        self._ttft_queue_sum_s = 0.0
        self._ttft_own_prefill_sum_s = 0.0
        self._ttft_prefill_iterations_sum = 0
        # what the paged kernel's walk follows (monotone totals, counted at
        # dispatch from the rows' lengths): the table entries that hold a
        # block some query of the call attends, and the entries the tables
        # have — their ratio is the share of a (row, entry) grid that is live;
        # and the softmax steps the kernel takes over them, a tile of entries
        # each: entries over (tiles x the tile's width) is how full a tile is
        self._paged_entries_walked = 0
        self._paged_entries_table = 0
        self._paged_tiles_walked = 0
        self._paged_chunk_steps = 0
        # the same walk by kind, where the model has window kinds (monotone
        # totals; the sums above hold every kind's): entries walked in the
        # layers that keep all of the past and in those that keep a window,
        # and the entries of live positions that a window layer's walk did
        # not visit, which one kind for every layer would have walked
        self._paged_by_kind = dict.fromkeys(("full", "window", "behind_window"), 0)
        self._window_blocks_freed = 0
        # what the slot-state kernels' work follows (monotone totals, counted
        # at the decode dispatch from the host's mask): the slot states a
        # dispatch has to step - live lanes x steps x state layers - and
        # those the cache holds - every slot's; 0 where no layer keeps state
        self._state_slots_live = 0
        self._state_slots_held = 0
        # what the pick's sampler stage follows (monotone totals, counted at
        # dispatch from the host's copy of the lanes): runs of pick_tokens
        # (decode dispatches and first picks), and those in which some lane
        # samples — the ones whose lax.cond takes the sort and the draw
        self._pick_dispatches = 0
        self._pick_draw_dispatches = 0
        # block rounds (monotone totals; all 0 for a model that decodes one
        # token a step): rounds as dispatched; forwards x the lanes live in
        # them; positions the live lanes' rounds committed that no earlier
        # round or the prompt had fixed; and the tokens emitted from them
        # once EOS and length cuts are taken. Nothing here divides one by
        # another
        self._block_totals = dict.fromkeys(_BLOCK_TOTALS, 0)
        # static HBM model for the hbm watermark fallback: params + the
        # paged pools (+ scales), the same inventory the PR 8 preflight
        # prices — used verbatim when the backend has no memory_stats()
        self._static_hbm_bytes = int(
            sum(
                np.size(x) * np.dtype(getattr(x, "dtype", np.float32)).itemsize
                for x in jax.tree_util.tree_leaves(self._params)
            )
            + sum(p.size * np.dtype(p.dtype).itemsize for p in self._cache.values())
        )

        #: program name -> (jitted fn, abstract operands of its first
        #: dispatch): what compiled_text() lowers against
        self._dispatched: dict[str, tuple] = {}
        if self._spec:
            decode_fn = self._build_spec_decode_fn()
        elif self._block is not None:
            decode_fn = self._build_block_decode_fn()
        else:
            decode_fn = self._build_decode_fn()
        self._decode_fn = self._remember_first_dispatch("decode", decode_fn)
        self._prefill_fn = self._remember_first_dispatch(
            "prefill", self._build_prefill_fn()
        )
        # block-granular pool edits for CoW copies and swap restores:
        # donated, and the pool is the in-place operand of one scatter, so
        # XLA aliases the pool buffer instead of copying the whole pool
        # per block. These are *separate* tiny executables —
        # the one-compiled-DECODE-executable contract is about
        # ``_decode_fn``, whose trace counter they never touch. Block ids
        # ride as traced int32 scalars so every block reuses one compile.
        self._copy_block_fn = jax.jit(
            lambda pool, src, dst: pool.at[:, dst].set(pool[:, src]),
            donate_argnums=(0,),
        )
        # batched restore: the id vector's length is padded to a power of
        # two (pad entries scatter zeros into the null block, which is
        # never attended), so the executable count stays O(log blocks),
        # not one per distinct swap size
        self._write_blocks_fn = jax.jit(
            lambda pool, ids, rows: pool.at[:, ids].set(rows),
            donate_argnums=(0,),
        )
        # a slot's state is zeroed when a request is placed in it: one
        # donated row-set per leaf, the slot a traced scalar
        self._zero_slot_fn = jax.jit(
            lambda leaf, slot: leaf.at[:, slot].set(0), donate_argnums=(0,)
        )
        # grammar-row install: one donated row-set per table, the row id a
        # traced scalar so every grammar reuses one compile — same tiny-
        # executable discipline as the block edits above (never touches
        # the decode trace counter)
        self._write_grammar_row_fn = jax.jit(
            lambda tab, row, data: tab.at[row].set(data),
            donate_argnums=(0,),
        )
        # first-token pick: the prefill executable returns the
        # prompt-final logits, and the lane transform runs on them as a
        # [1, vocab] slice of the SAME pick_tokens the decode scan uses —
        # one tiny extra executable, zero extra model forwards, and exact
        # key parity with decode (position 0)
        eos_id = cfg.eos_token_id
        topn = cfg.logprobs_topn

        def first_pick(logits, lanes, gmask, base_key):
            return pick_tokens(
                logits, lanes, lanes["dfa_state"], jnp.int32(0), gmask,
                base_key, eos_id=eos_id, logprobs_topn=topn,
            )

        self._first_pick_fn = jax.jit(first_pick)

    def _place_on_mesh(self, inner) -> None:
        """GSPMD placement over ``self.mesh``: every device-side input to
        the compiled step gets an explicit ``NamedSharding`` so the first
        dispatch compiles the sharded program and every later dispatch
        reuses it (donated pool buffers keep their sharding, so the
        signature — avals + shardings — never drifts). Host mirrors
        (block tables, positions, tokens) stay plain numpy: they are
        uncommitted inputs GSPMD replicates for free."""
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.sharding import (
            infer_param_sharding,
            paged_kv_sharding,
            shard_params,
        )
        from ..utils.dataclasses import FullyShardedDataParallelPlugin

        mesh = self.mesh
        rules = getattr(inner, "partition_rules", None)
        shardings = infer_param_sharding(
            self._params, mesh, FullyShardedDataParallelPlugin(), rules
        )
        self._params = shard_params(self._params, shardings)
        pool_sharding = paged_kv_sharding(mesh, self._kv_heads)
        self._kp = jax.device_put(self._kp, pool_sharding)
        self._vp = jax.device_put(self._vp, pool_sharding)
        if self._ks is not None:
            from ..parallel.sharding import paged_kv_scale_sharding

            scale_sharding = paged_kv_scale_sharding(mesh, self._kv_heads)
            self._ks = jax.device_put(self._ks, scale_sharding)
            self._vs = jax.device_put(self._vs, scale_sharding)
        # scheduler-adjacent scalars must live on the SAME device set as the
        # sharded params — a single-device-committed leaf among mesh-committed
        # ones is an incompatible-devices error at dispatch
        rep = NamedSharding(mesh, PartitionSpec())
        self._base_key = jax.device_put(self._base_key, rep)
        # grammar tables are read-gathered per slot — tiny, replicated
        self._gmask = jax.device_put(self._gmask, rep)
        self._gtrans = jax.device_put(self._gtrans, rep)

    def _idle_lanes(self) -> dict:
        """The cached device-committed blank lane dict for all-inert
        dispatches. Every value is already a (replicated, on-mesh) jax
        array, so handing it to the compiled step costs zero host work —
        no per-iteration rebuild, no numpy→device transfer. Correct for
        any all-inert batch because ``pick_tokens`` reads nothing of a
        request from an inert lane: blank lanes leave the filters the
        identity, keep the sampler's ``lax.cond`` on its greedy side, and
        every slot is served the argmax of its logits."""
        if self._lanes_idle is None:
            lanes = blank_lanes(self.config.num_slots, self.config.rep_window)
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                rep = NamedSharding(self.mesh, PartitionSpec())
                lanes = {k: jax.device_put(v, rep) for k, v in lanes.items()}
            else:
                lanes = {k: jnp.asarray(v) for k, v in lanes.items()}
            self._lanes_idle = lanes
        return self._lanes_idle

    def _hbm_preflight(self, inner, pool_shape, num_kv_heads, pool_dtype, mesh) -> None:
        """shard-check's SP004 at the serving seam: predicted per-device
        bytes of params (under the placement ``_place_on_mesh`` would pick)
        plus both paged pools — plus, with speculation armed, the
        ``draft_params`` tier (the transient in-trace slice of the target's
        first layers the spec executable materialises) — refused against
        ``hbm_budget_gb`` BEFORE a single buffer allocates."""
        from ..analysis.shardplan import engine_preflight

        report = engine_preflight(
            self._params,
            getattr(inner, "partition_rules", None),
            mesh,
            pool_shape,
            num_kv_heads,
            pool_dtype,
            self.config.hbm_budget_gb,
            swap_gb=self.config.swap_gb or None,
            draft_layers=self._spec.layers if self._spec else None,
            stacked_prefix=getattr(inner, "stacked_params_prefix", "layers"),
            # fixed costs beside the parameters: the per-slot state, and the
            # pools of the kinds that keep a window (resident by construction)
            state_bytes=(self.state_bytes_per_slot * self.config.num_slots
                         + self.window_pool_bytes),
            pool_leaves=self._cache_spec.pool_leaves,
        )
        self.hbm_preflight = report
        if report["over"]:
            gib = 1 << 30
            draft = (
                f" + draft {report['draft_bytes'] / gib:.3f}"
                if report.get("draft_bytes") else ""
            )
            state = (
                f" + slot state {report['state_bytes'] / gib:.3f}"
                if report["state_bytes"] else ""
            )
            raise ValueError(
                f"SP004: engine refuses to start — predicted "
                f"{report['total_bytes'] / gib:.3f} GiB/device "
                f"(params {report['params_bytes'] / gib:.3f}{draft} + "
                f"kv pools {report['pool_bytes'] / gib:.3f}{state}) exceeds the "
                f"{self.config.hbm_budget_gb:.3f} GiB budget. Lower "
                f"num_blocks/max_seq_len (or use serve --auto-blocks), shard "
                f"over a larger mesh, shrink the draft (or spec_k=0), or "
                f"raise the budget"
            )

    # -- compiled programs ---------------------------------------------------

    def _remember_first_dispatch(self, program: str, jitted):
        def dispatch(*args):
            if program not in self._dispatched:
                self._dispatched[program] = (jitted, jax.tree.map(_abstract, args))
            return jitted(*args)

        return dispatch

    def compiled(self, program: str):
        """The ``"decode"`` or ``"prefill"`` executable, compiled for the
        operands of its first dispatch: ``as_text()`` is its optimised
        HLO, ``memory_analysis()`` its temporaries. This compiles the
        program again: a read where a persistent compile cache is
        configured, a full compile otherwise."""
        jitted, operands = self._dispatched[program]
        return jitted.lower(*operands).compile()

    def compiled_text(self, program: str) -> str:
        """Optimised HLO of the ``"decode"`` or ``"prefill"`` executable —
        how ``chip_smoke.py`` checks that the Pallas kernels were reached
        (``tpu_custom_call``), on which per-device shapes, and that the KV
        pool stays where it is (:func:`accelerate_tpu.utils.hlo.buffers_moved`)."""
        return self.compiled(program).as_text()

    def scope_table(self, program: str) -> dict:
        """``{instruction name: (result shape, scope stack)}`` of the
        ``"decode"`` or ``"prefill"`` executable
        (:func:`accelerate_tpu.utils.hlo.op_scopes` of its compiled text):
        a TPU trace names a device operation by its instruction, and this
        is the way back to the ``jax.named_scope`` it was traced under."""
        from ..utils.hlo import op_scopes

        return op_scopes(self.compiled_text(program))

    # the pool's leaves by their old names: the block-granular edits (CoW,
    # swap) and the tests address one pool at a time
    def _cache_leaf(name):
        def get(self):
            return self._cache.get(name)

        def put(self, value):
            self._cache[name] = value

        return property(get, put)

    _kp, _vp = _cache_leaf("k"), _cache_leaf("v")
    _ks, _vs = _cache_leaf("k_scale"), _cache_leaf("v_scale")
    del _cache_leaf

    def _build_decode_fn(self):
        """The burst decode: the sampling lanes
        (:func:`sampling.blank_lanes` schema), the grammar tables, and the
        derived-key root ride as traced inputs of the ONE decode
        executable — their shapes/dtypes are engine geometry, so
        per-request variation is data, never a retrace. Each burst step
        runs :func:`sampling.pick_tokens` (whose sampler stage sits under
        a ``lax.cond`` on whether any lane samples) and advances the
        per-slot DFA state in-trace for mid-burst masking; the host
        re-derives the authoritative state per emitted token, so discarded
        burst tails never corrupt it.  The per-step top-N logprob harvest
        rides the scan outputs through the one existing device_get."""
        apply_fn, cfg = self._apply_fn, self.config
        eos_id = cfg.eos_token_id
        topn = cfg.logprobs_topn
        counted = bool(self._step_counters)

        def decode(params, cache, block_tables, pos0, toks, active,
                   lanes, gmask, gtrans, base_key):
            self._decode_traces += 1  # traced-body side effect: cache misses only

            def one_step(carry, t):
                cache, toks, pos, dfa = carry
                out = apply_fn(
                    params,
                    input_ids=toks,
                    paged_kv=cache,
                    block_tables=block_tables,
                    cache_positions=pos,
                    paged_write_mask=active,  # PREFILL/free lanes must not scribble
                )
                logits = out["logits"][:, -1, :]
                tok, logp_tok, top_vals, top_ids = pick_tokens(
                    logits, lanes, dfa, t, gmask, base_key,
                    eos_id=eos_id, logprobs_topn=topn,
                )
                dfa = gtrans[lanes["grammar_row"], dfa, tok]
                ys = (tok, logp_tok, top_vals, top_ids)
                if counted:
                    ys += (out["step_counters"],)
                return (out["paged_kv"], tok[:, None], pos + 1, dfa), ys

            (cache, _, _, _), ys = jax.lax.scan(
                one_step, (cache, toks, pos0, lanes["dfa_state"]),
                jnp.arange(cfg.decode_burst),
            )
            # toks_out: [burst, num_slots]; logprob outputs [burst, slots(, N)];
            # behind them the step counters of a model that has any, [burst, ...]
            return (cache, *ys)

        # the cache — pools, scale arrays when quantized, slot state where
        # the model keeps one — is one donated operand of every step program
        return jax.jit(decode, donate_argnums=(1,))

    def _build_spec_decode_fn(self):
        """The speculative round — when ``spec_k`` is armed this IS the
        engine's one decode executable. One dispatch runs the whole round:

        1. **draft scan**: ``k`` steps of the early-exit draft (the
           target's first ``draft_layers`` layers), autoregressing through
           the target's own pool, in place — the paged step addresses the
           pool by layer, so the draft writes layers ``0..draft_layers-1``
           of the one donated buffer and no slice of it is ever made;
           identical weights make its K/V a strict subset of the target's,
           so the draft needs no cache of its own. It proposes through the
           SAME lane transform the plain decode uses (grammar mask,
           filters, per-slot derived keys — ``TAG_DRAFT``);
        2. **one verify forward** of static shape ``[num_slots, k+1]`` over
           ``[pending, d_1 .. d_k]`` through the fused paged-attention
           kernel (quantize-on-scatter + in-register dequant ride along at
           every ``kv_dtype``), every position scored through the lane
           transform again. The verify re-scatters ALL layers at the
           round's positions — including the draft layers, so the rows
           the draft scan left in the pool are overwritten, from the same
           tokens and weights, before anything but the draft reads them;
        3. **acceptance**, split per slot:

        * greedy slots keep the exact longest-agreeing-prefix path
          (:func:`~accelerate_tpu.generation.spec_accept_tokens`, the
          single source of acceptance semantics with ``generate()``, over
          the *filtered* target argmax — token-identical to the non-spec
          engine, and the filter re-check is what keeps accepted drafts
          inside a constrained slot's language);
        * sampled slots run standard speculative rejection sampling
          (:func:`sampling.rejection_accept`): accept draft ``d_j`` with
          prob ``min(1, p_j(d_j)/q_j(d_j))``, resample the first rejection
          from the clamped residual ``max(p - q, 0)``, bonus-sample from
          ``p_k`` on full acceptance.  ``p`` and ``q`` come out of the one
          shared :func:`sampling.dist_logprobs`, so both sides of the
          ratio see identical temperature/top-k/top-p/grammar filtering —
          an out-of-language draft has ``p = 0`` and is rejected with
          certainty, and the residual stays in-language.

        The repetition-penalty ring is held constant across the round (a
        documented approximation — consistent between ``p`` and ``q``, so
        the acceptance identity is unaffected).

        Rollback of rejected drafts is pure position bookkeeping: the host
        advances each slot by ``accept+1``, the next round re-writes the
        stale rows before any query can attend them, and no pool edit
        beyond the normal scatter ever happens. Donation discipline and the
        traced-body compile counter are identical to the plain decode fn,
        so ``decode_compiles == 1`` remains the asserted contract."""
        from ..generation import spec_accept_tokens

        apply_fn, cfg = self._apply_fn, self.config
        draft_apply = self._draft_apply
        k = cfg.spec_k
        eos_id = cfg.eos_token_id

        def spec_decode(params, cache, block_tables, pos0, toks, active,
                        lanes, gmask, gtrans, base_key):
            self._decode_traces += 1  # traced-body side effect: cache misses only
            row = lanes["grammar_row"]

            def dstep(carry, t):
                cache, tok, pos, dfa = carry
                out = draft_apply(
                    params,
                    input_ids=tok,
                    paged_kv=cache,
                    block_tables=block_tables,
                    cache_positions=pos,
                    paged_write_mask=active,  # PREFILL/free lanes must not scribble
                )
                filt = apply_filters(
                    out["logits"][:, -1, :], lanes, dfa, lanes["pos"] + t,
                    gmask, eos_id,
                )
                greedy = jnp.argmax(filt, axis=-1).astype(jnp.int32)
                logq = dist_logprobs(filt, lanes)
                keys = slot_keys(base_key, lanes["seed"], lanes["pos"] + t, TAG_DRAFT)
                nxt = jnp.where(
                    lanes["sample"], categorical_per_slot(keys, logq), greedy
                ).astype(jnp.int32)
                return (out["paged_kv"], nxt[:, None], pos + 1, gtrans[row, dfa, nxt]), (
                    nxt, jnp.exp(logq))

            (cache, _, _, _), (d, q) = jax.lax.scan(
                dstep, (cache, toks, pos0, lanes["dfa_state"]), jnp.arange(k),
            )
            d = d.T  # [num_slots, k] draft proposals; q: [k, slots, vocab]

            chunk = jnp.concatenate([toks, d], axis=1)  # [num_slots, k+1]
            vmask = jnp.broadcast_to(active, (cfg.num_slots, k + 1))
            out = apply_fn(
                params,
                input_ids=chunk,
                paged_kv=cache,
                block_tables=block_tables,
                cache_positions=pos0,
                paged_write_mask=vmask,
            )
            cache = out["paged_kv"]
            tlogits = out["logits"]  # [num_slots, k+1, vocab]

            # DFA states along the draft path (k is small and static): the
            # verify filters each position with the state its PREFIX put
            # the automaton in — this is the mask re-check
            states = [lanes["dfa_state"]]
            for j in range(k):
                states.append(gtrans[row, states[j], d[:, j]])
            filts = [
                apply_filters(
                    tlogits[:, j, :], lanes, states[j], lanes["pos"] + j,
                    gmask, eos_id,
                )
                for j in range(k + 1)
            ]
            preds = jnp.stack(
                [jnp.argmax(f, axis=-1) for f in filts], axis=1
            ).astype(jnp.int32)
            accept_g, seq_g = spec_accept_tokens(d, preds)

            p = jnp.stack([jnp.exp(dist_logprobs(f, lanes)) for f in filts], axis=0)
            u = jnp.stack(
                [
                    uniform_per_slot(
                        slot_keys(base_key, lanes["seed"], lanes["pos"] + j, TAG_ACCEPT)
                    )
                    for j in range(k)
                ],
                axis=1,
            )  # [num_slots, k]
            accept_s, seq_s = rejection_accept(
                d, p, q, u, base_key, lanes["seed"], lanes["pos"]
            )

            sample = lanes["sample"]
            accept = jnp.where(sample, accept_s, accept_g).astype(jnp.int32)
            tok_seq = jnp.where(sample[:, None], seq_s, seq_g).astype(jnp.int32)
            return cache, tok_seq, accept

        return jax.jit(spec_decode, donate_argnums=(1,))

    def _init_block_rounds(self, inner, mesh) -> None:
        """Geometry of the block rounds, checked at bring-up, and what is
        refused beside them (``stats()['block_round_refuses']``)."""
        cfg, blk = self.config, self._block
        name = getattr(inner, "name", type(inner).__name__)
        b = blk.block_length
        t = b if cfg.denoise_steps is None else int(cfg.denoise_steps)
        if t < 1 or b % t:
            raise ValueError(
                f"denoise_steps {t} does not divide {name!r}'s block_length {b}: a pass "
                "fixes one of denoise_steps equal sub-blocks"
            )
        for what, value in (("block_size", cfg.block_size), ("prefill_chunk", cfg.prefill_chunk)):
            if value % b:
                raise ValueError(
                    f"{what} {value} is not a multiple of {name!r}'s block_length {b}: a "
                    "block of the model may not straddle a page of the pool or a prefill chunk"
                )
        self._denoise_steps = t
        self.block_round_refuses = {
            "grammar": "a DFA advances one token at a time, and a denoise pass fixes "
                       "several positions from logits that did not see each other",
            "spec_k": "a speculative round drafts and verifies one token a position, "
                      "causally; a block round is not causal inside its block",
            "repetition_penalty": "the penalty's window would have to hold tokens of the "
                                  "same pass, which are picked together",
            "mesh": "the round has not been built under a mesh (ROADMAP Reach 8)",
        }
        for armed, what in ((cfg.spec_k, "spec_k"), (mesh is not None, "mesh")):
            if armed:
                raise ValueError(
                    f"{what} is not supported for {name!r}, which generates by diffusion "
                    f"over blocks of {b}: {self.block_round_refuses[what]}"
                )

    def _build_block_decode_fn(self):
        """The block round — the one decode executable of a model that
        declares ``block_decode``. One dispatch runs ``decode_burst`` rounds;
        a round takes every live slot's next block ``[pos, pos + B)``:

        1. **denoise passes** ``t = 0 .. T-1`` (``T = denoise_steps``): one
           forward of static shape ``[num_slots, B]`` over the block as it
           stands (known positions their tokens, the rest the mask token)
           against the pool. The paged step scatters the block's keys and
           values at its positions first and every query then attends
           everything written before the END of its block, so the block
           sees itself as of this pass. Pass ``t`` fixes the still-masked
           positions of sub-block ``t``, ``[t*B/T, (t+1)*B/T)``, and reads
           no other row: the step is asked for the logits of those ``B/T``
           positions of every slot, so the head and the pick run over
           ``[num_slots * B/T]`` rows with each slot's lanes broadcast (a
           sampled lane draws every position from its own row, keyed by
           its output position). A position's log-probability is that of
           the pass that fixed it;
        2. **the commit pass**: one forward over the clean block. What it
           writes at ``[pos, pos + B)`` is what later blocks attend: it
           overwrites the denoise passes' rows before any other query reads
           them, as a speculative round's verify overwrites its draft's, so
           nothing is rolled back and no second cache exists. Its logits
           are not used, and no head is computed for it.

        In the first round a slot's block may open with known positions (a
        prompt's last ``n mod B`` tokens): ``known`` keeps them. Later rounds
        of the dispatch start from a fully masked block ``B`` further on.
        Every pass runs for every slot (a pass with nothing to fix in some
        slot is that slot's waste; shapes are static). Outputs: the cache,
        ``[burst, slots, B]`` tokens and log-probabilities (top-N beside
        them), and the step counters of every forward, ``[burst, T + 1,
        ...]``. Donation and the traced-body compile counter are the plain
        decode's: ``decode_compiles == 1`` stays the asserted contract."""
        apply_fn, cfg, blk = self._apply_fn, self.config, self._block
        eos_id, topn = cfg.eos_token_id, cfg.logprobs_topn
        n_top = max(int(topn), 1)
        counted = bool(self._step_counters)
        b, t_steps, slots = blk.block_length, self._denoise_steps, cfg.num_slots
        sub = b // t_steps
        mask_id = jnp.int32(blk.mask_token_id)
        col = jnp.arange(sub, dtype=jnp.int32)

        def decode_block_rounds(params, cache, block_tables, pos0, toks, known, active,
                                lanes, gmask, base_key):
            self._decode_traces += 1  # traced-body side effect: cache misses only
            rows = {name: jnp.repeat(lane, sub, axis=0) for name, lane in lanes.items()}
            write = jnp.broadcast_to(active, (slots, b))

            def forward(cache, x, pos, logit_positions=None):
                return apply_fn(
                    params, input_ids=x, paged_kv=cache, block_tables=block_tables,
                    cache_positions=pos,
                    paged_write_mask=write,  # PREFILL/free lanes must not scribble
                    logit_positions=logit_positions,
                )

            def one_round(carry, n):
                cache, x, known, pos = carry
                # what each pass reports of its sub-block (zeros at known positions)
                logp, tvals, tids, counters = [], [], [], []
                for t in range(t_steps):
                    lo, hi = t * sub, (t + 1) * sub
                    with jax.named_scope("denoise_pass"):
                        out = forward(cache, x, pos, jnp.broadcast_to(lo + col, (slots, sub)))
                    cache = out["paged_kv"]
                    if counted:
                        counters.append(out["step_counters"])
                    # a row's output position: lanes["pos"] counts from the
                    # block's first position (the known ones lie before it)
                    row_lanes = dict(rows, pos=(
                        (lanes["pos"] + n * b + lo)[:, None] + col[None, :]
                    ).reshape(slots * sub))
                    tok, lp, tv, ti = pick_tokens(
                        out["logits"].reshape(slots * sub, -1), row_lanes,
                        row_lanes["dfa_state"], jnp.int32(0), gmask, base_key,
                        eos_id=eos_id, logprobs_topn=topn,
                    )
                    fix = ~known[:, lo:hi]
                    x = x.at[:, lo:hi].set(
                        jnp.where(fix, tok.reshape(slots, sub), x[:, lo:hi]))
                    logp.append(jnp.where(fix, lp.reshape(slots, sub), 0.0))
                    tvals.append(jnp.where(fix[..., None], tv.reshape(slots, sub, n_top), 0.0))
                    tids.append(jnp.where(fix[..., None], ti.reshape(slots, sub, n_top), 0))
                with jax.named_scope("commit_pass"):
                    out = forward(cache, x, pos)
                ys = (x, *(jnp.concatenate(parts, axis=1) for parts in (logp, tvals, tids)))
                if counted:
                    counters.append(out["step_counters"])
                    ys += (jax.tree.map(lambda *c: jnp.stack(c), *counters),)
                nxt = (out["paged_kv"], jnp.full_like(x, mask_id), jnp.zeros_like(known),
                       pos + b)
                return nxt, ys

            (cache, _, _, _), ys = jax.lax.scan(
                one_round, (cache, toks, known, pos0), jnp.arange(cfg.decode_burst))
            return (cache, *ys)

        return jax.jit(decode_block_rounds, donate_argnums=(1,))

    def _build_prefill_fn(self):
        """A prompt's chunk. The head runs on the one row somebody reads:
        the prompt's last real position, which the first token is picked
        from (``last_idx``; only meaningful on the final chunk — the host
        ignores the row otherwise). No token comes of a block model's
        prefill, so its chunk asks for no logits and returns none: the head
        falls out of the compiled program."""
        apply_fn = self._apply_fn
        has_state = bool(self._cache_spec.slot_state)
        counted = bool(self._step_counters)
        reads_last = self._block is None

        def prefill(params, cache, block_table, start, chunk, valid,
                    last_idx, slot):
            self._prefill_traces += 1
            # a model that keeps per-slot state is told which slot's rows
            # this prompt's chunk continues from and leaves
            state_kw = {"state_slots": slot} if has_state else {}
            out = apply_fn(
                params,
                input_ids=chunk,  # [1, prefill_chunk]
                paged_kv=cache,
                block_tables=block_table,  # [1, mb]
                cache_positions=start,  # [1]
                paged_write_mask=valid,  # drops the padded tail
                logit_positions=last_idx.reshape(1, 1) if reads_last else None,
                **state_kw,
            )
            done = (out["paged_kv"],)
            if reads_last:
                done += (out["logits"][0, 0],)
            if counted:
                done += (out["step_counters"],)
            return done

        return jax.jit(prefill, donate_argnums=(1,))

    # -- public API ----------------------------------------------------------

    def add_request(
        self,
        prompt,
        max_new_tokens: int | None = None,
        arrival_time: float | None = None,
        priority: str = "interactive",
        deadline_ms: float | None = None,
        trace_id: str | None = None,
        upstream_hop: bool = False,
        sampling=None,
        grammar: dict | None = None,
        tenant: str | None = None,
    ) -> Request:
        """Enqueue one request. ``deadline_ms`` is a *relative* budget from
        now: once it elapses the scheduler finishes the request with
        ``finish_reason="deadline_exceeded"`` (partial output kept, blocks
        freed the same iteration). A malformed value raises ValueError —
        the serve front end answers that as an error row, mirroring the
        unknown-``priority`` handling.

        ``trace_id`` is the request's distributed-trace identity: a
        well-formed supplied id (the router's, or a client's) survives
        verbatim; otherwise one is generated here. It rides every answer
        row, request-scoped trace event, and latency exemplar.
        ``upstream_hop=True`` declares that a routing tier dispatched this
        request (and emitted the flow arrow's tail) — the engine then
        lands the arrow's head at arrival. A standalone engine must leave
        it False even for client-supplied ids, or every request counts as
        an orphaned flow in the merged timeline.

        ``sampling`` is a :class:`SamplingParams` (or a dict of its
        fields) scoped to THIS request; ``None`` inherits the engine-wide
        defaults. ``grammar`` is a constrained-decoding spec
        (``{"type": "regex", ...}`` or ``{"type": "json_schema", ...}``)
        compiled here — admission fails loudly on an unsupported grammar
        or when every grammar row is held by a live request, never
        mid-decode.

        ``tenant`` is the usage ledger's accounting dimension, riding the
        same machinery as ``priority``/``trace_id``: any non-empty string
        is taken verbatim (stripped, bounded), everything else normalizes
        to ``"default"`` — unknown-safe, never an admission gate. It is
        echoed on the answer row beside the accrued costs."""
        upstream = upstream_hop and valid_trace_id(trace_id)
        req = Request(
            prompt=[int(t) for t in np.asarray(prompt).reshape(-1)],
            max_new_tokens=int(
                self.config.max_new_tokens if max_new_tokens is None else max_new_tokens
            ),
            priority=priority,
            trace_id=ensure_trace_id(trace_id),
            tenant=normalize_tenant(tenant),
            block_len=self._block.block_length if self._block else 1,
        )
        if arrival_time is not None:
            req.arrival_time = arrival_time
        if deadline_ms is not None:
            try:
                budget_ms = float(deadline_ms)
            except (TypeError, ValueError):
                budget_ms = float("nan")
            if not budget_ms > 0:  # also rejects NaN
                raise ValueError(
                    f"malformed deadline_ms {deadline_ms!r}: want a positive "
                    "number of milliseconds"
                )
            req.deadline = time.perf_counter() + budget_ms / 1000.0
        params = resolve_sampling(sampling, self._default_sampling)
        if params.logprobs > self.config.logprobs_topn:
            raise ValueError(
                f"request wants logprobs={params.logprobs} but the engine "
                f"compiled logprobs_topn={self.config.logprobs_topn}; raise "
                "EngineConfig.logprobs_topn (a traced-shape choice, so it "
                "is per-engine, not per-request)"
            )
        req.sampling = params
        if self._block is not None:
            for armed, what in ((grammar is not None, "grammar"),
                                (params.repetition_penalty != 1.0, "repetition_penalty")):
                if armed:
                    raise ValueError(
                        f"{what} is not supported beside a block round: "
                        f"{self.block_round_refuses[what]}"
                    )
        if grammar is not None:
            g = compile_grammar(
                grammar, self._vocab_size,
                eos_id=self.config.eos_token_id,
                max_states=self.config.grammar_states,
            )
            req.grammar_row = self._acquire_grammar_row(g)
            req.dfa_state = g.start
        try:
            self.scheduler.submit(req)
        except BaseException:
            if req.grammar_row:
                self._release_grammar_row(req)
            raise
        if self.usage is not None:
            self.usage.begin(req)
        tr = get_tracer()
        if tr:
            # the engine-side async span opens at ARRIVAL (stamped with the
            # request's own arrival_time, so span math reproduces the
            # engine-reported TTFT exactly); a request that arrived with an
            # upstream trace_id also lands the flow-arrow head the
            # router's dispatch tail points at
            tr.request_begin(
                req.trace_id, "req/arrive", ts=req.arrival_time,
                request_id=req.request_id, prompt_tokens=req.prompt_len,
                max_new_tokens=req.max_new_tokens, priority=req.priority,
            )
            if upstream:
                tr.flow(req.trace_id, "f")
        return req

    def step(self) -> list[Request]:
        """One engine iteration: evict finished → admit queued → one
        prefill chunk → harvest the in-flight round → one decode dispatch
        over every slot. Returns requests that finished this iteration.

        With ``async_dispatch`` (the default) the decode dispatch is
        double-buffered: the round handed off at the end of iteration *i*
        is harvested at iteration *i+1*'s harvest point, so the schedule
        and prefill work above it runs WHILE the device computes. Every
        dispatch still happens strictly after the previous round's
        harvest, so the decode inputs — and therefore the emitted tokens —
        are identical to the synchronous loop; tokens simply surface one
        ``step()`` call later, and ``run_until_idle()``/``stream()`` keep
        stepping until the drain flush lands them."""
        if self._start_time is None:
            self._start_time = self._last_stats_t = time.perf_counter()
        # ONE global read per iteration when tracing is disabled — every
        # request-event site below keys off this cached (falsy) handle
        self._tr = get_tracer() or None
        sched = self.scheduler
        finished: list[Request] = []

        fl = self._flight
        tokens_before = self._tokens_emitted
        self._fl_begin()

        deferred_deadline: list[Request] = []
        if sched.deadline_live:  # guarded: deadline-free = one int check
            now = time.perf_counter()
            inflight_slots = None
            if self._inflight is not None:
                # an expired member of the in-flight round still has a
                # token landing at this step's harvest — the token the
                # synchronous engine emitted LAST step. Defer its
                # expiry to just after the harvest point so the two
                # loops stay token-identical.
                inflight_slots = {r.slot for r in self._inflight.live}
            for req in sched.expire_deadlines(now, skip_slots=inflight_slots):
                if req.slot is None:
                    self._release_expired_queued(req)
                self._deadline_expired += 1
                finished.append(req)
            if inflight_slots:
                deferred_deadline = [
                    r for r in self._inflight.live
                    if r.deadline is not None and now > r.deadline
                ]
        sched.evict_finished()
        self._admit_and_place()

        self._fl_switch("prefill")
        # one chunk per PREFILLING SLOT per iteration: slot turnover is
        # never throttled to one admission per decode burst, while any
        # single prompt still advances at most one chunk between decode
        # steps — the TTFT/stall bound chunked prefill exists for
        prefilling = sched.active(RequestState.PREFILL)
        for req in prefilling:
            self._prefill_one_chunk(req, finished)

        # harvest point: the previous iteration's round lands here,
        # exactly one iteration late. Backlog entries were force-harvested
        # by a mid-schedule fence (swap-out) and drain into THIS step's
        # finished list — a fenced finish is still returned exactly once.
        if self._harvest_backlog:
            finished.extend(self._harvest_backlog)
            self._harvest_backlog.clear()
        self._harvest_inflight(finished)
        for req in deferred_deadline:
            # the member's in-flight token has now been emitted (exactly
            # the output the synchronous engine had at its sweep) — expire
            # it before the next dispatch; blocks free at the next evict
            if req.state is RequestState.DECODE:
                req.finish_reason = "deadline_exceeded"
                req.finish_time = time.perf_counter()
                req.state = RequestState.FINISHED
                self._deadline_expired += 1
                finished.append(req)

        self._fl_switch("dispatch")
        decoding = sched.active(RequestState.DECODE)
        if decoding:
            self._dispatch_decode(decoding, finished)
        if not self.config.async_dispatch:
            # synchronous escape hatch: harvest the round we just
            # dispatched before leaving the iteration (the pre-item-5 loop)
            self._harvest_inflight(finished)

        self._fl_switch("harvest", "harvest/close")
        self._iterations += 1
        self._occupancy_sum += sched.occupancy
        for req in finished:
            if req.grammar_row:
                self._release_grammar_row(req)
        self._completed.extend(finished)
        self._completed_total += len(finished)
        if self._tr is not None:
            for req in finished:
                # exactly one end event per request, whatever path finished
                # it (eos/length/out_of_blocks/deadline, queued or running)
                self._tr.request_end(
                    req.trace_id, "req/finish", ts=req.finish_time,
                    finish_reason=req.finish_reason,
                    new_tokens=len(req.output_tokens),
                    ttft_s=req.ttft_s, tpot_s=req.tpot_s,
                )
        if self.usage is not None:
            # close each finished request's account NOW (before the answer
            # rows emit) — held blocks drop to 0 on BOTH sides of the
            # block-second integral, so the extra iteration the scheduler
            # holds them before the next evict sweep is excluded
            # consistently, and the summary rides the telemetry row
            for req in finished:
                summary = self.usage.finish(req)
                if summary is not None:  # exactly-once across re-lists
                    req.usage = summary
        self._emit_telemetry(finished)
        self._fl_iter_span.set_metadata(
            decoding=len(decoding), prefill_chunks=len(prefilling),
            tokens=self._tokens_emitted - tokens_before,
        )
        rec = self._fl_finish()
        if rec is not None:
            t0, wall, phases, overlap = rec
            entry = fl.record(
                self._iterations, t0, wall,
                overlap_hidden_s=overlap, intervals=self._fl_intervals,
                parts=self._fl_parts, t_start_unix_ns=self._fl_unix_ns,
                counters={
                    **{name: int(total) for name, total in self._step_counters.items()
                       if not total.shape},
                    **self._block_stats(),
                    **self._window_stats(totals_only=True),
                },
                **phases,
            )
            fl.current_phase = "idle"
            reg = get_active_registry()
            if reg:
                observe_flight(reg, entry)
            if self._tr is not None:
                # host share over time as a Perfetto counter track, plus
                # one instant per iteration carrying the phase breakdown
                # (the wall-corrected reader behind `trace tail
                # --iterations` consumes these)
                self._tr.counter("serve/iteration", fl.host_fraction())
                self._tr.instant(
                    "serve/flight",
                    **{k: v for k, v in entry.items() if k not in _FLIGHT_CLOCK_KEYS},
                )
        return finished

    def run_until_idle(self, max_iterations: int | None = None) -> list[Request]:
        """Drain queue + slots + the in-flight round; returns every
        request finished during the drain (scheduling-bug guard:
        ``max_iterations`` bounds the loop). The final drain flush — the
        step that only harvests the last in-flight round — counts as an
        iteration like any other; the cap is checked BEFORE stepping, so
        a cap that lands exactly on the drain boundary still returns
        every finished request (and raising never swallows them)."""
        done: list[Request] = []
        it = 0
        while self.scheduler.has_work() or self._inflight is not None:
            if max_iterations is not None and it >= max_iterations:
                raise RuntimeError(f"engine not idle after {it} iterations")
            done.extend(self.step())
            it += 1
        return done

    def stream(self, prompt, max_new_tokens: int | None = None):
        """Generator yielding this request's tokens as the engine emits
        them (other in-flight requests keep decoding underneath)."""
        req = self.add_request(prompt, max_new_tokens)
        served = 0
        while req.state is not RequestState.FINISHED:
            self.step()
            while served < len(req.output_tokens):
                yield req.output_tokens[served]
                served += 1
        while served < len(req.output_tokens):
            yield req.output_tokens[served]
            served += 1

    def reset_stats(self) -> None:
        """Zero the measurement state (iterations, tokens, occupancy,
        completed-request percentiles, wall clock) while keeping the
        compiled programs, pages, and compile counters — so a bench can
        warm up and then measure without the warmup's idle-engine TTFT and
        low-occupancy drain iterations biasing the reported percentiles."""
        self._iterations = 0
        self._tokens_emitted = 0
        self._occupancy_sum = 0.0
        self._start_time = None
        self._completed.clear()
        self._completed_total = 0
        self._last_stats_t = None
        self._last_stats_tokens = 0
        self._preemptions = 0
        self._state_resets = 0
        self._swapped_out_blocks = 0
        self._swapped_in_blocks = 0
        self._out_of_blocks_total = 0
        self._deadline_expired = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._sampled_greedy = 0
        self._sampled_sample = 0
        self._grammar_masked_steps = 0
        self._rej_drafted = 0
        self._rej_accepted = 0
        self._first_tokens_total = 0
        self._ttft_sum_s = self._ttft_queue_sum_s = self._ttft_own_prefill_sum_s = 0.0
        self._ttft_prefill_iterations_sum = 0
        self._paged_entries_walked = self._paged_entries_table = self._paged_tiles_walked = 0
        self._paged_chunk_steps = 0
        self._paged_by_kind = dict.fromkeys(self._paged_by_kind, 0)
        self._window_blocks_freed = 0
        self._state_slots_live = self._state_slots_held = 0
        self._pick_dispatches = self._pick_draw_dispatches = 0
        self._block_totals = dict.fromkeys(_BLOCK_TOTALS, 0)
        for total in self._step_counters.values():
            total[...] = 0
        self._pending_counters = []  # dispatched before the reset: not this window's
        # hit accounting restarts with the measurement window; the trie and
        # its cached blocks deliberately stay warm (steady-state behaviour
        # is what a warmed bench leg measures)
        self.scheduler.prompt_tokens_admitted = 0
        self.scheduler.prefix_hit_tokens = 0
        if self.radix is not None:
            self.radix.evicted_blocks = 0
            self.radix.inserted_blocks = 0
        # the flight ring is measurement state like everything above: a
        # warmup→reset→measure cycle must report post-reset iterations
        # only, for stats()['host_fraction'] and the ring both
        if self._flight is not None:
            self._flight.reset()
        # like the flight ring: the ledger is measurement state — rollups
        # zero, live requests re-base their block integrals at now
        if self.usage is not None:
            self.usage.reset()

    def _hbm_watermarks(self) -> dict:
        """Live device-memory watermarks where the backend exposes them
        (``Device.memory_stats()`` — TPU/GPU runtimes), else the static
        params+pools model the PR 8 preflight prices, labelled
        ``"estimate"`` so a CPU reading is never mistaken for a real
        high-water mark. Headroom appears when a limit is known (backend
        ``bytes_limit`` or the configured ``hbm_budget_gb``)."""
        used = peak = limit = None
        source = "estimate"
        try:
            mem = jax.local_devices()[0].memory_stats()
            if mem and "bytes_in_use" in mem:
                used = int(mem["bytes_in_use"])
                peak = int(mem.get("peak_bytes_in_use", used))
                limit = int(mem["bytes_limit"]) if "bytes_limit" in mem else None
                source = "memory_stats"
        except Exception:
            pass
        if used is None:
            used = peak = self._static_hbm_bytes
        if limit is None and self.config.hbm_budget_gb is not None:
            limit = int(self.config.hbm_budget_gb * (1 << 30))
        out = {
            "hbm_used_bytes": used,
            "hbm_peak_bytes": peak,
            "hbm_bytes_source": source,
        }
        if limit is not None:
            out["hbm_limit_bytes"] = limit
            out["hbm_headroom_bytes"] = limit - used
        return out

    def _block_stats(self) -> dict:
        """The block rounds' totals as ``stats()`` and the flight entries
        carry them (empty for a model that decodes one token a step):
        forwards as run — ``denoise_steps`` denoise and one commit a round —
        beside what the engine counts."""
        if self._block is None:
            return {}
        rounds = self._block_totals["block_rounds_total"]
        return {
            **self._block_totals,
            "block_denoise_forwards_total": rounds * self._denoise_steps,
            "block_commit_forwards_total": rounds,
            "block_forwards_total": rounds * (self._denoise_steps + 1),
        }

    def _window_stats(self, totals_only: bool = False) -> dict:
        """What ``stats()`` says of a model whose paged layers are of two
        kinds (empty for a model of one): the fixed numbers, the pools'
        occupancy by kind, and the monotone totals - which the flight entries
        carry too (``totals_only``), so that a reader can difference them
        over a span."""
        if not self._window_kinds:
            return {}
        totals = {
            "window_blocks_freed_total": self._window_blocks_freed,
            "paged_entries_walked_full_total": self._paged_by_kind["full"],
            "paged_entries_walked_window_total": self._paged_by_kind["window"],
            "paged_entries_behind_window_total": self._paged_by_kind["behind_window"],
        }
        if totals_only:
            return totals
        full = [k for k in self._kinds if not k.window]
        window = self._window_kinds
        store = np.dtype(self.kv_dtype)
        held = sum(a.allocated_count for a in self.window_allocators.values())
        return {
            "kv_window": window[0].window,
            "kv_full_layers": sum(k.layers for k in full),
            "kv_window_layers": sum(k.layers for k in window),
            "kv_bytes_per_position_full": sum(
                k.bytes_per_token(store, self._quantized) for k in full),
            "kv_bytes_per_position_window": sum(
                k.bytes_per_token(store, self._quantized) for k in window),
            "window_blocks_per_slot": sum(self.window_blocks_per_slot.values()),
            "window_num_blocks": sum(self.window_num_blocks.values()),
            "window_pool_bytes": self.window_pool_bytes,
            # the totals above count the first kind's pool alone, as num_blocks does
            "allocated_blocks_full": self.allocator.allocated_count,
            "allocated_blocks_window": held,
            "free_blocks_window": sum(a.free_count for a in self.window_allocators.values()),
            "cached_blocks_full": 0, "cached_blocks_window": 0,  # no prefix cache
            **totals,
        }

    def _spec_stats(self) -> dict:
        """Speculative health fields (accept rate is the TPOT lever — each
        round costs one dispatch and emits accept+1 tokens). The SINGLE
        source for both export surfaces, ``stats()`` and the telemetry
        step rows; empty when speculation is off (monitor keys off
        ``spec_k``)."""
        if self._spec is None:
            return {}
        return {
            "spec_k": self.config.spec_k,
            "spec_draft": str(self._spec),
            "spec_drafted_tokens": self._spec_drafted,
            "spec_accepted_tokens": self._spec_accepted,
            "spec_accept_rate": (
                self._spec_accepted / self._spec_drafted
                if self._spec_drafted else 0.0
            ),
        }

    def _sampling_stats(self) -> dict:
        """Per-slot sampling/grammar health fields. Like ``_spec_stats``,
        the SINGLE source for both ``stats()`` and the telemetry step
        rows. The rejection counters only appear with speculation armed —
        they are the sampled-slot analogue of the greedy accept rate."""
        out = {
            "sampled_tokens_greedy": self._sampled_greedy,
            "sampled_tokens_sample": self._sampled_sample,
            "grammar_masked_steps": self._grammar_masked_steps,
            "grammar_rows_live": sum(1 for r in self._row_refs if r > 0),
        }
        if self._spec is not None:
            out["rejection_drafted_tokens"] = self._rej_drafted
            out["rejection_accepted_tokens"] = self._rej_accepted
            out["rejection_accept_rate"] = (
                self._rej_accepted / self._rej_drafted
                if self._rej_drafted else 0.0
            )
        return out

    def stats(self) -> dict:
        """Aggregate serving health: goodput, TTFT/TPOT percentiles over
        completed requests, mean slot occupancy, and the compile counters
        the one-executable contract is asserted against."""
        sched = self.scheduler
        cached = self.radix.cached_block_count if self.radix is not None else 0
        cached_exclusive = (
            self.radix.exclusive_block_count() if self.radix is not None else 0
        )
        out = {
            "iterations": self._iterations,
            # exact cumulative count — NOT the percentile window's length
            # (the ring caps history; the counter keeps counting past it)
            "completed": self._completed_total,
            "completed_window": len(self._completed),
            "queue_depth": sched.queue_depth,
            "active_slots": len(sched.active()),
            "num_slots": self.config.num_slots,
            "tokens_emitted": self._tokens_emitted,
            "decode_compiles": self._decode_traces,
            "prefill_compiles": self._prefill_traces,
            # the route the compiled steps took through ops/paged_attention
            # (a static choice by platform; the engine forces none)
            "paged_attention_impl": default_paged_attention_impl(),
            # kv_dtype policy: bytes one cached token moves/holds (K+V
            # payload + scales across layers) and how many max-length
            # requests the pool can hold concurrently — the capacity rows
            # `serve --auto-blocks` reports ratios of
            "kv_dtype": self.kv_dtype,
            "kv_bytes_per_token": self.kv_bytes_per_token,
            "kv_bytes_per_block": self.kv_bytes_per_token * self.config.block_size,
            "kv_slot_capacity": self.kv_slot_capacity,
            # which layers keep what (models/cache.py): paged K/V by token,
            # slot state by slot, whatever the sequence's length
            "kv_layers": self._cache_spec.paged_layers,
            # the kinds of paged layer (1: every one keeps the whole past; the
            # kinds' own numbers follow where there are more)
            "kv_kinds": len(self._kinds),
            # a latent pool (0: K and V per kv head): the entries of a row that
            # are also the values, and what a token's rows cost as stored
            # (576 values kept 640 wide: kv_bytes_per_token says the same)
            "latent_rank": self._cache_spec.latent_rank,
            "latent_bytes_per_token": (
                self.kv_bytes_per_token if self._cache_spec.latent_rank else 0),
            "state_layers": self._cache_spec.state_layers,
            "state_dtype": next(
                (leaf.dtype for leaf in self._cache_spec.slot_state.values() if leaf.dtype),
                None),
            "state_bytes_per_slot": self.state_bytes_per_slot,
            "state_bytes_total": self.state_bytes_per_slot * self.config.num_slots,
            "state_resets_total": self._state_resets,
            "prefix_cache": self.radix is not None,
            "free_blocks": self.allocator.free_count,
            # blocks live requests hold (shared prefix blocks included);
            # blocks held ONLY by the radix cache are reported separately —
            # at idle, allocated_blocks is 0 and free + cached == usable
            "allocated_blocks": self.allocator.allocated_count - cached_exclusive,
            "cached_blocks": cached,
            "slot_occupancy_mean": (
                self._occupancy_sum / self._iterations if self._iterations else 0.0
            ),
            "prefix_hit_tokens": sched.prefix_hit_tokens,
            "prefix_hit_ratio": (
                sched.prefix_hit_tokens / sched.prompt_tokens_admitted
                if sched.prompt_tokens_admitted
                else 0.0
            ),
            "preemptions": self._preemptions,
            "swapped_out_blocks": self._swapped_out_blocks,
            "swapped_in_blocks": self._swapped_in_blocks,
            "out_of_blocks_total": self._out_of_blocks_total,
            "deadline_expired_total": self._deadline_expired,
            # time to first token by where it went (monotone totals over
            # the first tokens emitted since reset): queue = arrival ->
            # first admission; own prefill = time inside this request's
            # _prefill_one_chunk calls; the rest of ttft_sum_s was spent
            # admitted, waiting behind decode rounds and other prompts
            "first_tokens_total": self._first_tokens_total,
            "ttft_sum_s": self._ttft_sum_s,
            "ttft_queue_sum_s": self._ttft_queue_sum_s,
            "ttft_own_prefill_sum_s": self._ttft_own_prefill_sum_s,
            "ttft_prefill_iterations_sum": self._ttft_prefill_iterations_sum,
            # paged attention's work: table entries walked (each row's own,
            # ceil of its length over the block size, dead slots one) against
            # the entries its tables hold, both x the layers that were run
            "paged_entries_walked_total": self._paged_entries_walked,
            "paged_entries_table_total": self._paged_entries_table,
            # the kernel's softmax steps over the walked entries, a tile of
            # ops/paged_attention.py's _TILE entries each (a row's last one
            # part full): walked / (tiles x paged_tile_entries) is the tiles' fill.
            # A chunk's call takes a wider tile a step: the step is booked as the
            # tiles of paged_tile_entries it holds, and counted beside them
            "paged_tiles_walked_total": self._paged_tiles_walked,
            "paged_tile_entries": tile_entries(self._mb, bool(self._cache_spec.latent_rank)),
            "paged_chunk_steps_total": self._paged_chunk_steps,
            # the slot state's work: states a decode dispatch had to step
            # (live lanes x burst x state layers: what ops/ssm.py's kernel
            # walks) against those the cache holds for every slot
            "state_slots_live_total": self._state_slots_live,
            "state_slots_held_total": self._state_slots_held,
            # the pick's work: runs of pick_tokens, and those in which some
            # lane samples (the only ones that sort the vocabulary and draw)
            "pick_dispatches_total": self._pick_dispatches,
            "pick_draw_dispatches_total": self._pick_draw_dispatches,
            # the rows of a prompt's chunk the head runs on: the one the
            # first token is picked from, or none (a block model's chunk)
            "head_rows_per_chunk": int(self._block is None),
        }
        if self._block is not None:
            # tokens are counted as emitted and forwards as run: a round is
            # denoise_steps + 1 forwards and commits block_length positions
            # a live lane, of which EOS and length cuts emit fewer; the head
            # and the pick of a denoise pass run on its sub-block of every slot
            out.update(block_length=self._block.block_length,
                       denoise_steps=self._denoise_steps,
                       head_rows_per_pass=(self.config.num_slots * self._block.block_length
                                           // self._denoise_steps),
                       block_round_refuses=dict(self.block_round_refuses),
                       **self._block_stats())
        if self._step_counters:
            # summed at each harvest, by the engine's thread alone: a reading
            # lacks the round in flight and the chunks dispatched since
            out.update(self._model_stats)
            out.update({name: total.tolist() for name, total in self._step_counters.items()})
        out.update(self._window_stats())
        out.update(self._spec_stats())
        out.update(self._sampling_stats())
        out.update(self._hbm_watermarks())
        if out["hbm_bytes_source"] == "memory_stats":
            # every local device, not only the first: a program that sits
            # on device 0 of a four-chip host shows here
            out["hbm_used_bytes_per_device"] = [
                int(d.memory_stats()["bytes_in_use"]) for d in jax.local_devices()
            ]
        if self.usage is not None:
            # totals + capped by_tenant + heavy hitters + the conservation
            # partner totals (device_wait_seconds / pool_block_seconds)
            out["usage"] = self.usage.snapshot()
        if self._flight is not None:
            # host_fraction + iteration p50/p99 + per-phase breakdowns
            # over the ring window (empty until an iteration records)
            out.update(self._flight.summary())
        if self.prefix_cache_off_reason is not None:
            out["prefix_cache_off_reason"] = self.prefix_cache_off_reason
        if self.radix is not None:
            out["radix_inserted_blocks"] = self.radix.inserted_blocks
            out["radix_evicted_blocks"] = self.radix.evicted_blocks
        if self._swap is not None:
            out["swap_used_blocks"] = self._swap.used_blocks
            out["swap_capacity_blocks"] = self._swap.capacity_blocks
            out["swap_pool_host_bytes"] = (
                self._swap.capacity_blocks * self._swap.bytes_per_block
            )
        if self.mesh is not None:
            from ..mesh import mesh_axis_sizes

            out["mesh"] = mesh_axis_sizes(self.mesh)
        if self.retrace_report is not None:
            out["retrace_report"] = self.retrace_report
        if self.hbm_preflight is not None:
            out["hbm_preflight"] = self.hbm_preflight
        if self._start_time is not None:
            elapsed = time.perf_counter() - self._start_time
            out["elapsed_s"] = elapsed
            out["tokens_per_sec"] = self._tokens_emitted / elapsed if elapsed > 0 else 0.0
        # latency percentiles over the completion window, overall and per
        # priority class — the per-tenant-SLO groundwork: "p99 TTFT" alone
        # hides an interactive regression behind a batch flood
        window = list(self._completed)
        for attr, key in (("ttft_s", "ttft_s"), ("tpot_s", "tpot_s")):
            values = [getattr(r, attr) for r in window if getattr(r, attr) is not None]
            if not values:
                continue
            entry = {
                "p50": float(np.percentile(values, 50)),
                "p99": float(np.percentile(values, 99)),
            }
            by_class = {}
            for cls in {r.priority for r in window}:
                cls_values = [
                    getattr(r, attr) for r in window
                    if r.priority == cls and getattr(r, attr) is not None
                ]
                if cls_values:
                    by_class[cls] = {
                        "p50": float(np.percentile(cls_values, 50)),
                        "p99": float(np.percentile(cls_values, 99)),
                    }
            if by_class:
                entry["by_class"] = by_class
            out[key] = entry
        return out

    # -- iteration internals -------------------------------------------------

    def _fl_begin(self) -> None:
        """Open the iteration: its ``serve/iteration`` span, the
        ``serve/schedule`` span under it, and — when the recorder is on —
        the flight accounting in the "schedule" phase, all on one clock
        read (plus the iteration's anchor on the profiler's clock)."""
        fl = self._flight
        if self._fl_span is not None:
            # the last iteration raised between two boundaries: its spans
            # end here, not in the open-span registry of a hang report
            if self._fl_part_span is not None:
                span_exit(self._fl_part_span)
                self._fl_part_span = None
            span_exit(self._fl_span)
            span_exit(self._fl_iter_span)
        if fl is None:
            t = None
        else:  # the two clocks read back to back: one instant, two names
            t, self._fl_unix_ns = time.perf_counter(), time.time_ns()
        self._fl_iter_span = span_enter(
            trace_span(_ITERATION_SPAN, iteration=self._iterations + 1), t
        )
        self._fl_span = span_enter(trace_span(_PHASE_SPANS["schedule"]), t)
        self._fl_cur = "schedule"
        if fl is None:
            self._fl_phases = None
            return
        self._fl_t0 = self._fl_last = t
        self._fl_phases = dict.fromkeys(ITERATION_PHASES, 0.0)
        self._fl_intervals = []
        self._fl_parts = []
        self._fl_overlap = 0.0
        # hidden-overlap rule: an interval counts as hidden iff a round
        # was in flight when it OPENED (and it is not device_wait) — the
        # schedule work at the top of an async steady-state iteration runs
        # entirely under the previous round
        self._fl_hidden = self._inflight is not None
        fl.current_phase = "schedule"

    def _fl_close(self) -> tuple:
        """Close the open interval into its phase bucket and the interval
        list, and the open part with it, on one read; returns ``(stamp,
        duration)`` — ``(None, None)`` when the recorder is off, where a
        boundary reads no clock."""
        if self._fl_phases is None:
            self._fl_part_close(None)
            return None, None
        t = time.perf_counter()
        self._fl_part_close(t)
        dt = t - self._fl_last
        self._fl_phases[self._fl_cur] += dt
        self._fl_intervals.append(
            (self._fl_cur, self._fl_last - self._fl_t0, t - self._fl_t0)
        )
        if self._fl_hidden:
            self._fl_overlap += dt
        self._fl_last = t
        return t, dt

    def _fl_switch(self, phase: str, part: str | None = None) -> float | None:
        """THE phase boundary: close the open interval (bucket, interval
        list, ``serve/<phase>`` span, the open part) and open ``phase`` —
        and ``part`` of it, where the phase starts with one — on the same
        clock read. Phases may be re-entered (the async loop visits "harvest"
        both at the harvest point and for bookkeeping) — the buckets
        accumulate, and their sum telescopes to the iteration wall
        exactly, which ``FlightRecorder.record`` asserts. Returns the
        closed interval's duration (None when the recorder is off) — the
        usage ledger accrues the EXACT ``device_wait`` float the flight
        recorder does, which is what makes Σ per-request decode shares ==
        flight ``device_wait`` an identity, not an estimate."""
        t, dt = self._fl_close()
        span_exit(self._fl_span, t)
        self._fl_span = span_enter(trace_span(_PHASE_SPANS[phase]), t)
        self._fl_cur = phase
        if part is not None:
            self._fl_part_open(part, t)
        if dt is not None:
            # decided at OPEN time: device_wait is by definition the
            # residual the host could NOT hide, so it never accrues overlap
            self._fl_hidden = self._inflight is not None and phase != "device_wait"
            self._flight.current_phase = phase
        return dt

    def _fl_part_open(self, name: str, t: float | None) -> None:
        self._fl_part_span = span_enter(trace_span(_PART_SPANS[name]), t)
        self._fl_part_at = (name, t)

    def _fl_part_close(self, t: float | None) -> None:
        span = self._fl_part_span
        if span is None:
            return
        self._fl_part_span = None
        span_exit(span, t)
        if self._fl_phases is not None:
            name, t_open = self._fl_part_at
            self._fl_parts.append((name, t_open - self._fl_t0, t - self._fl_t0))

    def _fl_part(self, name: str | None, need: bool = False) -> float | None:
        """A part boundary inside the open phase: close the open part (its
        ``serve/<phase>/<part>`` span, its ``parts`` row) and open ``name``
        (``"<phase>/<part>"``; None: the phase's rest) on ONE clock read,
        which every observer of that boundary takes. With the recorder off
        no clock is read and None returned, as at a phase boundary — unless
        an observer ``need``s the stamp whatever is recording (a prefill
        chunk's own-prefill accounting): that read is then the boundary's."""
        t = time.perf_counter() if need or self._fl_phases is not None else None
        self._fl_part_close(t)
        if name is not None:
            self._fl_part_open(name, t)
        return t

    def _fl_finish(self):
        """Close the last interval and both spans; returns ``(t0, wall_s,
        phases, overlap_hidden_s)`` for ``FlightRecorder.record`` (None
        when the recorder is disabled)."""
        t, _ = self._fl_close()
        span_exit(self._fl_span, t)
        span_exit(self._fl_iter_span, t)
        self._fl_span = self._fl_iter_span = None
        self._fl_cur = "idle"
        if t is None:
            return None
        phases, self._fl_phases = self._fl_phases, None
        return self._fl_t0, t - self._fl_t0, phases, self._fl_overlap

    def _harvest_inflight(self, finished: list[Request]) -> None:
        """Blocking harvest of the in-flight round: ONE device_get of
        everything the round surfaces, then token emission through the
        same ``_emit_token`` path both engine modes share (eos / length /
        grammar-final / stop-trim are host state, so finish semantics are
        inherited, not re-implemented). A member that finished while the
        round was in flight emits nothing — its lane result is discarded
        exactly like a mid-burst eos tail."""
        rd = self._inflight
        if rd is None:
            return
        u = self.usage
        pre = None if u is None else [len(r.output_tokens) for r in rd.live]
        self._fl_switch("device_wait")
        # flight disabled: the ledger stamps its own device_wait interval
        # around the blocking device_get (flight enabled: it reuses the
        # EXACT float _fl_switch closed, so the two totals are identical)
        t_dw = (
            time.perf_counter()
            if u is not None and self._fl_phases is None
            else 0.0
        )
        # the model's step counters (this round's and the prefill chunks'
        # since the last harvest) ride the SAME device_get as the tokens (a
        # speculative round hands none back; the chunks' are then fetched
        # where they are summed)
        counted = (rd.counters, self._pending_counters) if self._step_counters else ()
        if rd.kind == "spec":
            tok_seq, accept = (
                np.asarray(x) for x in jax.device_get((rd.toks, rd.accept))
            )
        elif rd.harvest_lp or rd.kind == "block":
            # the logprob surfaces ride the SAME device_get — no second
            # dispatch, no extra sync point
            next_toks, logps, tvals, tids, *counted = jax.device_get(
                (rd.toks, rd.logps, rd.tvals, rd.tids, *counted))
            next_toks, logps, tvals, tids = (
                np.asarray(x) for x in (next_toks, logps, tvals, tids))
        else:
            next_toks, *counted = jax.device_get((rd.toks, *counted))
            next_toks = np.asarray(next_toks)  # [burst, slots]
        if counted:
            self._pending_counters = []
            self._sum_step_counters(*counted)
        self._inflight = None
        dw = self._fl_switch("harvest", "harvest/emit")
        if u is not None and dw is None:
            dw = time.perf_counter() - t_dw
        if rd.kind == "spec":
            k = self.config.spec_k
            if self._tr is not None:
                self._tr.instant(
                    "serve/spec_round", slots=len(rd.live), k=k,
                    trace_ids=[r.trace_id for r in rd.live],
                    accepted=[int(accept[r.slot]) for r in rd.live],
                )
            for req in rd.live:
                a = int(accept[req.slot])
                self._spec_drafted += k
                self._spec_accepted += a
                if u is not None:
                    u.accrue_spec(req, k, a)
                if req.sampling is not None and req.sampling.do_sample:
                    # rejection-sampling health, counted over sampled slots
                    # only (greedy slots use exact-prefix acceptance)
                    self._rej_drafted += k
                    self._rej_accepted += a
                for t in range(a + 1):
                    if req.state is RequestState.FINISHED:
                        break  # mid-round eos/length: the run's tail is waste
                    self._emit_token(req, int(tok_seq[req.slot, t]), finished)
        elif rd.kind == "block":
            # [burst, slots, B]: a slot's rounds in order, a round's positions
            # in order; the first round's known positions were emitted before
            # (or are the prompt's). EOS or the length budget inside a block
            # ends the request there: the rest of the block, and the later
            # rounds, are committed and not emitted
            before = self._tokens_emitted
            for req in rd.live:
                want_lp = req.sampling is not None and req.sampling.logprobs
                first = int(rd.known[req.slot])
                for n, j in np.ndindex(next_toks.shape[0], next_toks.shape[2]):
                    if n == 0 and j < first:
                        continue
                    if req.state is RequestState.FINISHED:
                        break
                    entry = None
                    if want_lp:
                        entry = self._logprob_entry(
                            req.sampling, float(logps[n, req.slot, j]),
                            tvals[n, req.slot, j], tids[n, req.slot, j],
                        )
                    self._emit_token(
                        req, int(next_toks[n, req.slot, j]), finished, entry
                    )
            self._block_totals["block_tokens_emitted_total"] += self._tokens_emitted - before
        else:
            for req in rd.live:
                want_lp = (
                    rd.harvest_lp
                    and req.sampling is not None
                    and req.sampling.logprobs
                )
                for t in range(self.config.decode_burst):
                    if req.state is RequestState.FINISHED:
                        break  # mid-burst eos/length: tail lane-steps are waste
                    entry = None
                    if want_lp:
                        entry = self._logprob_entry(
                            req.sampling, float(logps[t, req.slot]),
                            tvals[t, req.slot], tids[t, req.slot],
                        )
                    self._emit_token(
                        req, int(next_toks[t, req.slot]), finished, entry
                    )
        if u is not None:
            # apportion the round's device_wait across its batch, weighted
            # by the tokens each request actually emitted from this
            # harvest (stop-trim can shrink output_tokens — clamp at 0);
            # an all-discarded round splits equally so no interval is lost
            emitted = [
                max(0, len(r.output_tokens) - p) for r, p in zip(rd.live, pre)
            ]
            shares = (
                [(r.request_id, e) for r, e in zip(rd.live, emitted) if e]
                if any(emitted)
                else [(r.request_id, 1) for r in rd.live]
            )
            u.accrue_decode(dw, shares)
        self._fl_part(None)

    def _sum_step_counters(self, of_round, of_chunks) -> None:
        """Add fetched step counters to the running sums: a decode round's
        ``{name: [burst, ...]}`` (``None`` for a round without any) and each
        prefill chunk's ``{name: [...]}``."""
        for counters in ([of_round] if of_round else []) + list(of_chunks):
            for name, total in self._step_counters.items():
                got = np.asarray(counters[name], np.int64)
                total += got.reshape(-1, *total.shape).sum(axis=0)

    def _fence_inflight(self) -> bool:
        """Synchronize with the in-flight round before host code touches
        pool rows it may still be writing (swap-out's device_get). The
        round is harvested into the backlog — its tokens land on their
        requests NOW (an in-flight member already owns that token in the
        synchronous engine's timeline), any finishes park until the step's
        harvest point drains them into the finished list — and the evict
        sweep runs so the caller's capacity math sees the freed slots.
        Returns True when a round was actually fenced."""
        if self._inflight is None:
            return False
        prev = self._fl_cur if self._fl_phases is not None else None
        self._harvest_inflight(self._harvest_backlog)
        self.scheduler.evict_finished()
        if prev is not None:
            # resume the interrupted phase: the fence's device_wait +
            # harvest intervals were attributed; the remainder of the
            # interrupted phase keeps telescoping (a round is in flight
            # only while ``schedule`` runs, which has no part to resume)
            self._fl_switch(prev)
        return True

    def _admit_and_place(self) -> None:
        """Admission plus its device obligations (CoW copies, swap-in
        restores), looped with priority preemption: when the head of the
        waiting queue outranks a running request and cannot be admitted
        (no slot, or no blocks even after cache eviction), the
        lowest-priority victim is swapped to host DRAM and admission
        retries. Strictly-higher rank only — equal classes never thrash
        each other at admission."""
        sched = self.scheduler
        while True:
            for req in sched.admit():
                now = time.perf_counter()
                if req.admit_time is None:  # a re-admission keeps the first
                    req.admit_time = now
                if self._tr is not None:
                    self._tr.request_instant(
                        req.trace_id, "req/admit", ts=now, slot=req.slot,
                        queued_s=now - req.arrival_time,
                        radix_hit_tokens=req.matched_tokens,
                        restored=req.preempted,
                    )
                self._place_admitted(req)
            head = sched.peek_head()
            if head is None or self._swap is None:
                return
            victim = sched.pick_victim()
            if victim is None or priority_rank(victim.priority) <= priority_rank(
                head.priority
            ):
                return
            if not self._swap_out(victim):
                return  # swap full: the head waits its turn

    def _place_admitted(self, req: Request) -> None:
        """The device half of admission: restore a preempted request's
        swapped rows into its freshly allocated blocks, or run the pending
        copy-on-write block copy for a partial-prefix hit. A model's
        per-slot state starts from zero for whoever is placed in the slot —
        a new request, or one preempted and now recomputed."""
        if self._cache_spec.slot_state:
            slot = np.int32(req.slot)
            for name in self._cache_spec.slot_state:
                self._cache[name] = self._zero_slot_fn(self._cache[name], slot)
            self._state_resets += 1
        if req.swap_plan:
            swap_t0 = time.perf_counter() if self._tr is not None else 0.0
            # one gathered scatter per pool (mirrors _swap_out's batched
            # device_get), padded with null-block zero rows
            n = len(req.swap_plan)
            m = 1 << max(0, (n - 1).bit_length())
            layers, _, bs, width = self._kp.shape
            dtype = np.dtype(self._kp.dtype)
            ids = np.full((m,), NULL_BLOCK, np.int32)
            k_rows = np.zeros((layers, m, bs, width), dtype)
            v_rows = np.zeros_like(k_rows)
            ks_rows = vs_rows = None
            if self._quantized:
                ks_rows = np.ones((layers, m, bs, self._kv_heads), np.float32)
                vs_rows = np.ones_like(ks_rows)
            for j, (idx, handle) in enumerate(req.swap_plan):
                ids[j] = req.blocks[idx]
                k, v, ksc, vsc = self._swap.load(handle)
                # the host mirror keeps heads apart; the pool folds them
                k_rows[:, j] = k.reshape(layers, bs, width)
                v_rows[:, j] = v.reshape(layers, bs, width)
                if self._quantized:
                    ks_rows[:, j] = ksc
                    vs_rows[:, j] = vsc
            self._kp = self._write_blocks_fn(self._kp, ids, k_rows)
            self._vp = self._write_blocks_fn(self._vp, ids, v_rows)
            if self._quantized:
                # scale rows ride the same batched restore — a quantized
                # block without its scales is garbage, so they move as one
                self._ks = self._write_blocks_fn(self._ks, ids, ks_rows)
                self._vs = self._write_blocks_fn(self._vs, ids, vs_rows)
            for _, handle in req.swap_plan:
                self._swap.release(handle)
            self._swapped_in_blocks += n
            req.swap_plan = []
            req.preempted = False
            if self.usage is not None:
                # restored blocks re-enter the held count (admit() stamped
                # the pre-restore count with swap_plan still pending)
                self.usage.accrue_swap(
                    req, bytes_in=n * self._swap.bytes_per_block
                )
                self.usage.update_blocks(req)
            if self._tr is not None:
                # seconds ride the event: swap-in stalls are exactly the
                # tail-latency share `trace tail` attributes to this phase
                self._tr.request_instant(
                    req.trace_id, "req/swap_in", blocks=n,
                    seconds=time.perf_counter() - swap_t0,
                )
            if req.state is RequestState.DECODE and req.output_tokens:
                # resume feeding the last emitted token at context_len (a
                # block model feeds none: its rounds start from the request)
                self._pending_tok[req.slot] = req.output_tokens[-1]
        elif req.cow is not None:
            src, dst = req.cow
            # every leaf of the pool, payload AND scales: the CoW copy is
            # byte-exact, so the private copy dequantizes identically to the
            # cached block
            for leaf in self._pool_arrays:
                self._cache[leaf] = self._copy_block_fn(
                    self._cache[leaf], np.int32(src), np.int32(dst))
            self.allocator.decref([src])  # drop the eviction pin
            req.cow = None

    def _swap_out(self, victim: Request) -> bool:
        """Preempt ``victim``: device_get its unshared blocks into the host
        swap pool, release them, free the slot, and re-queue the request at
        the front of its priority class. "Unshared" means no *other live
        request* reads the block: a block shared only with the radix cache
        is swapped too (the victim's reference drops; the cache's copy
        stays resident at refcount 1, LRU-evictable — retaining it under
        the victim's ref would pin capacity the preemption exists to
        free). Blocks another live request maps keep the victim's
        reference and stay resident — their HBM is shared anyway. Returns
        False when the swap pool cannot hold the victim (caller falls back
        to truncation or waiting)."""
        # fence FIRST: an in-flight round may still be writing the
        # victim's rows — and holds a token the synchronous engine would
        # already have emitted, which must land on the victim before it
        # re-queues (pending_tok on resume is output_tokens[-1]). The
        # fence may finish the victim (eos/length on the harvested token)
        # or free its slot entirely; capacity is then already available
        # and there is nothing left to swap — report success so the
        # caller retries admission/growth instead of picking a new victim.
        if self._fence_inflight() and (
            victim.state is RequestState.FINISHED or victim.slot is None
        ):
            return True
        swappable = []
        for i, b in enumerate(victim.blocks):
            rc = self.allocator.refcount(b)
            if rc == 1 or (
                rc == 2 and self.radix is not None and self.radix.is_cached(b)
            ):
                swappable.append(i)
        if self._swap is None or not self._swap.can_hold(len(swappable)):
            return False
        swap_t0 = time.perf_counter() if self._tr is not None else 0.0
        plan: list[tuple[int, int]] = []
        released = [victim.blocks[i] for i in swappable]
        if released:
            # ONE device round trip for the whole victim: the 2–4 pool
            # gathers (k/v rows plus scale mirrors when quantized) ride a
            # single device_get of a tuple, not one blocking transfer
            # each; ids padded to a power of two (null-block reads, rows
            # discarded host-side) so the gather compiles O(log blocks)
            # executables, symmetric with _place_admitted's restore
            n = len(released)
            m = 1 << max(0, (n - 1).bit_length())
            idx = np.full((m,), NULL_BLOCK, np.int32)
            idx[:n] = released
            gathers = [self._kp[:, idx], self._vp[:, idx]]
            if self._quantized:
                gathers += [self._ks[:, idx], self._vs[:, idx]]
            rows = jax.device_get(tuple(gathers))
            k_rows, v_rows = rows[0], rows[1]  # [layers, m, bs, kv*hd]
            ks_rows = vs_rows = None
            if self._quantized:
                ks_rows, vs_rows = rows[2], rows[3]  # [layers, m, bs, kv]
            for j, i in enumerate(swappable):
                plan.append((
                    i,
                    self._swap.store(
                        k_rows[:, j].reshape(self._swap.block_shape),
                        v_rows[:, j].reshape(self._swap.block_shape),
                        None if ks_rows is None else ks_rows[:, j],
                        None if vs_rows is None else vs_rows[:, j],
                    ),
                ))
        # refcount-1 blocks return to the freelist; cache-shared ones stay
        # allocated under the cache's own (now sole, evictable) reference
        self.allocator.decref(released)
        victim.swap_plan = plan
        self.scheduler.requeue_preempted(victim)
        self._preemptions += 1
        self._swapped_out_blocks += len(plan)
        if self.usage is not None:
            # swapped blocks leave the victim's held count (host DRAM is
            # not pool occupancy); retained shared blocks keep accruing
            self.usage.accrue_swap(
                victim, bytes_out=len(plan) * self._swap.bytes_per_block
            )
            self.usage.update_blocks(victim)
        if self._tr is not None:
            self._tr.request_instant(
                victim.trace_id, "req/preempt", blocks=len(plan),
                swap_out_s=time.perf_counter() - swap_t0,
            )
        return True

    def _preempt_by_recompute(self, victim: Request) -> bool:
        """Preempt ``victim`` with nothing kept: its blocks go back to the
        pool and it re-queues at the front of its class; on re-admission its
        slot's state is zeroed and prompt plus emitted tokens are prefilled
        again (:meth:`_prefill_one_chunk`). For a model whose past is not
        all in blocks — there is no host copy of a recurrent state to
        restore. Always succeeds."""
        # fence first, as _swap_out does: the in-flight round holds a token
        # that must land on the victim before it re-queues
        if self._fence_inflight() and (
            victim.state is RequestState.FINISHED or victim.slot is None
        ):
            return True
        self.allocator.decref(victim.blocks)
        victim.blocks = []
        self.scheduler.release_window_blocks(victim)  # every kind's go back
        victim.prefill_pos = 0
        self.scheduler.requeue_preempted(victim, recompute=True)
        self._preemptions += 1
        if self.usage is not None:
            self.usage.update_blocks(victim)
        if self._tr is not None:
            self._tr.request_instant(victim.trace_id, "req/preempt", blocks=0, recompute=True)
        return True

    def _release_expired_queued(self, req: Request) -> None:
        """A request that expired while *queued* holds no slot, but a
        preempted one still owns swap handles (host DRAM) and references on
        blocks it shares with live requests — release both. Pure block-table
        and refcount edits; the compiled executables never run for it."""
        if req.swap_plan:
            for _, handle in req.swap_plan:
                self._swap.release(handle)
            swapped = {idx for idx, _ in req.swap_plan}
            retained = [b for i, b in enumerate(req.blocks) if i not in swapped]
            if retained:
                self.allocator.decref(retained)
            req.swap_plan = []
        req.blocks = []
        self.scheduler.release_window_blocks(req)
        if self.usage is not None:
            self.usage.update_blocks(req)

    def _force_finish_out_of_blocks(
        self, req: Request, finished: list[Request]
    ) -> None:
        req.finish_reason = "out_of_blocks"
        req.finish_time = time.perf_counter()
        req.state = RequestState.FINISHED
        self._out_of_blocks_total += 1
        finished.append(req)
        # free the blocks NOW (not at next step's evict sweep) so the
        # requests this truncation is making room for can grow this
        # iteration
        self.scheduler.evict_finished()

    def _sync_block_table(self, req: Request) -> None:
        """The slot's tables from what the request holds, every kind's in
        step: the first kind's blocks in order from entry 0; a window kind's
        at the entries it holds, the null block behind the window and ahead
        of what is allocated."""
        row = self._block_tables[req.slot]
        row[:] = 0
        if len(self._kinds) == 1:
            row[: len(req.blocks)] = req.blocks
            return
        row[0, : len(req.blocks)] = req.blocks
        for n, kind in enumerate(self._kinds[1:], start=1):
            held = req.window_blocks.get(kind.name)
            if held:
                row[n, list(held)] = list(held.values())

    def _advance_windows(self, req: Request, first: int, end: int) -> None:
        """Before a dispatch whose queries of ``req`` stand at ``first .. end
        - 1``: every window kind holds a block for each table entry from the
        one that holds the oldest visible position (``first - window + 1``)
        to the one that holds ``end - 1``, and gives back the entries wholly
        behind that span. No query of this dispatch or of a later one sees a
        freed position (queries only move on), and every dispatch that could
        was handed to the device before this one: a decode round was
        harvested before the next is built, and a prompt's earlier chunk is
        ahead of whatever program rewrites the block, in the device's order.
        A kind's pool holds every slot's window (``window_num_blocks``), so
        the allocation cannot fail."""
        bs = self.config.block_size
        for kind in self._window_kinds:
            held = req.window_blocks.setdefault(kind.name, {})
            alloc = self.window_allocators[kind.name]
            lo = max(first - kind.window + 1, 0) // bs
            hi = min((end - 1) // bs, self._mb - 1)
            # ``held`` is a run of consecutive entries in rising order (what
            # is added below, less what went from its front)
            behind = list(itertools.takewhile(lambda j: j < lo, held))
            if behind:
                alloc.free([held.pop(j) for j in behind])
                self._window_blocks_freed += len(behind)
            missing = range(max(lo, next(reversed(held), lo - 1) + 1), hi + 1)
            if missing:
                held.update(zip(missing, alloc.allocate(len(missing))))

    def _count_paged_entries(self, first, queries: int, layers: int, calls: int = 1) -> None:
        """Book one dispatch's paged-attention calls: ``first`` holds the
        first query's cache position of every row of every step (any
        shape), each row asks ``queries`` positions, ``layers`` layers run
        it, ``calls`` times over (the forwards of a block round). A row walks
        the entries up to its last query's block — the trip count
        ``ops/paged_attention.py`` reads from the same positions (a block
        model's chunks and rounds end on a block's end, where the last
        query's last visible position is the last query), a tile of them a
        softmax step (``tiles_walked``) - the tile of a call of this many
        stacked query rows, ``queries`` x the query heads a kv head: a
        chunk's wide step is booked as the ``paged_tile_entries``-entry tiles
        it holds, and in ``paged_chunk_steps_total`` as itself."""
        first = np.asarray(first, np.int64)
        bs, mb, latent = self.config.block_size, self._mb, bool(self._cache_spec.latent_rank)
        narrow = tile_entries(mb, latent)
        # a model with window kinds runs every kind's layers (``layers`` is
        # their sum): each walks from the entry that holds a row's oldest
        # visible position (``window_walk``: entry 0 without a window), and
        # the entries a window layer's walk left behind are booked beside
        by_kind = bool(self._window_kinds)
        walks = ([(k.window, k.layers, k.kv_heads) for k in self._kinds] if by_kind
                 else [(0, layers, self._kv_heads)])
        for window, n, kv_heads in walks:
            lo, end = window_walk(first, queries, window, bs, mb)
            n *= calls
            walked = int((end - lo).sum()) * n
            self._paged_entries_walked += walked
            self._paged_entries_table += first.size * mb * n
            stacked = queries * (self._query_heads // kv_heads)
            steps = int(tiles_walked(
                end, mb, latent, first=lo if window else None, stacked=stacked).sum()) * n
            wide = tile_entries(mb, latent, stacked)
            self._paged_tiles_walked += steps * -(-wide // narrow)
            if wide > narrow:
                self._paged_chunk_steps += steps
            if by_kind:
                self._paged_by_kind["window" if window else "full"] += walked
                self._paged_by_kind["behind_window"] += int(lo.sum()) * n

    def _count_state_slots(self, active) -> None:
        """Book one decode dispatch's slot-state steps from the mask the
        executable is handed: every step of the burst, every layer that
        keeps state steps the live lanes' states - the list
        ``ops/ssm.py:live_slots`` derives from the same mask - of the
        ``num_slots`` the cache holds."""
        steps = self.config.decode_burst * self._cache_spec.state_layers
        self._state_slots_live += int(active.sum()) * steps
        self._state_slots_held += self.config.num_slots * steps

    def _count_pick(self, lanes, runs: int = 1) -> None:
        """Book ``runs`` runs of :func:`sampling.pick_tokens` (one a decode
        dispatch or first pick; one a denoise pass of a block round) from the
        host's copy of the lanes they are handed: the sampler stage runs when
        some lane samples. The cached idle lanes are blank by construction
        and live on the device; they are not read back."""
        self._pick_dispatches += runs
        if lanes is not self._lanes_idle and lanes["sample"].any():
            self._pick_draw_dispatches += runs

    def _prefill_one_chunk(self, req: Request, finished: list[Request]) -> None:
        """One chunk of one prompt, in parts: ``prefill/operands`` up to the
        compiled call, ``prefill/call`` (the host's side of the dispatch),
        on a prompt's last chunk ``prefill/first_pick`` and
        ``prefill/first_fetch`` (:meth:`_first_token_pick`), and
        ``prefill/emit`` to the chunk's end. The stamp that opens the first
        part and the one that opens ``emit`` — on the final chunk it follows
        the blocking first-token fetch — are the request's own-prefill
        stamps: the usage ledger's prefill accrual, the ``req/prefill_chunk``
        event and ``first_token_time`` all take them instead of reading the
        clock again."""
        t0 = self._fl_part("prefill/operands", need=True)
        cfg = self.config
        c = cfg.prefill_chunk
        start = req.prefill_pos
        # a request preempted by recomputation comes back with the tokens it
        # had emitted: all but the last (still pending) are prefilled again
        # behind the prompt, and nothing is emitted for them a second time
        replay = req.recompute and bool(req.output_tokens)
        blk = self._block
        if blk is None:
            seq = list(req.prompt) + req.output_tokens[:-1] if replay else req.prompt
            total = len(seq)
        else:
            # a block model prefills whole blocks only (a chunk's last query
            # must see the end of its block); the last ``n mod B`` known
            # tokens open the first round's block as clean positions. Every
            # emitted token is committed or in that tail: none is pending
            seq = list(req.prompt) + req.output_tokens if replay else req.prompt
            total = len(seq) // blk.block_length * blk.block_length
        end = min(start + c, total)
        chunk = np.zeros((1, c), np.int32)
        chunk[0, : max(end - start, 0)] = seq[start:end]
        valid = np.zeros((1, c), bool)
        valid[0, : max(end - start, 0)] = True
        if self._window_kinds and end > start:
            self._advance_windows(req, start, end)
        self._sync_block_table(req)
        is_final = end >= total
        last_idx = np.int32((total - 1) - start if is_final else 0)

        if end > start:  # (a block model's prompt may hold no whole block to prefill)
            self._count_paged_entries([start], c, self._cache_spec.paged_layers)
            table = self._block_tables[req.slot : req.slot + 1].copy()
            pos0, slot = np.asarray([start], np.int32), np.asarray([req.slot], np.int32)
            self._fl_part("prefill/call")
            self._cache, *rest = self._prefill_fn(
                self._params, self._cache, table, pos0, chunk, valid, last_idx, slot)
            self._fl_part(None)
            if blk is None:  # (a block model's chunk hands back no logits)
                logits = rest.pop(0)
            # a chunk's step counters stay on the device until the next harvest
            self._pending_counters.extend(rest)
        req.prefill_pos = end
        req.prefill_iterations += 1
        lp_entry = None
        if is_final and req.recompute:
            # recomputed: preempted before its first token or after, the
            # request is whole again when this chunk has run
            req.recompute = req.preempted = False
        if is_final and blk is not None:
            # no token comes of a block model's prefill (the logits at a
            # position are that position's own token's): the first round
            # emits the first tokens. prefill_pos stays a prompt position
            req.prefill_pos = min(end, req.prompt_len)
            req.state = RequestState.DECODE
        elif is_final and replay:
            # the cache holds prompt + fed output again: decoding resumes
            # with the token that was pending when the request was preempted
            req.prefill_pos = req.prompt_len
            self._pending_tok[req.slot] = req.output_tokens[-1]
            req.state = RequestState.DECODE
        elif is_final:
            # picked from the prompt-final logits through the SAME lane
            # transform decode uses (position 0 of the request's derived
            # key stream); blocks until the chunk (and all before it) ran
            tok, lp_entry = self._first_token_pick(req, logits)
        t1 = self._fl_part("prefill/emit", need=True)
        req.own_prefill_s += t1 - t0
        if self.usage is not None:
            self.usage.accrue_prefill(req, t1 - t0)
        if self._tr is not None:
            # one event per CHUNK (bounded by prompt_len / prefill_chunk),
            # never per token
            self._tr.request_instant(
                req.trace_id, "req/prefill_chunk", ts=t1, start=start, end=end,
                final=is_final,
            )
        if is_final and not replay:
            if self.radix is not None:
                # the prompt's full blocks now hold valid K/V: adopt them
                # into the prefix trie (refcount+1 = the cache's reference)
                # so later admissions with the same leading tokens map them
                # (a block model's pages hold whole blocks of its own, so a
                # page's K/V depend on no token past the page's end)
                self.radix.insert(req.prompt, req.blocks)
            if blk is None:
                self._emit_token(req, tok, finished, lp_entry, now=t1)
                if req.state is not RequestState.FINISHED:
                    req.state = RequestState.DECODE
        self._fl_part(None)

    def _ensure_decode_capacity(self, req: Request, finished: list[Request]) -> None:
        """Growth for one decode lane, with swap preemption under pool
        exhaustion. Eviction of refcount-1 cached blocks happens inside
        ``grow_for_decode``; when even that fails, the lowest-priority
        victim (possibly ``req`` itself — a request never preempts a
        *higher*-priority one) is swapped to host DRAM and growth retries.
        Truncation (``out_of_blocks``) is the last resort: swap disabled or
        full, or ``req`` alone in the pool with nothing left to reclaim."""
        sched = self.scheduler
        # a model with per-slot state has no swap tier (refused at bring-up):
        # its victim gives its blocks back and is recomputed on re-admission
        # (so does a block model's where there is no swap tier: every token it
        # emitted is in its replayed prefill or its open block, none pending)
        # and so does a latent pool's, whose rows the swap tier cannot mirror
        # and so does a model with window kinds, whose swap tier is refused
        recompute = bool(self._cache_spec.slot_state or self._cache_spec.latent_rank
                         or self._window_kinds) or (
            self._block is not None and self._swap is None)
        preempt = self._preempt_by_recompute if recompute else self._swap_out
        while not sched.grow_for_decode(req, tokens_ahead=self._decode_lookahead):
            if self._swap is None and not recompute:
                # no swap tier: keep PR 4's FCFS contract — the request
                # that failed to grow is the one truncated, never an
                # innocent neighbor that fit its reservation
                self._force_finish_out_of_blocks(req, finished)
                return
            victim = sched.pick_victim() or req
            if priority_rank(victim.priority) < priority_rank(req.priority):
                victim = req  # never evict someone more important than req
            if victim is req and len(sched.active()) <= 1:
                # req is the sole tenant. Swapping itself out only helps if
                # something else would run first — a strictly higher-priority
                # waiting head admits before req's front-of-class re-queue.
                # Otherwise req re-admits immediately and ping-pongs through
                # the swap pool forever: the pool is simply too small for it,
                # and truncation is the honest answer.
                head = sched.peek_head()
                if head is None or priority_rank(head.priority) >= priority_rank(
                    req.priority
                ):
                    self._force_finish_out_of_blocks(req, finished)
                    return
            if not preempt(victim):
                # swap full: truncation may only roll downhill — a
                # strictly lower-priority victim pays, equal priority
                # keeps the requester-pays rule (no innocent neighbor
                # truncated for a peer)
                if priority_rank(victim.priority) > priority_rank(req.priority):
                    self._force_finish_out_of_blocks(victim, finished)
                    continue
                self._force_finish_out_of_blocks(req, finished)
                return
            if victim is req:
                return  # req is queued for re-admission; lane goes idle

    def _dispatch_decode(
        self, decoding: list[Request], finished: list[Request]
    ) -> None:
        """Build this round's operands and hand the ONE compiled decode
        executable to the runtime — non-blocking: the results stay device
        futures in ``self._inflight`` until ``_harvest_inflight`` lands
        them (next iteration's harvest point in async mode, immediately
        after this returns in sync mode)."""
        cfg = self.config
        self._fl_part("dispatch/capacity")
        # pass 1 — capacity: grow every lane (evicting cached blocks,
        # preempting victims, truncating last-resort). A later lane's
        # preemption may take an *earlier* lane out of its slot, so lane
        # state is only materialised in pass 2, over the survivors.
        for req in decoding:
            if req.slot is None or req.state is not RequestState.DECODE:
                continue  # preempted or force-finished by an earlier lane
            self._ensure_decode_capacity(req, finished)
        self._fl_part("dispatch/operands")
        pos0 = np.zeros((cfg.num_slots,), np.int32)
        active = np.zeros((cfg.num_slots, 1), bool)
        toks = np.zeros((cfg.num_slots, 1), np.int32)
        live: list[Request] = []
        for req in decoding:
            # a dispatch writes up to `_decode_lookahead` positions ahead
            # (capped at the request's own budget); lane-steps past the
            # budget scatter into the null block and are dropped host-side
            if req.slot is None or req.state is not RequestState.DECODE:
                continue
            if self._window_kinds:
                # as far as this dispatch writes and the request's budget goes
                self._advance_windows(req, req.context_len, min(
                    req.context_len + self._decode_lookahead,
                    req.prompt_len + req.max_new_tokens, cfg.max_seq_len))
            self._sync_block_table(req)
            pos0[req.slot] = req.context_len
            toks[req.slot, 0] = self._pending_tok[req.slot]
            active[req.slot, 0] = True
            live.append(req)
        if not live:
            self._fl_part(None)
            return
        known = n_known = None
        if self._block is not None:
            # every slot's open block: the tokens it already knows (a prompt's
            # tail; nothing once a round has run), the mask token elsewhere
            b = self._block.block_length
            toks = np.full((cfg.num_slots, b), self._block.mask_token_id, np.int32)
            known = np.zeros((cfg.num_slots, b), bool)
            for req in live:
                tail = (req.prompt + req.output_tokens)[req.context_len:]
                toks[req.slot, : len(tail)] = tail
                known[req.slot, : len(tail)] = True
            n_known = known.sum(axis=1)

        # per-slot lanes: rebuilt from the live requests on EVERY dispatch
        # (pos/ring/DFA state re-derived from the request, so preemption,
        # swap, and slot reassignment can never desynchronise them); the
        # shapes/dtypes are engine geometry — one abstract signature forever.
        # When every live request is inert the cached device-resident blank
        # dict stands in: such a batch's own lanes would differ from it in
        # `pos` and `seed` alone, which only a sampling or min-token lane reads
        if all(
            (req.sampling or self._default_sampling).inert
            and not req.grammar_row
            for req in live
        ):
            lanes = self._idle_lanes()
        else:
            lanes = blank_lanes(cfg.num_slots, cfg.rep_window)
            for req in live:
                params = req.sampling or self._default_sampling
                set_slot_lane(
                    lanes, req.slot, params,
                    # (block rounds count output positions from the open
                    # block's first position, known ones included)
                    pos=len(req.output_tokens) - (
                        0 if n_known is None else int(n_known[req.slot])),
                    grammar_row=req.grammar_row, dfa_state=req.dfa_state,
                    recent=(
                        req.prompt + req.output_tokens
                        if params.repetition_penalty != 1.0
                        else ()
                    ),
                )

        # signature capture costs ~8 shape/dtype formats per dispatch, so it
        # rides the same armed-instrumentation gate as every other hot-path
        # site (one global read each when disabled); the retrace *counter*
        # check below stays unconditional — it is just two int compares
        decode_sig = None
        if _get_sanitizer() or get_active_recorder():
            args = [
                *(("cache." + name, a) for name, a in sorted(self._cache.items())),
                ("block_tables", self._block_tables), ("pos0", pos0),
                ("toks", toks), ("active", active),
                *((("known", known),) if known is not None else ()),
                *sorted(lanes.items()),
                ("gmask", self._gmask), ("gtrans", self._gtrans),
                ("base_key", self._base_key),
            ]
            decode_sig = tuple(
                (name, tuple(np.shape(v)), str(getattr(v, "dtype", type(v).__name__)))
                for name, v in args
            )

        if self._spec is not None:
            self._spec_decode_dispatch(pos0, toks, active, lanes, live, decode_sig)
            return
        if self._block is not None:
            self._block_decode_dispatch(
                pos0, toks, known, n_known, active, lanes, live, decode_sig)
            return
        self._count_paged_entries(
            pos0 + np.arange(cfg.decode_burst)[:, None], 1, self._cache_spec.paged_layers
        )
        self._count_state_slots(active)
        self._count_pick(lanes)
        next_toks, logps, tvals, tids, *counters = self._call_decode(
            decode_sig, pos0, toks, active, lanes, self._gmask, self._gtrans, self._base_key)
        if self._tr is not None:
            # request identity on the decode timeline WITHOUT per-token
            # spans: one instant per dispatch carries the whole slot batch
            self._tr.instant(
                "serve/decode_batch", slots=len(live),
                burst=cfg.decode_burst,
                trace_ids=[r.trace_id for r in live],
            )
        harvest_lp = cfg.logprobs_topn > 0 and any(
            r.sampling is not None and r.sampling.logprobs for r in live
        )
        self._inflight = _InFlightRound(
            kind="burst", live=live, toks=next_toks, logps=logps,
            tvals=tvals, tids=tids, harvest_lp=harvest_lp,
            counters=counters[0] if counters else None,
        )

    def _call_decode(self, decode_sig: tuple | None, *operands) -> list:
        """The call of the ONE decode executable (a burst, a speculative
        round or block rounds: whichever was built), with a copy of the
        block tables, as the ``dispatch/call`` part; keeps the cache it
        hands back and returns the rest."""
        tables = self._block_tables.copy()
        self._fl_part("dispatch/call")
        self._cache, *out = self._decode_fn(self._params, self._cache, tables, *operands)
        self._fl_part(None)
        self._check_one_executable(decode_sig)
        return out

    def _spec_decode_dispatch(
        self, pos0, toks, active, lanes, live: list[Request],
        decode_sig: tuple | None,
    ) -> None:
        """One speculative round: dispatch the single compiled
        draft+verify executable; ``_harvest_inflight`` later emits each
        live slot's accepted prefix + correction through the SAME
        host-side ``_emit_token`` path the plain engine uses (eos and
        length budgets are host state, so greedy parity with the non-spec
        engine is inherited, not re-implemented). Rollback is implicit: a
        slot advances by ``accept+1`` positions; the rejected rows beyond
        that are re-scattered by the next round before anything can
        attend them."""
        # the draft's k single-query steps through its own layers, then the
        # one verify forward of k + 1 queries through all of them
        k = self.config.spec_k
        self._count_paged_entries(pos0 + np.arange(k)[:, None], 1, self._spec.layers)
        self._count_paged_entries(pos0, k + 1, self._cache_spec.paged_layers)
        tok_seq, accept = self._call_decode(
            decode_sig, pos0, toks, active, lanes, self._gmask, self._gtrans, self._base_key)
        # the round's [num_slots, k+1] token matrix and [num_slots]
        # accepted-prefix vector stay device futures; the serve/spec_round
        # instant needs the accept values, so it moves to the harvest
        self._inflight = _InFlightRound(
            kind="spec", live=live, toks=tok_seq, accept=accept
        )

    def _block_decode_dispatch(
        self, pos0, toks, known, n_known, active, lanes, live: list[Request],
        decode_sig: tuple | None,
    ) -> None:
        """``decode_burst`` block rounds in one dispatch of the single
        compiled executable; ``_harvest_inflight`` later emits each live
        slot's new tokens, block by block in order, through the same
        ``_emit_token`` as every other round (EOS and length budgets are
        host state). What is counted here is what was dispatched: rounds,
        forwards x live lanes, and the positions the live lanes' rounds
        commit beyond those already known (``n_known [slots]``)."""
        cfg, b, t = self.config, self._block.block_length, self._denoise_steps
        burst, n_live = cfg.decode_burst, len(live)
        # every forward of every round asks B queries a row from the block's start
        self._count_paged_entries(
            pos0 + b * np.arange(burst)[:, None], b, self._cache_spec.paged_layers,
            calls=t + 1)
        self._count_pick(lanes, runs=burst * t)
        totals = self._block_totals
        totals["block_rounds_total"] += burst
        totals["block_slot_forwards_total"] += burst * (t + 1) * n_live
        totals["block_positions_committed_total"] += burst * b * n_live - int(n_known.sum())
        next_toks, logps, tvals, tids, *counters = self._call_decode(
            decode_sig, pos0, toks, known, active, lanes, self._gmask, self._base_key)
        if self._tr is not None:
            self._tr.instant(
                "serve/block_rounds", slots=n_live, rounds=burst, block_length=b,
                denoise_steps=t, trace_ids=[r.trace_id for r in live],
            )
        self._inflight = _InFlightRound(
            kind="block", live=live, toks=next_toks, logps=logps, tvals=tvals, tids=tids,
            harvest_lp=True, counters=counters[0] if counters else None, known=n_known,
        )

    def _check_one_executable(self, decode_sig: tuple | None) -> None:
        """ONE compiled decode executable is the engine's core contract.
        When the trace counter moves past 1, diff the dispatch's abstract
        signature against the first trace's and put the named argument in
        the failure message — "decode re-traced" alone sends the operator
        bisecting; "block_tables went (8, 32):int32 -> (8, 64):int32" names
        the bug. ``decode_sig`` is None when no instrumentation is armed
        (the counter still catches the retrace, just without arg naming).
        Armed sanitizer ⇒ raise; otherwise record + surface via
        ``stats()['retrace_report']`` and telemetry."""
        traced_now = self._decode_traces != self._decode_traces_seen
        self._decode_traces_seen = self._decode_traces
        if not traced_now or self._decode_traces <= 1:
            self._decode_sig = decode_sig
            return
        if self._decode_sig is not None and decode_sig is not None:
            from ..analysis.compiled import diff_signatures, format_signature_diff

            diff = diff_signatures(self._decode_sig, decode_sig)
            detail = (
                format_signature_diff(diff)
                if diff is not None
                else "abstract signature unchanged (params/pages identity drift?)"
            )
        else:
            detail = (
                "fingerprint not captured — enable sanitizer or telemetry "
                "for argument naming"
            )
        self._decode_sig = decode_sig
        message = (
            f"serving engine decode re-traced (compile #{self._decode_traces}; "
            f"the one-compiled-executable contract is broken) — fingerprint "
            f"diff vs previous dispatch: {detail}"
        )
        self.retrace_report = message
        tel = get_active_recorder()
        if tel:
            tel.record_event("serving_retrace", message=message)
        if _get_sanitizer():
            raise RuntimeError(message)

    def _first_token_pick(self, req: Request, logits):
        """Per-slot first-token pick from the prompt-final logits the
        prefill executable already returns: one ``[1, vocab]`` run of the
        shared :func:`sampling.pick_tokens` at output position 0 — exact
        key parity with the decode lanes, so a preempted-and-restarted
        request reproduces its first token too. ``prefill/first_pick`` is
        the host's side of that run, ``prefill/first_fetch`` the blocking
        reads of what it picked."""
        self._fl_part("prefill/first_pick")
        params = req.sampling or self._default_sampling
        lanes = blank_lanes(1, self.config.rep_window)
        set_slot_lane(
            lanes, 0, params, pos=0, grammar_row=req.grammar_row,
            dfa_state=req.dfa_state,
            recent=req.prompt if params.repetition_penalty != 1.0 else (),
        )
        self._count_pick(lanes)
        tok, logp, tvals, tids = self._first_pick_fn(
            logits[None], lanes, self._gmask, self._base_key
        )
        self._fl_part("prefill/first_fetch")
        entry = None
        if params.logprobs:
            entry = self._logprob_entry(
                params, float(logp[0]), np.asarray(tvals[0]), np.asarray(tids[0])
            )
        return int(tok[0]), entry

    @staticmethod
    def _logprob_entry(params, logp: float, top_vals, top_ids) -> dict:
        n = int(params.logprobs)
        return {
            "logprob": logp,
            "top": [
                [int(i), float(v)]
                for i, v in zip(top_ids[:n], top_vals[:n])
            ],
        }

    # -- grammar row lifecycle ------------------------------------------------

    def _acquire_grammar_row(self, g) -> int:
        """Pin one row of the device-resident grammar tables for a live
        request. Rows are refcounted by grammar hash — concurrent requests
        with the same schema share one row (and one upload). A fully-idle
        row keeps its compiled tables cached LRU-style, so the common
        serve pattern (many requests, few schemas) uploads each grammar
        once; eviction only happens when a NEW grammar needs a row and
        every free row is someone's cache entry. Runs at admission, so
        exhaustion (every row pinned by a live request) fails the
        add_request loudly instead of wedging a slot mid-decode."""
        row = self._grammar_rows.get(g.hash)
        if row is not None:
            self._row_lru.pop(g.hash, None)
            self._row_refs[row] += 1
            return row
        row = next(
            (
                r
                for r in range(1, self.config.grammar_slots + 1)
                if self._row_refs[r] == 0 and r not in self._row_grammar
            ),
            None,
        )
        if row is None:
            if not self._row_lru:
                raise ValueError(
                    f"all {self.config.grammar_slots} grammar rows are held by "
                    "live requests; raise EngineConfig.grammar_slots or retry "
                    "after a constrained request finishes"
                )
            old_hash, row = self._row_lru.popitem(last=False)
            del self._grammar_rows[old_hash]
        allow, trans = g.padded_tables(self.config.grammar_states)
        self._gmask = self._write_grammar_row_fn(
            self._gmask, jnp.int32(row), jnp.asarray(allow)
        )
        self._gtrans = self._write_grammar_row_fn(
            self._gtrans, jnp.int32(row), jnp.asarray(trans)
        )
        self._grammar_rows[g.hash] = row
        self._row_grammar[row] = g
        self._row_refs[row] += 1
        return row

    def _release_grammar_row(self, req: Request) -> None:
        row = req.grammar_row
        if not row:
            return
        req.grammar_row = 0
        self._row_refs[row] -= 1
        if self._row_refs[row] == 0:
            # idle: keep the uploaded tables as an LRU cache entry so the
            # next request with this schema skips the host→device write
            self._row_lru[self._row_grammar[row].hash] = row

    def _emit_token(
        self, req: Request, tok: int, finished: list[Request], lp_entry=None,
        now: float | None = None,
    ) -> None:
        if now is None:
            now = time.perf_counter()
        req.output_tokens.append(tok)
        self._pending_tok[req.slot] = tok
        self._tokens_emitted += 1
        params = req.sampling
        if params is not None and params.do_sample:
            self._sampled_sample += 1
        else:
            self._sampled_greedy += 1
        if lp_entry is not None:
            lp_entry["token"] = tok
            if req.logprobs is None:
                req.logprobs = []
            req.logprobs.append(lp_entry)
        if req.first_token_time is None:
            req.first_token_time = now
            self._first_tokens_total += 1
            self._ttft_sum_s += now - req.arrival_time
            self._ttft_queue_sum_s += (
                req.admit_time if req.admit_time is not None else now
            ) - req.arrival_time
            self._ttft_own_prefill_sum_s += req.own_prefill_s
            self._ttft_prefill_iterations_sum += req.prefill_iterations
            if self._tr is not None:
                self._tr.request_instant(
                    req.trace_id, "req/first_token", ts=now,
                    ttft_s=now - req.arrival_time,
                )
        eos = self.config.eos_token_id
        if eos is not None and tok == eos:
            req.finish_reason = "eos"
        elif len(req.output_tokens) >= req.max_new_tokens:
            req.finish_reason = "length"
        if req.grammar_row:
            # advance the AUTHORITATIVE automaton state host-side (the
            # in-trace advance only fed mid-burst masking); entering a
            # state with no live continuation means the match is complete
            self._grammar_masked_steps += 1
            if self.usage is not None:
                self.usage.accrue_grammar(req)
            g = self._row_grammar[req.grammar_row]
            req.dfa_state = g.advance(req.dfa_state, tok)
            if req.finish_reason is None and g.final[req.dfa_state]:
                req.finish_reason = "stop"
        if (
            req.finish_reason is None
            and params is not None
            and params.stop
        ):
            n = match_stop(req.output_tokens, params.stop)
            if n:
                # the matched stop sequence is not part of the answer
                del req.output_tokens[-n:]
                req.finish_reason = "stop"
        if req.finish_reason is not None:
            req.finish_time = now
            req.state = RequestState.FINISHED
            finished.append(req)

    # -- observability -------------------------------------------------------

    def _emit_telemetry(self, finished: list[Request]) -> None:
        tel = get_active_recorder()
        if not tel:
            return
        for req in finished:
            tel.record_serving(
                kind="request",
                request_id=req.request_id,
                trace_id=req.trace_id,
                priority=req.priority,
                tenant=req.tenant,
                prompt_tokens=req.prompt_len,
                new_tokens=len(req.output_tokens),
                ttft_s=req.ttft_s,
                tpot_s=req.tpot_s,
                finish_reason=req.finish_reason,
                # device_time_s / kv_block_seconds / swap_bytes — the
                # closed per-request account (absent on a no-ledger engine)
                **(req.usage or {}),
            )
        interval = self.config.stats_interval
        if interval and self._iterations % interval == 0:
            now = time.perf_counter()
            window_s = now - (self._last_stats_t or now)
            window_tokens = self._tokens_emitted - self._last_stats_tokens
            self._last_stats_t, self._last_stats_tokens = now, self._tokens_emitted
            sched = self.scheduler
            tel.record_serving(
                kind="step",
                iteration=self._iterations,
                tokens_per_sec=(window_tokens / window_s) if window_s > 0 else None,
                queue_depth=sched.queue_depth,
                active_slots=len(sched.active()),
                slot_occupancy=sched.occupancy,
                free_blocks=self.allocator.free_count,
                kv_dtype=self.kv_dtype,
                kv_bytes_per_token=self.kv_bytes_per_token,
                kv_slot_capacity=self.kv_slot_capacity,
                decode_compiles=self._decode_traces,
                # cumulative totals: the monitor reads a bounded JSONL tail,
                # so run-total counts must ride every row, not be re-counted
                completed_total=self._completed_total,
                tokens_total=self._tokens_emitted,
                prefix_hit_tokens=sched.prefix_hit_tokens,
                prefix_hit_ratio=(
                    sched.prefix_hit_tokens / sched.prompt_tokens_admitted
                    if sched.prompt_tokens_admitted
                    else 0.0
                ),
                preemptions=self._preemptions,
                swapped_out_blocks=self._swapped_out_blocks,
                swapped_in_blocks=self._swapped_in_blocks,
                out_of_blocks_total=self._out_of_blocks_total,
                deadline_expired_total=self._deadline_expired,
                **self._spec_stats(),
                **self._sampling_stats(),
                **self._hbm_watermarks(),
                **(
                    self._flight.telemetry_fields()
                    if self._flight is not None
                    else {}
                ),
                **(
                    {"usage": self.usage.snapshot()}
                    if self.usage is not None
                    else {}
                ),
            )
