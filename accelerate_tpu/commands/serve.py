"""``accelerate-tpu serve`` — drive the continuous-batching engine from
JSONL on stdin or a local HTTP endpoint.

Request protocol (one JSON object per line / per POST body):
``{"id": <any>, "prompt": [token ids], "max_new_tokens": <int?>,
"priority": "interactive"|"batch"?, "deadline_ms": <number?>,
"tenant": <str?>, "sampling": {...}?, "grammar": {...}?}``;
each completion is written back as
``{"id", "tenant", "tokens", "ttft_s", "tpot_s", "finish_reason"}`` plus
the usage ledger's measured costs (``device_time_s`` /
``kv_block_seconds`` / ``swap_bytes``) when accounting is on. ``priority``
defaults to ``interactive``; under pool pressure the scheduler swaps
``batch`` victims to host DRAM before ever touching interactive ones.
``deadline_ms`` is a relative budget: once it elapses the scheduler
finishes the request with ``finish_reason="deadline_exceeded"`` (partial
tokens kept, KV blocks freed the same iteration); a malformed value is
answered with an error row, like an unknown ``priority``. ``sampling``
carries per-request :class:`~accelerate_tpu.serving.SamplingParams`
fields (temperature/top_k/top_p/seed/stop/...); ``grammar`` a
constrained-decoding spec (:mod:`accelerate_tpu.serving.grammar`) —
both ride the ONE compiled decode executable as lane inputs.
Prompts are raw token ids — tokenization is deliberately out of scope (the
engine is model-zoo-generic and this box ships no tokenizer assets).
``--http`` additionally mounts the OpenAI-compatible door
(``POST /v1/completions`` + ``/v1/chat/completions``, SSE streaming and
non-streaming — :mod:`accelerate_tpu.serving.openai_api`), where string
prompts byte-tokenize and ``response_format={"type": "json_schema"}``
maps onto ``grammar``.

The engine loop owns the main thread; stdin/HTTP submissions land in a
thread-safe inbox the loop drains between iterations, so network/pipe
latency never stalls decode. ``--logging-dir`` turns on telemetry so
``accelerate-tpu monitor <dir>`` shows live serving health (tokens/s,
queue depth, slot occupancy, TTFT).

Lifecycle (the router's dispatch + drain signals):

* ``GET /healthz`` reports a real state machine — ``starting`` (engine
  building/compiling), ``ready`` (loop serving), ``draining`` (SIGTERM
  observed: admission stopped, in-flight finishing) — plus live
  ``queue_depth``/``active_slots`` gauges;
* SIGTERM reuses the resilience :class:`PreemptionHandler` flag: the loop
  observes it between iterations, stops admission (late requests get an
  error *answer*, never silence), drains everything already admitted, and
  exits 0. kill-proven in ``tests/test_router.py``.
"""

from __future__ import annotations

import json
import os
import queue
import sys
import threading
import time

#: seconds the drain loop waits for pipe-buffered stragglers after going
#: idle — lines written before the signal but not yet through the reader
#: thread still deserve answers
_DRAIN_IDLE_GRACE_S = 0.75


class ServeHealth:
    """The front end's lifecycle state machine: ``starting`` → ``ready`` →
    ``draining``. Transitions are one-way; readers (the /healthz handler,
    the stdin reader, the engine loop) only ever look at ``state``."""

    def __init__(self, replica_id: int | None = None):
        from ..analysis.lockwatch import maybe_watch

        self.replica_id = replica_id
        self._state = "starting"
        self._lock = maybe_watch(threading.Lock(), "ServeHealth._lock")

    @property
    def state(self) -> str:
        with self._lock:  # every reader goes through here (race-check RC001)
            return self._state

    @property
    def ready(self) -> bool:
        return self.state == "ready"

    @property
    def draining(self) -> bool:
        return self.state == "draining"

    def mark_ready(self) -> None:
        with self._lock:
            if self._state == "starting":
                self._state = "ready"

    def mark_draining(self) -> None:
        with self._lock:
            self._state = "draining"

    def payload(self, engine=None) -> dict:
        """The /healthz answer: state + the router's dispatch gauges."""
        out = {
            "state": self.state,
            "pid": os.getpid(),
            "replica_id": self.replica_id,
            "queue_depth": None,
            "active_slots": None,
            "num_slots": None,
        }
        if engine is not None:
            try:
                out["queue_depth"] = int(engine.scheduler.queue_depth)
                out["active_slots"] = len(engine.scheduler.active())
                out["num_slots"] = int(engine.config.num_slots)
                # cumulative engine-side deadline evictions: expiries happen
                # in the replica (the slot is evicted, the partial answer
                # still delivered), so without this the fleet totals — and
                # the windowed error-rate objective reading them — would
                # only ever see *router-queue* expiries
                out["deadline_expired"] = int(
                    getattr(engine, "_deadline_expired", 0)
                )
            except Exception:
                pass
        return out


def _build_model(args):
    import jax.numpy as jnp

    from ..models import LlamaConfig, LlamaForCausalLM

    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    if getattr(args, "model_config", None):
        # a published config.json: the zoo's own mapping by model_type (an
        # unknown one is refused with the list of known ones), random weights
        from ..models import config_from_hf_json, model_factory_for_config

        config = config_from_hf_json(args.model_config)
        return model_factory_for_config(config)(config, seed=args.seed, dtype=dtype)
    presets = {
        "tiny": lambda: LlamaConfig.tiny(
            vocab_size=256, hidden_size=64, layers=2, heads=4, seq=max(args.max_seq_len, 128)
        ),
        # the bench flagship slice (~700M): the largest single-chip shape
        "flagship": lambda: LlamaConfig.flagship_700m(
            max_position_embeddings=max(args.max_seq_len, 1024)
        ),
    }
    config = presets[args.preset]()
    return LlamaForCausalLM.from_config(config, seed=args.seed, dtype=dtype)


def _plan_kv_dtype(args) -> str:
    """The storage dtype string the shard-check HBM model prices blocks
    with — quantized policies price payload + scale arrays, so
    ``--auto-blocks`` sizes the pool from the bytes the engine will
    actually allocate (capacity ~doubles at int8/fp8)."""
    from ..analysis.shardplan import kv_storage_name

    return kv_storage_name(
        args.kv_dtype, "bfloat16" if args.dtype == "bf16" else "float32"
    )


class _PreflightRefusal(Exception):
    """Engine construction refused to start (the SP004 pre-flight, or
    invalid geometry) — distinct from a ValueError escaping the live
    serving loop, which must not be mislabeled as a startup refusal."""


def _auto_num_blocks(args, model, mesh) -> int:
    """``--auto-blocks``: size ``num_blocks`` from the shard-check HBM
    model instead of a hand-picked constant. Budget = ``--hbm-gb`` or the
    attached device's reported HBM; raises ValueError (the SP004 refusal)
    when neither is known or even one request's blocks don't fit."""
    from ..analysis.shardplan import auto_num_blocks, mesh_sizes_of, plan_kv_pool, plan_params
    from ..models.cache import cache_spec_of
    from ..mesh import device_hbm_bytes
    from ..serving.blocks import blocks_needed

    budget_bytes = (
        int(args.hbm_gb * (1 << 30)) if args.hbm_gb is not None else device_hbm_bytes()
    )
    if budget_bytes is None:
        raise ValueError(
            "SP004: --auto-blocks needs an HBM budget, and this backend "
            "reports no device memory limit — pass --hbm-gb"
        )
    inner = getattr(model, "_model", None) or model
    sizes = (
        mesh_sizes_of(mesh) if mesh is not None
        else {ax: 1 for ax in ("dp", "pp", "fsdp", "ep", "cp", "tp")}
    )
    rules = getattr(inner, "partition_rules", None)
    params_bytes = sum(
        p.bytes_per_device for p in plan_params(model.params, sizes, rules=rules)
    )
    # which layers hold paged K/V and what is kept per slot beside them: the
    # model's own declaration (models/cache.py). The slot state is a fixed
    # cost like the parameters — it does not grow with the blocks — and is
    # replicated (the engine refuses a mesh for such a model)
    spec = cache_spec_of(inner).with_state_dtype(getattr(args, "state_dtype", "auto"))
    state_bytes = args.num_slots * spec.state_bytes_per_slot(
        "bfloat16" if args.dtype == "bf16" else "float32")
    # the kinds of paged layer: num_blocks counts the first kind's pool; a
    # kind that keeps a window has a pool sized by what a slot keeps
    # resident, a fixed cost like the state (and printed with it)
    first = spec.paged_kinds[0]
    window_bytes = 0
    if spec.window_kinds:
        from ..ops.fp8 import kv_storage_dtype

        store, quantized = (
            ("bfloat16" if args.dtype == "bf16" else "float32", False)
            if args.kv_dtype in (None, "auto") else kv_storage_dtype(args.kv_dtype))
        chunk = max(args.prefill_chunk, args.decode_burst)
        window_bytes = spec.window_pool_bytes(
            args.num_slots, args.max_seq_len, args.block_size, chunk, store, quantized)
        pools = spec.window_pools(args.num_slots, args.max_seq_len, args.block_size, chunk)
        print("auto-blocks: " + ", ".join(
            f"{name} kind {n} blocks ({per_slot} a slot x {args.num_slots} slots + the null "
            "block)" for name, (per_slot, n) in pools.items())
            + f", {window_bytes / (1 << 30):.3f} GiB, whatever num_blocks is", file=sys.stderr)
        state_bytes += window_bytes
    per_block = sum(
        p.bytes_per_device
        for p in plan_kv_pool(
            num_layers=first.layers,
            num_kv_heads=spec.kv_heads,
            head_dim=spec.pool_width // spec.kv_heads,
            num_slots=1,
            block_size=args.block_size,
            max_seq_len=args.max_seq_len,
            num_blocks=1,
            mesh_sizes=sizes,
            dtype=_plan_kv_dtype(args),
            pool_leaves=spec.pool_leaves,
        )
    )
    blocks_per_slot = blocks_needed(args.max_seq_len, args.block_size)
    full_residency = args.num_slots * blocks_per_slot + 1
    num_blocks, headroom = auto_num_blocks(
        budget_bytes,
        params_bytes + state_bytes,
        per_block,
        full_residency_blocks=full_residency,
        min_blocks=blocks_per_slot + 1,  # one full request + the null block
    )
    gib = 1 << 30
    fixed = "slot state and window pools" if window_bytes else "slot state"
    print(
        f"auto-blocks: {num_blocks} blocks "
        f"({per_block / 1e6:.2f} MB/block/device; full residency "
        f"{full_residency}) — params {params_bytes / gib:.3f} GiB/device"
        f"{f' + {fixed} {state_bytes / gib:.3f}' if state_bytes else ''}, "
        f"predicted headroom {headroom / gib:.3f} GiB under the "
        f"{budget_bytes / gib:.3f} GiB budget",
        file=sys.stderr,
    )
    return num_blocks


def _make_engine(args):
    from ..mesh import configure_compile_cache
    from ..serving import EngineConfig, InferenceEngine

    configure_compile_cache()  # before the backend's first compile
    mesh = None
    if getattr(args, "mesh", False):
        from ..mesh import build_mesh

        mesh = build_mesh()  # MeshPlugin reads ACCELERATE_MESH_* env vars
    model = _build_model(args)
    num_blocks = args.num_blocks
    if args.auto_blocks:
        num_blocks = _auto_num_blocks(args, model, mesh)
    engine = InferenceEngine(
        model,
        EngineConfig(
            num_slots=args.num_slots,
            block_size=args.block_size,
            max_seq_len=args.max_seq_len,
            num_blocks=num_blocks,
            prefill_chunk=args.prefill_chunk,
            decode_burst=args.decode_burst,
            eos_token_id=args.eos_token_id,
            do_sample=args.temperature is not None,
            temperature=args.temperature if args.temperature is not None else 1.0,
            seed=args.seed,
            max_new_tokens=args.max_new_tokens,
            hbm_budget_gb=args.hbm_gb,
            prefix_cache=args.prefix_cache,
            swap_gb=args.swap_gb,
            kv_dtype=args.kv_dtype,
            state_dtype=getattr(args, "state_dtype", "auto"),
            spec_k=args.spec_k,
            denoise_steps=getattr(args, "denoise_steps", None),
            draft=args.draft,
            flight_history=args.flight_history,
            stats_interval=getattr(args, "stats_interval", 32),
            logprobs_topn=args.logprobs_topn,
            async_dispatch=not getattr(args, "sync_engine", False),
            usage_accounting=getattr(args, "usage_accounting", True),
        ),
        mesh=mesh,
    )
    for name, n in engine.window_num_blocks.items():
        # --num-blocks is the first kind's count; a window kind's is derived
        print(f"serve: {name} kind: {n} blocks ({engine.window_blocks_per_slot[name]} a slot x "
              f"{args.num_slots} slots + the null block), "
              f"{engine.window_pool_bytes / (1 << 30):.3f} GiB", file=sys.stderr)
    return engine


def _write_flight_drain(logging_dir, engine, k: int = 32) -> None:
    """On a SIGTERM drain, persist the flight recorder's last-``k``
    iterations beside the run's other artifacts — the post-mortem twin of
    the watchdog's HANG_REPORT ``flight_tail``, for engines that exited
    cleanly but slowly."""
    if not logging_dir or engine is None:
        return
    fl = getattr(engine, "_flight", None)
    if fl is None:
        return
    path = os.path.join(logging_dir, f"FLIGHT_DRAIN_{os.getpid()}.json")
    try:
        with open(path, "w") as f:
            json.dump(
                {
                    "type": "flight_drain",
                    "pid": os.getpid(),
                    "ts": time.time(),
                    "current_phase": fl.current_phase,
                    "iterations": fl.iterations,
                    "host_fraction": fl.host_fraction(),
                    "entries": fl.tail(k),
                },
                f, indent=2,
            )
    except OSError:
        pass


def _result_dict(req, req_id) -> dict:
    out = {
        "id": req_id,
        "trace_id": req.trace_id,
        "tenant": req.tenant,
        "tokens": req.output_tokens,
        "prompt_tokens": req.prompt_len,
        "ttft_s": req.ttft_s,
        "tpot_s": req.tpot_s,
        "finish_reason": req.finish_reason,
    }
    if req.logprobs is not None:
        out["logprobs"] = req.logprobs
    if req.usage is not None:
        # the usage ledger's answer-row costs: what THIS request spent
        # (device_time_s / kv_block_seconds / swap_bytes, measured)
        out.update(req.usage)
    return out


def _at_the_door(inbox, payload, cb=None) -> None:
    """Every front end's way into the engine loop's inbox: the payload is
    stamped with the moment it was received (``perf_counter`` seconds),
    which ``_engine_loop`` hands to ``add_request(arrival_time=)`` — the
    loop drains the inbox once per iteration, and without the stamp a
    server-side ``ttft_s`` would start up to an iteration late. The key is
    the server's own: a client's value is overwritten."""
    if isinstance(payload, dict):
        payload["_arrival"] = time.perf_counter()
    inbox.put((payload, cb))


def _engine_loop(engine, inbox, emit, stop, health=None, handler=None,
                 max_queue=None):
    """Drain inbox → step → deliver completion dicts; idle-sleep when empty
    so a quiet server doesn't spin a core. A malformed or over-budget
    request is answered with an ``{"error": ...}`` result — it must never
    kill the loop out from under the other in-flight requests.

    Exit conditions: ``stop`` (stdin EOF / server teardown) with nothing
    left in flight, or a drain (SIGTERM → ``health.draining``) once the
    engine has been idle for a short grace window — stragglers already in
    the pipe still get answered.

    A payload may carry a ``_stream`` callable (the OpenAI SSE path):
    it is called with each NEW token chunk as decode emits them. When the
    request has stop sequences, streaming lags by ``max(len(stop)) - 1``
    tokens so a delta can never over-send tokens a matched stop sequence
    later truncates — the final result row is always authoritative and
    exactly completes what was streamed."""
    pending = {}  # engine request_id -> (user id, per-request callback)
    streams = {}  # engine request_id -> [stream_cb, req, served, holdback]

    def deliver(result, cb):
        emit(result)
        if cb is not None:
            cb(result)

    idle_since = None
    while True:
        if (
            handler is not None
            and handler.preemption_requested
            and health is not None
            and not health.draining
        ):
            health.mark_draining()
        try:
            while True:
                payload, cb = inbox.get_nowait()
                req_id = payload.get("id") if isinstance(payload, dict) else None
                if (
                    max_queue is not None
                    and engine.scheduler.queue_depth >= max_queue
                ):
                    # bounded admission: an explicit over-capacity answer
                    # beats letting the waiting queue grow without limit
                    # (the router's shed path does class-aware shedding;
                    # the single-engine bound is a hard backstop)
                    deliver({
                        "id": req_id,
                        "error": f"over capacity: engine queue depth "
                        f"{engine.scheduler.queue_depth} at --max-queue "
                        f"{max_queue} — request shed",
                    }, cb)
                    continue
                try:
                    req = engine.add_request(
                        payload["prompt"], payload.get("max_new_tokens"),
                        # the door's stamp (absent when a caller fills the
                        # inbox itself: the clock then starts here)
                        arrival_time=payload.get("_arrival"),
                        priority=payload.get("priority", "interactive"),
                        deadline_ms=payload.get("deadline_ms"),
                        trace_id=payload.get("trace_id"),
                        # only a routed replica closes the router's flow
                        # arrow — a standalone serve emitting flow heads
                        # would count every request as an orphaned flow
                        upstream_hop=(
                            health is not None
                            and health.replica_id is not None
                            and payload.get("trace_id") is not None
                        ),
                        sampling=payload.get("sampling"),
                        grammar=payload.get("grammar"),
                        tenant=payload.get("tenant"),
                    )
                except Exception as e:  # noqa: BLE001 — reported, not fatal
                    deliver({"id": req_id, "error": str(e)}, cb)
                    continue
                pending[req.request_id] = (payload.get("id"), cb)
                stream_cb = payload.get("_stream")
                if stream_cb is not None:
                    hold = 0
                    if req.sampling is not None and req.sampling.stop:
                        hold = max(len(s) for s in req.sampling.stop) - 1
                    streams[req.request_id] = [stream_cb, req, 0, hold]
        except queue.Empty:
            pass
        if engine.scheduler.has_work():
            idle_since = None
            for req in engine.step():
                req_id, cb = pending.pop(req.request_id, (None, None))
                streams.pop(req.request_id, None)
                deliver(_result_dict(req, req_id), cb)
            for entry in streams.values():
                stream_cb, req, served, hold = entry
                avail = len(req.output_tokens) - hold
                if avail > served:
                    stream_cb(req.output_tokens[served:avail])
                    entry[2] = avail
            continue
        if stop.is_set() and inbox.empty():
            return  # EOF/teardown: the pipe is closed, nothing more can arrive
        if health is not None and health.draining:
            if idle_since is None:
                idle_since = time.monotonic()
            elif time.monotonic() - idle_since > _DRAIN_IDLE_GRACE_S:
                return
            time.sleep(0.01)
        else:
            time.sleep(0.005)


def serve_command(args) -> int:
    # live metrics registry: the telemetry hook (when --logging-dir is set)
    # and the /metrics scrape both publish through it — the vLLM-style
    # in-process exposition, vs the sidecar for embedded-serverless training
    from ..metrics.registry import MetricsRegistry, set_active_registry
    from ..resilience.preemption import PreemptionHandler

    set_active_registry(MetricsRegistry())
    if args.logging_dir:
        from ..diagnostics.tracing import Tracer, set_active_tracer
        from ..telemetry import TelemetryRecorder, set_active_recorder

        set_active_recorder(TelemetryRecorder(logging_dir=args.logging_dir))
        # request-scoped tracing rides the same switch as telemetry: every
        # request's lifecycle (arrive → admit → prefill → first token →
        # finish) lands in this process's trace file, stitched fleet-wide
        # by `accelerate-tpu trace merge`/`trace tail` via the trace_id
        set_active_tracer(Tracer(
            logging_dir=args.logging_dir,
            process_name=(
                f"replica_{args.replica_id}" if args.replica_id is not None
                else "serve"
            ),
        ))

    health = ServeHealth(replica_id=args.replica_id)
    # SIGTERM = drain request (the preemption contract): flag only; the
    # engine loop observes it between iterations. Ctrl-C keeps its
    # KeyboardInterrupt fast path below.
    handler = PreemptionHandler(handle_sigint=False)
    handler.install()

    inbox: queue.Queue = queue.Queue()
    stop = threading.Event()
    out_lock = threading.Lock()

    def emit(result):
        with out_lock:
            print(json.dumps(result), flush=True)

    # fault injection (serving/chaos.py): a parse error is a bring-up
    # refusal — a typo'd spec silently running a clean "chaos" test would
    # certify nothing
    from ..serving.chaos import ChaosInjector, ChaosSpecError

    try:
        chaos = ChaosInjector.from_spec(args.chaos_spec, replica_id=args.replica_id)
    except ChaosSpecError as e:
        emit({"error": str(e)})
        print(f"serve: refusing to start: {e}", file=sys.stderr)
        handler.uninstall()
        return 2
    if chaos is not None:
        print(
            f"serve: chaos injection armed (replica {args.replica_id})",
            file=sys.stderr,
        )
        if not args.http:
            print(
                "serve: chaos faults fire at the HTTP replica boundary — "
                "stdin mode ignores the spec", file=sys.stderr,
            )

    # seeded replayable workload (serving/workload.py): same contract as
    # --chaos-spec — a malformed spec is a bring-up refusal (exit 2), not
    # a silent empty run
    from ..serving.workload import (
        TraceSpecError,
        generate_schedule,
        parse_trace_spec,
        run_schedule,
        write_workload_manifest,
    )

    trace_spec = trace_schedule = None
    if args.trace:
        try:
            trace_spec = parse_trace_spec(args.trace)
            trace_schedule = generate_schedule(trace_spec)
        except TraceSpecError as e:
            emit({"error": str(e)})
            print(f"serve: refusing to start: {e}", file=sys.stderr)
            handler.uninstall()
            return 2
        if args.http:
            # the HTTP door has external clients driving it; a workload
            # generator feeding the same inbox would interleave with them
            print(
                "serve: --trace drives the stdin/JSONL loop — HTTP mode "
                "ignores the spec (route --trace drives a fleet)",
                file=sys.stderr,
            )
            trace_spec = trace_schedule = None

    try:
        if args.http:
            # factory form: the server binds FIRST (so /healthz answers
            # `starting` while the engine builds/compiles), then the engine
            # comes up and the state flips to `ready`. Only a ValueError
            # raised while BUILDING the engine is a refusal — one escaping
            # the live serving loop later must keep its traceback.
            def build_engine():
                try:
                    return _make_engine(args)
                except ValueError as e:
                    raise _PreflightRefusal(str(e)) from e

            try:
                return _serve_http(build_engine, inbox, stop,
                                   args.http, health=health, handler=handler,
                                   chaos=chaos, max_queue=args.max_queue,
                                   logging_dir=args.logging_dir)
            except _PreflightRefusal as e:
                # SP004 pre-flight refusal (or invalid geometry): an error
                # row + exit 2, the same contract as shard-check
                emit({"error": str(e)})
                print(f"serve: refusing to start: {e}", file=sys.stderr)
                return 2

        try:
            engine = _make_engine(args)
        except ValueError as e:
            emit({"error": str(e)})
            print(f"serve: refusing to start: {e}", file=sys.stderr)
            return 2
        # stdin/JSONL mode: a reader thread feeds the inbox; EOF arms stop
        # and the loop drains what's in flight before exiting. Once
        # draining, admission stops — late lines are answered, not queued.
        health.mark_ready()

        def read_stdin():
            for line in sys.stdin:
                line = line.strip()
                if not line:
                    continue
                try:
                    payload = json.loads(line)
                except json.JSONDecodeError as e:
                    with out_lock:
                        print(json.dumps({"error": f"bad JSON: {e}"}), flush=True)
                    continue
                if health.draining:
                    req_id = payload.get("id") if isinstance(payload, dict) else None
                    emit({"id": req_id, "error": "draining: admission stopped"})
                    continue
                _at_the_door(inbox, payload)
            stop.set()

        if trace_schedule is not None:
            if args.logging_dir:
                write_workload_manifest(args.logging_dir, trace_spec, trace_schedule)
            print(
                f"serve: replaying workload {trace_spec.as_text()} "
                f"({len(trace_schedule)} requests)", file=sys.stderr,
            )

            def feed_trace():
                run_schedule(
                    trace_schedule,
                    lambda payload: _at_the_door(inbox, payload),
                    should_stop=lambda: health.draining or stop.is_set(),
                )
                stop.set()

            threading.Thread(target=feed_trace, daemon=True).start()
        else:
            threading.Thread(target=read_stdin, daemon=True).start()
        try:
            _engine_loop(engine, inbox, emit, stop, health=health,
                         handler=handler, max_queue=args.max_queue)
        except KeyboardInterrupt:
            pass
        stats = engine.stats()
        drained = " (drained on SIGTERM)" if health.draining else ""
        if health.draining:
            _write_flight_drain(args.logging_dir, engine)
        print(
            f"served {stats['completed']} requests, "
            f"{stats['tokens_emitted']} tokens "
            f"({stats.get('tokens_per_sec', 0.0):.1f} tok/s), "
            f"decode compiles {stats['decode_compiles']}, "
            f"paged route {stats['paged_attention_impl']}, "
            f"device bytes in use "
            f"{stats.get('hbm_used_bytes_per_device', 'not reported')}{drained}",
            file=sys.stderr,
        )
        return 0
    finally:
        handler.uninstall()


def _serve_http(engine, inbox, stop, port, health=None, handler=None,
                chaos=None, max_queue=None, logging_dir=None) -> int:
    """Minimal local HTTP front end: POST /generate blocks until the
    request completes (400 on a rejected one, 503 while starting or
    draining); GET /healthz answers the lifecycle state machine +
    queue/slot gauges; GET /stats returns engine health JSON; GET /metrics
    answers OpenMetrics text from the active registry (refreshed from
    ``engine.stats()`` on each scrape); GET /profile?seconds=N captures an
    on-demand jax-profiler window + flight-recorder dump into
    ``logging_dir/profiles/`` while the engine keeps serving (409 when a
    capture is already running, 400 without a logging dir).

    ``chaos`` (a :class:`~accelerate_tpu.serving.chaos.ChaosInjector`)
    injects scheduled faults at this boundary: ``kill``/``stop``/``delay``
    and 503 bursts fire per received ``/generate`` request, health-check
    blackouts tear ``/healthz`` connections. Disabled = one falsy check
    per request, like the telemetry null object.

    ``engine`` may be a ready instance or a zero-arg factory — with a
    factory the server binds and answers ``/healthz`` as ``starting``
    *while* the engine builds, which is what the router's bring-up
    health-checks observe."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from ..metrics.ingest import observe_engine_stats
    from ..metrics.openmetrics import CONTENT_TYPE, render_openmetrics
    from ..metrics.registry import get_active_registry
    from ..serving.openai_api import OPENAI_PATHS, OpenAIFrontend

    health = health or ServeHealth()
    box = {"engine": None if callable(engine) else engine}
    frontend = OpenAIFrontend(
        lambda payload, cb: _at_the_door(inbox, payload, cb), streaming="delta"
    )

    class Handler(BaseHTTPRequestHandler):
        # HTTP/1.1 so SSE streams ride chunked transfer encoding (every
        # non-stream answer already sends Content-Length)
        protocol_version = "HTTP/1.1"
        #: one capture at a time — jax.profiler has a single global trace
        #: session; a concurrent request gets an explicit 409, not a crash
        profile_lock = threading.Lock()

        def log_message(self, *a):  # quiet
            pass

        def _send(self, code, payload: dict):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _send_metrics(self):
            registry = get_active_registry()
            if registry and box["engine"] is not None:
                try:
                    observe_engine_stats(registry, box["engine"].stats())
                except Exception:
                    pass
            body = render_openmetrics(registry).encode()
            self.send_response(200)
            self.send_header("Content-Type", CONTENT_TYPE)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            # split off the query string (Prometheus scrape params,
            # /profile?seconds=N) instead of dropping it
            path, _, query = self.path.partition("?")
            path = path.rstrip("/")
            if path == "/metrics":
                self._send_metrics()
            elif path == "/profile":
                self._handle_profile(query)
            elif path == "/healthz":
                if chaos is not None and chaos.healthz_blackout():
                    # injected health blackout: tear the connection — the
                    # prober sees exactly what a starved /healthz looks like
                    self.close_connection = True
                    return
                self._send(200, health.payload(box["engine"]))
            elif path in ("", "/stats", "/health"):
                eng = box["engine"]
                self._send(200, eng.stats() if eng is not None
                           else {"state": health.state})
            else:
                self._send(404, {"error": "unknown path"})

        def _handle_profile(self, query: str):
            """On-demand windowed capture: jax.profiler trace + the flight
            iterations that land inside the window. Runs in this handler
            thread — the engine loop keeps stepping underneath, which is
            the point (profile the engine *while it serves*)."""
            eng = box["engine"]
            if eng is None or not health.ready:
                self._send(503, {"error": f"engine not ready: {health.state}"})
                return
            if not logging_dir:
                self._send(400, {"error": "profiling needs --logging-dir"})
                return
            from urllib.parse import parse_qs

            try:
                seconds = float((parse_qs(query).get("seconds") or ["2.0"])[0])
            except (TypeError, ValueError):
                self._send(400, {"error": "seconds must be a number"})
                return
            seconds = min(max(seconds, 0.05), 120.0)
            if not Handler.profile_lock.acquire(blocking=False):
                self._send(409, {"error": "a profile capture is already running"})
                return
            try:
                from ..serving.flight import capture_profile_window

                manifest = capture_profile_window(logging_dir, seconds, engine=eng)
            except Exception as e:  # noqa: BLE001 — reported, never fatal
                self._send(500, {"error": f"profile capture failed: {e}"})
                return
            finally:
                Handler.profile_lock.release()
            self._send(200, manifest)

        def _send_sse(self, events):
            """Stream SSE events as HTTP/1.1 chunked transfer frames; a
            client hangup mid-stream is normal teardown, not an error."""
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            try:
                for event in events:
                    data = event.encode()
                    self.wfile.write(
                        f"{len(data):X}\r\n".encode() + data + b"\r\n"
                    )
                    self.wfile.flush()
                self.wfile.write(b"0\r\n\r\n")
            except (BrokenPipeError, ConnectionResetError):
                self.close_connection = True

        def _handle_openai(self, path: str, raw: bytes):
            """The OpenAI-compatible door: same chaos/lifecycle gates as
            /generate, OpenAI-shaped error objects on every refusal."""
            def err(status, message, type_="invalid_request_error"):
                self._send(status, {"error": {
                    "message": message, "type": type_,
                    "param": None, "code": None,
                }})

            if chaos is not None and chaos.on_generate() == "err503":
                err(503, "chaos: injected 503 burst", "server_error")
                return
            if not health.ready:
                err(503, f"not accepting requests: {health.state}",
                    "server_error")
                return
            try:
                body = json.loads(raw)
            except json.JSONDecodeError as e:
                err(400, f"bad JSON: {e}")
                return
            kind, *rest = frontend.handle(path, body)
            if kind == "sse":
                self._send_sse(rest[0])
            else:
                self._send(rest[0], rest[1])

        def do_POST(self):
            path = self.path.rstrip("/")
            # read the body up front: on a keep-alive connection an early
            # refusal that skips the body would desync the next request
            try:
                n = int(self.headers.get("Content-Length", 0) or 0)
            except ValueError:
                n = 0
            raw = self.rfile.read(n) if n else b""
            if path in OPENAI_PATHS:
                self._handle_openai(path, raw)
                return
            if path != "/generate":
                self._send(404, {"error": "unknown path"})
                return
            if chaos is not None:
                # kill/stop never return; delay sleeps in this handler
                # thread (the request is mid-flight, exactly like a slow
                # engine); a 503 burst answers before admission
                if chaos.on_generate() == "err503":
                    self._send(503, {"error": "chaos: injected 503 burst"})
                    return
            if not health.ready:
                # starting or draining: an explicit answer, so the router
                # (or any client) fails fast instead of queueing into a
                # front end that will never serve it
                self._send(503, {"error": f"not accepting requests: {health.state}"})
                return
            try:
                payload = json.loads(raw)
                if not isinstance(payload, dict):
                    raise ValueError("body must be a JSON object")
                if not payload.get("prompt"):
                    raise ValueError("missing prompt")
            except (ValueError, json.JSONDecodeError) as e:
                self._send(400, {"error": str(e)})
                return
            done = threading.Event()
            answer: dict = {}  # NOT `box` — that closure holds the engine

            def cb(result):
                answer["result"] = result
                done.set()

            _at_the_door(inbox, payload, cb)
            done.wait()
            result = answer["result"]
            self._send(400 if "error" in result else 200, result)

    class Server(ThreadingHTTPServer):
        # default request_queue_size=5: under router redispatch churn a LIVE
        # replica would refuse connections, which reads as a transport death
        request_queue_size = 128

    server = Server(("127.0.0.1", port), Handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    print(f"serving on http://127.0.0.1:{port} "
          f"(POST /generate + /v1/completions + /v1/chat/completions, "
          f"GET /healthz, GET /stats, GET /metrics)",
          file=sys.stderr)
    try:
        if box["engine"] is None:
            box["engine"] = engine()  # /healthz says `starting` during this build
        health.mark_ready()
        _engine_loop(box["engine"], inbox, lambda *a: None, stop,
                     health=health, handler=handler, max_queue=max_queue)
    except KeyboardInterrupt:
        pass
    finally:
        # build failures (the pre-flight refusal) must also unbind the
        # port — a leaked server thread answers /healthz `starting` forever
        server.shutdown()
        if health.draining:
            _write_flight_drain(logging_dir, box["engine"])
    return 0


def add_parser(subparsers):
    p = subparsers.add_parser(
        "serve",
        help="Continuous-batching inference engine over stdin/JSONL or local HTTP",
    )
    p.add_argument("--preset", choices=("tiny", "flagship"), default="tiny",
                   help="model shape (random weights; prompts are token ids)")
    p.add_argument("--model-config", default=None, metavar="CONFIG_JSON",
                   help="serve the model a published config.json describes "
                   "(by its model_type, through the zoo's config_from_hf_json; "
                   "random weights) instead of a --preset")
    p.add_argument("--dtype", choices=("f32", "bf16"), default="f32")
    p.add_argument("--num-slots", type=int, default=8,
                   help="decode batch slots (the compiled step's static dim)")
    p.add_argument("--block-size", type=int, default=16, help="KV block tokens")
    p.add_argument("--max-seq-len", type=int, default=512,
                   help="per-request prompt+output cap")
    p.add_argument("--prefill-chunk", type=int, default=32,
                   help="prompt tokens prefilled per engine iteration")
    p.add_argument("--decode-burst", type=int, default=8,
                   help="decode steps per dispatch (scheduling granularity)")
    p.add_argument("--num-blocks", type=int, default=None,
                   help="paged KV pool blocks (default: full residency — "
                   "num_slots x blocks-per-slot + 1)")
    p.add_argument("--hbm-gb", type=float, default=None,
                   help="per-device HBM budget: the engine runs the "
                   "shard-check pre-flight and refuses to start (error row, "
                   "exit 2) if params + pools exceed it")
    p.add_argument("--auto-blocks", action="store_true",
                   help="size num_blocks from the shard-check HBM model "
                   "(budget: --hbm-gb, or the device's reported HBM) and log "
                   "the chosen count + predicted headroom")
    p.add_argument("--max-new-tokens", type=int, default=64,
                   help="default output budget when a request omits it")
    p.add_argument("--max-queue", type=int, default=None,
                   help="bounded admission: shed (error row) any request "
                   "arriving while this many are already waiting for a slot "
                   "(default: unbounded, the pre-robustness behaviour)")
    p.add_argument("--trace", default=None, metavar="SPEC",
                   help="drive the engine from a seeded replayable workload "
                   "instead of stdin: 'name:seed:duration:rps' with name in "
                   "bursty-diurnal|longctx-flood|agentic|overbudget-storm, "
                   "or 'replay:<path>' for a recorded schedule (same seed = "
                   "byte-identical schedule; malformed spec = exit 2)")
    p.add_argument("--chaos-spec", default=None,
                   help="fault-injection schedule for chaos testing (env "
                   "ACCELERATE_CHAOS_SPEC; seed via ACCELERATE_CHAOS_SEED): "
                   "e.g. 'r0:kill@5;r1:delay@3:0.2;err503@2:3;blackout@6:1.5' "
                   "— kill -9 / SIGSTOP / delay / 503 burst / healthz "
                   "blackout keyed on the replica's /generate request "
                   "ordinal; deterministic per (spec, seed). HTTP mode only")
    # prefix sharing + swap preemption knobs (env defaults let a fleet
    # flip them without touching every replica's command line). Parsed
    # defensively: add_parser runs while building EVERY subcommand's
    # parser, so a malformed env value must warn, not kill `monitor`.
    prefix_env = os.environ.get("ACCELERATE_SERVE_PREFIX_CACHE", "1")
    p.add_argument("--prefix-cache", dest="prefix_cache", action="store_true",
                   default=prefix_env.strip().lower()
                   not in ("0", "false", "no", "off", ""),
                   help="radix prefix sharing over the block pool (default "
                   "on; env ACCELERATE_SERVE_PREFIX_CACHE=0 disables)")
    p.add_argument("--no-prefix-cache", dest="prefix_cache", action="store_false",
                   help="disable prefix sharing (every prompt prefills cold)")
    usage_env = os.environ.get("ACCELERATE_SERVE_USAGE", "1")
    p.add_argument("--usage-accounting", dest="usage_accounting",
                   action="store_true",
                   default=usage_env.strip().lower()
                   not in ("0", "false", "no", "off", ""),
                   help="conservation-checked per-request usage ledger: "
                   "device-seconds, KV block-seconds, swap bytes by tenant/"
                   "class (default on; env ACCELERATE_SERVE_USAGE=0 disables)")
    p.add_argument("--no-usage-accounting", dest="usage_accounting",
                   action="store_false",
                   help="disable the usage ledger (answer rows carry no "
                   "cost fields; stats()/telemetry carry no usage snapshot)")
    try:
        swap_default = float(os.environ.get("ACCELERATE_SERVE_SWAP_GB", "0") or 0)
    except ValueError:
        print(
            "accelerate-tpu: ignoring malformed ACCELERATE_SERVE_SWAP_GB="
            f"{os.environ['ACCELERATE_SERVE_SWAP_GB']!r} (want GiB as a float)",
            file=sys.stderr,
        )
        swap_default = 0.0
    p.add_argument("--swap-gb", type=float, default=swap_default,
                   help="host-DRAM KV swap tier in GiB (default 0 = off; env "
                   "ACCELERATE_SERVE_SWAP_GB): under pool exhaustion the "
                   "lowest-priority request is swapped out instead of being "
                   "truncated with finish_reason=out_of_blocks")
    kv_env = os.environ.get("ACCELERATE_SERVE_KV_DTYPE", "auto").strip().lower()
    if kv_env not in ("auto", "bf16", "f32", "int8", "fp8"):
        print(
            "accelerate-tpu: ignoring malformed ACCELERATE_SERVE_KV_DTYPE="
            f"{kv_env!r} (want auto|bf16|f32|int8|fp8)",
            file=sys.stderr,
        )
        kv_env = "auto"
    p.add_argument("--kv-dtype", choices=("auto", "bf16", "f32", "int8", "fp8"),
                   default=kv_env,
                   help="KV pool storage policy (default auto = the params' "
                   "compute dtype; env ACCELERATE_SERVE_KV_DTYPE): int8/fp8 "
                   "quantize on scatter with per-row amax scales — half the "
                   "decode bytes, ~2x the slot capacity at equal --hbm-gb")
    p.add_argument("--state-dtype", choices=("auto", "bf16"), default="auto",
                   help="storage of the per-slot recurrent state of a model that "
                   "keeps one (default auto = what the model declares, float32 for "
                   "Granite-4.0-H): bf16 halves the bytes a slot holds and a decode "
                   "step streams, at one more rounding of the state per decoded "
                   "token; refused for a model whose cache is blocks only")
    try:
        spec_k_default = int(os.environ.get("ACCELERATE_SERVE_SPEC_K", "0") or 0)
    except ValueError:
        print(
            "accelerate-tpu: ignoring malformed ACCELERATE_SERVE_SPEC_K="
            f"{os.environ['ACCELERATE_SERVE_SPEC_K']!r} (want an integer)",
            file=sys.stderr,
        )
        spec_k_default = 0
    p.add_argument("--spec-k", type=int, default=spec_k_default,
                   help="speculative decoding: draft this many tokens per "
                   "slot per round and verify them in ONE [num_slots, k+1] "
                   "compiled forward (default 0 = off; env "
                   "ACCELERATE_SERVE_SPEC_K). Greedy requests stay "
                   "token-identical to the non-speculative engine; sampled "
                   "requests verify by rejection sampling. A bad spec/draft "
                   "combination is a startup refusal (error row, exit 2)")
    p.add_argument("--denoise-steps", type=int, default=None, metavar="T",
                   help="denoise passes of a block round, for a model that generates by "
                        "diffusion over blocks (sdar_moe): pass t fixes sub-block t of T, "
                        "left to right, then one pass commits the clean block. Default: the "
                        "block's length (one token a pass); must divide it. Fewer passes "
                        "are fewer forwards a token and a coarser conditioning. Refused for "
                        "a model that decodes one token a step; such a model refuses "
                        "--spec-k, --mesh, grammars and repetition penalties")
    p.add_argument("--draft", default=os.environ.get(
                       "ACCELERATE_SERVE_DRAFT", "early_exit:2"),
                   help="draft policy when --spec-k > 0 (env "
                   "ACCELERATE_SERVE_DRAFT): 'early_exit:N' runs the "
                   "target's own first N layers as the draft, sharing the "
                   "target's paged pool — no second cache, no extra "
                   "weights resident")
    try:
        flight_default = int(
            os.environ.get("ACCELERATE_SERVE_FLIGHT_HISTORY", "256") or 256
        )
    except ValueError:
        print(
            "accelerate-tpu: ignoring malformed ACCELERATE_SERVE_FLIGHT_HISTORY="
            f"{os.environ['ACCELERATE_SERVE_FLIGHT_HISTORY']!r} (want an integer)",
            file=sys.stderr,
        )
        flight_default = 256
    p.add_argument("--flight-history", type=int, default=flight_default,
                   help="per-iteration flight recorder ring size (default "
                   "256; 0 disables; env ACCELERATE_SERVE_FLIGHT_HISTORY): "
                   "host-vs-device phase attribution behind "
                   "stats()['host_fraction'], `trace tail --iterations`, "
                   "GET /profile, and HANG_REPORT flight tails")
    try:
        stats_default = int(
            os.environ.get("ACCELERATE_SERVE_STATS_INTERVAL", "32") or 32
        )
    except ValueError:
        print(
            "accelerate-tpu: ignoring malformed ACCELERATE_SERVE_STATS_INTERVAL="
            f"{os.environ['ACCELERATE_SERVE_STATS_INTERVAL']!r} (want an integer)",
            file=sys.stderr,
        )
        stats_default = 32
    p.add_argument("--stats-interval", type=int, default=stats_default,
                   help="emit a telemetry kind=\"step\" row (windowed "
                   "throughput, cumulative counters, the usage-ledger "
                   "snapshot) every N engine iterations (default 32; 0 "
                   "disables; env ACCELERATE_SERVE_STATS_INTERVAL)")
    try:
        logprobs_default = int(
            os.environ.get("ACCELERATE_SERVE_LOGPROBS_TOPN", "0") or 0
        )
    except ValueError:
        print(
            "accelerate-tpu: ignoring malformed ACCELERATE_SERVE_LOGPROBS_TOPN="
            f"{os.environ['ACCELERATE_SERVE_LOGPROBS_TOPN']!r} (want an integer)",
            file=sys.stderr,
        )
        logprobs_default = 0
    p.add_argument("--logprobs-topn", type=int, default=logprobs_default,
                   help="top-N per-step logprobs harvest ceiling (default 0 "
                   "= disabled; env ACCELERATE_SERVE_LOGPROBS_TOPN): the "
                   "harvest shape is static engine geometry, so requests opt "
                   "in UP TO this cap via the OpenAI 'logprobs' field; "
                   "unsupported with --spec-k > 0")
    p.add_argument(
        "--sync-engine", action="store_true",
        default=os.environ.get("ACCELERATE_SYNC_ENGINE", "") not in ("", "0"),
        help="disable double-buffered dispatch and run the synchronous "
        "step loop (schedule, dispatch, blocking harvest every "
        "iteration; env ACCELERATE_SYNC_ENGINE=1): escape hatch for "
        "A/B timing and for triaging suspected overlap bugs — tokens "
        "are identical either way, only the host-hiding differs")
    p.add_argument("--eos-token-id", type=int, default=None)
    p.add_argument("--temperature", type=float, default=None,
                   help="default sampling temperature when a request sends no "
                   "per-request params (default: greedy; per-request "
                   "temperature always wins)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--mesh", action="store_true",
                   help="shard the engine over the attached mesh "
                   "(ACCELERATE_MESH_* env vars declare the shape)")
    p.add_argument("--replica-id", type=int, default=None,
                   help="identity stamped on /healthz when running behind "
                   "`accelerate-tpu route`")
    p.add_argument("--http", type=int, default=None, metavar="PORT",
                   help="serve a local HTTP endpoint instead of stdin JSONL")
    p.add_argument("--logging-dir", default=None,
                   help="enable telemetry here (accelerate-tpu monitor shows "
                   "serving health)")
    p.set_defaults(func=serve_command)
    return p
