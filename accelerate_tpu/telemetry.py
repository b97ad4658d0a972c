"""Step-level telemetry: compile/recompile events, device memory, throughput.

The reference treats observability as host-side experiment tracking only
(``tracking.py``'s ``GeneralTracker`` zoo). On a JAX/TPU backend the signals
that explain performance — recompiles, HBM high-water marks, dispatch vs
device time, ICI collective bytes — live in XLA and are invisible to a
tracker that only sees what the user logs. This module is the unifying
consumer of the raw ingredients the codebase already had: the compile cache
in :mod:`accelerate_tpu.lazy` (hooked via :func:`lazy.set_compile_callback`),
the HLO collective-bytes parser in :mod:`accelerate_tpu.utils.hlo`, and the
``jax.profiler`` plumbing around ``ProfileContext``.

Three sinks, one record stream:

* a **ring buffer** with p50/p95/max summaries — ``accelerator.telemetry.summary()``
* a **JSONL trail** under ``{logging_dir}/telemetry/telemetry.jsonl`` —
  crash-safe append (one ``write``+``flush`` per record), main-process only
* **tracker fan-out** through ``Accelerator.log()`` into whatever trackers
  are initialized, gated on the main process exactly like
  ``tracking.on_main_process``

Enable with ``Accelerator(telemetry=True)`` or ``ACCELERATE_TELEMETRY=1``.
Disabled, every instrumentation point holds a :data:`NULL_TELEMETRY`
singleton whose methods are no-ops — the hot path pays one attribute read.

The JSONL trail is size-capped (``ACCELERATE_TELEMETRY_MAX_BYTES``, default
64 MB, keeping ``ACCELERATE_TELEMETRY_KEEP_SEGMENTS`` rotated segments) —
:func:`telemetry_segments` lists a trail's segments oldest-first for
readers (``accelerate-tpu monitor``, the metrics exporter). An active
:class:`~accelerate_tpu.metrics.MetricsRegistry` additionally receives
every record through :func:`accelerate_tpu.metrics.ingest.observe_record`
— the ``GET /metrics`` surface.

Record schema (every record carries ``type``, ``ts``, and ``schema`` —
see :data:`SCHEMA_VERSION`):

``step``     — ``step``, ``optimizer_steps``, ``step_time_s``,
               ``dispatch_s``, ``device_s``, ``examples``, ``tokens``,
               ``examples_per_sec``, ``tokens_per_sec``, ``sync_gradients``,
               ``accum_phase``, ``skipped``, ``recompiles`` and (when a step
               program's FLOPs are known and the chip's peak is in the
               table) ``mfu``.
``compile``  — ``label``, ``static_key``, ``lower_s``, ``compile_s``,
               ``total_s``, ``flops``, ``bytes_accessed``,
               ``collective_bytes``, ``recompiles`` (cumulative), and
               ``mono`` — the phases' raw *monotonic* timestamps
               (``lower_start``/``compile_start``/``compile_end``, same
               ``perf_counter`` clock the diagnostics trace spans use).
               ``ts`` stays wall-clock like every record; ``mono`` is what
               lines a compile record up with the per-host trace timeline.
               Sanitizer-armed compiles add ``fingerprint``/``changed_args``
               /``collective_digest`` and ``arg_bytes_predicted``/
               ``arg_bytes_actual`` (shard-plan model vs real shard buffers)
               (trace export / ``accelerate-tpu trace merge``). When the
               AOT path fingerprinted the signature (always on the AOT
               path): ``fingerprint``, and on a re-trace ``changed_args``
               naming the argument whose shape/dtype changed; with the
               sanitizer armed, ``collective_digest`` (the ordered
               collective-sequence hash ``monitor`` diffs across hosts).
``memory``   — ``device_bytes_in_use``, ``device_peak_bytes``,
               ``host_rss_bytes`` (sampled every ``memory_interval`` steps).
``generate`` — ``mode``, ``new_tokens``, ``seconds``, ``tokens_per_sec``
               and, for speculative decoding, ``accept_rate`` /
               ``verify_rounds``.
``serving``  — continuous-batching engine rows: ``kind="step"`` (periodic
               — ``tokens_per_sec``, ``queue_depth``, ``slot_occupancy``,
               ``free_blocks``, ``decode_compiles``) and
               ``kind="request"`` (per completion — ``ttft_s``,
               ``tpot_s``, ``prompt_tokens``, ``new_tokens``,
               ``finish_reason``, ``priority`` — the metrics ingest's
               ``{class=...}`` label — and ``trace_id``, which becomes
               the OpenMetrics exemplar linking a latency bucket to the
               request's stitched trace).
``profile``  — ``trace_dir``, ``steps``, ``active_steps`` (one record per
               finished ``accelerator.profile()`` session).
``checkpoint`` — ``kind`` (``save``/``restore``), ``seconds``, ``bytes``,
               ``shard_count``, ``async``, ``path`` (emitted by
               ``checkpointing.py`` on every save/restore; async saves
               report at commit time, so ``seconds`` spans snapshot →
               durable rename).
``event``    — free-form (``kind`` + fields), e.g. the ``prepare`` timing
               and the ``preemption`` emergency-save marker.
"""

from __future__ import annotations

import json
import os
import time
from collections import deque
from typing import Any, Callable

import numpy as np

from .logging import get_logger
from .metrics.ingest import observe_record as _observe_metrics_record
from .metrics.registry import get_active_registry as _get_metrics_registry

logger = get_logger(__name__)

#: version stamped as ``schema`` on every emitted record. Readers
#: (``monitor``, the metrics exporter) must skip-with-warning rows whose
#: version is NEWER than theirs instead of KeyError-ing on reshaped fields;
#: rows with no ``schema`` field are the pre-versioning legacy format and
#: are accepted. Bump on any backward-incompatible row reshape.
SCHEMA_VERSION = 1


def schema_compatible(row: dict) -> bool:
    """True when this reader understands ``row``'s schema version (missing
    field = legacy = compatible; garbage values are incompatible)."""
    version = row.get("schema", 0)
    try:
        return int(version) <= SCHEMA_VERSION
    except (TypeError, ValueError):
        return False


def telemetry_segments(jsonl_path: str) -> list[str]:
    """Existing JSONL segments for a trail, oldest → newest: rotated
    ``telemetry.jsonl.N`` … ``telemetry.jsonl.1`` then the live file.
    Readers (``monitor``'s tail, the metrics exporter) iterate this instead
    of assuming one unbounded file."""
    segments: list[str] = []
    suffixes = []
    try:
        directory = os.path.dirname(jsonl_path) or "."
        base = os.path.basename(jsonl_path)
        for name in os.listdir(directory):
            if name.startswith(base + "."):
                tail = name[len(base) + 1 :]
                if tail.isdigit():
                    suffixes.append(int(tail))
    except OSError:
        pass
    for n in sorted(suffixes, reverse=True):
        segments.append(f"{jsonl_path}.{n}")
    if os.path.exists(jsonl_path):
        segments.append(jsonl_path)
    return segments

#: Peak dense bf16 FLOPs/s per chip by device kind (public spec sheets).
#: Override per-run with
#: ``TelemetryRecorder(peak_flops=...)`` or ``ACCELERATE_TELEMETRY_PEAK_FLOPS``.
PEAK_FLOPS_TABLE: tuple[tuple[str, float], ...] = (
    ("v6e", 918e12),
    ("v6 lite", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

#: compile labels that constitute "the train step" — their cost facts feed
#: the MFU estimate and the recompile counter the summary reports
_STEP_LABELS = ("fused_step", "grad", "forward", "opt_apply")


def _percentiles(values) -> dict[str, float]:
    arr = np.asarray(list(values), dtype=np.float64)
    if arr.size == 0:
        # an empty ring must yield an empty dict, not a numpy warning +
        # NaNs — summary() can race a concurrent close()/clear in crash
        # paths (the atexit flush) where the deques were never fed
        return {}
    return {
        "p50": float(np.percentile(arr, 50)),
        "p95": float(np.percentile(arr, 95)),
        "max": float(arr.max()),
    }


def _is_main_process() -> bool:
    """Same gate as ``tracking.on_main_process`` (a fresh ``PartialState``
    is the Borg view of process identity)."""
    try:
        from .state import PartialState

        return bool(PartialState().is_main_process)
    except Exception:
        return True


def _host_rss_bytes() -> int | None:
    try:
        import resource

        # linux reports ru_maxrss in KiB
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return None


class _NullTelemetry:
    """The disabled-mode recorder: every method is a no-op, ``bool()`` is
    False, and ``summary()`` is empty. Instrumentation points hold this
    singleton so the enabled check is one truthiness test."""

    enabled = False
    sync_device = False

    def __bool__(self):
        return False

    def note_batch(self, *a, **k):
        pass

    def note_backward(self, *a, **k):
        pass

    def record_step(self, *a, **k):
        pass

    def record_generation(self, *a, **k):
        pass

    def record_serving(self, *a, **k):
        pass

    def record_profile(self, *a, **k):
        pass

    def record_checkpoint(self, *a, **k):
        pass

    def record_event(self, *a, **k):
        pass

    def record_memory(self, *a, **k):
        pass

    def summary(self):
        return {}

    def close(self):
        pass


NULL_TELEMETRY = _NullTelemetry()

#: process-wide active recorder, so free functions (the generation decode
#: loops) can report without threading an accelerator through their args
_ACTIVE: _NullTelemetry | "TelemetryRecorder" = NULL_TELEMETRY


def get_active_recorder():
    return _ACTIVE


def set_active_recorder(recorder) -> None:
    global _ACTIVE
    _ACTIVE = recorder if recorder is not None else NULL_TELEMETRY


class TelemetryRecorder:
    """Collects step/compile/memory/generation records and serves them to
    the three sinks. Construction registers the compile-miss callback on
    :mod:`accelerate_tpu.lazy`'s compile cache; ``close()`` (or a later
    recorder) unregisters it.

    Args:
        logging_dir: root under which ``telemetry/telemetry.jsonl`` is
            appended (no file sink when None).
        tracker_sink: ``callable(values_dict, step)`` — normally the
            owning ``Accelerator.log`` — invoked on the main process only.
        ring_size: per-kind ring buffer capacity backing ``summary()``.
        memory_interval: sample ``device.memory_stats()`` + host RSS every
            N step records (0 disables sampling).
        peak_flops: chip peak FLOPs/s for the MFU estimate; default looks
            up the attached device kind in :data:`PEAK_FLOPS_TABLE`
            (``ACCELERATE_TELEMETRY_PEAK_FLOPS`` overrides). Unknown kinds
            (CPU hosts) leave ``mfu`` unset — see the telemetry guide for
            why a CPU MFU would be meaningless.
        sync_device: block on the updated params after each optimizer step
            to split wall time into dispatch vs device-blocked. Costs the
            host-runahead pipelining; set False (or
            ``ACCELERATE_TELEMETRY_NO_SYNC=1``) to keep fully-async
            stepping and record dispatch time only.
    """

    def __init__(
        self,
        logging_dir: str | None = None,
        tracker_sink: Callable[[dict, int | None], Any] | None = None,
        ring_size: int = 1024,
        memory_interval: int = 10,
        peak_flops: float | None = None,
        sync_device: bool | None = None,
    ):
        self.enabled = True
        self._closed = False
        self._tracker_sink = tracker_sink
        self._ring_size = int(ring_size)
        self.memory_interval = int(memory_interval)
        if sync_device is None:
            from .utils.environment import parse_flag_from_env

            sync_device = not parse_flag_from_env("ACCELERATE_TELEMETRY_NO_SYNC")
        self.sync_device = bool(sync_device)

        env_peak = os.environ.get("ACCELERATE_TELEMETRY_PEAK_FLOPS")
        if peak_flops is None and env_peak:
            peak_flops = float(env_peak)
        self._peak_flops = peak_flops  # None → resolve lazily from the device

        # ring buffers (per kind, so step percentiles aren't diluted)
        self.records: deque = deque(maxlen=self._ring_size)
        self._step_times: deque = deque(maxlen=self._ring_size)
        self._dispatch_times: deque = deque(maxlen=self._ring_size)
        self._device_times: deque = deque(maxlen=self._ring_size)
        self._examples_rates: deque = deque(maxlen=self._ring_size)
        self._tokens_rates: deque = deque(maxlen=self._ring_size)

        # counters
        self.step_count = 0
        self.optimizer_step_count = 0
        self.recompile_count = 0
        self.skipped_step_count = 0
        #: steps whose skip verdict was UNKNOWN at record time (fp16 fused
        #: path: the finite-grads flag was still on device) — distinct from
        #: "not skipped" so summaries stay honest about what they counted
        self.unknown_skip_count = 0
        self.compile_seconds_total = 0.0
        self._static_keys: set = set()
        self._step_flops: float | None = None  # last step-program cost fact
        self._step_collective_bytes: int | None = None

        # per-step scratch fed by backward()/note_batch
        self._pending_examples: int | None = None
        self._pending_tokens: int | None = None
        self._pending_backward_s: float = 0.0
        self._last_step_end: float | None = None

        # JSONL sink (main process only; crash-safe append). The trail is
        # size-capped: past ACCELERATE_TELEMETRY_MAX_BYTES the live file
        # rolls to telemetry.jsonl.1 (older segments shift up, the oldest
        # beyond ACCELERATE_TELEMETRY_KEEP_SEGMENTS drops) — a weeks-long
        # serving job must not grow an unbounded trail. 0 disables rotation.
        self._jsonl = None
        self._jsonl_path = None
        self._jsonl_bytes = 0
        self._jsonl_max_bytes = int(
            os.environ.get("ACCELERATE_TELEMETRY_MAX_BYTES", str(64 * 1024 * 1024))
        )
        self._jsonl_keep = max(
            1, int(os.environ.get("ACCELERATE_TELEMETRY_KEEP_SEGMENTS", "4"))
        )
        if logging_dir is not None and _is_main_process():
            tel_dir = os.path.join(logging_dir, "telemetry")
            os.makedirs(tel_dir, exist_ok=True)
            self._jsonl_path = os.path.join(tel_dir, "telemetry.jsonl")
            try:
                self._jsonl_bytes = os.path.getsize(self._jsonl_path)
            except OSError:
                self._jsonl_bytes = 0
            self._jsonl = open(self._jsonl_path, "a")

        from .lazy import set_compile_callback

        set_compile_callback(self._on_compile)

        # crash paths that never reach Accelerator.end_training() (uncaught
        # exceptions, sys.exit from user code) must still leave a complete
        # JSONL tail — close() is idempotent, so the normal path unregisters
        # and this is a no-op there
        import atexit

        atexit.register(self.close)

    # -- sinks ---------------------------------------------------------------

    def _emit(self, record: dict, fan_out: bool = True, step: int | None = None):
        record.setdefault("ts", time.time())
        record.setdefault("schema", SCHEMA_VERSION)
        self.records.append(record)
        # metrics fan-out: the active MetricsRegistry (GET /metrics surface)
        # sees every record through the same mapping the sidecar exporter
        # replays from the JSONL — disabled is one global read
        metrics_registry = _get_metrics_registry()
        if metrics_registry:
            try:
                _observe_metrics_record(metrics_registry, record)
            except Exception:  # the scrape surface must never kill training
                logger.warning("metrics ingest failed", exc_info=True)
        if self._jsonl is not None:
            try:
                line = json.dumps(record, default=_json_default) + "\n"
                self._jsonl.write(line)
                self._jsonl.flush()
                self._jsonl_bytes += len(line)
                if self._jsonl_max_bytes and self._jsonl_bytes >= self._jsonl_max_bytes:
                    self._rotate_jsonl()
            except ValueError:  # closed file (end_training raced a record)
                pass
        if fan_out and self._tracker_sink is not None and _is_main_process():
            values = {
                f"telemetry/{k}": v
                for k, v in record.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool) and k != "ts"
            }
            if values:
                try:
                    self._tracker_sink(values, step)
                except Exception:  # tracker failures must not kill training
                    logger.warning("telemetry tracker fan-out failed", exc_info=True)

    def _rotate_jsonl(self):
        """Size-capped rollover: close the live file, shift rotated
        segments up one slot (dropping the oldest beyond the keep count),
        move the live trail to ``.1``, reopen fresh. Readers that follow
        :func:`telemetry_segments` see one continuous trail across the
        roll; a crash mid-rotation loses at most the rename in flight (the
        segment files themselves are never rewritten)."""
        if self._jsonl is None or self._jsonl_path is None:
            return
        try:
            self._jsonl.close()
        except Exception:
            pass
        self._jsonl = None
        path = self._jsonl_path
        try:
            oldest = f"{path}.{self._jsonl_keep}"
            if os.path.exists(oldest):
                os.unlink(oldest)
            for n in range(self._jsonl_keep - 1, 0, -1):
                src = f"{path}.{n}"
                if os.path.exists(src):
                    os.replace(src, f"{path}.{n + 1}")
            os.replace(path, f"{path}.1")
        except OSError:
            logger.warning("telemetry JSONL rotation failed", exc_info=True)
        try:
            self._jsonl = open(path, "a")
            self._jsonl_bytes = 0
        except OSError:
            logger.warning("telemetry JSONL reopen failed; file sink disabled",
                           exc_info=True)
            self._jsonl = None

    # -- compile events (lazy.py miss callback) ------------------------------

    def _on_compile(self, facts: dict):
        self.recompile_count += 1
        self._static_keys.add(facts.get("static_key"))
        total_s = float(facts.get("lower_s") or 0.0) + float(facts.get("compile_s") or 0.0)
        self.compile_seconds_total += total_s
        if facts.get("label") in _STEP_LABELS and facts.get("flops"):
            self._step_flops = float(facts["flops"])
            self._step_collective_bytes = facts.get("collective_bytes")
        record = {
            "type": "compile",
            "label": facts.get("label"),
            "static_key": facts.get("static_key"),
            "lower_s": facts.get("lower_s"),
            "compile_s": facts.get("compile_s"),
            "total_s": total_s,
            "mono": facts.get("mono"),
            "flops": facts.get("flops"),
            "bytes_accessed": facts.get("bytes_accessed"),
            "collective_bytes": facts.get("collective_bytes"),
            "mosaic_custom_calls": facts.get("mosaic_custom_calls"),
            "recompiles": self.recompile_count,
        }
        # analysis/compiled.py fingerprint: present whenever the AOT path
        # computed one. ``changed_args`` NAMES the argument whose
        # shape/dtype perturbed the signature — the "why did this
        # re-trace" answer, directly in the trail. The arg_bytes pair is
        # the shard-plan model's predicted per-device bytes vs the real
        # shard buffers (sanitizer-armed compiles only)
        for key in ("fingerprint", "changed_args", "collective_digest",
                    "arg_bytes_predicted", "arg_bytes_actual"):
            if facts.get(key) is not None:
                record[key] = facts[key]
        self._emit(record, step=self.optimizer_step_count)

    # -- per-step plumbing ---------------------------------------------------

    def note_batch(self, examples: int | None, tokens: int | None):
        """Batch geometry of the loss about to be stepped (fed by
        ``Accelerator.backward`` from the deferred graph's inputs)."""
        self._pending_examples = examples
        self._pending_tokens = tokens

    def note_backward(self, seconds: float):
        """Host time spent inside ``backward()`` (graph bookkeeping on the
        fused path; grad dispatch on the split path) — folded into the next
        step record's ``dispatch_s``."""
        self._pending_backward_s += float(seconds)

    def record_step(
        self,
        dispatch_s: float,
        device_s: float | None = None,
        sync_gradients: bool = True,
        skipped: bool | None = False,  # None = unknown (fp16 flag on device)
    ):
        now = time.perf_counter()
        self.step_count += 1
        if skipped is None:
            self.unknown_skip_count += 1
        elif skipped:
            self.skipped_step_count += 1
        # an unknown verdict counts toward optimizer_steps (the usual case:
        # the device flag resolves to "fine"); unknown_skip records how many
        # carried that assumption
        if sync_gradients and not skipped:
            self.optimizer_step_count += 1
        dispatch_s = float(dispatch_s) + self._pending_backward_s
        self._pending_backward_s = 0.0
        # true loop cadence when available (includes the user's host work);
        # first step falls back to the instrumented spans
        if self._last_step_end is not None:
            step_time_s = now - self._last_step_end
        else:
            step_time_s = dispatch_s + (device_s or 0.0)
        self._last_step_end = now

        examples, tokens = self._pending_examples, self._pending_tokens
        self._pending_examples = self._pending_tokens = None

        record = {
            "type": "step",
            "step": self.step_count,
            "optimizer_steps": self.optimizer_step_count,
            "step_time_s": step_time_s,
            "dispatch_s": dispatch_s,
            "device_s": device_s,
            "sync_gradients": bool(sync_gradients),
            "accum_phase": "sync" if sync_gradients else "accumulate",
            "skipped": None if skipped is None else bool(skipped),
            "recompiles": self.recompile_count,
        }
        self._step_times.append(step_time_s)
        self._dispatch_times.append(dispatch_s)
        if device_s is not None:
            self._device_times.append(device_s)
        if examples and step_time_s > 0:
            record["examples"] = examples
            record["examples_per_sec"] = examples / step_time_s
            self._examples_rates.append(record["examples_per_sec"])
        if tokens and step_time_s > 0:
            record["tokens"] = tokens
            record["tokens_per_sec"] = tokens / step_time_s
            self._tokens_rates.append(record["tokens_per_sec"])
        mfu = self._mfu(step_time_s)
        if mfu is not None:
            record["mfu"] = mfu
        self._emit(record, step=self.optimizer_step_count)

        if self.memory_interval and self.step_count % self.memory_interval == 0:
            self.record_memory()

    def _resolve_peak_flops(self) -> float | None:
        if self._peak_flops is not None:
            return self._peak_flops
        try:
            import jax

            kind = jax.devices()[0].device_kind.lower()
        except Exception:
            return None
        for key, peak in PEAK_FLOPS_TABLE:
            if key in kind:
                self._peak_flops = peak
                return peak
        return None  # unknown chip (or a CPU host): no credible MFU

    def _mfu(self, step_time_s: float) -> float | None:
        peak = self._resolve_peak_flops()
        if peak is None or not self._step_flops or step_time_s <= 0:
            return None
        try:
            import jax

            n_dev = jax.device_count()
        except Exception:
            n_dev = 1
        # cost_analysis reports the whole (sharded) program's FLOPs; peak is
        # per chip, so normalise by the device count the program spans
        return float(self._step_flops) / step_time_s / (peak * n_dev)

    # -- interval / event records -------------------------------------------

    def record_memory(self):
        device_in_use = device_peak = None
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats() or {}
            device_in_use = stats.get("bytes_in_use")
            device_peak = stats.get("peak_bytes_in_use")
        except Exception:
            pass
        self._emit(
            {
                "type": "memory",
                "step": self.step_count,
                "device_bytes_in_use": device_in_use,
                "device_peak_bytes": device_peak,
                "host_rss_bytes": _host_rss_bytes(),
            },
            step=self.optimizer_step_count,
        )

    def record_generation(
        self,
        mode: str,
        new_tokens: int,
        seconds: float,
        accept_rate: float | None = None,
        verify_rounds: int | None = None,
    ):
        record = {
            "type": "generate",
            "mode": mode,
            "new_tokens": int(new_tokens),
            "seconds": float(seconds),
            "tokens_per_sec": (new_tokens / seconds) if seconds > 0 else None,
        }
        if accept_rate is not None:
            record["accept_rate"] = float(accept_rate)
        if verify_rounds is not None:
            record["verify_rounds"] = int(verify_rounds)
        self._emit(record, step=self.optimizer_step_count)

    def record_serving(self, kind: str, **fields):
        """One serving-engine row (fed by ``serving.engine``): ``kind`` is
        ``"step"`` (periodic — tokens/s over the window, queue depth, slot
        occupancy, free KV blocks, decode-compile count) or ``"request"``
        (per completion — TTFT/TPOT seconds, prompt/new token counts,
        finish reason). ``accelerate-tpu monitor`` renders the latest of
        each."""
        self._emit({"type": "serving", "kind": kind, **fields}, step=self.optimizer_step_count)

    def record_profile(self, trace_dir: str, steps: int, active_steps: int = 0):
        self._emit(
            {
                "type": "profile",
                "trace_dir": trace_dir,
                "steps": int(steps),
                "active_steps": int(active_steps),
            },
            step=self.optimizer_step_count,
        )

    def record_checkpoint(
        self,
        kind: str,
        seconds: float | None = None,
        bytes_written: int | None = None,
        shard_count: int | None = None,
        is_async: bool = False,
        path: str | None = None,
    ):
        """One record per checkpoint save/restore (fed by
        ``checkpointing.py``): how long, how many bytes, how many per-host
        shard dirs, and whether the write rode the async writer."""
        self._emit(
            {
                "type": "checkpoint",
                "kind": kind,
                "seconds": None if seconds is None else float(seconds),
                "bytes": None if bytes_written is None else int(bytes_written),
                "shard_count": None if shard_count is None else int(shard_count),
                "async": bool(is_async),
                "path": path,
            },
            step=self.optimizer_step_count,
        )

    def record_event(self, kind: str, **fields):
        self._emit({"type": "event", "kind": kind, **fields}, step=self.optimizer_step_count)

    # -- queries -------------------------------------------------------------

    def summary(self) -> dict:
        """Aggregate view over the ring buffer: step-time percentiles,
        median throughput, cumulative recompile/compile accounting, and the
        latest memory sample."""
        out: dict = {
            "steps": self.step_count,
            "optimizer_steps": self.optimizer_step_count,
            "skipped_steps": self.skipped_step_count,
            "unknown_skip": self.unknown_skip_count,
            "recompiles": self.recompile_count,
            "distinct_static_keys": len(self._static_keys),
            "compile_seconds_total": self.compile_seconds_total,
        }
        if self._step_times:
            out["step_time_s"] = _percentiles(self._step_times)
            out["dispatch_s"] = _percentiles(self._dispatch_times)
        if self._device_times:
            out["device_s"] = _percentiles(self._device_times)
        if self._examples_rates:
            out["examples_per_sec"] = float(np.median(list(self._examples_rates)))
        if self._tokens_rates:
            out["tokens_per_sec"] = float(np.median(list(self._tokens_rates)))
        if self._step_flops:
            out["step_flops"] = self._step_flops
            if self._step_collective_bytes is not None:
                out["step_collective_bytes"] = self._step_collective_bytes
        for record in reversed(self.records):
            if record.get("type") == "memory":
                out["memory"] = {
                    k: record[k]
                    for k in ("device_bytes_in_use", "device_peak_bytes", "host_rss_bytes")
                }
                break
        return out

    @property
    def jsonl_path(self) -> str | None:
        return self._jsonl_path

    def close(self):
        """Idempotent: safe to call from end_training(), the atexit hook,
        and a Borg takeover in any order."""
        from .lazy import get_compile_callback, set_compile_callback

        if get_compile_callback() is self._on_compile:
            set_compile_callback(None)
        if _ACTIVE is self:
            set_active_recorder(None)
        if self._jsonl is not None:
            try:
                self._jsonl.close()
            except Exception:
                pass
            self._jsonl = None
        if not self._closed:
            self._closed = True
            import atexit

            try:
                atexit.unregister(self.close)
            except Exception:
                pass


def _json_default(obj):
    if hasattr(obj, "item"):
        try:
            return obj.item()
        except Exception:
            pass
    return str(obj)


def batch_geometry(input_values) -> tuple[int | None, int | None]:
    """(examples, tokens) of a step's input leaves: examples from the first
    array's leading dim; tokens from the first rank-2 integer array
    (``input_ids``-shaped). Best-effort — None when nothing matches."""
    examples = tokens = None
    for leaf in input_values:
        shape = getattr(leaf, "shape", None)
        if not shape:
            continue
        if examples is None and len(shape) >= 1 and shape[0] > 0:
            examples = int(shape[0])
        dtype = str(getattr(leaf, "dtype", ""))
        if tokens is None and len(shape) == 2 and ("int" in dtype):
            tokens = int(shape[0]) * int(shape[1])
        if examples is not None and tokens is not None:
            break
    return examples, tokens
