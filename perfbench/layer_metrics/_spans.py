"""What the readers of the program's own spans and scopes share.

**Scopes.** The program names the parts of its model step with
``jax.named_scope`` (``embed``, ``layers``, ``attn_proj``, ``kv_write``,
``attn_kernel``, ``mlp``, ``head``, ``sample``, and in the fused train step
``loss`` and ``optimizer``). XLA keeps the scope stack of an operation as
its ``op_name`` — ``jit(step)/loss/transpose(jvp(layers))/while/body/
closed_call/checkpoint/rematted_computation/mlp/dot_general``. The TPU's
trace does not carry it: an event is named by its HLO instruction
(``%fusion.12 = bf16[64,4096]{...} fusion(...)``) and has no string beside
(seen on the v5e, PR 24). So the program hands out, per compiled program,
the table from instruction to stack (``InferenceEngine.scope_table``,
``accelerate_tpu.lazy.scope_table``), and an event finds its stack by its
instruction's name and result shape. An operation belongs to the innermost
scope of its stack; ``layers`` with no scope inside it is ``layer_carry``
(the scan's slicing and writing back of what it carries), and an operation
with no stack (the compiler's own layout copies) or no scope is
``unscoped``.

**Passes.** The transforms a scope was traced under are part of the same
stack: ``jvp(...)`` alone is the forward pass, ``transpose(jvp(...))`` the
backward pass, ``rematted_computation`` under it the recomputed forward of
``jax.checkpoint``; the ``optimizer`` scope is its own pass.

**Flight intervals.** The engine's flight entries carry the iteration's
start as ``time.time_ns()`` (``t_start_unix_ns``) and its phases as
``(phase, start_s, end_s)`` from there. The profiler stamps host events with
the same clock less the session's start, which the reduced trace does not
keep; :func:`session_start_ns` finds it from the harness's own
``perfbench/engine.step`` spans, each of which wraps one iteration.
"""

from __future__ import annotations

import bisect
import re
import sys

SCOPES = ("embed", "attn_proj", "kv_write", "attn_kernel", "mlp", "head",
          "sample", "loss", "optimizer")
PASSES = ("fwd", "bwd", "remat", "optimizer", "other")
PHASES = ("schedule", "prefill", "dispatch", "device_wait", "harvest")

_RESULT_SHAPE = re.compile(r" = \(*([a-z]\w*\[[0-9,]*\])")
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
_ENGINE_STEP = "perfbench/engine.step"


def scope_tables(lc: dict) -> list:
    """The program's instruction-to-stack tables for the programs this cell
    runs (kept in ``lc`` once made): the engine's decode and prefill
    programs — the engine is reached through the recorder's wrapper, which
    still holds the freed engine's ``step`` — or the fused train step's.
    ``[]`` from a program that hands out none."""
    if "scope_tables" not in lc:
        tables = []
        try:
            engine = getattr(getattr(lc.get("recorder"), "_step", None), "__self__", None)
            if engine is not None:
                if hasattr(engine, "scope_table"):
                    tables = [engine.scope_table(p) for p in ("decode", "prefill")]
            else:
                from accelerate_tpu import lazy

                if hasattr(lazy, "scope_table"):
                    tables = [lazy.scope_table("fused_step")]
        except Exception as e:  # noqa: BLE001 — the run's result stands without these metrics
            print(f"perfbench scopes: no table ({type(e).__name__}: {e})", file=sys.stderr)
        lc["scope_tables"] = [t for t in tables if t]
    return lc["scope_tables"]


def name_stack(event, tables: list) -> str:
    """The scope stack of a device event: its instruction's entry in one of
    the tables, the one of the same result shape where two programs share
    the instruction's name ('' where none has it)."""
    found = [t[event.name] for t in tables if event.name in t]
    if len(found) > 1:
        m = _RESULT_SHAPE.search(event.text)
        found = [f for f in found if m and f[0] == m.group(1)] or found
    return found[0][1] if found else ""


def _parts(stack: str) -> list:
    """The stack's components, each reduced to the name a transform wraps:
    ``transpose(jvp(layers))`` -> ``layers``."""
    out = []
    for comp in stack.split("/"):
        words = _WORD.findall(comp.split("[", 1)[0])
        out.append(words[-1] if words else "")
    return out


def scope_of(stack: str) -> str:
    for part in reversed(_parts(stack)):
        if part in SCOPES:
            return part
        if part == "layers":
            return "layer_carry"
    return "unscoped"


def pass_of(stack: str) -> str:
    if "optimizer" in _parts(stack):
        return "optimizer"
    if "rematted_computation" in stack:
        return "remat"
    if "transpose(" in stack:
        return "bwd"
    if "jvp(" in stack:
        return "fwd"
    return "other"


def busiest(trace: dict) -> dict:
    """The device whose shares are reported: the one busy longest (the
    shares of one device sum to 100; the worst of each over devices would
    not)."""
    return max(trace["devices"].values(), key=lambda d: d["busy_ns"])


def _self_by_stack(dev: dict, tables: list) -> list:
    """``[(scope stack, operation, self ns)]`` of the device, summed over
    the events that share both; worked out once and kept beside the
    device's other sums, for every reader of this family."""
    from perfbench.reduce import xplane

    if "self_by_stack" not in dev:
        sums: dict = {}
        for e, self_ns in xplane.self_times(dev["ops"]):
            if self_ns:
                key = (name_stack(e, tables), xplane.base_name(e.name))
                sums[key] = sums.get(key, 0.0) + self_ns
        dev["self_by_stack"] = [(stack, op, ns) for (stack, op), ns in sums.items()]
    return dev["self_by_stack"]


def self_shares(dev: dict, tables: list, key_of) -> dict:
    """Self time of the device's operations by ``key_of(scope stack)``, in
    per cent of its busy time."""
    out: dict = {}
    for stack, _, ns in _self_by_stack(dev, tables):
        k = key_of(stack)
        out[k] = out.get(k, 0.0) + ns
    busy = dev["busy_ns"]
    return {k: 100.0 * v / busy for k, v in out.items()} if busy else {}


def unscoped_names(dev: dict, tables: list, top: int = 8) -> list:
    """``[(operation, seconds)]``: what ``scope.unscoped_pct`` is made of."""
    out: dict = {}
    for stack, op, ns in _self_by_stack(dev, tables):
        if scope_of(stack) == "unscoped":
            out[op] = out.get(op, 0.0) + ns / 1e9
    return sorted(out.items(), key=lambda kv: -kv[1])[:top]


# -- flight intervals on the trace's clock ----------------------------------

def stamped_flights(lc: dict) -> list:
    rec = lc.get("recorder")
    return [e for e in getattr(rec, "flight", [])
            if "t_start_unix_ns" in e and "intervals" in e]


def session_start_ns(flights: list, spans: list, tol_ns: int = 50_000):
    """``(start, pairs)``: the profiler session's start on the wall clock,
    and the ``(flight, span)`` pairs it rests on. Every ``engine.step`` span
    wraps exactly one flight iteration and lasts a few microseconds longer,
    so only pairs of nearly one length are candidates, and the true start
    is the value of ``flight start - span start`` that most of them agree
    on (to within ``tol_ns``, far less than an iteration lasts); the median
    of the agreeing differences is taken. ``(None, [])`` without a pair."""
    spans = [s for s in spans if s.name == _ENGINE_STEP]
    diffs = sorted((f["t_start_unix_ns"] - int(s.start_ns), i, j)
                   for i, f in enumerate(flights) for j, s in enumerate(spans)
                   if -tol_ns <= s.dur_ns - f["wall_s"] * 1e9 <= 10 * tol_ns)
    best, lo = [], 0
    for hi in range(len(diffs)):
        while diffs[hi][0] - diffs[lo][0] > tol_ns:
            lo += 1
        if hi - lo + 1 > len(best):
            best = diffs[lo:hi + 1]
    if not best:
        return None, []
    mid = len(best) // 2  # whole nanoseconds: a float cannot hold them
    start = best[mid][0] if len(best) % 2 else (best[mid - 1][0] + best[mid][0]) // 2
    return start, [(flights[i], spans[j]) for _, i, j in best]


def flight_overhang_ns(start: int, pairs: list) -> float:
    """How far the worst-placed iteration sticks out of the harness's span
    of that iteration, once laid on the trace's clock by its own stamp."""
    worst = 0.0
    for f, s in pairs:
        lo = f["t_start_unix_ns"] - start
        hi = lo + f["wall_s"] * 1e9
        worst = max(worst, s.start_ns - lo, hi - s.end_ns)
    return worst


def idle_by_phase(gaps: list, flights: list, start: int) -> dict:
    """Idle nanoseconds of ``gaps`` (in time order) by the engine phase that
    ran then; ``outside_step`` where no iteration ran."""
    out = dict.fromkeys((*PHASES, "outside_step"), 0.0)
    ends = [ge for _, ge in gaps]
    for f in flights:
        base = f["t_start_unix_ns"] - start
        for phase, a, b in f["intervals"]:
            lo, hi = base + a * 1e9, base + b * 1e9
            for gs, ge in gaps[bisect.bisect_right(ends, lo):]:
                if gs >= hi:
                    break
                out[phase] += min(ge, hi) - max(gs, lo)
    out["outside_step"] = max(sum(ge - gs for gs, ge in gaps) - sum(out.values()), 0.0)
    return out
