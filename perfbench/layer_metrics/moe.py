"""Routed experts: what the router did with the window's tokens, from the
program's own counters, and the expert product's share of its roofline.

The program counts, at every step program it dispatches (a decode step of
every slot, or one prefill chunk) and in every routed layer, the (token,
choice) pairs each expert was given (``stats()``: ``moe_dispatches_total``,
``moe_pairs_routed_total``, ``moe_experts_touched_total`` — experts given at
least one pair, summed over dispatches and layers — and
``moe_load_max_total`` — the busiest expert's pairs, likewise — beside the
fixed ``moe_layers``, ``moe_experts``, ``moe_top_k``). A program without
such counters (one with no routed layer, or the parent of the PR that
brought them) reads ``None`` everywhere here.

* ``moe.experts_touched_pct``: experts touched over experts there, per
  dispatch and layer. Near 100 every expert's matrices are read every step,
  and the step is the weights' stream.
* ``moe.load_max_over_mean``: the busiest expert's pairs over the mean
  expert's, per dispatch and layer (1 is even; a decode step's few rows are
  uneven by chance; how uneven the router's bias leaves a whole window is
  in ``stats()`` ``moe_expert_pairs``, layer by layer).
* ``moe.pairs_per_dispatch``: pairs a routed layer handles in one dispatch
  (live tokens x ``moe_top_k``).
* ``moe.experts_roofline_pct``: the least time the chip could take for the
  expert products of the traced span, over the time the trace books under
  the program's ``moe_experts`` scope (grouping, the products, the weighted
  sum back). **How counters meet 4 s of trace:** nothing is modelled. The
  engine stamps its running ``moe_experts_touched_total`` and
  ``moe_pairs_routed_total`` on every flight entry as of that iteration's
  harvest (``counters``), and the recorder keeps the window's entries; the
  span's experts and pairs are the newest entry ended by the span's end
  less the newest ended by its start (``stats0`` where the span opens the
  window). A harvest brings what the device finished since the last one,
  so the count is off by up to one iteration's work at each end of the
  span, as the paged kernel's share is. Every dispatch of this product is
  bound by its bytes, so the least time of the sums is the sum of the
  dispatches' least times.

The expert product's operations and bytes, from shapes alone (a new kernel
brings them in its reader's file, README): an expert given any pair has its
three matrices read once, ``3 * h * f`` entries; a pair costs
``2 * 3 * h * f`` operations.
"""

from perfbench import counts
from perfbench.layer_metrics import _spans

SCOPE = "moe_experts"
TOTALS = ("moe_dispatches_total", "moe_pairs_routed_total", "moe_experts_touched_total",
          "moe_load_max_total")


def expert_product_cost(cfg: dict, touched: float, pairs: float, itemsize: int = 2) -> dict:
    """Expert products in which ``touched`` experts are read (``w1 | w3``
    and ``w2``, counted once for each dispatch and layer that touches one)
    and ``pairs`` rows go through them; the rows' own bytes in and out are
    counted too."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"flops": 2.0 * 3 * h * f * pairs,
            "bytes": (3.0 * h * f * touched + 2.0 * h * pairs) * itemsize}


def window_totals(lc: dict) -> dict | None:
    """The counters' growth over the window, and the fixed numbers."""
    s0, s1 = lc.get("stats0") or {}, lc.get("stats1") or {}
    if any(k not in s1 or k not in s0 for k in TOTALS) or "moe_layers" not in s1:
        return None
    d = {k: float(s1[k]) - float(s0[k]) for k in TOTALS}
    if d["moe_dispatches_total"] <= 0:
        return None
    d.update(layers=int(s1["moe_layers"]), experts=int(s1["moe_experts"]))
    return d


def span_growth(lc: dict, span) -> dict | None:
    """What the scalar counters grew by over the traced span, from the
    flight entries' stamps (``t_start`` is on the span's clock)."""
    rec = lc.get("recorder")
    rows = [(e["t_start"] + e["wall_s"], e["counters"])
            for e in getattr(rec, "flight", ()) if "counters" in e]
    ended = [c for end, c in rows if end <= span[1]]
    if not ended:
        return None
    before = [c for end, c in rows if end <= span[0]]
    lo = before[-1] if before else lc["stats0"]
    return {k: float(ended[-1][k]) - float(lo[k]) for k in TOTALS}


def scope_seconds(lc: dict) -> float | None:
    """Device self time under the program's ``moe_experts`` scope, busiest
    device, in the traced span."""
    trace, tables = lc.get("trace"), _spans.scope_tables(lc)
    if trace is None or not tables:
        return None
    dev = _spans.busiest(trace)
    share = _spans.self_shares(
        dev, tables, lambda stack: _spans.scope_of(stack, frozenset({SCOPE}))).get(SCOPE)
    return share / 100.0 * dev["busy_ns"] / 1e9 if share else None


def read(name: str, lc: dict):
    w = window_totals(lc)
    if w is None:
        return None
    per_layer_dispatch = w["moe_dispatches_total"] * w["layers"]
    if name == "moe.experts_touched_pct":
        return 100.0 * w["moe_experts_touched_total"] / (per_layer_dispatch * w["experts"])
    if name == "moe.load_max_over_mean":
        if not w["moe_pairs_routed_total"]:
            return None
        return w["moe_load_max_total"] / (w["moe_pairs_routed_total"] / w["experts"])
    if name == "moe.pairs_per_dispatch":
        return w["moe_pairs_routed_total"] / per_layer_dispatch
    if name == "moe.experts_roofline_pct":
        span, busy = lc.get("trace_span"), scope_seconds(lc)
        grown = span_growth(lc, span) if span and busy else None
        if not grown or not grown["moe_experts_touched_total"]:
            return None
        least = counts.roofline(expert_product_cost(
            lc["config"], grown["moe_experts_touched_total"], grown["moe_pairs_routed_total"]),
            counts.peaks(lc["device_kind"]))["least_s"]
        return 100.0 * least / busy
    return None
