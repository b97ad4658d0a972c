"""Kernels: the paged attention kernel's share of its roofline in a model
whose paged layers are of two kinds - layers that attend the whole context
beside layers that attend a sliding window (``sliding_window_layout`` /
``sliding_window_size`` in the configuration) - the least time the chip could
take for the calls the traced span made over the time the trace books under
the kernel's name, both kinds' calls together (they are one kernel).

``kernel.py`` counts every layer at the row's whole context, which for a
window layer counts keys its queries do not see and would read over 100
here. The count of this file, from shapes alone (README: a configuration
whose kernel does other work brings its operations and bytes in its reader's
file), for ONE layer's call:

* a row of ``q`` queries whose last stands at context ``ctx`` (``ctx`` cached
  positions, the row's own among them) reads, in a full layer, K and V of
  all ``ctx`` positions once: ``2 * n_kv * hd * itemsize`` bytes a position;
  in a window layer of the positions visible to ANY of its queries: from
  ``max(0, ctx - q - window + 1)`` on, ``min(ctx, window - 1 + q)`` of them.
  The queries in and the output out are counted beside (``2 * q * heads *
  hd``).
* it costs ``4 * heads * hd`` operations (the two products) a (query, visible
  key) pair. A decode row (``q`` 1) has ``ctx`` pairs in a full layer and
  ``min(ctx, window)`` in a window layer. A chunk's own rows are attended
  causally: query ``i`` of a chunk that starts at ``start`` sees ``start + i
  + 1`` keys in a full layer and ``min(start + i + 1, window)`` in a window
  layer, summed over the chunk exactly.

Positions, not whole blocks or tiles: the kernel copies whole blocks of 16
and multiplies whole tiles of 128 keys, masked, so the share reads low and
never over 100. Calls are bound one by one (the least time of a sum of calls
is the sum of their least times). The rows come from the recorder, read
before the step, so a dispatch's contexts are up to one harvest behind what
it ran: the count errs low, as ``kernel.py``'s does. A configuration without
the window keys, or a trace without the kernel, reads ``None``.
"""

from perfbench import counts
from perfbench.layer_metrics import _util

KERNEL = "paged_attention"


def visible_pairs(start: int, q: int, window: int) -> float:
    """(query, visible key) pairs of ``q`` queries at positions ``start ..
    start + q - 1``, each seeing the keys up to its own, the last ``window``
    of them where ``window`` is above 0."""
    if not window:
        return q * start + q * (q + 1) / 2.0
    # query i sees min(start + i + 1, window): whole triangle until the window fills
    ramp = max(0, min(q, window - start))               # queries that still see every key
    return ramp * start + ramp * (ramp + 1) / 2.0 + (q - ramp) * float(window)


def call_cost(cfg: dict, rows, window: int, kv_itemsize: int = 2, act_itemsize: int = 2) -> dict:
    """ONE layer's call over ``rows``: ``[(start, q)]``, ``q`` queries a row
    from position ``start``; ``window`` 0 is a layer that sees every key."""
    nh, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], counts.head_dim(cfg)
    flops = nbytes = 0.0
    for start, q in rows:
        ctx = start + q
        keys = min(ctx, window - 1 + q) if window else ctx
        flops += 4.0 * nh * hd * visible_pairs(start, q, window)
        nbytes += 2.0 * keys * nkv * hd * kv_itemsize + 2.0 * q * nh * hd * act_itemsize
    return {"flops": flops, "bytes": nbytes}


def layer_windows(cfg: dict) -> list:
    """The window of each layer that runs the kernel (0: the whole past)."""
    n = cfg["num_hidden_layers"]
    return [cfg["sliding_window_size"] if w else 0 for w in cfg["sliding_window_layout"][:n]]


def least_s(lc: dict) -> float | None:
    rec, cfg, span = lc["recorder"], lc["config"], lc.get("trace_span")
    if span is None or not rec.iter_t or "sliding_window_layout" not in cfg:
        return None
    peak = counts.peaks(lc["device_kind"])
    burst, item = lc["decode_burst"], lc["kv_itemsize"]
    windows = layer_windows(cfg)
    kinds = {w: windows.count(w) for w in set(windows)}  # layers of each window
    total = 0.0
    for t, dec, pre in zip(rec.iter_t, rec.decode_contexts, rec.prefill_chunks):
        if not span[0] <= t < span[1]:
            continue
        calls = [[(c - 1 + s, 1) for c in dec] for s in range(burst if dec else 0)]
        calls += [[(start, n)] for start, n in pre if n > 0]
        for rows in calls:
            for window, layers in kinds.items():
                cost = call_cost(cfg, rows, window, item)
                total += layers * counts.roofline(cost, peak)["least_s"]
    return total


def read(name: str, lc: dict):
    trace = lc.get("trace")
    if trace is None or name != "kernel.paged_attention.window_roofline_pct":
        return None
    least = least_s(lc)
    kern = _util.worst_device(trace, lambda d: _util.kernel_ns(d, [KERNEL]))
    if not least or not kern:
        return None
    return 100.0 * least / (kern / 1e9)
