"""Kernels: the paged-attention kernel's share of its roofline where the
decode program is a block round — the least time the chip could take for
the calls the traced span made over the kernel's time in the trace.

``layer_metrics/kernel.py`` reckons a decode dispatch as ``decode_burst``
steps of one query a row; that is not what a block round runs, so this
reader brings its own count (README: a new count comes in its reader's
file). For every dispatch that started inside the traced span, from the rows
the harness saw decoding when it was built:

* ``decode_burst`` rounds; round ``n`` of a row whose known tokens end at
  ``c`` stands at the block ``[s, s + B)``, ``s = floor(c / B) * B + n * B``;
* a round is ``T + 1`` forwards (``T`` denoise passes, one commit pass), each
  one call a layer of ``B`` queries a row against the row's context up to the
  END of the block, ``s + B`` positions (attention is bidirectional inside a
  block), keys and values read once a call;
* a prefill chunk is one call a layer of ``n`` queries (whole blocks) against
  ``start + n`` positions; a query sees, on average over a chunk that starts
  and ends on a block's edge, ``start + (n + B) / 2`` of them.

``B``, ``T`` come from the configuration (``block_length``,
``denoise_steps``); operations and bytes of a call from
``counts.paged_attention_cost``. The rows are read before the step, so a
dispatch's contexts are up to one harvest (``decode_burst * B`` positions)
behind what it ran: the count errs low, as ``kernel.py``'s does. A program
that runs no block round, or a configuration without those keys, reads
``None``.
"""

from perfbench import counts
from perfbench.layer_metrics import _util

KERNEL = "paged_attention"


def round_calls(cfg: dict, known: list, n: int) -> tuple:
    """(context lengths, query lengths) of ONE call of round ``n`` of a
    dispatch over rows whose known tokens end at ``known``."""
    b = cfg["block_length"]
    return [c // b * b + (n + 1) * b for c in known], [b] * len(known)


def dispatch_cost(cfg: dict, known: list, burst: int, kv_itemsize: int = 2) -> dict:
    """Operations and bytes of one layer's calls in one dispatch of
    ``burst`` rounds: ``denoise_steps + 1`` calls a round."""
    calls = cfg["denoise_steps"] + 1
    total = {"flops": 0.0, "bytes": 0.0}
    for n in range(burst):
        cost = counts.paged_attention_cost(cfg, *round_calls(cfg, known, n), kv_itemsize)
        total = {k: total[k] + calls * cost[k] for k in total}
    return total


def chunk_cost(cfg: dict, start: int, n: int, kv_itemsize: int = 2) -> dict | None:
    """One layer's call for a prefill chunk of ``n`` positions from
    ``start`` (cut back to whole blocks); ``None`` where none is whole."""
    b = cfg["block_length"]
    n = n // b * b
    if n <= 0:
        return None
    cost = counts.paged_attention_cost(cfg, [start + n], [n], kv_itemsize)
    cost["flops"] *= (start + (n + b) / 2.0) / (start + n)
    return cost


def least_s(lc: dict) -> float | None:
    rec, cfg, span = lc["recorder"], lc["config"], lc.get("trace_span")
    if span is None or not rec.iter_t or not cfg.get("block_length") or not cfg.get("denoise_steps"):
        return None
    peak = counts.peaks(lc["device_kind"])
    layers, burst, item = counts.kv_layers(cfg), lc["decode_burst"], lc["kv_itemsize"]
    total = 0.0
    for t, dec, pre in zip(rec.iter_t, rec.decode_contexts, rec.prefill_chunks):
        if not span[0] <= t < span[1]:
            continue
        # every call is bound on its own: the least time of a sum of calls is
        # the sum of their least times
        for n in range(burst if dec else 0):
            one = counts.paged_attention_cost(cfg, *round_calls(cfg, dec, n), item)
            total += (layers * (cfg["denoise_steps"] + 1)
                      * counts.roofline(one, peak)["least_s"])
        for start, n in pre:
            cost = chunk_cost(cfg, start, n, item)
            if cost is not None:
                total += layers * counts.roofline(cost, peak)["least_s"]
    return total


def read(name: str, lc: dict):
    trace = lc.get("trace")
    stats = lc.get("stats1") or {}
    if trace is None or "block_rounds_total" not in stats:
        return None  # no trace, or a program that runs no block round
    least = least_s(lc)
    kern = _util.worst_device(trace, lambda d: _util.kernel_ns(d, [KERNEL]))
    if not least or not kern:
        return None
    return 100.0 * least / (kern / 1e9)
