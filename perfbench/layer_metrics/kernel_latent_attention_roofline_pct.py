"""Kernels: the latent-attention kernel's share of its roofline (and of the
device's busy time) — the least time the chip could take for the calls the
traced span made over the time the trace books under the kernel's name.

A latent cache keeps ONE vector of ``kv_lora_rank + qk_rope_head_dim``
values a token and layer (DeepSeek-V3: 512 + 64 = 576) for all heads, and
the kernel computes attention in the absorbed form: every head's query,
carried into the cache's coordinates, scores each cached vector over all its
values and the softmax sums the vectors' first ``kv_lora_rank`` values. The
count is of those two products over cached positions, in that form, and of
nothing else (``counts.paged_attention_cost`` reads ``2 * ctx * n_kv * hd``
bytes and ``hd``-wide products, which is not this kernel; README: a new
kernel's operations and bytes come in its reader's file):

* a row of ``q`` queries against ``ctx`` cached positions reads ``ctx * 576 *
  itemsize`` bytes once, the queries in (``heads * q * 576``) and the output
  out (``heads * q * 512``) beside it;
* it costs ``2 * heads * q * ctx * (576 + 512)`` operations; a chunk's own
  rows are attended causally, so a query of a chunk that starts at ``start``
  sees ``start + (n + 1) / 2`` positions on average.

The absorption products (``q_nope W_kvb_k^T``, the way back through
``W_kvb_v``) are the program's ``mla_absorb`` scope, not this kernel, and are
not counted; the values are counted 576 wide although the pool stores them
640 wide (whole lane tiles) and the kernel multiplies the padding too. So
the share can read low and never over 100. Every call of the traced span
took this kernel (the program has one attention path, prefill and decode).
Calls are bound one by one: the least time of a sum of calls is the sum of
their least times. The rows come from the recorder, read before the step,
so a dispatch's contexts are up to one harvest behind what it ran: the count
errs low, as ``kernel.py``'s does. A configuration without the latent keys,
or a trace without the kernel, reads ``None``.
"""

from perfbench import counts
from perfbench.layer_metrics import _util

KERNEL = "latent_attention"


def latent_attention_cost(cfg: dict, context_lens, q_lens, kv_itemsize: int = 2,
                          act_itemsize: int = 2) -> dict:
    """ONE layer's call: row ``i`` has ``q_lens[i]`` queries against
    ``context_lens[i]`` cached positions."""
    rank = cfg["kv_lora_rank"]
    width = rank + cfg["qk_rope_head_dim"]
    nh = cfg["num_attention_heads"]
    flops = nbytes = 0.0
    for ctx, ql in zip(context_lens, q_lens):
        flops += 2.0 * nh * ql * ctx * (width + rank)
        nbytes += ctx * width * kv_itemsize + nh * ql * (width + rank) * act_itemsize
    return {"flops": flops, "bytes": nbytes}


def least_s(lc: dict) -> float | None:
    rec, cfg, span = lc["recorder"], lc["config"], lc.get("trace_span")
    if span is None or not rec.iter_t or "kv_lora_rank" not in cfg:
        return None
    peak = counts.peaks(lc["device_kind"])
    layers, burst, item = counts.kv_layers(cfg), lc["decode_burst"], lc["kv_itemsize"]
    total = 0.0
    for t, dec, pre in zip(rec.iter_t, rec.decode_contexts, rec.prefill_chunks):
        if not span[0] <= t < span[1]:
            continue
        for s in range(burst if dec else 0):
            cost = latent_attention_cost(cfg, [c + s for c in dec], [1] * len(dec), item)
            total += layers * counts.roofline(cost, peak)["least_s"]
        for start, n in pre:
            cost = latent_attention_cost(cfg, [start + n], [n], item)
            cost["flops"] *= (start + (n + 1) / 2.0) / (start + n)
            total += layers * counts.roofline(cost, peak)["least_s"]
    return total


def read(name: str, lc: dict):
    trace = lc.get("trace")
    if trace is None:
        return None
    if name == "kernel.latent_attention.busy_pct":
        return _util.worst_device(
            trace, lambda d: 100.0 * _util.kernel_ns(d, [KERNEL]) / d["busy_ns"]
            if d["busy_ns"] and _util.kernel_ns(d, [KERNEL]) else None)
    least = least_s(lc)
    kern = _util.worst_device(trace, lambda d: _util.kernel_ns(d, [KERNEL]))
    if not least or not kern:
        return None
    return 100.0 * least / (kern / 1e9)
