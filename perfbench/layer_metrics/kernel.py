"""Kernels: share of device busy time, and share of the roofline — the
least time the chip could take for the calls the traced window made (the
larger of operations over peak FLOP/s and bytes over peak bytes/s, from
``perfbench/counts.py`` and the rows the harness saw) over the kernel's
time in the trace."""

from perfbench import counts
from perfbench.layer_metrics import _util

FLASH = ("flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkv")


def _paged_least_s(lc: dict) -> float | None:
    """Least seconds for every paged-attention call of the iterations that
    started inside the traced span of the window."""
    rec, cfg, span = lc["recorder"], lc["config"], lc.get("trace_span")
    if span is None or not rec.iter_t:
        return None
    peak = counts.peaks(lc["device_kind"])
    layers, burst = cfg["num_hidden_layers"], lc["decode_burst"]
    total = 0.0
    for t, dec, pre in zip(rec.iter_t, rec.decode_contexts, rec.prefill_chunks):
        if not span[0] <= t < span[1]:
            continue
        for s in range(burst if dec else 0):
            cost = counts.paged_attention_cost(
                cfg, [c + s for c in dec], [1] * len(dec), lc["kv_itemsize"])
            total += layers * counts.roofline(cost, peak)["least_s"]
        for start, n in pre:
            # a chunk's queries see the cached prefix and, causally, half of
            # the chunk on average; its keys and values are read once
            cost = counts.paged_attention_cost(cfg, [start + n], [n], lc["kv_itemsize"])
            cost["flops"] *= (start + (n + 1) / 2.0) / (start + n)
            total += layers * counts.roofline(cost, peak)["least_s"]
    return total


def read(name: str, lc: dict):
    trace = lc.get("trace")
    if trace is None:
        return None
    if name == "kernel.paged_attention.busy_pct":
        return _util.worst_device(
            trace, lambda d: 100.0 * _util.kernel_ns(d, ["paged_attention"]) / d["busy_ns"]
            if d["busy_ns"] else None)
    if name == "kernel.paged_attention.roofline_pct":
        least = _paged_least_s(lc)
        kern = _util.worst_device(trace, lambda d: _util.kernel_ns(d, ["paged_attention"]))
        if least is None or not kern:
            return None
        return 100.0 * least / (kern / 1e9)
    if name == "kernel.flash_attention.busy_pct":
        return _util.worst_device(
            trace, lambda d: 100.0 * _util.kernel_ns(d, FLASH) / d["busy_ns"]
            if d["busy_ns"] else None)
    if name == "kernel.flash_attention.roofline_pct":
        steps = lc.get("traced_steps")
        kern = _util.worst_device(trace, lambda d: _util.kernel_ns(d, FLASH))
        if not steps or not kern:
            return None
        cfg, tr = lc["config"], lc["traffic"]
        cost = counts.flash_attention_cost(
            cfg, tr["global_batch"] // lc["chips"], tr["seq_len"])
        least = counts.roofline(cost, counts.peaks(lc["device_kind"]))["least_s"]
        return 100.0 * steps * cfg["num_hidden_layers"] * least / (kern / 1e9)
    return None
