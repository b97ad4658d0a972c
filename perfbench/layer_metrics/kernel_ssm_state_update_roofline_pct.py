"""Kernels: ``ssm_state_update``, the decode-time recurrence of the Mamba-2
layers — its share of device busy time, and its share of the roofline: the
least time the chip could take for the **live decoding rows** of the traced
iterations over the kernel's time in the trace. The kernel's grid visits
every slot, live or not, and copies a dead slot's state through; those
visits are its cost and not its floor, so at partial occupancy the share
reads well under 100.

The kernel's operations and bytes, from shapes alone (a new kernel brings
them in its reader's file, README): one row of one layer reads and writes
its state ``[H, P, N]`` once each and reads ``x``, ``dt``, ``B``, ``C`` and
writes ``y``.
"""

from perfbench import counts
from perfbench.layer_metrics import _util

KERNEL = "ssm_state_update"
#: the recurrent state is float32 whatever the served dtype (the model's
#: cache spec fixes it; the configuration file lists it under ``assumed``)
STATE_ITEMSIZE = 4


def mamba_layers(cfg: dict) -> int:
    return sum(1 for k in cfg.get("layer_types", ())[: cfg["num_hidden_layers"]] if k == "mamba")


def state_update_cost(cfg: dict, rows: int, act_itemsize: int = 2) -> dict:
    """ONE layer's call over ``rows`` live rows. Bytes: the state read and
    written, ``x [H, P]``, ``B`` and ``C`` ``[N]`` in the served dtype,
    ``dt [H]`` and the output ``y [H, P]`` in float32. FLOPs: decay times
    state plus ``dt x (outer) B`` (3 an element) and ``S C`` (2 an element)."""
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    state = h * p * n
    row_bytes = (2 * state * STATE_ITEMSIZE + (h * p + 2 * n) * act_itemsize
                 + h * 4 + h * p * 4)
    return {"flops": 5.0 * state * rows, "bytes": float(row_bytes) * rows}


def least_s(lc: dict) -> float | None:
    """Least seconds for every call of the decode rounds that started
    inside the traced span: ``decode_burst`` steps a round, every Mamba
    layer a step, the rows that were decoding when the round was built."""
    rec, cfg, span = lc["recorder"], lc["config"], lc.get("trace_span")
    layers = mamba_layers(cfg)
    if span is None or not rec.iter_t or not layers:
        return None
    peak = counts.peaks(lc["device_kind"])
    total = 0.0
    for t, dec in zip(rec.iter_t, rec.decode_contexts):
        if span[0] <= t < span[1] and dec:
            cost = state_update_cost(cfg, len(dec))
            total += lc["decode_burst"] * layers * counts.roofline(cost, peak)["least_s"]
    return total


def read(name: str, lc: dict):
    trace = lc.get("trace")
    if trace is None:
        return None
    if name == f"kernel.{KERNEL}.busy_pct":
        return _util.worst_device(
            trace, lambda d: 100.0 * _util.kernel_ns(d, [KERNEL]) / d["busy_ns"]
            if d["busy_ns"] and _util.kernel_ns(d, [KERNEL]) else None)
    if name == f"kernel.{KERNEL}.roofline_pct":
        least = least_s(lc)
        kern = _util.worst_device(trace, lambda d: _util.kernel_ns(d, [KERNEL]))
        if least is None or not kern:
            return None
        return 100.0 * least / (kern / 1e9)
    return None
