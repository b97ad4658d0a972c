"""Device: idle share of the traced window (1 - union of operation
intervals / window, worst device) and peak memory against the table's HBM.
The metric's suffix (``.chat``, ``.train``) names the kind of cell and is
not read here."""

from perfbench import counts
from perfbench.layer_metrics import _util


def read(name: str, lc: dict):
    if name.startswith("device.idle_pct"):
        trace = lc.get("trace")
        if trace is None:
            return None
        return _util.worst_device(
            trace, lambda d: 100.0 * (1.0 - d["busy_ns"] / trace["window_ns"]))
    if name.startswith("device.hbm_peak_pct"):
        peak = lc.get("memory_peak_bytes")
        if not peak:
            return None
        return 100.0 * peak / counts.peaks(lc["device_kind"])["hbm_bytes"]
    return None
