"""Data loader: the time a step waits for its batch (harness clock around
``next(loader)``), median."""

from perfbench.layer_metrics import _util


def read(name: str, lc: dict):
    if name == "data.wait_ms":
        return _util.median_or_none([x * 1e3 for x in lc.get("data_wait_s", [])])
    return None
