"""Model step: self time of the device's operations by the program's own
scope (see ``_spans``), in per cent of device busy time on the busiest
device. ``scope.<s>_pct.chat`` / ``.train``; over the scopes a cell lists
they sum to 100. Reading ``scope.unscoped_pct`` also
prints, on stderr, the operations that share is made of."""

import json
import sys

from perfbench.layer_metrics import _spans


def read(name: str, lc: dict):
    trace = lc.get("trace")
    parts = name.split(".")
    if trace is None or len(parts) != 3 or not parts[1].endswith("_pct"):
        return None
    tables = _spans.scope_tables(lc)
    if not tables:
        return None  # a program that hands out no scope table
    dev = _spans.busiest(trace)
    shares = _spans.self_shares(dev, tables, _spans.scope_of)
    if parts[1] == "unscoped_pct":
        # what the share is made of, beside the result line (on stderr)
        print("perfbench unscoped " + json.dumps(_spans.unscoped_names(dev, tables)),
              file=sys.stderr)
    return shares.get(parts[1][:-len("_pct")], 0.0)
