"""Model step, train: self time by pass — forward, backward, the
recomputed forward of ``remat``, the optimizer, and the rest — read from
the transforms in each operation's scope stack (see ``_spans``); per cent
of device busy time on the busiest device, summing to 100."""

from perfbench.layer_metrics import _spans


def read(name: str, lc: dict):
    trace = lc.get("trace")
    key = name.split(".")[1][:-len("_pct")] if name.count(".") == 1 else None
    if trace is None or key not in _spans.PASSES:
        return None
    tables = _spans.scope_tables(lc)
    if not tables:
        return None  # a program that hands out no scope table
    return _spans.self_shares(_spans.busiest(trace), tables, _spans.pass_of).get(key, 0.0)
