"""Load generator: how late it ran (sent - due). A starved generator must
not be read as a fast server."""

from perfbench.common import percentile


def read(name: str, lc: dict):
    late = lc.get("late_ms")
    if name == "gen.late_ms.p90" and late:
        return percentile(late, 0.90)
    return None
