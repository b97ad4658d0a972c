"""Client side of the engine loop: the plain 90th percentile of time to first
token (nearest rank), recorded beside the end-to-end ``ttft_ms.tail10``."""

from perfbench.common import percentile


def read(name: str, lc: dict):
    ttft = lc.get("ttft_ms")
    if name == "ttft_ms.p90" and ttft:
        return percentile(ttft, 0.90)
    return None
