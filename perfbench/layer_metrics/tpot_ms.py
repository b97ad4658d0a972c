"""Client side of the engine loop: the mean over all requests due in the
window of each request's time per output token — the steadier companion of
the end-to-end ``tpot_ms.p90`` (one order statistic of some 70 samples)."""


def read(name: str, lc: dict):
    tpot = lc.get("tpot_ms")
    if name == "tpot_ms.mean" and tpot:
        return sum(tpot) / len(tpot)
    return None
