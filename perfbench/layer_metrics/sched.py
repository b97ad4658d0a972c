"""Serving scheduler: the host's share of an iteration (from the program's
flight recorder, entries inside the window) and slot occupancy (sampled by
the harness's ``engine.step`` wrapper). With few layers the host's share is
larger than in a deployment of the full depth."""


def read(name: str, lc: dict):
    rec = lc.get("recorder")
    if rec is None:
        return None
    if name == "sched.host_share_pct":
        wall = sum(e["wall_s"] for e in rec.flight)
        if wall <= 0:
            return None
        hidden = sum(e["device_wait_s"] + e["overlap_hidden_s"] for e in rec.flight)
        return 100.0 * max(0.0, 1.0 - hidden / wall)
    if name == "sched.slot_occupancy_pct" and rec.occupancy:
        return 100.0 * sum(rec.occupancy) / len(rec.occupancy)
    return None
