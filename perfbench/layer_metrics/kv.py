"""KV manager: requests preempted or cut for want of blocks inside the
window (the program's counters, window end minus window start), and the
largest share of the pool's blocks that were off the allocator's free list
at any iteration of the window — held by a live request or kept by the
prefix cache — read by the harness's ``engine.step`` wrapper."""


def read(name: str, lc: dict):
    s0, s1 = lc.get("stats0"), lc.get("stats1")
    if name == "kv.preemptions" and s0 and s1:
        keys = ("preemptions", "out_of_blocks_total")
        return float(sum(s1[k] - s0[k] for k in keys))
    rec = lc.get("recorder")
    if name == "kv.pool_used_pct" and rec is not None and rec.blocks_used:
        return 100.0 * max(rec.blocks_used) / lc["num_blocks"]
    return None
