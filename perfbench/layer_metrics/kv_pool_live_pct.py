"""KV manager: the share of the pool's blocks that live requests hold at the
window's end (``stats()`` ``allocated_blocks``, shared prefix blocks
included), and — as ``kv.pool_cached_pct`` — the share only the prefix
cache keeps (``cached_blocks``). Beside ``kv.pool_used_pct``, which reads
the allocator's free list from outside and cannot tell the two apart."""

KEYS = {"kv.pool_live_pct": "allocated_blocks", "kv.pool_cached_pct": "cached_blocks"}


def read(name: str, lc: dict):
    s1, blocks = lc.get("stats1"), lc.get("num_blocks")
    if not s1 or not blocks or KEYS.get(name) not in s1:
        return None
    return 100.0 * s1[KEYS[name]] / blocks
