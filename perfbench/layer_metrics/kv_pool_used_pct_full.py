"""KV manager, a model whose paged layers are of two kinds: the share of a
kind's pool that live requests hold at the window's end (``stats()``
``allocated_blocks_full`` over the blocks ``--num-blocks`` gives the kind
that keeps the whole past; ``allocated_blocks_window`` over
``window_num_blocks``, the pool the engine derives for the kind that keeps a
window: ``num_slots`` x its blocks a slot + the null block). The window
kind's reads low by construction wherever slots are free: its pool holds
every slot's window. A program with one kind of layer reports neither
counter and reads ``None``."""

KEYS = {"kv.pool_used_pct.full": ("allocated_blocks_full", None),
        "kv.pool_used_pct.window": ("allocated_blocks_window", "window_num_blocks")}


def read(name: str, lc: dict):
    s1 = lc.get("stats1") or {}
    held, total = KEYS.get(name, (None, None))
    if held not in s1:
        return None
    blocks = s1.get(total) if total else lc.get("num_blocks")
    return 100.0 * s1[held] / blocks if blocks else None
