"""Kernels: what the paged attention kernel was asked to walk, from the
program's own counters (``stats()``, booked at dispatch from the host's
positions; window end minus window start).

* ``paged.table_live_pct``: ``paged_entries_walked_total`` over
  ``paged_entries_table_total`` - the table entries a row's own context
  reaches (a free row one) over those the tables hold, both x the layers and
  calls that ran: the share of the tables that is live. The rest is what a
  kernel that visited every entry would waste, and the free rows' share of it
  is what skipping them would save.
* ``paged.tile_fill_pct``: entries walked over ``paged_tiles_walked_total`` x
  ``paged_tile_entries`` (the entries a softmax step takes, the kernel's own
  constant, which ``stats()`` hands out): how full the steps' tiles are. A
  row's last tile is part full, a free row's holds one entry of the tile's.

A program without the counters (or without ``paged_tile_entries``, for the
fill) reads ``None``.
"""


def _moved(lc: dict, key: str):
    s0, s1 = lc.get("stats0") or {}, lc.get("stats1") or {}
    return float(s1[key]) - float(s0[key]) if key in s0 and key in s1 else None


def read(name: str, lc: dict):
    walked = _moved(lc, "paged_entries_walked_total")
    if name == "paged.table_live_pct":
        table = _moved(lc, "paged_entries_table_total")
        return 100.0 * walked / table if walked is not None and table else None
    if name == "paged.tile_fill_pct":
        tiles = _moved(lc, "paged_tiles_walked_total")
        tile = (lc.get("stats1") or {}).get("paged_tile_entries")
        return 100.0 * walked / (tiles * tile) if walked is not None and tiles and tile else None
    return None
