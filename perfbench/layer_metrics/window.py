"""KV manager and kernels, a model whose paged layers are of two kinds
(``stats()`` ``kv_kinds`` 2: layers that keep the whole past beside layers
that keep a window of it). From the program's own counters, window end minus
window start; a program without them (one kind of layer, or the parent of
the PR that brought them) reads ``None``.

* ``window.walk_saved_pct``: ``paged_entries_behind_window_total`` over that
  plus ``paged_entries_walked_window_total`` - of the table entries that hold
  a live position of a row, the share a window layer's walk did not visit
  because they lie behind the window: what one cache kind for every layer
  would have walked, and read, on top. 0 while every context is shorter than
  the window.
* ``window.freed_blocks_per_request``: ``window_blocks_freed_total`` over the
  requests completed in the window: blocks of the window kind that went back
  to its allocator while their request was still running (the blocks a
  request holds when it ends are not among them).
"""


def _moved(lc: dict, key: str):
    s0, s1 = lc.get("stats0") or {}, lc.get("stats1") or {}
    return float(s1[key]) - float(s0[key]) if key in s0 and key in s1 else None


def read(name: str, lc: dict):
    if name == "window.walk_saved_pct":
        behind = _moved(lc, "paged_entries_behind_window_total")
        walked = _moved(lc, "paged_entries_walked_window_total")
        if behind is None or walked is None or behind + walked <= 0:
            return None
        return 100.0 * behind / (behind + walked)
    if name == "window.freed_blocks_per_request":
        freed, done = _moved(lc, "window_blocks_freed_total"), _moved(lc, "completed")
        return freed / done if freed is not None and done else None
    return None
