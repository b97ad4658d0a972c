"""Training batches: token ids drawn from the seed, every row different.
Traffic file keys: ``seq_len``, ``global_batch`` (sequences a step; the
batch is counted in tokens and held fixed), ``batches`` (distinct batches
made in set-up and cycled — the loader's work is collation and placement,
not generation)."""

from __future__ import annotations

import numpy as np

from perfbench.generators import base


def make(traffic: dict, seed: int, seconds: float, vocab_size: int) -> list:
    """``[batches][global_batch, seq_len]`` int32."""
    rng = base.rng_for(seed, 7)
    shape = (int(traffic["batches"]), int(traffic["global_batch"]), int(traffic["seq_len"]))
    return list(rng.integers(0, vocab_size, size=shape, dtype=np.int64).astype(np.int32))
