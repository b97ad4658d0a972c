"""What every generator shares: the request record, seeded permutations,
and length sets that are the same for every seed.

A seed changes the ORDER of the work, never the work: each phase of a run
(ramp, window, drain) draws a fixed set of lengths — the quantiles of the
stated distribution at ``(i + 0.5) / n`` — and a fixed set of gaps, and the
seed draws the order they come in and the token ids. Two seeds therefore
offer the same tokens to prefill and decode and the same number of
arrivals, in another order, so runs differ by scheduling and not by
sampling luck. How far a seed may move a length from its place is the
generator's and the traffic file's to say (``open_loop_lognormal``:
``shuffle_group``; ``closed_loop``: anywhere).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np


@dataclass
class Req:
    rid: int
    due_s: float  # seconds after the start of the ramp
    prompt: np.ndarray
    max_new_tokens: int
    phase: str  # "ramp" | "window" | "drain"
    client: int = -1
    # filled by the driver
    sent_s: float | None = None
    first_s: float | None = None
    last_s: float | None = None
    done_s: float | None = None
    tokens: list = field(default_factory=list)
    logprobs: list | None = None  # the program's own, one per served token
    error: str | None = None


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *[int(t) for t in tags]])


def length_set(spec: dict, n: int) -> np.ndarray:
    """``n`` whole lengths: the distribution's quantiles at ``(i+0.5)/n``,
    clipped to ``[min, max]``. ``spec``: ``{"dist": "lognormal", "median",
    "sigma", "min", "max"}``, ``{"dist": "uniform", "min", "max"}`` or
    ``{"dist": "fixed", "value"}``."""
    if n <= 0:
        return np.zeros((0,), np.int64)
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "fixed":
        return np.full((n,), int(spec["value"]), np.int64)
    if dist == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int) -> np.ndarray:
    """``n`` gaps of a Poisson process: the exponential's quantiles at
    ``(i + 0.5) / n`` (their order, and the scale that fits them to a span,
    are the generator's)."""
    u = (np.arange(n) + 0.5) / max(n, 1)
    return -np.log1p(-u)


def deal(values, block: int, rng: np.random.Generator) -> np.ndarray:
    """``values`` in an order in which every ``block`` consecutive ones hold
    about the same mix: sorted, cut into ``block`` strata, and each of the
    ``len(values) / block`` blocks takes one value from every stratum; the
    order inside a block, and which value of a stratum goes to which block,
    are ``rng``'s."""
    v = np.sort(np.asarray(values))
    n = len(v)
    if n == 0:
        return v
    blocks = max(1, int(round(n / max(int(block), 1))))
    held = [[] for _ in range(blocks)]
    for stratum in np.array_split(v, -(-n // blocks)):
        to = rng.permutation(blocks)[: len(stratum)]
        for x, j in zip(rng.permutation(stratum), to):
            held[j].append(x)
    return np.concatenate([rng.permutation(np.asarray(h, v.dtype)) for h in held])


def shuffle_groups(n: int, group: int, rng: np.random.Generator) -> np.ndarray:
    """A permutation of ``range(n)`` that moves an index only inside its
    group of ``group`` consecutive ones; where the groups start is ``rng``'s
    too. ``group <= 1`` moves nothing, ``group >= n`` anything."""
    idx = np.arange(n)
    if group <= 1 or n < 2:
        return idx
    first = -int(rng.integers(0, min(group, n)))
    for lo in range(first, n, group):
        a, b = max(lo, 0), min(lo + group, n)
        idx[a:b] = rng.permutation(idx[a:b])
    return idx


def prompt_ids(rng: np.random.Generator, length: int, vocab_size: int) -> np.ndarray:
    return rng.integers(0, vocab_size, size=int(length), dtype=np.int64).astype(np.int32)


def digest(reqs) -> str:
    """A fingerprint of a schedule (tests compare two generations)."""
    import hashlib

    h = hashlib.sha256()
    for r in reqs:
        h.update(f"{r.rid},{r.due_s:.9f},{r.max_new_tokens},{r.phase},{r.client};".encode())
        h.update(np.asarray(r.prompt, np.int32).tobytes())
    return h.hexdigest()
