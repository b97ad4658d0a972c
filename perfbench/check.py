"""The comparison that decides ``correct``.

Serving: for a sample of the requests the window finished, the plain
reference runs ONCE over each prompt with its served tokens (teacher
forced). At every served position it reads two things: the gap by which the
served token's logit lies below the reference's best (a sound greedy engine
serves the best token except where two logits tie within its rounding; a
token altered on its way out lies several spreads down), and the distance
between the log-probability the PROGRAM reported for that token (the
window's own requests ask for it, ``--logprobs-topn 1``) and the
reference's. That distance is the program's rounding noise in the logit
itself, so a narrower type anywhere on the path — weights, activations, the
KV pool — moves it in proportion. Each number compared has a limit of its
own in the configuration file, set from readings on the chip (``PERF.md``
gives them), and every run prints each number beside its limit.

A configuration that states a float32 recurrent state (``check.narrow_state``
names the narrower type its control stores) is held to that by a number
paired with the run's own seed, requests and positions: the reference runs a
second time over each sampled request with its state rounded to that type
once a token and nothing else changed, which says **which way and how far a
narrow state moves each served log-probability**, ``d = narrow - exact``.
``narrow_state_share`` is the least-squares share of ``d`` in the program's
signed error ``r = reported - exact``: ``sum(r d) / sum(d d)`` over every
served position of the sample. What a narrow state does to a sequence is no
noise (the reference's rounding and the program's move the same positions
the same way), so a program that stores the state in the narrow type reads
near 1 (0.86-0.99 on six seeds of the hybrid cell) and one whose state is as
wide as stated reads about 0 or under it (-0.17..+0.04 on eight), whatever
its rounding noise, large or small, does to the mean error. The mean absolute
error cannot tell the two apart on a seed whose sample leans little on the
state: there the narrow state adds less than sound seeds differ (``PERF.md``,
PR 35).
"""

from __future__ import annotations

import numpy as np

#: sequence lengths the reference is compiled for (a prompt with its served
#: tokens is padded up to the next one)
BUCKETS = (128, 512, 1024, 2048, 4096, 8192, 16384, 32768)
#: served positions are padded up to a multiple of this
ROW_BLOCK = 256


def _bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"sequence of {n} tokens is longer than the reference's largest bucket")


def sequence_readings(config: dict, seed: int, prompt, tokens, served_dtype: str,
                      **forward) -> dict:
    """The reference's reading at each served position of one request:
    ``gaps`` (best logit minus the served token's), ``logprobs`` (the
    served token's log-probability) and the mean logit spread. ``forward``
    goes to the reference's ``logits_at`` (``state_dtype=``)."""
    from perfbench import common

    prompt = np.asarray(prompt, np.int32).reshape(-1)
    tokens = np.asarray(tokens, np.int32).reshape(-1)
    n_p, n_t = len(prompt), len(tokens)
    ids = np.concatenate([prompt, tokens[:-1]])
    valid = len(ids)
    padded = np.zeros((_bucket(valid),), np.int32)
    padded[:valid] = ids
    rows = np.arange(n_p - 1, n_p - 1 + n_t)
    n_rows = -(-n_t // ROW_BLOCK) * ROW_BLOCK
    rows_p = np.concatenate([rows, np.full((n_rows - n_t,), rows[-1])])
    logits = np.asarray(common.load_reference(config).logits_at(
        config, seed, padded, valid, rows_p, served_dtype, **forward), np.float64)[:n_t]
    best = logits.max(axis=-1)
    at_token = logits[np.arange(n_t), tokens]
    lse = best + np.log(np.exp(logits - best[:, None]).sum(axis=-1))
    return {"gaps": best - at_token, "logprobs": at_token - lse,
            "logit_std": float(np.mean(logits.std(axis=-1)))}


def served(config: dict, seed: int, sample: list, served_dtype: str) -> dict:
    """The numbers compared, each beside its limit, over a sample of
    ``(prompt, served tokens, reported log-probabilities or None)``.
    ``gap_max`` is in units of the reference's logit spread (the standard
    deviation over the vocabulary, averaged), so its limit means the same at
    any width; ``logprob_err_mean`` is in nats; ``narrow_state_share`` (the
    module's docstring) is a share, read where the configuration's check
    names a ``narrow_state``."""
    limits = config["check"]["limits"]
    narrow = config["check"].get("narrow_state")
    if not sample:
        return {"ok": False, "reason": "the window finished no request to compare",
                "numbers": {}, "limits": limits}
    gaps, errs, moved, n_tokens = [], [], [], 0
    for prompt, tokens, reported in sample:
        out = sequence_readings(config, seed, prompt, tokens, served_dtype)
        gaps.append(out["gaps"] / out["logit_std"])
        if reported is not None and len(reported) == len(tokens):
            errs.append(np.asarray(reported, np.float64) - out["logprobs"])
            if narrow:
                moved.append(sequence_readings(config, seed, prompt, tokens, served_dtype,
                                               state_dtype=narrow)["logprobs"] - out["logprobs"])
        n_tokens += len(tokens)
    allg = np.concatenate(gaps)
    numbers = {"gap_max": float(allg.max())}
    beside = {"gap_mean": float(allg.mean()), "off_best_share": float(np.mean(allg > 0))}
    if len(errs) == len(sample):
        alle = np.concatenate(errs)
        numbers["logprob_err_mean"] = float(np.abs(alle).mean())
        beside["logprob_err_max"] = float(np.abs(alle).max())
        if narrow:
            d = np.concatenate(moved)
            beside["narrow_state_moves_mean"] = float(np.abs(d).mean())
            if (d * d).sum() > 0:
                numbers["narrow_state_share"] = float((alle * d).sum() / (d * d).sum())
    missing = [k for k in limits if k not in numbers]
    ok = not missing and all(numbers[k] <= limits[k] for k in limits)
    out = {"ok": bool(ok), "numbers": numbers, "limits": limits, "beside": beside,
           "requests": len(sample), "tokens": n_tokens}
    if missing:
        out["reason"] = (f"nothing to compare for {missing}: the program reported no "
                         "log-probabilities, or the configuration's check names no narrow_state "
                         "that moves one")
    return out


def norm_gap(program: dict, reference: dict) -> tuple:
    """The worst leaf's gap between the program's norm and the reference's
    (not the norm of their difference), against the reference's norm of
    that leaf or of the median leaf, whichever is larger — some gradients
    are all but zero. Returns ``(gap, leaf)``."""
    med = float(np.median(list(reference.values())))
    worst, where = 0.0, None
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, med)
        if gap > worst:
            worst, where = gap, name
    return worst, where


def trained(config: dict, program: dict, reference: dict) -> dict:
    """Training's numbers, each beside its limit: every followed step's
    loss, the first gradient's norm as the optimizer got it, and the norm
    of the parameters' change after the followed steps."""
    limits = config["check"]["limits"]
    n = len(reference["losses"])
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(program["losses"][:n], reference["losses"]))
    g_gap, g_leaf = norm_gap(program["grad_norms"], reference["grad_norms"])
    u_gap, u_leaf = norm_gap(program["update_norms"], reference["update_norms"])
    numbers = {"loss_gap": loss_gap, "grad_norm_gap": g_gap, "update_norm_gap": u_gap}
    ok = all(numbers[k] <= limits[k] for k in limits)
    return {"ok": bool(ok), "numbers": numbers, "limits": limits,
            "worst_leaf": {"grad": g_leaf, "update": u_leaf},
            "losses": {"program": program["losses"][:n], "reference": reference["losses"]}}
