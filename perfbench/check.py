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


def sequence_readings(config: dict, seed: int, prompt, tokens, served_dtype: str) -> dict:
    """The reference's reading at each served position of one request:
    ``gaps`` (best logit minus the served token's), ``logprobs`` (the
    served token's log-probability) and the mean logit spread."""
    from perfbench.reference import mistral

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
    logits = np.asarray(mistral.logits_at(
        config, seed, padded, valid, rows_p, served_dtype), np.float64)[:n_t]
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
    any width; ``logprob_err_mean`` is in nats."""
    limits = config["check"]["limits"]
    if not sample:
        return {"ok": False, "reason": "the window finished no request to compare",
                "numbers": {}, "limits": limits}
    gaps, errs, n_tokens = [], [], 0
    for prompt, tokens, reported in sample:
        out = sequence_readings(config, seed, prompt, tokens, served_dtype)
        gaps.append(out["gaps"] / out["logit_std"])
        if reported is not None and len(reported) == len(tokens):
            errs.append(np.abs(np.asarray(reported, np.float64) - out["logprobs"]))
        n_tokens += len(tokens)
    allg = np.concatenate(gaps)
    numbers = {"gap_max": float(allg.max())}
    beside = {"gap_mean": float(allg.mean()), "off_best_share": float(np.mean(allg > 0))}
    if len(errs) == len(sample):
        alle = np.concatenate(errs)
        numbers["logprob_err_mean"] = float(alle.mean())
        beside["logprob_err_max"] = float(alle.max())
    missing = [k for k in limits if k not in numbers]
    ok = not missing and all(numbers[k] <= limits[k] for k in limits)
    out = {"ok": bool(ok), "numbers": numbers, "limits": limits, "beside": beside,
           "requests": len(sample), "tokens": n_tokens}
    if missing:
        out["reason"] = f"nothing to compare for {missing}: the program reported no log-probabilities"
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
