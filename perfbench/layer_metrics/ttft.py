"""Serving scheduler: where a first token's wait goes, from the totals the
engine keeps at the boundaries a request crosses (``stats()``
``first_tokens_total``, ``ttft_sum_s``, ``ttft_queue_sum_s``,
``ttft_own_prefill_sum_s``, ``ttft_prefill_iterations_sum``; window end
minus window start, divided by the first tokens emitted in the window).

``queue`` is arrival to admission, ``own_prefill`` the time inside the
request's own prefill chunks as the host saw it, ``interleave`` what remains
of the engine's TTFT (admitted, behind decode rounds and other prompts'
chunks). ``outside_engine`` is the mean of the harness's TTFT (from *due*)
less the engine's (from ``add_request``): the wait in the loop's inbox plus
the generator's lateness. Its two populations differ at the window's edges:
the harness's are the requests due in the window, the engine's the first
tokens emitted in it."""

KEYS = {
    "ttft.queue_ms.mean": "ttft_queue_sum_s",
    "ttft.own_prefill_ms.mean": "ttft_own_prefill_sum_s",
    "ttft.prefill_iters.mean": "ttft_prefill_iterations_sum",
}


def _delta(lc: dict, key: str):
    s0, s1 = lc.get("stats0"), lc.get("stats1")
    if not s0 or not s1 or key not in s0 or key not in s1:
        return None  # a program without the counters
    return s1[key] - s0[key]


def read(name: str, lc: dict):
    n = _delta(lc, "first_tokens_total")
    if not n:
        return None
    if name == "ttft.prefill_iters.mean":
        return _delta(lc, KEYS[name]) / n
    if name in KEYS:
        return 1e3 * _delta(lc, KEYS[name]) / n
    engine_ms = 1e3 * _delta(lc, "ttft_sum_s") / n
    if name == "ttft.interleave_ms.mean":
        return engine_ms - 1e3 * (_delta(lc, "ttft_queue_sum_s")
                                  + _delta(lc, "ttft_own_prefill_sum_s")) / n
    ttft = lc.get("ttft_ms")
    if name == "ttft.outside_engine_ms.mean" and ttft:
        return sum(ttft) / len(ttft) - engine_ms
    return None
