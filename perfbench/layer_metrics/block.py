"""Block rounds: what a forward yields, from the program's own counters.

The program counts (``stats()``): ``block_tokens_emitted_total`` — tokens
emitted from block rounds, after EOS and length cuts; ``block_slot_forwards_total``
— forwards dispatched x the lanes live in them (a round is ``denoise_steps``
denoise forwards and one commit forward); ``block_positions_committed_total``
— positions the live lanes' rounds committed that neither the prompt nor an
earlier round had fixed. All three over the window (``stats1 - stats0``).

* ``block.tokens_per_forward``: tokens emitted over forwards x live lanes:
  ``B / (T + 1)`` at best (4 / 3 at ``B = 4``, ``T = 2``; a model that
  decodes one token a step yields 1), less what finished requests' last
  blocks and the rest of their burst waste. Higher is better.
* ``block.waste_pct``: positions committed and not emitted, in per cent of
  those committed: answers that end inside a block, and the rounds of a
  burst that run on after a request's end.

A program without such counters reads ``None``.
"""

TOTALS = ("block_tokens_emitted_total", "block_slot_forwards_total",
          "block_positions_committed_total")


def window_totals(lc: dict) -> dict | None:
    s0, s1 = lc.get("stats0") or {}, lc.get("stats1") or {}
    if any(k not in s0 or k not in s1 for k in TOTALS):
        return None
    return {k: float(s1[k]) - float(s0[k]) for k in TOTALS}


def read(name: str, lc: dict):
    w = window_totals(lc)
    if w is None:
        return None
    if name == "block.tokens_per_forward":
        forwards = w["block_slot_forwards_total"]
        return w["block_tokens_emitted_total"] / forwards if forwards else None
    if name == "block.waste_pct":
        committed = w["block_positions_committed_total"]
        if not committed:
            return None
        return 100.0 * (1.0 - w["block_tokens_emitted_total"] / committed)
    return None
