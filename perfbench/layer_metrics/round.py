"""Block rounds: where a round's device time goes, by the kind of forward.

A model that generates by diffusion over blocks runs, a round, some denoise
forwards (each followed by the pick) and one commit forward over the clean
block. The program traces them under the named scopes ``denoise_pass`` and
``commit_pass``, outside the layer scopes (the ``scope.*`` metrics keep
filing the same operations by layer part).

* ``round.denoise_pct.chat``: device self time under ``denoise_pass``, in
  per cent of device busy time on the busiest device;
* ``round.commit_pct.chat``: the same under ``commit_pass``. What is left
  of 100 is the pick, the prefill chunks and the round's own bookkeeping.

A program without such scopes (the parent of the PR that brought them, or a
model that decodes one token a step) reads ``None``.
"""

from perfbench.layer_metrics import _spans

PASSES = ("denoise_pass", "commit_pass")


def pass_of(stack: str) -> str:
    """The round's pass an operation was traced under ('' for none)."""
    parts = _spans._parts(stack)
    return next((p for p in PASSES if p in parts), "")


def read(name: str, lc: dict):
    trace, tables = lc.get("trace"), _spans.scope_tables(lc)
    parts = name.split(".")
    if trace is None or not tables or len(parts) != 3 or not parts[1].endswith("_pct"):
        return None
    shares = _spans.self_shares(_spans.busiest(trace), tables, pass_of)
    if not any(p in shares for p in PASSES):
        return None  # a program that runs no block round
    return shares.get(parts[1][:-len("_pct")] + "_pass", 0.0)
