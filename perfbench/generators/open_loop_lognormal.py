"""Open loop: requests are due on a schedule whatever the server does.

Traffic file keys: ``rate_rps`` (fixed, found once by a sweep),
``prompt_tokens`` / ``output_tokens`` (length specs, see ``base.length_set``;
lognormal by the name, but any stated distribution is read), ``ramp_s``
(the same traffic before the window, unmeasured), ``drain_s`` (the same
traffic after it, offered until every request due in the window has ended,
at most this long). Every prompt is unique random ids: no shared prefix.

What the seed draws. Every seed offers the same set of lengths and gaps
(``base.length_set``, ``base.exponential_gaps``). They are laid
out by the traffic file's ``schedule_seed`` so that every ``block``
consecutive arrivals hold the same mix of short and long (``base.deal``): a
window of 70 heavy-tailed requests is a small sample, and on the chip six
seeds that each drew the whole order spread by 3-12 % while two runs of one
seed agreed to under 1 % (PERF.md, PR 23). The run's ``--seed`` then draws
the order inside every group of ``shuffle_group`` consecutive arrivals —
prompts and answers separately, so their pairing is the seed's too — moves
every arrival by up to ``jitter_s`` either way, and draws the token ids
(and, in the driver, the weights). ``shuffle_group`` at or above the number
of arrivals is a whole-order draw; without ``schedule_seed`` the layout is
the run seed's as well.
"""

from __future__ import annotations

import numpy as np

from perfbench.generators import base

PHASES = ("ramp", "window", "drain")


class Load:
    closed = False

    def __init__(self, traffic: dict, seed: int, seconds: float, vocab_size: int):
        self.traffic = traffic
        self.ramp_s = float(traffic["ramp_s"])
        self.window_s = float(seconds)
        self.drain_s = float(traffic["drain_s"])
        rate = float(traffic["rate_rps"])
        spans = {"ramp": self.ramp_s, "window": self.window_s, "drain": self.drain_s}
        starts = {"ramp": 0.0, "window": self.ramp_s, "drain": self.ramp_s + self.window_s}
        self.reqs = []
        rid = 0
        jitter = float(traffic.get("jitter_s", 0.0))
        block = int(traffic.get("block", 10))
        group = int(traffic.get("shuffle_group", 1))
        for tag, phase in enumerate(PHASES):
            n = int(round(rate * spans[phase]))
            rng = base.rng_for(seed, tag)
            layout = (base.rng_for(int(traffic["schedule_seed"]), tag)
                      if "schedule_seed" in traffic else rng)
            prompts = base.deal(base.length_set(traffic["prompt_tokens"], n), block, layout)
            outputs = base.deal(base.length_set(traffic["output_tokens"], n), block, layout)
            t = np.cumsum(base.deal(base.exponential_gaps(n + 1), block, layout))
            times = t[:-1] * (spans[phase] / t[-1]) + starts[phase] if n else t[:0]
            prompts = prompts[base.shuffle_groups(n, group, rng)]
            outputs = outputs[base.shuffle_groups(n, group, rng)]
            times = np.clip(times + rng.uniform(-jitter, jitter, size=n),
                            starts[phase], starts[phase] + spans[phase] - 1e-6)
            for t_due, p, o in zip(times, prompts, outputs):
                self.reqs.append(base.Req(
                    rid=rid, due_s=float(t_due),
                    prompt=base.prompt_ids(rng, p, vocab_size),
                    max_new_tokens=int(o), phase=phase,
                ))
                rid += 1

    def initial(self) -> list:
        return list(self.reqs)

    def on_complete(self, req, now_s: float) -> list:
        return []


def make(traffic: dict, seed: int, seconds: float, vocab_size: int) -> Load:
    return Load(traffic, seed, seconds, vocab_size)
