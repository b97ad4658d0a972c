"""Closed loop: ``clients`` callers, each sending its next request when the
previous one has ended — offered load above the knee, judged on tokens per
second. Traffic file keys: ``clients``, ``prompt_tokens`` / ``output_tokens``
(length specs), ``ramp_s`` (unmeasured; the clients start spread over its
first tenth), ``drain_s`` (kept for the schedule's format; a closed loop
measures what completes inside the window and follows nothing past it).

Every client has a fixed list of requests drawn for the whole run, so the
work on offer does not depend on how fast the server answers; only how far
down its list each client gets does.
"""

from __future__ import annotations

from perfbench.generators import base

#: requests held ready per client; a client that exhausts them stops (the
#: run's counts then say so)
PER_CLIENT = 64


class Load:
    closed = True

    def __init__(self, traffic: dict, seed: int, seconds: float, vocab_size: int):
        self.traffic = traffic
        self.ramp_s = float(traffic["ramp_s"])
        self.window_s = float(seconds)
        self.drain_s = float(traffic.get("drain_s", 0.0))
        self.clients = int(traffic["clients"])
        n = self.clients * PER_CLIENT
        rng = base.rng_for(seed, 0)
        prompts = rng.permutation(base.length_set(traffic["prompt_tokens"], n))
        outputs = rng.permutation(base.length_set(traffic["output_tokens"], n))
        self._lists = []
        rid = 0
        for c in range(self.clients):
            reqs = []
            for j in range(PER_CLIENT):
                i = c * PER_CLIENT + j
                reqs.append(base.Req(
                    rid=rid, due_s=0.0,
                    prompt=base.prompt_ids(rng, prompts[i], vocab_size),
                    max_new_tokens=int(outputs[i]), phase="", client=c,
                ))
                rid += 1
            self._lists.append(reqs)
        self._next = [0] * self.clients

    def _phase(self, t: float) -> str:
        if t < self.ramp_s:
            return "ramp"
        return "window" if t < self.ramp_s + self.window_s else "drain"

    def _take(self, client: int, due_s: float):
        i = self._next[client]
        if i >= PER_CLIENT:
            return []
        self._next[client] = i + 1
        req = self._lists[client][i]
        req.due_s = due_s
        req.phase = self._phase(due_s)
        return [req]

    def initial(self) -> list:
        spread = self.ramp_s / 10.0
        out = []
        for c in range(self.clients):
            out += self._take(c, spread * c / max(self.clients, 1))
        return out

    def on_complete(self, req, now_s: float) -> list:
        return self._take(req.client, now_s)


def make(traffic: dict, seed: int, seconds: float, vocab_size: int) -> Load:
    return Load(traffic, seed, seconds, vocab_size)
