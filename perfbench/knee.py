"""The knee of an engine that never runs short of anything but slots, for
the builder of such a cell; the benchmark's own runs never call it.

    python3 perfbench/knee.py --workload W --rates 3.6,4.1,... --ramp-s 25 [--seconds S] [--seed N]

``probe.py sweep`` calls a rate sustained while the backlog is no deeper at
the window's end than at its start. Where every slot's blocks are resident
and tens of requests are in flight, that backlog wanders by its own square
root and the rule reads the wandering (PERF.md, PR 36: 2.0/s not sustained,
2.83/s sustained, and no request had waited for a slot up to 4.0/s). What
such an engine runs out of is slots: once all are taken a request waits at
the door, and above that rate the wait grows for as long as the load lasts.

So a rate is **sustained** here when the window completed at least 97 % of
what arrived in it AND no iteration of the window ended with every slot
taken (the recorder's occupancy, read after each ``engine.step``). The
rates run in rising order on ONE seed, so every rate is offered the same
lengths in the same order with the gaps scaled, and the sweep stops once
five rates have run and the last two were not sustained. Beside the verdict
each line carries what moves with it: slots taken (mean, most), the mean
wait for a slot (``ttft_queue_sum_s`` over the window's first tokens), both
tails, the mean request time (the ramp of the cell is 1.5 x that at the
committed rate) and the backlog at both edges. The check runs over one
request, enough to see the program served what it was asked. A file of
its own because only a ``benchmark`` PR edits ``probe.py`` (README); such
a PR may fold this rule into ``probe.py sweep``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import common  # noqa: E402


def verdict(observed: dict, occupancy: list) -> bool:
    return bool(observed["arrivals_in_window"] > 0
                and observed["completed_in_window"] >= 0.97 * observed["arrivals_in_window"]
                and occupancy and max(occupancy) < 1.0)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True, help="rising, comma-separated")
    p.add_argument("--ramp-s", type=float, required=True)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)

    common.ensure_program()
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, args.workload)
    device = common.require_chips(cell["chips"])
    common.configure_jax()
    driver = common.load_driver(config["program"])
    config = {**config, "check": {**config["check"], "sample_requests": 1}}
    rates = sorted(float(r) for r in args.rates.split(","))
    verdicts = []
    for rate in rates:
        ctx = common.Ctx(cell=cell, config=config, seed=args.seed, trace=False,
                         seconds=args.seconds or bench["run_seconds"],
                         traffic={**traffic, "rate_rps": rate, "ramp_s": args.ramp_s,
                                  "drain_s": 0.0})
        out = driver.run(ctx)
        lc, o = out["layer_ctx"], out["observed"]
        occupancy = lc["recorder"].occupancy
        firsts = lc["stats1"]["first_tokens_total"] - lc["stats0"]["first_tokens_total"]
        waited = lc["stats1"]["ttft_queue_sum_s"] - lc["stats0"]["ttft_queue_sum_s"]
        verdicts.append(verdict(o, occupancy))
        print("perfbench knee " + json.dumps({
            "rate_rps": rate, "sustained": verdicts[-1], "seed": args.seed, "device": device,
            "slots_taken_pct_mean": 100.0 * sum(occupancy) / max(len(occupancy), 1),
            "slots_taken_pct_max": 100.0 * max(occupancy, default=0.0),
            "iterations_all_slots_taken": sum(1 for x in occupancy if x >= 1.0),
            "slot_wait_ms_mean": 1e3 * waited / firsts if firsts else None,
            "correct": out["correct"], "failed": out["failed"], "values": out["values"],
            "observed": o}), flush=True)
        if len(verdicts) >= 5 and not any(verdicts[-2:]):
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
