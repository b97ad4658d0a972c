"""Tools for the builder of a cell, run once on the chip when the cell is
defined; the benchmark's own runs never call them.

    python3 perfbench/probe.py sweep   --workload W --rates 0.5,0.8,... [--seconds S]
    python3 perfbench/probe.py seeds   --workload W --seeds 1,2,3 [--seconds S]
    python3 perfbench/probe.py control --workload W --seeds 1,2,3 [--seconds S]

``sweep`` runs the cell's traffic at each rate and prints what completed
against what was offered (sustained: completed >= 97 % of arrivals in the
window and the backlog no deeper at its end than at its start). ``seeds``
runs the cell as committed on several seeds in one process and prints the
numbers the check compared; ``control`` does the same with the
configuration's lower-precision control switched on — the PROGRAM's own
path (``check.control`` in the configuration file), which has to come out
as not correct. ``--traffic k=v,...`` replaces numbers of the traffic file
for a reading that needs no full window (``ramp_s=0``). One process, one
engine at a time, each freed before the next.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import common  # noqa: E402


def control_flags(config: dict) -> list:
    flags = list(config["serve_flags"])
    for flag, value in config["check"]["control"]["serve_flags_replace"].items():
        if flag in flags:
            flags[flags.index(flag) + 1] = value
        else:
            flags += [flag, value]
    return flags


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("mode", choices=("sweep", "seeds", "control"))
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", default="")
    p.add_argument("--seeds", default="1")
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--traffic", default="", help="k=v,... numbers of the traffic file replaced")
    args = p.parse_args(argv)

    common.ensure_program()
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, args.workload)
    device = common.require_chips(cell["chips"])
    common.configure_jax()
    seconds = args.seconds or bench["run_seconds"]
    driver = common.load_driver(config["program"])
    seeds = [int(s) for s in args.seeds.split(",")]
    replaced = {k: float(v) for k, v in
                (kv.split("=") for kv in args.traffic.split(",") if kv)}
    variants = []
    if args.mode == "sweep":
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            variants.append(({"rate_rps": rate, "drain_s": 0.0}, seeds[i % len(seeds)], {}))
    else:
        extra = {}
        if args.mode == "control":
            if config["program"] == "serve_engine":
                extra = {"serve_flags": control_flags(config)}
            else:
                extra = {"accelerator_kwargs": config["check"]["control"]["accelerator"]}
        variants = [({}, s, extra) for s in seeds]
    for override, seed, extra in variants:
        ctx = common.Ctx(cell=cell, config=config, traffic={**traffic, **override, **replaced},
                         seed=seed, seconds=seconds, trace=False, **extra)
        out = driver.run(ctx)
        row = {"mode": args.mode, "seed": seed, **override, "device": device,
               "correct": out["correct"], "attempted": out["attempted"],
               "failed": out["failed"], "values": out["values"],
               "observed": out["observed"], "check": out["check"]}
        if args.mode == "sweep":
            o = out["observed"]
            row["sustained"] = bool(
                o["arrivals_in_window"] > 0
                and o["completed_in_window"] >= 0.97 * o["arrivals_in_window"]
                and o["backlog_end"] <= o["backlog_start"])
        print("perfbench probe " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
