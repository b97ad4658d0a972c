"""``python -m perfbench.rehearse <cell> [--seed N] [--seconds S]``

The same code as a run, at the tiny sizes each configuration and traffic
file states under ``rehearsal``, on ``JAX_PLATFORMS=cpu`` (four virtual
devices for a four-chip cell). Prints COUNTS only — requests, tokens,
iterations, compiles, and what the check compared — never a value under a
metric's name: a CPU run says nothing about speed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None, root: str | None = None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("workload")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "cpu"
    from perfbench import common

    root = root or common.ROOT
    bench = common.benchmark(root)
    cell, config, traffic = common.find_cell(bench, args.workload, root)
    if cell["chips"] > 1 and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count={cell['chips']}"
                                   " --xla_cpu_collective_call_terminate_timeout_seconds=600")
    config, traffic = common.apply_rehearsal(config, traffic)
    ctx = common.Ctx(cell=cell, config=config, traffic=traffic, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace), rehearse=True)
    out = common.load_driver(config["program"]).run(ctx)
    # the readers must at least run; their values are not shown
    from perfbench import run as run_mod

    read = []
    for m in run_mod.metrics_for(bench, cell["name"], "per_layer"):
        try:
            value = common.metric_reader(m["name"])(m["name"], out["layer_ctx"])
        except KeyError:  # the CPU is not in the table of peaks, by design
            value = None
        if value is not None:
            read.append(m["name"])
    counts = {
        "rehearsal": cell["name"], "platform": "cpu",
        "correct": out["correct"], "attempted": out["attempted"], "failed": out["failed"],
        "counts": {k: v for k, v in out["observed"].items()
                   if isinstance(v, int) and not isinstance(v, bool)},
        "end_to_end_present": sorted(out["values"]),
        "per_layer_readable": read,
        "check": out["check"],
    }
    print(json.dumps(counts))
    return counts


if __name__ == "__main__":
    main()
    sys.exit(0)
