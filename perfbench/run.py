"""The benchmark's command:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json`` on the machine it is started on.
Prints, as the last line of its standard output, one JSON object with the
keys ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` on a traced run). Exits non-zero, with no result, where JAX
finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import common  # noqa: E402


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def metrics_for(bench: dict, cell_name: str, kind: str) -> list:
    """The metrics of ``kind`` that this cell may report: those without a
    ``workloads`` key, and those that list the cell."""
    return [m for m in bench[kind] if cell_name in m.get("workloads", [cell_name])]


def breakdown(trace: dict) -> dict:
    """Top device operations by self time (worst device) and the longest
    idle gaps by what the host was doing."""
    from perfbench.reduce import xplane

    dev = max(trace["devices"].values(), key=lambda d: d["busy_ns"])
    ops = sorted(dev["self_by_name"].items(), key=lambda kv: -kv[1])[:10]
    gaps = xplane.name_gaps(dev["gaps"], trace["host_spans"])
    gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s / 1e9] for n, s in ops],
            "idle_gaps": [[n, s / 1e9] for n, s in gaps]}


def result_line(bench: dict, cell: dict, out: dict, device: dict, trace: bool) -> dict:
    metrics = {}
    if trace:
        lc = out["layer_ctx"]
        for m in metrics_for(bench, cell["name"], "per_layer"):
            value = common.metric_reader(m["name"])(m["name"], lc)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_for(bench, cell["name"], "end_to_end"):
            if m["name"] in out["values"]:
                metrics[m["name"]] = {"value": out["values"][m["name"]], "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    t = out["layer_ctx"].get("trace") if trace else None
    if t is not None:
        busy = [d["busy_ns"] for d in t["devices"].values()]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = t["window_ns"] / 1e9
        line["breakdown"] = breakdown(t)
    return line


def main(argv=None) -> int:
    args = parse(argv)
    common.ensure_program()
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, args.workload)
    device = common.require_chips(cell["chips"])
    common.configure_jax()
    ctx = common.Ctx(cell=cell, config=config, traffic=traffic, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace))
    out = common.load_driver(config["program"]).run(ctx)
    print("perfbench observed " + json.dumps(out["observed"]))
    print("perfbench check " + json.dumps(out["check"]))
    print(json.dumps(result_line(bench, cell, out, device, bool(args.trace))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
