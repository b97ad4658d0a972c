"""Accelerator / lazy fused step: host-clock time of a loop turn, compiles
inside the window (must read 0), and model FLOP/s utilisation from the
end-to-end rate and the benchmark's own operation count (6 x non-embedding
parameters + causal attention, recomputation not counted)."""

from perfbench import counts
from perfbench.layer_metrics import _util


def read(name: str, lc: dict):
    if name == "train.step_ms":
        return _util.median_or_none([x * 1e3 for x in lc.get("step_s", [])])
    if name == "train.compiles_in_window":
        c = lc.get("compiles_in_window")
        return None if c is None else float(c)
    if name == "train.mfu_pct":
        rate = lc.get("end_to_end", {}).get("train_tok_s_chip")
        if rate is None:
            return None
        flops = counts.train_flops_per_token(lc["config"], lc["traffic"]["seq_len"])
        return 100.0 * rate * flops / counts.peaks(lc["device_kind"])["bf16_flops_per_s"]
    return None
