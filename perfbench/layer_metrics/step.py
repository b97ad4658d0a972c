"""Model step: device time of one program run, from the trace's modules
line — a decode step (the burst's module over its steps), a prefill chunk,
a train step."""

from perfbench.layer_metrics import _util


def read(name: str, lc: dict):
    trace = lc.get("trace")
    if trace is None:
        return None
    if name == "step.decode_ms":
        ms = _util.median_or_none(_util.module_durations_ms(trace, "jit_decode"))
        return None if ms is None else ms / lc["decode_burst"]
    if name == "step.prefill_chunk_ms":
        return _util.median_or_none(_util.module_durations_ms(trace, "jit_prefill"))
    if name == "step.train_device_ms":
        return _util.median_or_none(_util.module_durations_ms(trace, "jit_step"))
    return None
