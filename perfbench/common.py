"""The harness's shared pieces: finding a cell's files by the names in
``BENCHMARK.json``, the device check, clocks, the trace window, memory
readings, and the hand-over from a run's window to the check."""

from __future__ import annotations

import gc
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: kernels by the stable names the program gives them; the reducer books an
#: operation whose name or metadata holds one of these under that name
KERNEL_NAMES = (
    "paged_attention",
    "flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkv",
)

LLAMA_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "max_position_embeddings",
    "rope_theta", "rms_norm_eps", "tie_word_embeddings",
)


@dataclass
class Ctx:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool = False
    #: the control's door: serve flags that replace the configuration's
    serve_flags: list | None = None
    #: the control's door for training: ``Accelerator`` keywords replaced
    accelerator_kwargs: dict = field(default_factory=dict)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return read_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(bench: dict, name: str, root: str = ROOT) -> tuple:
    """(cell, config dict, traffic dict) by the names in ``BENCHMARK.json``:
    the configuration's ``file``, and ``perfbench/traffic/<traffic>.json``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = read_json(os.path.join(root, cfg_entry["file"]))
    traffic = read_json(os.path.join(root, "perfbench", "traffic", cell["traffic"] + ".json"))
    return cell, config, traffic


def apply_rehearsal(config: dict, traffic: dict) -> tuple:
    """The tiny sizes each file states for the CPU rehearsal."""
    config = {**config, **config.get("rehearsal", {})}
    traffic = {**traffic, **traffic.get("rehearsal", {})}
    return config, traffic


def llama_keys(config: dict) -> dict:
    return {k: config[k] for k in LLAMA_KEYS if k in config}


def load_generator(kind: str):
    return importlib.import_module(f"perfbench.generators.{kind}")


def load_driver(program: str):
    return importlib.import_module(f"perfbench.drivers.{program}")


def metric_reader(name: str):
    """The reader of a per-layer metric, found by the metric's name: a
    module named for the whole metric (dots as underscores), else one named
    for its family (the part before the first dot)."""
    for mod in (name.replace(".", "_").replace("-", "_"), name.split(".")[0]):
        path = os.path.join(HERE, "layer_metrics", mod + ".py")
        if os.path.exists(path):
            return importlib.import_module(f"perfbench.layer_metrics.{mod}").read
    raise SystemExit(f"perfbench: no reader for per-layer metric {name!r} "
                     f"under perfbench/layer_metrics/")


def require_chips(chips: int) -> dict:
    """The device as JAX reports it; anything but ``chips`` TPU chips of a
    kind in the peaks table ends the run with no result."""
    import jax

    from perfbench import counts

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"perfbench: found no accelerator (platform {platform!r}); "
                         "the benchmark measures on the chip or not at all")
    if len(devices) < chips:
        raise SystemExit(f"perfbench: the cell asks for {chips} chips, JAX found {len(devices)}")
    counts.peaks(devices[0].device_kind)
    return {"platform": platform, "kind": devices[0].device_kind, "count": chips}


def configure_jax():
    """The compile cache at the program's fixed path inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` puts it), and every program in
    it, however quickly it compiled: a run after the first compiles nothing."""
    import jax

    from accelerate_tpu.mesh import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def percentile(values, q: float) -> float:
    """The ``q``-quantile by the nearest-rank rule (no interpolation beyond
    the sample)."""
    vs = sorted(values)
    if not vs:
        raise ValueError("no sample")
    return float(vs[min(len(vs) - 1, max(0, math.ceil(q * len(vs)) - 1))])


def sleep_until(t: float):
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.25))


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend reports
    none, which is the CPU of a rehearsal)."""
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def engine_compiles(engine) -> int:
    s = engine.stats()
    return int(s["decode_compiles"]) + int(s["prefill_compiles"])


class TraceWindow:
    """Records ``seconds`` of the profiler's trace from a thread of its own,
    into a directory under ``TMPDIR`` that is removed once reduced."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        self._thread = threading.Thread(target=self._run, name="perfbench-trace", daemon=True)
        self.error = None
        #: perf_counter stamps around the recorded span
        self.span = None

    def _run(self):
        import jax

        try:
            jax.profiler.start_trace(self.dir)
            t_lo = time.perf_counter()
            time.sleep(self.seconds)
            t_hi = time.perf_counter()
            jax.profiler.stop_trace()
            self.span = (t_lo, t_hi)
        except BaseException as e:  # noqa: BLE001 — re-raised by reduced()
            self.error = e

    def start(self):
        self._thread.start()

    def wait(self):
        self._thread.join()

    def reduced(self, kernel_names, allow_no_device: bool = False):
        """The reduced trace; a trace in which no operation ran on a device
        is an error, except in a rehearsal on the CPU (then ``None``)."""
        from perfbench.reduce import xplane

        self.wait()
        try:
            if self.error is not None:
                raise self.error
            trace = xplane.load(xplane.find_xplane(self.dir))
            if not trace.devices and allow_no_device:
                return None
            return xplane.reduce_trace(trace, kernel_names)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def sample_finished(finished: list, seed: int, n: int) -> list:
    """``n`` of the requests the window finished, drawn from the seed, the
    longest (prompt + served tokens) always among them."""
    import numpy as np

    done = [r for r in finished if r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 99])
    picks = rng.permutation(len(rest))[: max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(picks)]


def free_engine(engine):
    """Give the device back before the reference runs: pools and weights."""
    import jax

    for name in ("_kp", "_vp", "_ks", "_vs", "_gmask", "_gtrans"):
        arr = getattr(engine, name, None)
        if arr is not None:
            arr.block_until_ready()
            arr.delete()
            setattr(engine, name, None)
    engine._inflight = None
    for leaf in jax.tree.leaves(engine._params):
        leaf.delete()
    engine._params = None
    gc.collect()


def check_served(config: dict, seed: int, sample: list, served_dtype: str) -> dict:
    from perfbench import check

    return check.served(config, seed, [(r.prompt, r.tokens, r.logprobs) for r in sample],
                        served_dtype)


def ensure_program():
    """The program under test sits beside ``perfbench/``; a directory that
    holds only the benchmark has nothing to measure."""
    if not os.path.isdir(os.path.join(ROOT, "accelerate_tpu")):
        print("perfbench: the program is not here — no accelerate_tpu/ beside "
              "perfbench/, nothing to measure", file=sys.stderr)
        raise SystemExit(2)
