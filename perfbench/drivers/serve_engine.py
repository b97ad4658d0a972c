"""Drives the serving engine the way ``serve`` does: one process, the
engine built by ``serve._make_engine`` from the flags in the configuration
file, ``serve._engine_loop`` in a thread, fed through its inbox by a feeder
that sleeps to each due time; tokens are timed at the client side of that
loop (the ``_stream`` callback and the result callback).

From the program come the engine and its counters; the weights, the
traffic, the clocks, the trace reduction and the check are the benchmark's.
"""

from __future__ import annotations

import argparse
import heapq
import itertools
import queue
import threading
import time
from statistics import median

import numpy as np
from jax.profiler import TraceAnnotation

from accelerate_tpu.serving.scheduler import RequestState
from perfbench import common, weights

#: seconds of a traced run's window that the profiler records (a trace of
#: the whole window is too large to bring back; counters cover all of it)
TRACE_SECONDS = 4.0


class _Abort(Exception):
    """Raised from the step wrapper to leave ``_engine_loop`` at once."""


def parse_serve_flags(flags: list):
    from accelerate_tpu.commands import serve

    cli = argparse.ArgumentParser()
    serve.add_parser(cli.add_subparsers())
    return cli.parse_args(["serve", *[str(f) for f in flags]])


def build_engine(config: dict, seed: int, flags: list):
    """The engine as ``serve`` builds it, around the configuration file's
    sizes and the benchmark's seeded weights (``serve`` itself offers
    presets only)."""
    import jax.numpy as jnp

    from accelerate_tpu.big_modeling import init_empty_weights
    from accelerate_tpu.commands import serve
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    args = parse_serve_flags(flags)
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32

    def build_model(_args):
        with init_empty_weights():
            model = LlamaForCausalLM.from_config(
                LlamaConfig(**common.llama_keys(config)), dtype=dtype)
        model.params = weights.make_tree(seed, model.params, dtype=dtype,
                                         scales=config.get("weight_scales"))
        return model

    original = serve._build_model
    serve._build_model = build_model
    try:
        return serve._make_engine(args), args
    finally:
        serve._build_model = original


class Recorder:
    """What the harness's wrappers around ``engine.step`` see, per
    iteration inside the window: occupancy, the flight recorder's newest
    entry, and the rows each decode and prefill call served (for the paged
    kernel's least bytes and operations)."""

    def __init__(self, engine, abort: threading.Event, annotate: bool):
        self.engine = engine
        self.abort = abort
        self.annotate = annotate
        self.t_lo = float("inf")
        self.t_hi = float("-inf")
        self.iterations = 0
        self.occupancy = []
        self.blocks_used = []  # per iteration: blocks of the pool off the free list
        self.iter_t = []  # perf_counter at the start of each iteration
        self.flight = []
        self.decode_contexts = []  # per iteration: context lengths of decoding rows
        self.prefill_chunks = []   # per iteration: [(start, tokens)] of prefilling rows
        self._step = engine.step
        engine.step = self.step

    def step(self):
        if self.abort.is_set():
            raise _Abort()
        t = time.perf_counter()
        inside = self.t_lo <= t < self.t_hi
        if inside:
            self.iter_t.append(t)
            self._before()
        if self.annotate:
            with TraceAnnotation("perfbench/engine.step"):
                out = self._step()
        else:
            out = self._step()
        if inside:
            self.iterations += 1
            self.occupancy.append(float(self.engine.scheduler.occupancy))
            self.blocks_used.append(int(self.engine.config.num_blocks)
                                    - int(self.engine.allocator.free_count))
            fl = getattr(self.engine, "_flight", None)
            if fl is not None:
                self.flight.extend(fl.tail(1))
        return out

    def _before(self):
        """Rows about to be served, read from the scheduler before the step
        (a request admitted inside the step is seen one iteration late)."""
        sched, cfg = self.engine.scheduler, self.engine.config
        dec = [r.prompt_len + len(r.output_tokens) for r in sched.active(RequestState.DECODE)]
        pre = [(r.prefill_pos, min(cfg.prefill_chunk, r.prompt_len - r.prefill_pos))
               for r in sched.active(RequestState.PREFILL)]
        self.decode_contexts.append(dec)
        self.prefill_chunks.append(pre)


class Feeder(threading.Thread):
    """Puts each request into the engine loop's inbox when it is due."""

    def __init__(self, load, inbox, t0: float, logprobs: int = 0):
        super().__init__(name="perfbench-feeder", daemon=True)
        self.load, self.inbox, self.t0 = load, inbox, t0
        #: every request asks for this many log-probabilities a token (the
        #: engine's ``--logprobs-topn``; 0 = the request says nothing)
        self.logprobs = int(logprobs)
        self._heap = []
        self._seq = itertools.count()
        self._cv = threading.Condition()
        self._halt = False
        self.sent = []
        self.done = []
        for r in load.initial():
            self._push(r)

    def _push(self, req):
        heapq.heappush(self._heap, (req.due_s, next(self._seq), req))

    def stop(self):
        with self._cv:
            self._halt = True
            self._cv.notify()

    def _on_stream(self, req, chunk):
        now = time.perf_counter() - self.t0
        if req.first_s is None:
            req.first_s = now
        req.last_s = now

    def _on_result(self, req, result):
        now = time.perf_counter() - self.t0
        req.done_s = now
        if "error" in result:
            req.error = str(result["error"])
        elif result.get("finish_reason") in ("out_of_blocks", "deadline_exceeded"):
            # answered, but cut short: the caller did not get what it asked for
            req.error = str(result["finish_reason"])
        else:
            req.tokens = list(result["tokens"])
            if "logprobs" in result:
                req.logprobs = [float(e["logprob"]) for e in result["logprobs"]]
            if req.first_s is None:
                req.first_s = now
            req.last_s = now
        with self._cv:
            self.done.append(req)
            for nxt in self.load.on_complete(req, now):
                self._push(nxt)
            self._cv.notify()

    def run(self):
        while True:
            with self._cv:
                while not self._halt:
                    wait = (self._heap[0][0] - (time.perf_counter() - self.t0)
                            if self._heap else 0.25)
                    if self._heap and wait <= 0:
                        break
                    self._cv.wait(timeout=min(max(wait, 0.0005), 0.25))
                if self._halt:
                    return
                _, _, req = heapq.heappop(self._heap)
            payload = {
                "id": req.rid,
                "prompt": req.prompt,
                "max_new_tokens": req.max_new_tokens,
                "_stream": lambda chunk, req=req: self._on_stream(req, chunk),
            }
            if self.logprobs:
                payload["sampling"] = {"logprobs": self.logprobs}
            req.sent_s = time.perf_counter() - self.t0
            self.sent.append(req)
            self.inbox.put((payload, lambda result, req=req: self._on_result(req, result)))


def warm_up(engine, args, vocab_size: int):
    """Compile or load the programs the window drives — a two-chunk prefill,
    the first-token pick, and the decode burst — and nothing else."""
    n = int(args.prefill_chunk) + 5
    rng = np.random.default_rng(0)
    engine.add_request(rng.integers(0, vocab_size, size=n).astype(np.int32),
                       2 * int(args.decode_burst))
    engine.run_until_idle()
    engine.reset_stats()


def run(ctx: common.Ctx) -> dict:
    import jax

    from accelerate_tpu.commands import serve

    cfg, traffic = ctx.config, ctx.traffic
    t_setup = time.perf_counter()
    engine, args = build_engine(cfg, ctx.seed, ctx.serve_flags or cfg["serve_flags"])
    vocab = cfg["vocab_size"]
    warm_up(engine, args, vocab)
    gen = common.load_generator(traffic["kind"])
    load = gen.make(traffic, ctx.seed, ctx.seconds, vocab)
    compiles0 = common.engine_compiles(engine)

    inbox: queue.Queue = queue.Queue()
    stop, abort = threading.Event(), threading.Event()
    rec = Recorder(engine, abort, annotate=ctx.trace)
    loop_error = []

    def loop():
        try:
            serve._engine_loop(engine, inbox, lambda r: None, stop)
        except _Abort:
            pass
        except BaseException as e:  # noqa: BLE001 — reported by the run, which then fails
            loop_error.append(e)

    loop_thread = threading.Thread(target=loop, name="perfbench-engine-loop", daemon=True)
    t0 = time.perf_counter()
    feeder = Feeder(load, inbox, t0, logprobs=int(args.logprobs_topn))
    loop_thread.start()
    feeder.start()

    # ramp: the same traffic, unmeasured; counts as set-up
    w_lo, w_hi = load.ramp_s, load.ramp_s + load.window_s
    common.sleep_until(t0 + w_lo)
    stats0 = engine.stats()
    rec.t_lo, rec.t_hi = t0 + w_lo, t0 + w_hi
    setup_s = time.perf_counter() - t_setup

    tracer = None
    if ctx.trace:
        tracer = common.TraceWindow(min(TRACE_SECONDS, load.window_s * 0.8))
        tracer.start()
    common.sleep_until(t0 + w_hi)
    stats1 = engine.stats()
    compiles1 = common.engine_compiles(engine)
    if tracer is not None:
        tracer.wait()

    # drain: the same traffic goes on until every request due in the window
    # has ended (an open loop), or not at all (a closed loop)
    expected = [r for r in load.initial() if r.phase == "window"]
    if not load.closed:
        deadline = t0 + w_hi + load.drain_s
        while time.perf_counter() < deadline and loop_thread.is_alive():
            if all(r.done_s is not None for r in expected):
                break
            time.sleep(0.05)
    feeder.stop()
    abort.set()
    stop.set()
    feeder.join(timeout=10)
    loop_thread.join(timeout=120)
    if loop_thread.is_alive() or feeder.is_alive():
        raise RuntimeError("the engine loop or the feeder did not stop")
    if loop_error:
        raise loop_error[0]
    memory_peak = common.memory_peak_bytes()

    # -- the window's numbers ------------------------------------------------
    win = [r for r in feeder.sent if r.phase == "window"]
    if load.closed:
        # what completed inside the window, whenever it was sent
        finished = [r for r in feeder.done if r.error is None and w_lo <= r.done_s < w_hi]
        attempted = len(finished) + sum(1 for r in feeder.done if r.error is not None)
        failed = attempted - len(finished)
    else:
        finished = [r for r in win if r.done_s is not None and r.error is None]
        attempted = len(expected)
        failed = attempted - len(finished)
    in_window = [r for r in feeder.done if r.error is None and w_lo <= r.done_s < w_hi]
    took = [r.done_s - r.due_s for r in finished]
    obs = {
        "window_s": load.window_s,
        "arrivals_in_window": sum(1 for r in feeder.sent if w_lo <= r.sent_s < w_hi),
        "completed_in_window": len(in_window),
        "backlog_start": int(stats0["queue_depth"]) + int(stats0["active_slots"]),
        "backlog_end": int(stats1["queue_depth"]) + int(stats1["active_slots"]),
        "queue_start": int(stats0["queue_depth"]), "queue_end": int(stats1["queue_depth"]),
        "request_s_mean": sum(took) / len(took) if took else None,
        "request_s_max": max(took) if took else None,
        "requests_due": attempted,
        "requests_finished": len(finished),
        "iterations": rec.iterations,
        "compiles_in_window": compiles1 - compiles0,
    }
    values = {}
    ttft = [(r.first_s - r.due_s) * 1e3 for r in finished if r.first_s is not None]
    tpot = [(r.last_s - r.first_s) / (len(r.tokens) - 1) * 1e3
            for r in finished if len(r.tokens) > 1]
    late = [(r.sent_s - r.due_s) * 1e3 for r in win if r.sent_s is not None]
    if not load.closed and ttft and tpot:
        # the slowest tenth of ALL requests due in the window, averaged: one
        # order statistic of 70 heavy-tailed samples jumps by 10 % when two
        # requests swap ranks (PERF.md, PR 23); the p90 itself is kept as a
        # per-layer reading
        slowest = sorted(ttft)[int(0.9 * len(ttft)):]
        values["ttft_ms.tail10"] = sum(slowest) / len(slowest)
        values["tpot_ms.p90"] = common.percentile(tpot, 0.90)
        obs.update(ttft_ms_p90=common.percentile(ttft, 0.90))
        obs.update(ttft_ms_p50=median(ttft), tpot_ms_p50=median(tpot), samples=len(ttft))
        # steadier statistics of the same samples, shown beside the tails
        for label, vals in (("ttft_ms", ttft), ("tpot_ms", tpot)):
            top = sorted(vals)[int(0.9 * len(vals)):]
            obs.update({f"{label}_mean": sum(vals) / len(vals),
                        f"{label}_p75": common.percentile(vals, 0.75),
                        f"{label}_top_decile_mean": sum(top) / len(top)})
        gen_tokens = sum(len(r.tokens) for r in finished)
        obs.update(tokens_generated=gen_tokens,
                   token_gap_ms_mean=sum((r.last_s - r.first_s) for r in finished
                                         if len(r.tokens) > 1) * 1e3
                   / max(sum(len(r.tokens) - 1 for r in finished if len(r.tokens) > 1), 1))
    if load.closed and finished:
        toks = sum(len(r.prompt) + len(r.tokens) for r in finished)
        values["serve_tok_s"] = toks / load.window_s
        obs.update(tokens_completed=toks)
    values["setup_s"] = setup_s

    layer_ctx = {
        "cell": ctx.cell, "config": cfg, "traffic": traffic, "args": args,
        "stats0": stats0, "stats1": stats1, "recorder": rec, "late_ms": late,
        "ttft_ms": ttft, "tpot_ms": tpot,
        "memory_peak_bytes": memory_peak,
        "num_blocks": int(engine.config.num_blocks), "end_to_end": values,
        "device_kind": jax.devices()[0].device_kind,
        "trace": tracer.reduced(common.KERNEL_NAMES, ctx.rehearse) if tracer is not None else None,
        "kv_itemsize": int(np.dtype(engine.kv_dtype).itemsize),
        "decode_burst": int(args.decode_burst),
        "trace_span": tracer.span if tracer is not None else None,
    }

    # -- free the program, then check what the window served ------------------
    sample = common.sample_finished(finished, ctx.seed, cfg["check"]["sample_requests"])
    served_dtype = "bfloat16" if args.dtype == "bf16" else "float32"
    rec.engine = None
    common.free_engine(engine)
    del engine
    check = common.check_served(cfg, ctx.seed, sample, served_dtype)
    correct = check["ok"] and obs["compiles_in_window"] == 0 and failed == 0 and bool(finished)
    return {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "values": values, "observed": obs, "check": check, "layer_ctx": layer_ctx,
        "memory_peak_bytes": memory_peak,
        "sample": [(r.prompt, r.tokens, r.logprobs) for r in sample],
    }
