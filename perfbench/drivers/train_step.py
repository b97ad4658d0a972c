"""Drives the fused train step through the five-line loop a user writes:
``Accelerator(...)`` on the mesh the configuration's ``env`` names →
``prepare`` → ``model(**batch)`` → ``backward`` → ``optimizer.step``, on
token ids drawn from the seed, every step ending in ``block_until_ready``.

Set-up builds ONE object — the prepared model, optimizer and loader with
the step they compile — drives it through its first steps from the seed
(what the check follows), and hands that same object to the window.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from perfbench import check, common, weights

TRACE_SECONDS = 4.0
#: times the distinct batches are repeated to make a loader longer than
#: any window (a window of 51 s at 0.2 s a step is 255 steps)
CYCLES = 128
#: steps a window must hold before "the loss fell" is judged
MIN_STEPS_TO_FALL = 8


def _find_mu(opt_state):
    """Adam's first moment, wherever optax nests it."""
    import jax

    for node in jax.tree.leaves(opt_state, is_leaf=lambda n: hasattr(n, "mu")):
        if hasattr(node, "mu"):
            return node.mu
    raise RuntimeError("the optimizer state holds no first moment (mu)")


def run(ctx: common.Ctx) -> dict:
    for k, v in ctx.config.get("env", {}).items():
        os.environ[k] = str(v)
    import jax
    import optax
    from jax.profiler import TraceAnnotation

    from accelerate_tpu import Accelerator
    from accelerate_tpu.big_modeling import init_empty_weights
    from accelerate_tpu.lazy import set_compile_callback
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.state import AcceleratorState, GradientState
    from accelerate_tpu.test_utils.training import SimpleLoader
    from perfbench.reference import train as ref_train

    cfg, traffic = ctx.config, ctx.traffic
    chips = ctx.cell["chips"]
    t_setup = time.perf_counter()
    accelerator = Accelerator(**{**cfg["accelerator"], **ctx.accelerator_kwargs})
    compiles: list = []
    set_compile_callback(compiles.append)
    with init_empty_weights():
        model = LlamaForCausalLM.from_config(
            LlamaConfig(**common.llama_keys(cfg), remat=cfg["remat"]))
    model.params = weights.make_tree(ctx.seed, model.params)
    batches = common.load_generator(traffic["kind"]).make(
        traffic, ctx.seed, ctx.seconds, cfg["vocab_size"])
    rows = [{"input_ids": r, "labels": r} for b in batches for r in b] * CYCLES
    lr = float(cfg["optimizer"]["learning_rate"])
    if cfg["optimizer"]["name"] != "adamw":
        raise SystemExit(f"perfbench: optimizer {cfg['optimizer']['name']!r} has no reference")
    model, optimizer, loader = accelerator.prepare(
        model, optax.adamw(lr), SimpleLoader(rows, int(traffic["global_batch"])))
    it = iter(loader)
    tokens_per_step = int(traffic["global_batch"]) * int(traffic["seq_len"])

    def one_step():
        t0 = time.perf_counter()
        with TraceAnnotation("perfbench/data.next"):
            batch = next(it)
        t1 = time.perf_counter()
        with TraceAnnotation("perfbench/train.step"):
            out = model(**batch)
            accelerator.backward(out.loss)
            optimizer.step()
            optimizer.zero_grad()
            jax.block_until_ready(model.params)
            loss = float(out.loss)
        return loss, t1 - t0, time.perf_counter() - t0

    # -- the first steps, which the reference follows -------------------------
    followed = int(cfg["check"]["steps"])
    program = {"losses": []}
    sumsq = jax.jit(ref_train.sumsq_tree)
    for i in range(followed):
        loss, _, _ = one_step()
        program["losses"].append(loss)
        if i == 0:
            # after one step Adam's first moment is (1 - b1) x the gradient
            # the optimizer was given
            mu = ref_train.leaf_norms(sumsq(_find_mu(optimizer.opt_state)))
            program["grad_norms"] = {k: v / (1.0 - 0.9) for k, v in mu.items()}
    shardings = jax.tree.map(lambda x: x.sharding, model.params)
    p0 = weights.make_tree(ctx.seed, model.params, out_shardings=shardings)
    program["update_norms"] = ref_train.leaf_norms(jax.jit(
        lambda a, b: ref_train.sumsq_tree(jax.tree.map(lambda x, y: x - y, a, b))
    )(model.params, p0))
    for leaf in jax.tree.leaves(p0):
        leaf.delete()
    del p0
    setup_s = time.perf_counter() - t_setup

    # -- the window -----------------------------------------------------------
    tracer = None
    if ctx.trace:
        tracer = common.TraceWindow(min(TRACE_SECONDS, ctx.seconds * 0.8))
        tracer.start()
    c0 = len(compiles)
    losses, waits, step_s = [], [], []
    t_w = time.perf_counter()
    while time.perf_counter() - t_w < ctx.seconds:
        loss, wait, took = one_step()
        losses.append(loss)
        waits.append(wait)
        step_s.append(took)
    elapsed = time.perf_counter() - t_w
    compiles_in_window = len(compiles) - c0
    if tracer is not None:
        tracer.wait()
    memory_peak = common.memory_peak_bytes()
    values = {
        "train_tok_s_chip": len(losses) * tokens_per_step / elapsed / chips,
        "setup_s": setup_s,
    }
    finite = all(np.isfinite(l) for l in losses)
    obs = {
        "window_s": elapsed, "steps": len(losses), "tokens_per_step": tokens_per_step,
        "compiles_in_window": compiles_in_window,
        "fused_step_compiles": sum(1 for c in compiles if c.get("label") == "fused_step"),
        "loss_first": losses[0], "loss_last": losses[-1], "loss_finite": bool(finite),
        # Adam's first steps on random tokens raise the loss before it falls:
        # judged once the window is long enough to be past them
        "loss_fell": bool(losses[-1] < program["losses"][0]) if len(losses) >= MIN_STEPS_TO_FALL else None,
        "mesh": {k: int(v) for k, v in dict(accelerator.mesh.shape).items() if v > 1},
    }
    trace = tracer.reduced(common.KERNEL_NAMES, ctx.rehearse) if tracer is not None else None
    traced_steps = None
    if trace is not None:
        traced_steps = max(
            sum(len(d) for n, d in dev["modules"].items() if n.startswith("jit_step"))
            for dev in trace["devices"].values())
    layer_ctx = {
        "cell": ctx.cell, "config": cfg, "traffic": traffic, "chips": chips,
        "step_s": step_s, "data_wait_s": waits, "compiles_in_window": compiles_in_window,
        "end_to_end": values, "memory_peak_bytes": memory_peak, "trace": trace,
        "traced_steps": traced_steps, "device_kind": jax.devices()[0].device_kind,
    }

    # -- free the program, then follow its first steps with the reference ------
    set_compile_callback(None)
    for leaf in jax.tree.leaves((model.params, optimizer.opt_state)):
        leaf.delete()
    del model, optimizer, loader, it, accelerator
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    gc.collect()
    reference = ref_train.follow(cfg, ctx.seed, batches[:followed], lr,
                                 devices=jax.devices()[:chips])
    result = check.trained(cfg, program, reference)
    correct = (result["ok"] and compiles_in_window == 0 and finite
               and obs["loss_fell"] is not False)
    return {
        "correct": bool(correct), "attempted": len(losses), "failed": 0,
        "values": values, "observed": obs, "check": result, "layer_ctx": layer_ctx,
        "memory_peak_bytes": memory_peak,
    }
