"""chip_smoke.py — does today's code start, compile and finish on the chip?

Runs the repo's two hot programs once each on the attached TPU, through the
entry points a user calls, at the full width of ``LlamaConfig.flagship_700m``
with seeded random weights:

* the trainer: the five-line ``Accelerator`` loop at batch 8 x seq 1024 in
  bf16, started by ``python -m accelerate_tpu.commands.launch``;
* the server: ``accelerate-tpu serve --preset flagship`` in stdin/JSONL
  mode, once with a bf16 KV pool and once with an int8 pool;

and checks the Pallas kernels under them against the repo's pure-JAX
references on the chip (paged attention vs ``impl="gather"``, latent attention
vs ``impl="lax"``, flash attention forward and gradients vs
``blockwise_attention``). On a host with four chips
it also trains under ``fsdp=2,tp=2`` and ``fsdp=4`` and serves under
``--mesh`` with ``tp=4``.

It is chip-or-fail and measurement-free: it exits non-zero when JAX finds no
TPU, when any phase fails, and in a directory that holds nothing else of the
repo. The seconds it prints are set-up facts (how long compiling and running
took here), never a rate. The last line of standard output is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``, naming the
device as JAX reports it and holding nothing else; the per-phase facts are on
the ``SUMMARY`` line before it.

A chip belongs to one process at a time, so this parent never initialises a
JAX backend: every phase is a child, run one after another, and a child's
non-zero exit, an error row, a missing answer or a timeout fails the script.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import re
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

#: the driver allows 1200 s on one chip, compilation included
DEADLINE_S = 1150.0
#: what one phase may take; a hung child is killed with its process group
PHASE_TIMEOUT_S = 600.0

TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 6

SERVE_ARGS = [
    "--preset", "flagship", "--dtype", "bf16", "--num-slots", "16",
    "--max-seq-len", "512", "--prefill-chunk", "128",
]
#: enough requests to reuse every slot; prompts longer than one prefill
#: chunk, so chunked prefill, slot reuse and a full decode batch all happen
SERVE_REQUESTS, PROMPT_LEN, NEW_TOKENS = 40, (32, 160), (16, 64)

#: the parent commit's kernel files, where a builder unpacked them for a
#: timing side by side (``git archive <parent> | tar -x -C _chip_tmp/parent``;
#: the directory is in ``.gitignore``): left out of the rows where they are not
PARENT_OPS = os.path.join(ROOT, "_chip_tmp", "parent", "accelerate_tpu", "ops")

#: |kernel - reference| ceilings on the chip, for unit-variance inputs.
#: Paged attention: same stored pool bytes on both sides, outputs rounded to
#: bf16 (2^-8 relative), the reference's f32 einsums at the TPU's default
#: (single bf16 pass) matmul precision. Flash attention: bf16 inputs and
#: outputs (magnitudes up to ~4, where three bf16 steps are 0.047), f32
#: softmax on both sides, probabilities rounded to bf16 before the kernel's
#: second matmul; gradients accumulate over the sequence, so they get a
#: bound relative to the largest reference entry.
PAGED_ATOL = 2e-2
FLASH_FWD_ATOL = 5e-2
FLASH_GRAD_RTOL = 3e-2
#: the state update: float32 arithmetic at ``highest`` on both sides, unit
#: inputs, so the state agrees to float32 rounding of values near 4 and
#: ``y`` (a sum of N of them) to N times that. The hybrid step: bf16
#: activations on both sides, the kernels' and the plain routes' sums in
#: another order — relative to the largest entry, as the flash gradients
SSM_ATOL = 1e-5
HYBRID_RTOL = 3e-2
#: the expert product: bf16 operands and float32 accumulation on both
#: sides, the hidden activation rounded to bf16 between the two products;
#: the two routes tile the contraction differently
MOE_RTOL = 2e-2

#: the latent kernel's row: heads, stored lanes, rank, lanes in use, block
#: size, table entries and pool blocks at the DeepSeek-V3 cell's widths; the
#: live decode rows' contexts; a chunk's length and where it ends
LATENT_GEOMETRY = (128, 640, 512, 576, 16, 1024, 2048)
LATENT_DECODE = (16000, 8192, 4096, 2048, 1024, 300, 17)
LATENT_CHUNK = (512, 4096)
#: the chat cells' chunk calls of the paged kernel: (name, the call's shape, table
#: entries, where the chunk ends, windows)
PAGED_CHUNKS = (
    ("smallthinker", dict(s=1024, nh=28, hd=128, n_kv=4), 1024, (1024, 4096, 16384), (0, 4096)),
    ("mistral", dict(s=256, nh=32, hd=128, n_kv=8), 256, (256, 1536, 3072), (0,)),
    ("lfm2 / hybrid", dict(s=256, nh=32, hd=64, n_kv=8), 256, (256, 1536, 3072), (0,)),
)

_COMPILED = re.compile(r"Finished XLA compilation of (\S+) in ([0-9.]+) sec")


class PhaseFailed(Exception):
    pass


# ---------------------------------------------------------------------------
# the parent: no JAX here
# ---------------------------------------------------------------------------


def _child_env(extra: dict | None = None) -> dict:
    env = dict(os.environ)
    # JAX itself refuses to start rather than fall back to the CPU
    env["JAX_PLATFORMS"] = "tpu"
    # one log line per executable: the phase's compile seconds and counts
    env["JAX_LOG_COMPILES"] = "1"
    env["PYTHONUNBUFFERED"] = "1"
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    env.update(extra or {})
    return env


def _run_child(name: str, cmd: list[str], deadline: float, *, env=None,
               stdin_text: str | None = None) -> dict:
    """One phase in its own process group; raises PhaseFailed unless it
    exits 0 inside its time. Returns stdout, stderr, wall seconds and the
    compile seconds JAX logged."""
    left = deadline - time.monotonic()
    if left <= 5:
        raise PhaseFailed(f"{name}: no time left before the {DEADLINE_S:.0f} s limit")
    t0 = time.monotonic()
    proc = subprocess.Popen(
        cmd, cwd=ROOT, env=_child_env(env), text=True, start_new_session=True,
        stdin=subprocess.PIPE if stdin_text is not None else subprocess.DEVNULL,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    try:
        out, err = proc.communicate(stdin_text, timeout=min(PHASE_TIMEOUT_S, left))
    except subprocess.TimeoutExpired:
        raise PhaseFailed(f"{name}: timed out") from None
    finally:
        # the group, not only the child: `launch` and `serve` spawn too
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    wall = time.monotonic() - t0
    # the whole of both streams, for whoever has to read a failure: the
    # tool brings chiprun_out/ back from the machine with the chip
    os.makedirs(LOG_DIR, exist_ok=True)
    for stream, text in (("out", out), ("err", err)):
        with open(os.path.join(LOG_DIR, f"{re.sub(r'[^a-z0-9]+', '_', name.lower())}.{stream}"), "w") as f:
            f.write(text)
    if proc.returncode != 0:
        raise PhaseFailed(
            f"{name}: exit code {proc.returncode}\n--- stdout\n{out[-3000:]}"
            f"\n--- stderr\n{err[-6000:]}"
        )
    compiles = [(m.group(1), float(m.group(2))) for m in _COMPILED.finditer(err)]
    return {
        "out": out, "err": err, "wall_s": wall, "compiles": compiles,
        "compile_s": sum(s for _, s in compiles),
    }


def _report(name: str, res: dict, facts: str = "") -> dict:
    """Print one phase's set-up facts and return them for the summary."""
    run_s = res["wall_s"] - res["compile_s"]
    print(
        f"[{name}] ok — compile {res['compile_s']:.1f} s "
        f"({len(res['compiles'])} executables, cache reads included), "
        f"everything else {run_s:.1f} s{facts}",
        flush=True,
    )
    return {"compile_s": round(res["compile_s"], 1), "run_s": round(run_s, 1)}


def _json_lines(text: str, tag: str) -> list[dict]:
    return [
        json.loads(line[len(tag):]) for line in text.splitlines()
        if line.startswith(tag)
    ]


def _self(phase: str, *args: str) -> list[str]:
    return [sys.executable, os.path.join(ROOT, "chip_smoke.py"), phase, *args]


def _serve_requests() -> list[dict]:
    import random

    rng = random.Random(0)
    return [
        {
            "id": i,
            "prompt": [rng.randrange(32000) for _ in range(rng.randint(*PROMPT_LEN))],
            "max_new_tokens": rng.randint(*NEW_TOKENS),
        }
        for i in range(SERVE_REQUESTS)
    ]


def _train_leg(name: str, deadline: float, mesh_flags: list[str]) -> dict:
    res = _run_child(
        name,
        [sys.executable, "-m", "accelerate_tpu.commands.launch",
         "--mixed_precision", "bf16", *mesh_flags, *_self("_train")[1:]],
        deadline,
    )
    (facts,) = _json_lines(res["out"], "TRAIN ") or [None]
    if facts is None:
        raise PhaseFailed(f"{name}: the train script printed no result\n{res['out'][-2000:]}")
    losses = facts["losses"]
    bad = []
    if len(losses) < 5 or not all(l == l and abs(l) != float("inf") for l in losses):
        bad.append(f"losses not finite over >= 5 steps: {losses}")
    elif not losses[-1] < losses[0]:
        bad.append(f"loss did not fall: {losses[0]} -> {losses[-1]}")
    logged = sum(1 for name, _ in res["compiles"] if name == "jit(step)")
    if facts["fused_step_compiles"] != 1 or logged != 1:
        bad.append(f"fused step compiled {facts['fused_step_compiles']} times by its own "
                   f"count and {logged} by JAX's log, not once")
    if facts["mosaic_custom_calls"] < 1:
        bad.append("the compiled train step holds no Mosaic custom call (flash kernel not reached)")
    bytes_in_use = facts["bytes_in_use_after_prepare"]
    if max(bytes_in_use) > 1.05 * min(bytes_in_use):
        bad.append(f"devices do not hold equal shares after prepare(): {bytes_in_use}")
    if bad:
        raise PhaseFailed(f"{name}: " + "; ".join(bad))
    out = _report(
        name, res,
        f"; loss {losses[0]:.4f} -> {losses[-1]:.4f} over {len(losses)} steps, "
        f"fused step compiled once with {facts['mosaic_custom_calls']} Mosaic "
        f"calls, mesh {facts['mesh']}, bytes in use per device after "
        f"prepare() {bytes_in_use}",
    )
    out.update(mesh=facts["mesh"], bytes_in_use=bytes_in_use)
    return out


def _serve_leg(name: str, deadline: float, extra_args: list[str],
               env: dict | None = None, sharded: bool = False) -> dict:
    requests = _serve_requests()
    res = _run_child(
        name,
        [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "serve",
         *SERVE_ARGS, *extra_args],
        deadline, env=env,
        stdin_text="".join(json.dumps(r) + "\n" for r in requests),
    )
    rows = [json.loads(line) for line in res["out"].splitlines() if line.startswith("{")]
    bad = []
    errors = [r for r in rows if "error" in r]
    if errors:
        bad.append(f"{len(errors)} error rows, first: {errors[0]}")
    answered = {r["id"]: r for r in rows if "error" not in r}
    for req in requests:
        row = answered.get(req["id"])
        if row is None:
            bad.append(f"request {req['id']} was not answered")
        elif len(row["tokens"]) != req["max_new_tokens"]:
            bad.append(
                f"request {req['id']} asked for {req['max_new_tokens']} tokens, "
                f"got {len(row['tokens'])} ({row.get('finish_reason')})"
            )
    closing = [l for l in res["err"].splitlines() if l.startswith("served ")]
    m = closing and re.search(
        r"decode compiles (\d+), paged route (\w+), device bytes in use (\[.*?\]|not reported)",
        closing[-1],
    )
    if not m:
        bad.append(f"no closing line on stderr: {res['err'][-1500:]}")
    elif m.group(1) != "1":
        bad.append(f"decode compiles {m.group(1)}, not 1")
    bytes_in_use = json.loads(m.group(3)) if m and m.group(3).startswith("[") else None
    if sharded and m:
        if not bytes_in_use or max(bytes_in_use) > 1.05 * min(bytes_in_use):
            bad.append(f"devices do not hold equal shares: {bytes_in_use}")
    if bad:
        raise PhaseFailed(f"{name}: " + "; ".join(bad[:5]))
    out = _report(
        name, res,
        f"; {len(requests)} requests answered in full, no error row, decode "
        f"compiles 1, paged route: {m.group(2)}, bytes in use per device "
        f"at exit {bytes_in_use}",
    )
    out.update(paged_route=m.group(2), bytes_in_use=bytes_in_use)
    return out


def main() -> int:
    if not os.path.isdir(os.path.join(ROOT, "accelerate_tpu")):
        print("chip_smoke: the program is not here — no accelerate_tpu/ beside "
              "this script, nothing to run", file=sys.stderr)
        return 2
    named = os.environ.get("JAX_PLATFORMS", "")
    if named and "tpu" not in named.lower().split(","):
        print(f"chip_smoke: found no chip — JAX_PLATFORMS={named} holds JAX to "
              "another platform, and this script runs on a TPU or not at all",
              file=sys.stderr)
        return 3

    started = time.monotonic()
    deadline = started + DEADLINE_S
    phases: dict[str, dict] = {}
    try:
        res = _run_child("probe", _self("_probe"), deadline)
        (device,) = _json_lines(res["out"], "PROBE ")
        if device["platform"] != "tpu":
            raise PhaseFailed(f"probe: JAX found no chip, platform is {device['platform']!r}")
        print(
            f"[probe] platform={device['platform']} device_kind={device['kind']!r} "
            f"count={device['count']} jax={device['jax']} jaxlib={device['jaxlib']} "
            f"libtpu={device['libtpu']}; compile cache: {device['compile_cache']}",
            flush=True,
        )
        four = device["count"] == 4
        if four:  # twice the legs, run by the builder: twice the time
            deadline += DEADLINE_S

        res = _run_child("kernels", _self("_kernels"), deadline)
        for row in _json_lines(res["out"], "KERNEL "):
            print(f"[kernels] {row['check']}: max |diff| {row['err']:.3g} "
                  f"(bound {row['bound']:.3g})", flush=True)
        phases["kernels"] = _report("kernels", res)

        legs = [("train", _train_leg, dict(mesh_flags=[])),
                ("serve bf16", _serve_leg, dict(extra_args=[])),
                ("serve int8", _serve_leg, dict(extra_args=["--kv-dtype", "int8"]))]
        if four:
            legs += [
                ("train fsdp=2,tp=2", _train_leg,
                 dict(mesh_flags=["--mesh_fsdp", "2", "--mesh_tp", "2"])),
                ("train fsdp=4", _train_leg, dict(mesh_flags=["--mesh_fsdp", "4"])),
                ("serve --mesh tp=4", _serve_leg,
                 dict(extra_args=["--mesh"], env={"ACCELERATE_MESH_TP": "4"}, sharded=True)),
            ]
        for name, leg, kwargs in legs:
            phases[name] = leg(name, deadline, **kwargs)

        res = _run_child("engine-check", _self("_engine_check"), deadline)
        for row in _json_lines(res["out"], "ENGINE "):
            print(
                f"[engine-check] {row['engine']}: paged route: {row['paged_route']}; "
                f"Mosaic calls decode {row['decode_mosaic_calls']} prefill "
                f"{row['prefill_mosaic_calls']}; kernel pool operand per device "
                f"{row['kernel_pool_shape']}; pool-sized instructions besides the "
                f"in-place scatters: {row['pool_moved'] or 'none'} (scale arrays: "
                f"{row['scales_moved'] or 'none'}); greedy tokens equal to generate(): "
                f"{row['agree']}/{row['compared']} (reported, not gated)",
                flush=True,
            )
        phases["engine-check"] = _report("engine-check", res)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        return 1

    ran = [name for name, _, _ in legs]
    print(f"legs run: {', '.join(ran)}; wall {time.monotonic() - started:.0f} s "
          "(set-up facts, not performance)", flush=True)
    print("SUMMARY " + json.dumps({"legs": ran, "phases": phases, "claim": None}),
          flush=True)
    print(_result_line(device), flush=True)
    return 0


def _result_line(device: dict) -> str:
    """The last line of standard output, which is the driver's: these keys
    and no other."""
    return json.dumps({
        "ok": True,
        "device": {"platform": str(device["platform"]), "kind": str(device["kind"]),
                   "count": int(device["count"])},
    })


# ---------------------------------------------------------------------------
# the children: each one process, each the chip's only holder while it runs
# ---------------------------------------------------------------------------


def _probe() -> None:
    import jax
    import jaxlib

    from accelerate_tpu.mesh import configure_compile_cache

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — the version is reported, not needed
        libtpu = "unknown"
    dev = jax.devices()[0]
    print("PROBE " + json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "jax": jax.__version__,
        "jaxlib": jaxlib.__version__, "libtpu": libtpu,
        "compile_cache": configure_compile_cache(),
    }))


def _kernel_row(check: str, got, want, bound: float, relative: bool = False) -> bool:
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.abs(got - want).max())
    if relative:
        bound = bound * float(np.abs(want).max())
    ok = bool(np.isfinite(got).all()) and err <= bound
    print("KERNEL " + json.dumps({"check": check, "err": err, "bound": bound, "ok": ok}),
          flush=True)
    return ok


def _parent_ops(name: str):
    """``ops/<name>.py`` of the parent commit as a module of this tree's
    ``accelerate_tpu.ops`` (its relative imports find this tree's files), or
    ``None`` where no builder unpacked the parent."""
    import importlib.util

    path = os.path.join(PARENT_OPS, name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("accelerate_tpu.ops._parent_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _paged_case(rng, b, s, nh, hd, bs, mb, store, n_kv=None, live=None, deepest=None):
    """Two-layer stacked pools whose layer 1 is written through real block
    tables (quantize-on-scatter for int8/fp8), rows at different depths,
    the last query at each row's end. With ``live``, only that many rows
    hold a context (between ``s`` and ``deepest`` positions, the first of
    them ``deepest``); the others are free slots as the engine dispatches
    them: position 0, every table entry the null block."""
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.fp8 import kv_storage_dtype
    from accelerate_tpu.ops.layers import write_paged_kv

    dtype, quantized = kv_storage_dtype(store)
    n_kv, live, deepest = n_kv or nh, b if live is None else live, deepest or mb * bs
    nb = b * mb + 1
    tables = (1 + np.arange(b * mb, dtype=np.int32)).reshape(b, mb)
    depth = rng.integers(s, deepest + 1, size=b).astype(np.int32)
    depth[0] = deepest  # one row fills its whole table, or the cell's longest context
    depth[live:] = 0
    tables[live:] = 0
    pools = [jnp.zeros((2, nb, bs, n_kv * hd), dtype)] * 2
    scales = [jnp.ones((2, nb, bs, n_kv), jnp.float32)] * 2 if quantized else []
    k, v = (
        jnp.asarray(rng.normal(size=(b, deepest, n_kv, hd)), jnp.bfloat16)
        for _ in range(2)
    )
    positions = np.broadcast_to(np.arange(deepest, dtype=np.int32), (b, deepest))
    written = write_paged_kv(
        *pools, 1, k, v, tables, positions, write_mask=positions < depth[:, None],
        **(dict(k_scale=scales[0], v_scale=scales[1]) if quantized else {}),
    )
    q = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.bfloat16)
    return q, written[:2], tables, np.maximum(depth - s, 0), written[2:]


def _kernels() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.mesh import configure_compile_cache
    from accelerate_tpu.ops.flash_attention import flash_attention
    from accelerate_tpu.ops.paged_attention import paged_attention

    configure_compile_cache()
    rng = np.random.default_rng(0)
    ok = True

    # paged attention at the flagship's decode and prefill-chunk shapes, and
    # at the benchmark's two older chat cells' decode shapes as their traffic
    # filled them: 64 slots x 256 table entries, GQA 32 / 8, 10 rows live to
    # 1,280 positions at head 128 (Mistral) and 30 to 640 at head 64 (the
    # hybrid); the cells as they run today are ``_paged_call_times``' rows
    cases = [
        (store, dict(b=b, s=s, nh=12, hd=128, mb=32))
        for store in ("bf16", "int8", "fp8") for b, s in ((16, 1), (1, 128))
    ] + [
        ("bf16", dict(b=64, s=1, nh=32, hd=128, mb=256, n_kv=8, live=10, deepest=1280)),
        ("bf16", dict(b=64, s=1, nh=32, hd=64, mb=256, n_kv=8, live=30, deepest=640)),
        # a block round's forward and a chunk under block_len 4 (GQA 32 / 4 of
        # 128, 20 rows live to 1,536 positions): a row's four queries see up
        # to the end of their block
        ("bf16", dict(b=64, s=4, nh=32, hd=128, mb=256, n_kv=4, live=20, deepest=1536,
                      block_len=4)),
        ("bf16", dict(b=1, s=256, nh=32, hd=128, mb=256, n_kv=4, deepest=2048, block_len=4)),
    ]
    for store, shape in cases:
        block_len = shape.pop("block_len", 1)
        q, pools, tables, idx, scales = _paged_case(rng, bs=16, store=store, **shape)
        idx = idx // block_len * block_len  # a round, and a chunk, start on a block's edge
        outs = {
            impl: jax.jit(
                lambda q, kp, vp, *sc, impl=impl: paged_attention(
                    q, kp, vp, 1, tables, idx, *sc, impl=impl, block_len=block_len)
            )(q, *pools, *scales)
            for impl in ("pallas", "gather")
        }
        live = f", {shape['live']} rows live" if "live" in shape else ""
        live += f", block_len {block_len}" if block_len > 1 else ""
        ok &= _kernel_row(
            f"paged attention {list(q.shape)} block 16, {store} pool{live}, "
            "pallas vs gather", outs["pallas"], outs["gather"], PAGED_ATOL)

    ok &= _paged_call_times(rng)
    ok &= _paged_chunk_times(rng)
    ok &= _latent_check(rng)

    # flash attention forward and gradients at the train shapes
    for b, s in ((8, 1024), (1, 8192)):
        qkv = [jnp.asarray(rng.normal(size=(b, s, 12, 128)), jnp.bfloat16) for _ in range(3)]
        probe = jnp.asarray(rng.normal(size=(b, s, 12, 128)), jnp.float32)
        ok &= _against_blockwise(f"flash attention %s [{b},{s},12,128] vs blockwise",
                                 flash_attention, qkv, probe)

    ok &= _hybrid_check(rng)
    ok &= _expert_product_check(rng)
    if len(jax.devices()) == 4:
        ok &= _ring_flash_check(rng)
    if not ok:
        sys.exit("a kernel disagrees with its reference beyond the stated bound")


def _paged_ms_a_call(mod, q, pools, tables, idx, block_len, calls=24, reps=5, window=0) -> float:
    """Milliseconds a call of ``mod``'s Pallas paged kernel: ``calls`` of them
    in one program, layer after layer as a decode step makes them."""
    import jax
    import jax.numpy as jnp

    def many(q, kp, vp):
        def one(i, acc):
            out = mod.paged_attention(q, kp, vp, i % 2, tables, idx, impl="pallas",
                                      block_len=block_len, window=window)
            return acc + out[0, 0, 0, 0].astype(jnp.float32)
        return jax.lax.fori_loop(0, calls, one, jnp.float32(0))

    many = jax.jit(many)
    jax.block_until_ready(many(q, *pools))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = many(q, *pools)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / (reps * calls)


def _paged_call_times(rng, lives=(2, 11, 22, 33, 64)) -> bool:
    """The paged kernel at the three chat configurations' decode shapes - 64
    rows x 256 table entries of 16, GQA 32 / 8 of 128 to 1,280 positions
    (Mistral), 32 / 8 of 64 to 768 (LFM2, the hybrid), 32 / 4 of 128 with four
    queries a row to 512 (SDAR) - with 2, 11, 22, 33 and 64 rows live:
    finite, against ``gather``, and the milliseconds a call takes (a set-up
    fact of this machine like the seconds of the other phases, for
    ``PERF.md``). Where a builder has unpacked the parent commit under
    ``_chip_tmp/parent`` (never committed), the parent's kernel is timed
    beside it and has to agree within the same bound."""
    import importlib

    import jax

    this = importlib.import_module("accelerate_tpu.ops.paged_attention")
    parent = _parent_ops("paged_attention")
    ok = True
    for name, shape in (
        ("mistral", dict(s=1, nh=32, hd=128, n_kv=8, deepest=1280)),
        ("lfm2 / hybrid", dict(s=1, nh=32, hd=64, n_kv=8, deepest=768)),
        ("sdar", dict(s=4, nh=32, hd=128, n_kv=4, deepest=512, block_len=4)),
    ):
        block_len = shape.pop("block_len", 1)
        # one pool a shape, every row written; a free slot is a row whose
        # table and position the call is handed as zeros
        q, pools, full_tables, full_idx, _ = _paged_case(
            rng, b=64, bs=16, mb=256, store="bf16", **shape)
        for live in lives:
            tables, idx = full_tables.copy(), full_idx // block_len * block_len
            tables[live:], idx[live:] = 0, 0
            run = lambda mod, impl: jax.jit(lambda q, kp, vp: mod.paged_attention(
                q, kp, vp, 1, tables, idx, impl=impl, block_len=block_len))(q, *pools)
            got = run(this, "pallas")
            label = (f"paged attention, {name} decode shape {list(q.shape)}, {live} of 64 rows "
                     f"live to {shape['deepest']}")
            ok &= _kernel_row(label + ", pallas vs gather", got, run(this, "gather"), PAGED_ATOL)
            row = {"check": label + ": ms a call", "ok": True, "tile": this._TILE,
                   "in_flight": this._IN_FLIGHT,
                   "ms_a_call": round(_paged_ms_a_call(this, q, pools, tables, idx, block_len), 4)}
            if parent:
                ok &= _kernel_row(label + ", pallas vs the parent's", got, run(parent, "pallas"),
                                  PAGED_ATOL)
                row["parent_ms_a_call"] = round(
                    _paged_ms_a_call(parent, q, pools, tables, idx, block_len), 4)
            print("KERNEL " + json.dumps(row), flush=True)
    return ok


def _paged_chunk_times(rng) -> bool:
    """A chunk's call of the paged kernel - more stacked query rows than one
    grid step's block, so a wide tile against a tall block of rows
    (``ops/paged_attention.py:_step_geometry``) - at the chat cells' chunk
    shapes: SmallThinker's (28 / 4 heads of 128, 1,024 tokens, a table of
    1,024 entries; the whole past and a window of 4,096), Mistral's (32 / 8 of
    128, 256 tokens, 256 entries) and LFM2's / the hybrid's (32 / 8 of 64).
    Finite, against ``gather``, the milliseconds a layer's call, and with the
    parent unpacked under ``_chip_tmp/parent`` its kernel beside it."""
    import importlib

    import jax

    this = importlib.import_module("accelerate_tpu.ops.paged_attention")
    parent = _parent_ops("paged_attention")
    ok = True
    for name, shape, mb, ends, windows in PAGED_CHUNKS:
        for end in ends:
            q, pools, tables, idx, _ = _paged_case(
                rng, b=1, bs=16, mb=mb, store="bf16", deepest=end, **shape)
            for window in windows:
                run = lambda mod, impl: jax.jit(lambda q, kp, vp: mod.paged_attention(
                    q, kp, vp, 1, tables, idx, impl=impl, window=window))(q, *pools)
                got = run(this, "pallas")
                label = (f"paged attention, {name} chunk {list(q.shape)} that ends at {end}"
                         + (f", window {window}" if window else ""))
                ok &= _kernel_row(label + ", pallas vs gather", got, run(this, "gather"),
                                  PAGED_ATOL)
                geometry = this._step_geometry(
                    shape["s"] * shape["nh"] // shape["n_kv"], mb, shape["n_kv"])
                row = {"check": label + ": ms a call", "ok": True, "tile": geometry[0],
                       "rows_a_grid_step": geometry[1],
                       "ms_a_call": round(_paged_ms_a_call(
                           this, q, pools, tables, idx, 1, calls=8, reps=3, window=window), 4)}
                if parent:
                    ok &= _kernel_row(label + ", pallas vs the parent's", got,
                                      run(parent, "pallas"), PAGED_ATOL)
                    row["parent_ms_a_call"] = round(_paged_ms_a_call(
                        parent, q, pools, tables, idx, 1, calls=8, reps=3, window=window), 4)
                print("KERNEL " + json.dumps(row), flush=True)
    return ok


def _latent_check(rng) -> bool:
    """The latent kernel (``ops/paged_attention.py:latent_attention``) at the
    DeepSeek-V3 cell's widths - 128 heads absorbed over rows stored 640 wide
    (512 of values, 64 of the shared rotated key, zeros), blocks of 16, 40
    slots x 1,024 table entries - against the ``lax`` route on the chip: a
    decode step with 7 of 40 rows live from 17 to 16,000 positions and the
    rest free slots as the engine dispatches them, and one 512-token chunk
    that ends at 4,096 (``LATENT_*``); finite, within the paged bound, and
    the milliseconds a call (a set-up fact of this machine, for ``PERF.md``).
    Where a builder unpacked a parent commit that has the kernel, it is timed
    beside it and has to agree within the same bound."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    this = importlib.import_module("accelerate_tpu.ops.paged_attention")
    parent = _parent_ops("paged_attention")
    parent = parent if hasattr(parent, "latent_attention") else None
    nh, width, rank, live_lanes, bs, mb, nb = LATENT_GEOMETRY
    lanes = jnp.arange(width) < live_lanes
    keys = jax.random.split(jax.random.PRNGKey(int(rng.integers(1 << 30))), 3)
    pool = (jax.random.normal(keys[0], (2, nb, bs, width), jnp.float32) * 0.5 * lanes
            ).astype(jnp.bfloat16)
    ok, slots = True, 40
    for label, s, contexts in (
        (f"decode, {len(LATENT_DECODE)} of {slots} rows live to {LATENT_DECODE[0]}", 1,
         LATENT_DECODE + (None,) * (slots - len(LATENT_DECODE))),
        ("a %d-token chunk that ends at %d" % LATENT_CHUNK, LATENT_CHUNK[0], LATENT_CHUNK[1:]),
    ):
        tables = np.zeros((len(contexts), mb), np.int32)
        idx = np.zeros((len(contexts),), np.int32)
        for i, context in enumerate(contexts):
            if context is not None:
                entries = (context - 1) // bs + 1
                tables[i, :entries] = 1 + rng.permutation(nb - 1)[:entries]
                idx[i] = context - s
        q = (jax.random.normal(keys[1 if s == 1 else 2], (len(contexts), s, nh, width),
                               jnp.float32) * lanes).astype(jnp.bfloat16)
        fns = {
            name: functools.partial(jax.jit(
                lambda q, pool, mod=mod, impl=impl: mod.latent_attention(
                    q, pool, 1, tables, idx, rank=rank, scale=0.135, impl=impl)), pool=pool)
            for name, mod, impl in (("pallas", this, "pallas"), ("lax", this, "lax"))
            + ((("parent", parent, "pallas"),) if parent else ())
        }
        label = f"latent attention {list(q.shape)} over rows of {width}, {label}"
        got = fns["pallas"](q)
        ok &= _kernel_row(label + ", pallas vs lax", got, fns["lax"](q), PAGED_ATOL)
        row = {"check": label + ": ms a call", "ok": True,
               "tile": this.tile_entries(mb, latent=True),
               "ms_a_call": round(_ms_a_call(fns["pallas"], q), 4)}
        if parent:
            ok &= _kernel_row(label + ", pallas vs the parent's", got, fns["parent"](q), PAGED_ATOL)
            row["parent_ms_a_call"] = round(_ms_a_call(fns["parent"], q), 4)
        print("KERNEL " + json.dumps(row), flush=True)
    return ok


def _latent() -> None:
    """The latent kernel's rows of :func:`_kernels` alone."""
    import numpy as np

    from accelerate_tpu.mesh import configure_compile_cache

    configure_compile_cache()
    if not _latent_check(np.random.default_rng(0)):
        sys.exit("the latent kernel disagrees with the scan beyond the stated bound")


def _paged_calls() -> None:
    """The paged kernel's per-call rows of :func:`_kernels` alone."""
    import numpy as np

    from accelerate_tpu.mesh import configure_compile_cache

    configure_compile_cache()
    rng = np.random.default_rng(0)
    if not (_paged_call_times(rng) & _paged_chunk_times(rng)):
        sys.exit("the paged kernel disagrees with its reference beyond the stated bound")


def _experts() -> None:
    """The expert product's rows of :func:`_kernels` alone."""
    import numpy as np

    from accelerate_tpu.mesh import configure_compile_cache

    configure_compile_cache()
    if not _expert_product_check(np.random.default_rng(0)):
        sys.exit("the grouped expert product disagrees with its ragged_dot twin")


def _expert_product_check(rng) -> bool:
    """The dropless expert product (``ops/moe.py``), two layers stacked and
    the second addressed in place: the grouped Pallas product against its
    ``ragged_dot`` twin; dead rows give zeros and add no pair. At
    LFM2-8B-A1B's widths (32 experts of 2048 x 1792, top 4 of a sigmoid
    router) at the chat cell's decode shape (64 slots, 24 live) and at a
    256-token chunk with a padded tail; at SDAR-30B-A3B-Chat's (128 experts
    of 2048 x 768, top 8 of a softmax over all of them) at a block round's
    forward (64 slots x 4 positions, 10 and 24 slots live) and a whole
    chunk, with the grouped product's milliseconds a call there (what
    ``_GMM_TILING``, chosen for 32 experts of 1,792, reads at 128 of 768)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops import moe

    ok = True
    keys = iter(jax.random.split(jax.random.PRNGKey(int(rng.integers(1 << 30))), 8))
    for e, h, f, k, scoring, shapes in (
        (32, 2048, 1792, 4, "sigmoid", ((64, 24), (256, 200))),
        (128, 2048, 768, 8, "softmax", ((256, 40), (256, 96), (256, 256))),
    ):
        # made on the device and handed over as operands: matrices a program
        # closes over are baked into it as constants (2.4 GB of them here)
        w_in = (jax.random.normal(next(keys), (2, e, h, 2 * f), jnp.float32)
                / np.sqrt(h)).astype(jnp.bfloat16)
        w_out = (jax.random.normal(next(keys), (2, e, f, h), jnp.float32)
                 / np.sqrt(f)).astype(jnp.bfloat16)
        gate = jnp.asarray(rng.normal(size=(h, e)) / np.sqrt(h), jnp.bfloat16)
        for rows, live_rows in shapes:
            x = jnp.asarray(rng.normal(size=(rows, h)), jnp.bfloat16)
            live = jnp.arange(rows) < live_rows
            experts, weights = jax.jit(
                lambda x: moe.route(x, gate, None, k, scoring=scoring))(x)
            fns = {
                impl: functools.partial(jax.jit(
                    lambda x, w_in, w_out, impl=impl: moe.expert_ffn(
                        x, experts, weights, w_in, w_out, live=live, layer=1, impl=impl)),
                    w_in=w_in, w_out=w_out)
                for impl in ("gmm", "ragged")
            }
            outs = {impl: fn(x) for impl, fn in fns.items()}
            label = (f"expert product [{rows},{h}] x {e} experts of {f} top {k}, "
                     f"{live_rows} rows live, gmm vs ragged_dot")
            ok &= _kernel_row(label, outs["gmm"][0], outs["ragged"][0], MOE_RTOL, relative=True)
            same = (np.array_equal(outs["gmm"][1], outs["ragged"][1])
                    and int(outs["gmm"][1].sum()) == live_rows * k
                    and not np.asarray(outs["gmm"][0], np.float32)[live_rows:].any())
            row = {"check": label + ": pairs counted, dead rows zero",
                   "err": 0.0 if same else 1.0, "bound": 0.0, "ok": same}
            if e == 128:
                touched = int((np.asarray(outs["gmm"][1]) > 0).sum())
                row.update(experts_touched=touched, tiling=list(moe._GMM_TILING), **{
                    impl + "_ms_a_call": round(_ms_a_call(fn, x), 4) for impl, fn in fns.items()})
                row["gmm_touched_gb_s"] = round(
                    touched * 3 * h * f * 2 / 1e6 / row["gmm_ms_a_call"], 1)
            print("KERNEL " + json.dumps(row), flush=True)
            ok &= same
    return ok


def _ms_a_call(fn, x, calls: int = 30) -> float:
    """Milliseconds a call of ``fn(x)``, dispatched back to back after one
    warm call, the last one waited for."""
    import jax

    jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(x)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / calls


def _hybrid_check(rng) -> bool:
    """The state-space path: ``ssm_state_update`` against its ``jnp`` twin at
    Granite-4.0-H-Micro's widths (64 heads of 64, state 128), and the hybrid
    paged step — a prefill chunk into one slot, then a decode step of every
    slot with two lanes masked — with both kernels against the same step on
    the plain routes (``lax`` paged attention at head 64, the ``jnp`` state
    update); a masked lane keeps its state bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models import granite_hybrid as gh
    from accelerate_tpu.ops import ssm

    ok = True
    slots, h, p, n = 16, 64, 64, 128
    state32 = jnp.asarray(rng.normal(size=(2, slots, h, p, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(slots, h, p)), jnp.bfloat16)
    dt = jax.nn.softplus(jnp.asarray(rng.normal(size=(slots, h)), jnp.float32) - 4)
    a = -jnp.exp(jnp.asarray(rng.normal(size=(h,)), jnp.float32) * 0.3)
    b_vec, c_vec = (jnp.asarray(rng.normal(size=(slots, n)), jnp.bfloat16) for _ in range(2))
    active = jnp.asarray(rng.random(slots) < 0.7)
    off = ~np.asarray(active)
    # the state as the model declares it, and as --state-dtype bf16 stores it:
    # there both routes round the same float32 value once, so they differ by
    # one step of bfloat16 (2**-8 of a value near 4) where its order of
    # summation put it on the other side of a rounding edge
    for name, dtype, bound in (("f32", jnp.float32, SSM_ATOL), ("bf16", jnp.bfloat16, 2.0 ** -6)):
        state = state32.astype(dtype)
        outs = {
            impl: jax.jit(lambda st, impl=impl: ssm.ssm_state_update(
                st, 1, x, dt, a, b_vec, c_vec, active, impl=impl))(state)
            for impl in ("pallas", "jnp")
        }
        label = f"ssm_state_update [{slots},{h},{p},{n}] {name} state, pallas vs jnp"
        ok &= _kernel_row(label + ", state", outs["pallas"][0], outs["jnp"][0], bound)
        # the twin's y under bfloat16 is no reference on the chip: XLA drops its
        # float32 -> bfloat16 -> float32 round trip (excess precision is allowed)
        # and multiplies the unrounded state, 0.1 off (my chip run, PR 27); the
        # kernel's y has to be what it STORED times C
        want_y = outs["jnp"][1] if name == "f32" else jax.jit(lambda st: jnp.where(
            active[:, None, None], jnp.einsum(
                "bhpn,bn->bhp", st[1].astype(jnp.float32), c_vec.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST), 0.0))(outs["pallas"][0])
        ok &= _kernel_row(label + ", y", outs["pallas"][1], want_y, SSM_ATOL * n)
        kept = np.array_equal(np.asarray(outs["pallas"][0].astype(jnp.float32))[1][off],
                              np.asarray(state.astype(jnp.float32))[1][off])
        print("KERNEL " + json.dumps({"check": f"ssm_state_update, {name} state: masked lanes bit-identical",
                                      "err": 0.0 if kept else 1.0, "bound": 0.0, "ok": kept}), flush=True)
        ok &= kept

    ok &= _state_update_live_rows(rng)

    # the hybrid paged step at published widths, four layers, a small vocabulary
    c = gh.GraniteHybridConfig(
        vocab_size=8192, num_hidden_layers=4, layer_types=("mamba", "mamba", "attention", "mamba"))
    params = jax.jit(lambda k: gh.init_granite_hybrid_params(k, c, jnp.bfloat16))(jax.random.PRNGKey(0))
    spec, n_slots, blocks, chunk = gh.cache_spec(c), 4, 64, 128
    tables = np.zeros((n_slots, 16), np.int32)
    tables[1], tables[3] = np.arange(1, 17), np.arange(17, 33)
    ids = rng.integers(0, c.vocab_size, size=(1, chunk)).astype(np.int32)
    toks = rng.integers(0, c.vocab_size, size=(n_slots, 1)).astype(np.int32)
    lanes = np.asarray([[False], [True], [False], [True]])

    def run():
        cache = {"k": jnp.zeros((1, blocks, 16, 512), jnp.bfloat16),
                 "v": jnp.zeros((1, blocks, 16, 512), jnp.bfloat16)}
        for name, leaf in spec.slot_state.items():
            cache[name] = jnp.full(leaf.array_shape(n_slots), 0.5, leaf.dtype or jnp.bfloat16)
            cache[name] = cache[name].at[:, 1].set(0)
        step = jax.jit(lambda cache, **kw: gh.granite_hybrid_apply(c, params, paged_kv=cache, **kw))
        pre = step(cache, input_ids=ids, block_tables=tables[1:2],
                   cache_positions=np.zeros((1,), np.int32),
                   paged_write_mask=np.ones((1, chunk), bool), state_slots=np.asarray([1], np.int32))
        dec = step(pre["paged_kv"], input_ids=toks, block_tables=tables,
                   cache_positions=np.asarray([0, chunk, 0, 0], np.int32), paged_write_mask=lanes)
        return pre["logits"][0], dec["logits"][lanes[:, 0], 0], dec["paged_kv"]

    kernels = run()
    routes = (sys.modules["accelerate_tpu.ops.paged_attention"], "default_paged_attention_impl",
              "lax"), (ssm, "default_ssm_impl", "jnp")
    saved = [getattr(mod, name) for mod, name, _ in routes]
    for mod, name, route in routes:
        setattr(mod, name, lambda route=route: route)
    try:
        plain = run()
    finally:
        for (mod, name, _), fn in zip(routes, saved):
            setattr(mod, name, fn)
    label = "hybrid paged step (2048 wide, mamba mamba attention mamba, head 64), kernels vs plain"
    ok &= _kernel_row(f"{label}: prefill chunk logits", kernels[0], plain[0], HYBRID_RTOL, relative=True)
    ok &= _kernel_row(f"{label}: decode logits", kernels[1], plain[1], HYBRID_RTOL, relative=True)
    ok &= _kernel_row(f"{label}: state", kernels[2]["ssm"], plain[2]["ssm"], HYBRID_RTOL, relative=True)
    idle = np.asarray(kernels[2]["ssm"])[:, [0, 2]]
    kept = bool((idle == 0.5).all())
    print("KERNEL " + json.dumps({"check": "hybrid decode step: masked lanes' state bit-identical",
                                  "err": 0.0 if kept else 1.0, "bound": 0.0, "ok": kept}), flush=True)
    return ok and kept


def _state_update_live_rows(rng, slots=64, h=64, p=64, n=128, lives=(11, 32, 64)) -> bool:
    """``ssm_state_update`` at the hybrid cell's decode shape - 64 slots of
    ``[64, 64, 128]`` float32 - with 11, 32 and 64 slots live: state and ``y``
    against the ``jnp`` twin, dead slots bit-identical, and the milliseconds
    a call takes (36 calls in one program, as a decode step makes them; a
    set-up fact of this machine like the seconds of the other phases, for
    ``PERF.md``). Where a builder has unpacked the parent commit under
    ``_chip_tmp/parent`` (never committed), the parent's kernel is timed
    beside it and its live rows have to be the same to the last bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops import ssm

    parent = _parent_ops("ssm")

    calls, reps = 36, 5
    state = jnp.asarray(rng.normal(size=(2, slots, h, p, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(slots, h, p)), jnp.bfloat16)
    dt = jax.nn.softplus(jnp.asarray(rng.normal(size=(slots, h)), jnp.float32) - 4)
    a = -jnp.exp(jnp.asarray(rng.normal(size=(h,)), jnp.float32) * 0.3)
    b_vec, c_vec = (jnp.asarray(rng.normal(size=(slots, n)), jnp.bfloat16) for _ in range(2))

    def ms_a_call(update, active):
        def many(st):
            def one(i, carry):
                st, acc = carry
                st, y = update(st, i % 2, x, dt, a, b_vec, c_vec, active, impl="pallas")
                return st, acc + y[0, 0, 0]
            return jax.lax.fori_loop(0, calls, one, (st, jnp.float32(0)))

        many = jax.jit(many, donate_argnums=0)
        st, _ = jax.block_until_ready(many(state + 0))
        t0 = time.perf_counter()
        for _ in range(reps):
            st, _ = many(st)
        jax.block_until_ready(st)
        return 1e3 * (time.perf_counter() - t0) / (reps * calls)

    ok, before = True, np.asarray(state)
    routes = {"pallas": (ssm, "pallas"), "jnp": (ssm, "jnp")}
    if parent:
        routes["parent"] = (parent, "pallas")
    for live in lives:
        on = np.zeros(slots, bool)
        on[rng.choice(slots, size=live, replace=False)] = True
        active = jnp.asarray(on)
        outs = {
            name: jax.jit(lambda st, mod=mod, impl=impl: mod.ssm_state_update(
                st, 1, x, dt, a, b_vec, c_vec, active, impl=impl))(state)
            for name, (mod, impl) in routes.items()
        }
        label = f"ssm_state_update [{slots},{h},{p},{n}] f32 state, {live} of {slots} live"
        ok &= _kernel_row(label + ", pallas vs jnp, state", outs["pallas"][0], outs["jnp"][0], SSM_ATOL)
        ok &= _kernel_row(label + ", pallas vs jnp, y", outs["pallas"][1], outs["jnp"][1], SSM_ATOL * n)
        new, y = np.asarray(outs["pallas"][0]), np.asarray(outs["pallas"][1])
        kept = (np.array_equal(new[1][~on], before[1][~on])
                and np.array_equal(new[0], before[0]) and not y[~on].any())
        row = {"check": label + ": dead slots and the other layer bit-identical, their y 0",
               "err": 0.0 if kept else 1.0, "bound": 0.0, "ok": kept,
               "ms_a_call": round(ms_a_call(ssm.ssm_state_update, active), 4)}
        if parent:
            same = (np.array_equal(new[1][on], np.asarray(outs["parent"][0])[1][on])
                    and np.array_equal(y[on], np.asarray(outs["parent"][1])[on]))
            row.update(parent_ms_a_call=round(ms_a_call(parent.ssm_state_update, active), 4),
                       live_rows_bit_equal_to_parent=same)
            row["ok"] = kept = kept and same
        print("KERNEL " + json.dumps(row), flush=True)
        ok &= kept
    return ok


def _out_and_grads(fn):
    """Jitted ``(q, k, v, probe) -> (out, dq, dk, dv)`` for the scalar
    ``sum(fn(q, k, v) * probe)``."""
    import jax
    import jax.numpy as jnp

    def scalar(q, k, v, probe):
        out = fn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * probe), out

    grad = jax.value_and_grad(scalar, argnums=(0, 1, 2), has_aux=True)

    def run(q, k, v, probe):
        (_, out), grads = grad(q, k, v, probe)
        return (out, *grads)

    return jax.jit(run)


def _against_blockwise(label: str, fn, qkv, probe) -> bool:
    """``fn``'s output and q/k/v gradients against ``blockwise_attention``.
    The reference runs three heads at a time (heads are independent, and the
    scan's saved residuals at seq 8192 would not fit the chip for twelve)."""
    import jax.numpy as jnp

    from accelerate_tpu.ops.flash_attention import blockwise_attention

    got = _out_and_grads(fn)(*qkv, probe)
    reference = _out_and_grads(blockwise_attention)
    groups = [
        reference(*(x[:, :, h:h + 3] for x in (*qkv, probe)))
        for h in range(0, qkv[0].shape[2], 3)
    ]
    want = [jnp.concatenate(parts, axis=2) for parts in zip(*groups)]
    ok = _kernel_row(label % "fwd", got[0], want[0], FLASH_FWD_ATOL)
    for name, g, w in zip("qkv", got[1:], want[1:]):
        ok &= _kernel_row(label % f"d{name}", g, w, FLASH_GRAD_RTOL, relative=True)
    return ok


def _ring_flash_check(rng) -> bool:
    """Ring attention with flash-kernel chunks over cp=4 against blockwise
    attention on the whole sequence: the one kernel that needs several chips."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.mesh import build_mesh
    from accelerate_tpu.parallel.context import context_parallel_attention
    from accelerate_tpu.utils.dataclasses import MeshPlugin

    mesh = build_mesh(MeshPlugin(dp=1, cp=4))
    b, s = 1, 8192
    qkv = [jnp.asarray(rng.normal(size=(b, s, 12, 128)), jnp.bfloat16) for _ in range(3)]
    probe = jnp.asarray(rng.normal(size=(b, s, 12, 128)), jnp.float32)

    def ring(q, k, v):
        return context_parallel_attention(q, k, v, None, mesh=mesh, mode="ring", causal=True)

    return _against_blockwise(f"ring flash %s [{b},{s},12,128] cp=4 vs blockwise",
                              ring, qkv, probe)


def _bytes_in_use() -> list[int]:
    import jax

    return [int(d.memory_stats()["bytes_in_use"]) for d in jax.local_devices()]


def _train() -> None:
    """The training script `launch` starts: the five-line loop."""
    import numpy as np
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.lazy import set_compile_callback
    from accelerate_tpu.mesh import mesh_axis_sizes
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.test_utils.training import SimpleLoader

    accelerator = Accelerator(mixed_precision="bf16")
    # compile facts of the fused step (a new Accelerator clears the hook,
    # so it is set after)
    compiles: list[dict] = []
    set_compile_callback(compiles.append)
    config = LlamaConfig.flagship_700m(
        max_position_embeddings=TRAIN_SEQ, remat="dots_saveable")
    model = LlamaForCausalLM.from_config(config, seed=0)
    ids = np.random.default_rng(0).integers(
        0, config.vocab_size, size=(TRAIN_BATCH, TRAIN_SEQ)).astype(np.int32)
    rows = [{"input_ids": row, "labels": row} for row in ids] * TRAIN_STEPS
    model, optimizer, loader = accelerator.prepare(
        model, optax.adamw(3e-4), SimpleLoader(rows, TRAIN_BATCH))
    bytes_in_use = _bytes_in_use()

    losses = []
    for batch in loader:
        out = model(**batch)
        accelerator.backward(out.loss)
        optimizer.step()
        optimizer.zero_grad()
        losses.append(float(out.loss))

    fused = [c for c in compiles if c["label"] == "fused_step"]
    print("TRAIN " + json.dumps({
        "losses": losses,
        "fused_step_compiles": len(fused),
        "mosaic_custom_calls": sum(c["mosaic_custom_calls"] for c in fused),
        "mesh": mesh_axis_sizes(accelerator.mesh),
        "bytes_in_use_after_prepare": bytes_in_use,
    }))


def _engine_check() -> None:
    """The engines `serve` built, built again the same way, to read what
    the serving process cannot hand over a pipe: the compiled text of the
    decode and prefill executables (Mosaic calls, the per-device shape of
    the pool the kernel reads, and whether the pool stays where it is:
    ``utils.hlo.buffers_moved``), and greedy agreement with generate()."""
    import jax
    import numpy as np

    from accelerate_tpu.commands import serve
    from accelerate_tpu.generation import generate
    from accelerate_tpu.utils.hlo import buffers_moved

    cli = argparse.ArgumentParser()
    serve.add_parser(cli.add_subparsers())

    engines = [("bf16 pool", [], {}), ("int8 pool", ["--kv-dtype", "int8"], {})]
    if len(jax.devices()) == 4:
        engines.append(("--mesh tp=4", ["--mesh"], {"ACCELERATE_MESH_TP": "4"}))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 32000, size=160).astype(np.int32) for _ in range(4)]
    new = 16
    ok = True
    reference = None
    for name, extra, env in engines:
        os.environ.update(env)
        args = cli.parse_args(["serve", *SERVE_ARGS, *extra])
        engine = serve._make_engine(args)
        requests = [engine.add_request(p, new) for p in prompts]
        engine.run_until_idle()
        texts = {p: engine.compiled_text(p) for p in ("decode", "prefill")}
        calls = {p: t.count('custom_call_target="tpu_custom_call"') for p, t in texts.items()}
        # the pool operand of the kernel, as the compiled program holds it on
        # one device — the stacked pool itself, not a layer's slab:
        # [layers, num_blocks, block, kv_heads_on_this_device * head_dim]
        kernel_lines = [l for l in texts["decode"].splitlines() if "tpu_custom_call" in l]
        pool = kernel_lines and re.search(
            r"operand_layout_constraints=\{.*?(\w+\[\d+,\d+,16,\d+\])", kernel_lines[0])
        route = engine.stats()["paged_attention_impl"]
        # on one device: the K/V pool or one layer's slab of it must be no
        # instruction's result but parameters, plumbing and the row scatters
        moved = {}
        for kind, arr in (("pool", engine._kp), ("scales", engine._ks)):
            if arr is None:
                moved[kind] = []
                continue
            n = arr.addressable_shards[0].data.size
            found = [buffers_moved(t, [n, n // engine._kp.shape[0]]) for t in texts.values()]
            moved[kind] = sorted(
                {f"{op} {shape}" for f in found for _, op, shape in f["moved"]}
                | {f"parameter({i}) not aliased" for f in found for i in f["unaliased"]}
            )
        if reference is None:  # generate() on the same seeded weights
            model = serve._build_model(args)
            reference = np.asarray(generate(
                model, np.stack(prompts), max_new_tokens=new, use_cache=True,
            ))[:, -new:]
        got = np.asarray([r.output_tokens for r in requests])
        print("ENGINE " + json.dumps({
            "engine": name, "paged_route": route,
            "decode_mosaic_calls": calls["decode"],
            "prefill_mosaic_calls": calls["prefill"],
            "kernel_pool_shape": pool.group(1) if pool else None,
            "pool_moved": moved["pool"], "scales_moved": moved["scales"],
            "agree": int((got == reference).sum()), "compared": int(got.size),
        }), flush=True)
        if (route == "pallas" and min(calls.values()) < 1) or moved["pool"]:
            ok = False
        del engine
    if not ok:
        sys.exit("paged route is pallas but an executable holds no Mosaic custom "
                 "call, or the KV pool moves inside a step program")


_CHILDREN = {
    "_probe": _probe, "_kernels": _kernels, "_paged_calls": _paged_calls, "_experts": _experts,
    "_latent": _latent, "_train": _train,
    "_engine_check": _engine_check,
}

if __name__ == "__main__":
    if len(sys.argv) > 1:
        _CHILDREN[sys.argv[1]]()
    else:
        sys.exit(main())
