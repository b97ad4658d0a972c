"""What the chip needs of the host side, checked without a chip: where the
compile cache goes, that ``chip_smoke.py`` refuses to run off the TPU, and
that every parent which spawns a JAX child is itself off JAX at that moment
(a chip belongs to one process: a parent that touched JAX holds it, and the
child then fails or hangs)."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**overrides):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    env.update(overrides)
    return env


def test_compile_cache_is_placed_from_outside_or_at_one_fixed_path(tmp_path):
    code = textwrap.dedent(
        """
        import jax
        from accelerate_tpu.state import PartialState
        PartialState()
        print("CACHE", jax.config.jax_compilation_cache_dir)
        """
    )
    placed = str(tmp_path / "placed")
    runs = {
        "placed": dict(cwd=REPO, env=_env(JAX_COMPILATION_CACHE_DIR=placed)),
        "from_repo": dict(cwd=REPO, env=_env()),
        "from_elsewhere": dict(cwd=str(tmp_path), env=_env()),
    }
    procs = {
        name: subprocess.Popen(
            [sys.executable, "-c", code], stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, **kw,
        )
        for name, kw in runs.items()
    }
    seen = {}
    for name, proc in procs.items():
        out, err = proc.communicate(timeout=120)
        assert proc.returncode == 0, err[-2000:]
        seen[name] = out.split("CACHE ", 1)[1].strip()
    assert seen["placed"] == placed
    fixed = os.path.join(REPO, ".compile_cache")
    assert seen["from_repo"] == seen["from_elsewhere"] == fixed
    # what the fixed path holds is built at run time, never committed
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".compile_cache/" in ignored


def test_chip_smoke_refuses_to_run_off_the_chip(tmp_path):
    script = os.path.join(REPO, "chip_smoke.py")
    held = subprocess.run(
        [sys.executable, script], capture_output=True, text=True, timeout=30,
        env=_env(JAX_PLATFORMS="cpu"), cwd=REPO,
    )
    assert held.returncode != 0
    assert "found no chip" in held.stderr and "JAX_PLATFORMS=cpu" in held.stderr
    assert held.stdout == ""  # no result line of any kind

    # alone, without the program beside it: nothing to run, whatever JAX finds
    shutil.copy(script, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in _env().items() if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    alone = subprocess.run(
        [sys.executable, str(tmp_path / "chip_smoke.py")], capture_output=True,
        text=True, timeout=30, env=env, cwd=str(tmp_path),
    )
    assert alone.returncode != 0
    assert "the program is not here" in alone.stderr
    assert alone.stdout == ""


def test_chip_smoke_result_line_holds_the_contract_keys_and_no_other():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    probe = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
             "jax": "0.9.0", "jaxlib": "0.9.0", "libtpu": "0.0.34",
             "compile_cache": "/somewhere"}
    line = chip_smoke._result_line(probe)
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_spawning_parents_hold_no_jax_backend():
    """`launch`, `route` and `chip_smoke.py` reach their first ``Popen``
    with ``xla_bridge._backends`` still empty."""
    code = textwrap.dedent(
        """
        import json, os, subprocess, sys
        from jax._src import xla_bridge

        seen = {}

        class Spawned(Exception):
            pass

        def recorder(name):
            def popen(*args, **kwargs):
                seen[name] = bool(xla_bridge._backends)
                raise Spawned(name)
            return popen

        def spawn_point(name, run):
            subprocess.Popen = recorder(name)
            try:
                run()
            except Spawned:
                pass

        from accelerate_tpu.commands import accelerate_cli
        spawn_point("launch", lambda: accelerate_cli.main(["launch", "no_such_script.py"]))
        spawn_point("route", lambda: accelerate_cli.main(["route", "--replicas", "1"]))
        import chip_smoke
        os.environ.pop("JAX_PLATFORMS")  # as on the machine with the chip
        spawn_point("chip_smoke", chip_smoke.main)
        print("SEEN", json.dumps(seen))
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120,
        env=_env(JAX_PLATFORMS="cpu"), cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    seen = json.loads(out.stdout.split("SEEN ", 1)[1])
    assert seen == {"launch": False, "route": False, "chip_smoke": False}
