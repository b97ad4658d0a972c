"""notebook_launcher / debug_launcher (reference ``launchers.py:40,269``).

On TPU with JAX there is no per-device process fork (the reference's
``xmp.spawn``): ONE process drives all local chips, so ``notebook_launcher``
validates the environment, sets the env-var contract, and calls the
function inline. ``debug_launcher`` runs the function on a virtual
N-device CPU mesh in a subprocess (fresh JAX runtime) — the analog of the
reference's gloo-on-localhost debug path.
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import textwrap


def notebook_launcher(
    function,
    args=(),
    num_processes: int | None = None,
    mixed_precision: str = "no",
    use_port: str = "29500",
    master_addr: str = "127.0.0.1",
    node_rank: int = 0,
    num_nodes: int = 1,
    **kwargs,
):
    """Run a training function on the attached TPU(s) from a notebook.

    ``num_processes`` is accepted for API parity but on JAX-TPU a single
    process drives every local chip; it is validated against the actual
    device count rather than used to fork.
    """
    import jax

    from .state import AcceleratorState, PartialState

    if AcceleratorState._shared_state or PartialState._shared_state:
        in_use = AcceleratorState if AcceleratorState._shared_state else PartialState
        raise ValueError(
            f"A {in_use.__name__} was already initialized in this process; "
            "notebook_launcher must run before any Accelerator is created "
            "(restart the kernel) — reference semantics, launchers.py:165-255."
        )
    n_dev = jax.local_device_count()
    if num_processes is not None and num_processes > n_dev:
        raise ValueError(
            f"num_processes={num_processes} but only {n_dev} local devices exist"
        )
    if mixed_precision not in ("no", "bf16", "fp16"):
        raise ValueError(f"unknown mixed_precision {mixed_precision!r}")
    os.environ["ACCELERATE_MIXED_PRECISION"] = mixed_precision
    if num_nodes > 1:
        os.environ.setdefault("ACCELERATE_COORDINATOR_ADDR", f"{master_addr}:{use_port}")
        os.environ.setdefault("ACCELERATE_NUM_PROCESSES", str(num_nodes))
        os.environ.setdefault("ACCELERATE_PROCESS_ID", str(node_rank))
    print(f"Launching training on {n_dev} device(s).")
    try:
        return function(*args)
    finally:
        os.environ.pop("ACCELERATE_MIXED_PRECISION", None)


def _can_import(function) -> bool:
    mod = getattr(function, "__module__", None)
    name = getattr(function, "__qualname__", getattr(function, "__name__", ""))
    return bool(mod and mod != "__main__" and "." not in name and "<" not in name)


def debug_launcher(function, args=(), num_processes: int = 2):
    """Run ``function`` against a virtual ``num_processes``-device CPU mesh
    in a fresh subprocess (JAX platform flags are fixed at first import, so
    in-process re-init is impossible — the subprocess IS the fresh runtime).
    The function must be importable (defined in a module, not a closure) or
    picklable."""
    import pickle

    with tempfile.TemporaryDirectory() as td:
        payload = os.path.join(td, "payload.pkl")
        if _can_import(function):
            spec = ("import", function.__module__, function.__qualname__)
        else:
            spec = ("pickle", None, None)
        with open(payload, "wb") as f:
            if spec[0] == "pickle":
                pickle.dump((function, args), f)
            else:
                pickle.dump((None, args), f)
        runner = textwrap.dedent(
            f"""
            import os, pickle, sys
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count={num_processes}"
            ).strip()
            sys.path.insert(0, {os.getcwd()!r})
            with open({payload!r}, "rb") as f:
                fn, args = pickle.load(f)
            if fn is None:
                import importlib
                fn = getattr(importlib.import_module({spec[1]!r}), {spec[2]!r})
            fn(*args)
            """
        )
        proc = subprocess.run(
            [sys.executable, "-c", runner],
            env={
                **os.environ,
                "ACCELERATE_DEBUG_RDV": "1",
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": (
                    os.environ.get("XLA_FLAGS", "")
                    + f" --xla_force_host_platform_device_count={num_processes}"
                ).strip(),
            },
        )
        if proc.returncode != 0:
            raise RuntimeError(f"debug_launcher function failed (exit {proc.returncode})")
