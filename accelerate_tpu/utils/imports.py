"""Feature-availability probes.

Role of the reference's ``utils/imports.py`` (~60 ``is_*_available`` gates,
``/root/reference/src/accelerate/utils/imports.py``) — but TPU-native: the
baseline stack is JAX/XLA, so the probes that matter are JAX backends and the
optional Python ecosystems (trackers, safetensors, torch-interop for
checkpoint import).
"""

from __future__ import annotations

import importlib.metadata
import importlib.util
import functools


def _is_package_available(pkg_name: str) -> bool:
    if importlib.util.find_spec(pkg_name) is None:
        return False
    try:
        importlib.metadata.version(pkg_name)
    except importlib.metadata.PackageNotFoundError:
        # Namespace packages (e.g. orbax) have no top-level dist metadata.
        pass
    return True


@functools.cache
def is_jax_available() -> bool:
    return _is_package_available("jax")


@functools.cache
def is_flax_available() -> bool:
    return _is_package_available("flax")


@functools.cache
def is_optax_available() -> bool:
    return _is_package_available("optax")


@functools.cache
def is_orbax_available() -> bool:
    return importlib.util.find_spec("orbax") is not None


@functools.cache
def is_torch_available() -> bool:
    return _is_package_available("torch")


@functools.cache
def is_safetensors_available() -> bool:
    return _is_package_available("safetensors")


@functools.cache
def is_transformers_available() -> bool:
    return _is_package_available("transformers")


@functools.cache
def is_datasets_available() -> bool:
    return _is_package_available("datasets")


@functools.cache
def is_tensorboard_available() -> bool:
    return _is_package_available("tensorboard") or _is_package_available("tensorboardX")


@functools.cache
def is_wandb_available() -> bool:
    return _is_package_available("wandb")


@functools.cache
def is_comet_ml_available() -> bool:
    return _is_package_available("comet_ml")


@functools.cache
def is_mlflow_available() -> bool:
    return _is_package_available("mlflow")


@functools.cache
def is_aim_available() -> bool:
    return _is_package_available("aim")


@functools.cache
def is_clearml_available() -> bool:
    return _is_package_available("clearml")


@functools.cache
def is_dvclive_available() -> bool:
    return _is_package_available("dvclive")


@functools.cache
def is_rich_available() -> bool:
    return _is_package_available("rich")


@functools.cache
def is_tqdm_available() -> bool:
    return _is_package_available("tqdm")


@functools.cache
def is_pandas_available() -> bool:
    return _is_package_available("pandas")


@functools.cache
def is_tpu_available() -> bool:
    """True when a real TPU backend is attached (not the CPU fake mesh)."""
    if not is_jax_available():
        return False
    import jax

    try:
        return jax.devices()[0].platform == "tpu"
    except RuntimeError:
        return False


@functools.cache
def is_multihost_available() -> bool:
    if not is_jax_available():
        return False
    import jax

    return jax.process_count() > 1
