"""Optimizer wrapper over optax.

Reference: ``AcceleratedOptimizer`` (``/root/reference/src/accelerate/
optimizer.py:37``) wraps a torch optimizer to (a) skip stepping while
gradients accumulate, (b) integrate the GradScaler, (c) detect skipped
steps. Here the optimizer is an optax ``GradientTransformation``; the
wrapper owns the optimizer state, the accumulated gradients, and the jitted
apply step. bf16 needs no loss scaling; with ``mixed_precision='fp16'`` a
dynamic :class:`LossScaler` scales the loss, skips non-finite steps
(preserving the ``optimizer_step_was_skipped`` contract, reference
``optimizer.py:154-169``), and grows/backs off the scale with the
reference GradScaler's schedule (``accelerator.py:496-520``).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax

from .analysis.sanitizer import get_active_sanitizer as _get_sanitizer
from .diagnostics.tracing import trace_span
from .state import AcceleratorState, GradientState


def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


class LossScaler:
    """Dynamic fp16 loss scaler — the reference's ``torch.cuda.amp.GradScaler``
    (``/root/reference/src/accelerate/accelerator.py:496-520``) rebuilt for the
    XLA execution model: the scale and the consecutive-good-step counter are
    DEVICE scalars, passed into the compiled step as inputs and returned
    updated. On the fused path the grow/backoff decision happens inside the
    jitted step (no host sync, no retrace when the scale changes); the split
    path updates eagerly, where the finite check already synchronises.

    Schedule (GradScaler semantics): non-finite grads → ``scale *=
    backoff_factor`` and the step is skipped; after ``growth_interval``
    consecutive finite steps → ``scale *= growth_factor``.
    """

    def __init__(
        self,
        init_scale: float = 65536.0,
        growth_factor: float = 2.0,
        backoff_factor: float = 0.5,
        growth_interval: int = 2000,
    ):
        if growth_factor <= 1.0:
            raise ValueError("growth_factor must be > 1.0")
        if not 0.0 < backoff_factor < 1.0:
            raise ValueError("backoff_factor must be in (0, 1)")
        self.growth_factor = float(growth_factor)
        self.backoff_factor = float(backoff_factor)
        self.growth_interval = int(growth_interval)
        self._scale = jnp.asarray(float(init_scale), jnp.float32)
        self._good_steps = jnp.asarray(0, jnp.int32)

    @property
    def scale(self) -> jax.Array:
        """The current scale as a device scalar (safe to pass into jit)."""
        return self._scale

    def get_scale(self) -> float:
        return float(jax.device_get(self._scale))

    # -- jit plumbing -------------------------------------------------------

    @property
    def trace_key(self) -> tuple:
        """The static config baked into a compiled step. The scale itself is
        traced, so growth/backoff never triggers a recompile."""
        return (self.growth_factor, self.backoff_factor, self.growth_interval)

    def state(self) -> tuple:
        return (self._scale, self._good_steps)

    def set_state(self, state) -> None:
        self._scale, self._good_steps = state

    def next_state(self, scale, good_steps, step_ok):
        """Pure GradScaler update rule; usable inside jit."""
        good = jnp.where(step_ok, good_steps + 1, 0)
        grow = good >= self.growth_interval
        new_scale = jnp.where(
            step_ok,
            jnp.where(grow, scale * self.growth_factor, scale),
            scale * self.backoff_factor,
        )
        return new_scale, jnp.where(grow, 0, good).astype(jnp.int32)

    def update(self, step_ok: bool) -> None:
        """Eager update (split path — the finite flag is already on host)."""
        self.set_state(self.next_state(self._scale, self._good_steps, jnp.bool_(step_ok)))

    # -- checkpoint contract (reference saves scaler.state_dict() as
    # ``scaler.pt``, ``checkpointing.py:60``) --------------------------------

    def state_dict(self) -> dict:
        return {
            "scale": self.get_scale(),
            "growth_factor": self.growth_factor,
            "backoff_factor": self.backoff_factor,
            "growth_interval": self.growth_interval,
            "_growth_tracker": int(jax.device_get(self._good_steps)),
        }

    def load_state_dict(self, sd: dict) -> None:
        self.growth_factor = float(sd.get("growth_factor", self.growth_factor))
        self.backoff_factor = float(sd.get("backoff_factor", self.backoff_factor))
        self.growth_interval = int(sd.get("growth_interval", self.growth_interval))
        self._scale = jnp.asarray(float(sd["scale"]), jnp.float32)
        self._good_steps = jnp.asarray(int(sd.get("_growth_tracker", 0)), jnp.int32)


class AcceleratedOptimizer:
    """Owns (tx, opt_state) for one prepared model."""

    def __init__(self, optimizer: optax.GradientTransformation, model=None, scaler=None):
        if isinstance(optimizer, AcceleratedOptimizer):
            raise ValueError("optimizer is already prepared")
        self.optimizer = optimizer  # the raw optax transformation
        self.model = model          # PreparedModel, bound during prepare()
        self.scaler = scaler        # LossScaler (fp16 only), shared per Accelerator
        self.accelerator_state = AcceleratorState() if AcceleratorState().initialized else None
        self.gradient_state = GradientState()
        self.opt_state = None
        self._grads = None
        self._grads_are_unscaled = False
        self._accumulated_steps = 0
        self._step_was_skipped = False
        self._jit_cache: dict[str, Any] = {}
        # fused fast path (set by Accelerator.backward / clip_grad_norm_)
        self._pending_loss = None
        self._pending_clip: float | None = None
        self._last_norm = None
        self._step_ok_device = None  # fp16: lazily-fetched finite flag
        self.comm_hook = None  # (hook_str, mesh): compressed dp grad reduction
        self.telemetry = None  # TelemetryRecorder, wired by prepare_optimizer
        self._fused_steps = 0  # numbers the profiler's ``train`` steps
        self.watchdog = None   # diagnostics Watchdog, wired by prepare_optimizer

    # -- initialisation (called by Accelerator.prepare) ----------------------

    def bind(self, model, opt_state_sharding=None):
        self.model = model
        if opt_state_sharding is not None:
            self.opt_state = jax.jit(
                self.optimizer.init, out_shardings=opt_state_sharding
            )(model.params)
        else:
            self.opt_state = jax.jit(self.optimizer.init)(model.params)
        return self

    # -- gradient plumbing ----------------------------------------------------

    def _accumulate_grads(self, grads):
        if self._grads_are_unscaled and self.scaler is not None:
            # grads already unscaled by a clip; bring the new contribution
            # into the same units before accumulating
            inv = 1.0 / self.scaler.scale
            grads = jax.tree.map(lambda g: g * inv, grads)
        if self._grads is None:
            self._grads = grads
        else:
            add = self._jit_cache.get("add")
            if add is None:
                add = jax.jit(_tree_add, donate_argnums=(0,))
                self._jit_cache["add"] = add
            self._grads = add(self._grads, grads)
        self._accumulated_steps += 1

    @property
    def grads(self):
        if self._grads is None and self._pending_loss is not None:
            # forcing the parked loss flushes the fused step to the split
            # path (its _pre_force_hook), which materialises the grads
            self._pending_loss.force()
        return self._grads

    def zero_grad(self, set_to_none: bool = True):
        """No-op while accumulating, clears at boundary — matching the
        reference's behaviour of only clearing on sync steps
        (``optimizer.py:111``)."""
        if self.gradient_state.sync_gradients:
            self._grads = None
            self._grads_are_unscaled = False
            self._accumulated_steps = 0

    # -- stepping -------------------------------------------------------------

    def _apply_fn(self):
        apply = self._jit_cache.get("apply")
        if apply is None:
            def _apply(params, opt_state, grads):
                updates, new_opt_state = self.optimizer.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                return new_params, new_opt_state

            apply = jax.jit(_apply, donate_argnums=(0, 1, 2))
            self._jit_cache["apply"] = apply
        return apply

    def _skip_fn(self):
        skip = self._jit_cache.get("skip")
        if skip is None:
            def _all_finite(grads):
                leaves = [jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads)]
                return jnp.all(jnp.stack(leaves))

            skip = jax.jit(_all_finite)
            self._jit_cache["skip"] = skip
        return skip

    def unscale_gradients(self):
        """Divide fp16 loss-scaled grads back to true units; idempotent
        (reference GradScaler.unscale_ integration, ``optimizer.py:154``)."""
        if self.scaler is None or self._grads is None or self._grads_are_unscaled:
            return
        inv = 1.0 / self.scaler.scale  # device scalar: no retrace on change
        unscale = self._jit_cache.get("unscale")
        if unscale is None:
            unscale = jax.jit(
                lambda g, s: jax.tree.map(lambda x: x * s, g), donate_argnums=(0,)
            )
            self._jit_cache["unscale"] = unscale
        self._grads = unscale(self._grads, inv)
        self._grads_are_unscaled = True

    def _fused_step(self):
        """Run the single compiled forward+backward+clip+update step for the
        parked loss (see Accelerator.backward's fast path)."""
        from .lazy import fused_step_fn_for

        loss = self._pending_loss
        clip = self._pending_clip
        self._pending_loss = None
        self._pending_clip = None
        object.__setattr__(loss, "_pre_force_hook", None)
        jitted, frozen, inputs = fused_step_fn_for(
            loss,
            self.model,
            self.optimizer,
            clip_norm=clip is not None,
            grad_scaler=self.scaler,
            comm_hook=self.comm_hook,
            opt_state=self.opt_state,
        )
        frozen_params = [m.params for m in frozen]
        scaler_state = self.scaler.state() if self.scaler is not None else ()
        self._fused_steps += 1
        # a numbered step in a profiler capture (XProf's step view groups
        # the device operations of the dispatch under it)
        with jax.profiler.StepTraceAnnotation("train", step_num=self._fused_steps):
            new_params, new_opt_state, loss_value, norm, step_ok, new_scaler_state = jitted(
                self.model.params, self.opt_state, frozen_params, inputs,
                clip if clip is not None else 0.0, scaler_state,
            )
        self.model.params = new_params
        self.opt_state = new_opt_state
        if self.scaler is not None:
            self.scaler.set_state(new_scaler_state)
        loss._set_forced(loss_value)
        sanitizer = _get_sanitizer()
        if sanitizer:
            # fused path: the loss materializes here — step-boundary
            # NaN/inf probe (forces the value; sanitize-mode cost)
            sanitizer.check_loss(loss_value)
        self._last_norm = norm
        self._step_ok_device = step_ok if self.scaler is not None else None
        self._step_was_skipped = False  # overridden lazily via step_was_skipped

    def step(self, closure=None):
        tel = self.telemetry
        tel_on = tel is not None and tel.enabled
        wd = self.watchdog
        dispatch = trace_span("step/dispatch", sync=self.gradient_state.sync_gradients)
        if not tel_on and wd is None:
            with dispatch:
                return self._step_inner(closure)
        import time

        t0 = time.perf_counter()
        with dispatch:
            self._step_inner(closure)
        t1 = time.perf_counter()
        device_s = None
        if (
            tel_on
            and tel.sync_device
            and self.model is not None
            and self.gradient_state.sync_gradients
        ):
            # realise the dispatched update: splits the step's wall time
            # into host dispatch vs device-blocked (costs the host-runahead
            # pipelining; the recorder's sync_device=False keeps full async)
            try:
                with trace_span("step/device_wait"):
                    jax.block_until_ready(self.model.params)
                device_s = time.perf_counter() - t1
            except Exception:
                device_s = None
        if tel_on:
            # fused fp16 keeps the finite flag on device; only fetch it when
            # the sync above already realised the step (no extra host round
            # trip) — otherwise report unknown rather than fabricate False
            skipped = self._step_was_skipped
            if self._step_ok_device is not None:
                skipped = self.step_was_skipped if tel.sync_device else None
            tel.record_step(
                dispatch_s=t1 - t0,
                device_s=device_s,
                sync_gradients=self.gradient_state.sync_gradients,
                skipped=skipped,
            )
        if wd is not None and self.gradient_state.sync_gradients:
            wd.step_completed()

    def _step_inner(self, closure=None):
        if not self.gradient_state.sync_gradients:
            self._step_was_skipped = False
            self._step_ok_device = None
            return
        if self._pending_loss is not None:
            self._fused_step()
            return
        self._step_ok_device = None  # split path reports skips synchronously
        if self._grads is None:
            self._step_was_skipped = True
            return
        if self.scaler is not None:
            # fp16 path: unscale, then skip + backoff on non-finite (and
            # count good steps toward regrowth — GradScaler.update semantics)
            self.unscale_gradients()
            ok = bool(self._skip_fn()(self._grads))
            self.scaler.update(ok)
            if not ok:
                self._step_was_skipped = True
                self._grads = None
                self._grads_are_unscaled = False
                self._accumulated_steps = 0
                return
        grads = self._grads
        new_params, new_opt_state = self._apply_fn()(self.model.params, self.opt_state, grads)
        self.model.params = new_params
        self.opt_state = new_opt_state
        self._grads = None
        self._grads_are_unscaled = False
        self._accumulated_steps = 0
        self._step_was_skipped = False

    @property
    def step_was_skipped(self) -> bool:
        """(Reference ``optimizer.py:200``.) On the fused fp16 path the
        finite-grads flag lives on device; fetched on first access."""
        if self._step_ok_device is not None:
            import numpy as np

            self._step_was_skipped = not bool(np.asarray(self._step_ok_device))
            self._step_ok_device = None
        return self._step_was_skipped

    # -- state dict -----------------------------------------------------------

    def state_dict(self):
        return jax.device_get(self.opt_state)

    def load_state_dict(self, state):
        # Preserve shardings of the live opt_state when re-loading.
        def _put(old, new):
            if isinstance(old, jax.Array) and hasattr(old, "sharding"):
                return jax.device_put(jnp.asarray(new, dtype=old.dtype), old.sharding)
            return new

        self.opt_state = jax.tree.map(_put, self.opt_state, state)

    # -- lr plumbing (scheduler compat) ---------------------------------------

    @property
    def param_groups(self):
        """Torch-compat view: one group exposing the injected hyperparams."""
        hp = _find_hyperparams(self.opt_state)
        if hp is None:
            return [{}]
        return [{k: (float(v) if jnp.ndim(v) == 0 else v) for k, v in hp.items()}]

    def set_hyperparam(self, name: str, value):
        hp = _find_hyperparams(self.opt_state)
        if hp is None:
            raise ValueError(
                "optimizer was not built with optax.inject_hyperparams; "
                "use accelerate_tpu.optim factories for schedulable optimizers"
            )
        hp[name] = jnp.asarray(value, dtype=jnp.asarray(hp[name]).dtype)

    @property
    def learning_rate(self):
        hp = _find_hyperparams(self.opt_state)
        if hp and "learning_rate" in hp:
            return float(jax.device_get(hp["learning_rate"]))
        return None


def _find_hyperparams(opt_state):
    """Locate an ``InjectStatefulHyperparamsState.hyperparams`` dict."""
    if opt_state is None:
        return None
    states = opt_state if isinstance(opt_state, tuple) else (opt_state,)
    for s in jax.tree.leaves(
        states, is_leaf=lambda x: hasattr(x, "hyperparams")
    ):
        if hasattr(s, "hyperparams"):
            return s.hyperparams
    return None
