"""Optimizer adapter — optax-backed, torch-optimizer-shaped.

Parity target: reference ``src/accelerate/optimizer.py`` (213 LoC,
``AcceleratedOptimizer``): no-op ``step``/``zero_grad`` while gradients are
accumulating, scaler integration, lazy XLA grad all-reduce at step time.

TPU-native redesign: the optimizer owns the optax ``GradientTransformation`` and a
*sharded* opt-state pytree (built from sharded params, so ZeRO-style optimizer
sharding is automatic — the reference's FSDP2 ``data_ptr`` re-mapping dance,
``accelerator.py:1400-1457``, has no analog).  The reference's lazy grad
all-reduce (``optimizer.py:149-155``) is unnecessary: gradients come out of the
jitted step already reduced over data axes by GSPMD.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import optax

from .state import AcceleratorState, GradientState
from .telemetry import get_telemetry as _get_telemetry
from .telemetry import span as _span

__all__ = ["AcceleratedOptimizer"]


def _update_body(
    tx_update, params, opt_state, grads, clip_norm, clip_value, health_ok=None,
    norm_ndp=None,
):
    """One optimizer update (traced body shared by the jit variants).

    ``clip_norm`` / ``clip_value`` < 0 disable the respective clip (static
    python floats would retrigger compilation; pass as arrays); 0 is a real
    clip that zeroes gradients, matching torch's ``clip_grad_{norm,value}_(0)``.
    Value clip (elementwise, reference ``clip_grad_value_``) applies first,
    then norm clip — matching a torch loop that calls both before ``step()``.

    Numerical-health gate (resilience/health.py): the PRE-clip global norm is
    the health verdict — a value clip would mask an Inf gradient into a
    finite one, so finiteness must be judged before any clip touches the
    tree.  When the verdict (optionally ANDed with ``health_ok``, the fused
    step's loss-finiteness flag) fails, the whole update is ``jnp.where``-
    gated to a zero delta: params AND optimizer state come back bit-identical
    (optax ``count`` included), all inside this one traced program — no extra
    dispatch, no host round-trip.  The returned ``health_norm`` is that
    pre-clip norm, forced non-finite whenever the verdict failed, so the host
    can detect the skip from a value it was reading anyway.

    ``norm_ndp`` (static; set by every caller on a mesh with active
    data-parallel axes, ``parallel/zero.py:zero_degree``) switches the two
    global norms to the canonical dp-chunked association and select-fences
    the update's dataflow boundaries.  Both are numerics-parity devices for
    the ZeRO sharded update: the chunked norm reduces identically over a
    replicated and a dp-sharded gradient tree, and the fences (selects on an
    always-true-at-runtime pred) stop XLA from FMA-contracting multiplies
    across stage boundaries differently in differently-partitioned programs.
    Selects pass values through bit-exactly, so on any single program this is
    a no-op numerically; across the eager / fused / fused+ZeRO programs it is
    what makes them agree to the last bit (tests/test_zero.py matrix).  With
    ``norm_ndp=None`` (no dp axes — the overwhelmingly common single-device
    test path) this body is exactly the legacy one.
    """
    with jax.named_scope("clip"):
        if norm_ndp:
            from .parallel.zero import chunked_global_norm

            # Runtime-true, compile-time-opaque fence pred.  x == x is the
            # NaN-check: True for every real clip argument INCLUDING inf
            # (clip_grad_norm_(inf) is the standard measure-without-clipping
            # idiom and must not trip the fence), never constant-foldable for
            # floats.  ANDing health_ok keeps the poisoned-step semantics:
            # zeroed grads make the norms finite, but ``ok`` still fails via
            # health_ok and health_norm is forced NaN below.
            fence = jnp.logical_and(clip_norm == clip_norm, clip_value == clip_value)
            if health_ok is not None:
                fence = jnp.logical_and(fence, health_ok)
            grads = jax.tree_util.tree_map(
                lambda g: jnp.where(fence, g, jnp.zeros_like(g)), grads
            )
            health_norm = chunked_global_norm(grads, norm_ndp, fence)
        else:
            health_norm = optax.global_norm(grads)
        ok = jnp.isfinite(health_norm)
        if health_ok is not None:
            ok = jnp.logical_and(ok, health_ok)
            health_norm = jnp.where(health_ok, health_norm, jnp.nan)
        # The clip scalars are float32 ARRAYS: left as they are they promote a
        # bf16 gradient tree (and, through optax's moments, the optimizer state)
        # to float32 — a program whose outputs no longer alias its donated
        # inputs, and a second compile when the wider state comes back in.
        def _value_clip(g):
            bound = clip_value.astype(g.dtype)
            return jnp.where(clip_value >= 0, jnp.clip(g, -bound, bound), g)

        grads = jax.tree_util.tree_map(_value_clip, grads)
        if norm_ndp:
            gnorm = chunked_global_norm(grads, norm_ndp, fence)
        else:
            gnorm = optax.global_norm(grads)
        scale = jnp.where(
            clip_norm >= 0, jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-12)), 1.0
        )
        grads = jax.tree_util.tree_map(lambda g: g * scale.astype(g.dtype), grads)
        if norm_ndp:
            grads = jax.tree_util.tree_map(
                lambda g: jnp.where(ok, g, jnp.zeros_like(g)), grads
            )
    with jax.named_scope("optimizer"):
        updates, new_opt_state = tx_update(grads, opt_state, params)
        if norm_ndp:
            updates = jax.tree_util.tree_map(
                lambda u: jnp.where(ok, u, jnp.zeros_like(u)), updates
            )
        new_params = optax.apply_updates(params, updates)
        new_params = jax.tree_util.tree_map(
            lambda n, o: jnp.where(ok, n, o), new_params, params
        )
        new_opt_state = jax.tree_util.tree_map(
            lambda n, o: jnp.where(ok, n, o), new_opt_state, opt_state
        )
    return new_params, new_opt_state, gnorm, health_norm


_update_step = partial(jax.jit, donate_argnums=(1, 2), static_argnums=(0,))(_update_body)


class AcceleratedOptimizer:
    """Wraps an optax transformation (or a converted torch optimizer) so the
    training loop keeps its imperative ``optimizer.step()`` shape.

    Gradients land here from ``accelerator.backward`` (the accumulation buffer);
    ``step()`` is a no-op while ``GradientState.sync_gradients`` is False —
    identical observable semantics to reference ``optimizer.py:145-181``.
    """

    def __init__(
        self,
        tx: optax.GradientTransformation,
        model=None,
        torch_optimizer=None,
        initial_lr: Optional[float] = None,
        host_offload_state: bool = False,
    ):
        self.tx = tx
        self._host_offload_requested = host_offload_state
        self._update_fn = None
        self.model = model  # PreparedModel owning the params
        self.torch_optimizer = torch_optimizer  # shadow for scheduler compat
        self.initial_lr = initial_lr
        self.gradient_state = GradientState()
        self.accelerator_state = AcceleratorState() if AcceleratorState._shared_state else None
        self.opt_state = None
        self._step_was_skipped = False
        # Persistent clips (<0: disabled) — set by engine-dialect config
        # (e.g. ds_config gradient_clipping) and applied every step.
        self._clip_norm = -1.0
        self._clip_value = -1.0
        # One-shot overrides armed by accelerator.clip_grad_{norm,value}_ and
        # consumed by the next real update — the reference's calls mutate
        # grads once per invocation, not forever after.
        self._clip_norm_once: Optional[float] = None
        self._clip_value_once: Optional[float] = None
        self._step_count = 0
        # Health-guard observables: the post-value-clip norm the clip logic
        # used, and the PRE-clip norm (non-finite <=> the update was gated to
        # a zero delta in-program).  Device scalars — reading them is a sync,
        # so only HealthGuard.check() (or the user) ever floats them.
        self._last_grad_norm = None
        self._last_health_norm = None
        # Checkpoint-manifest record of the carried opt-state layout; the
        # ZeRO fused step (pipeline/train_step.py) flips it to its sharded
        # descriptor when it re-places the state.
        self._opt_state_layout = {"kind": "replicated", "axes": [], "degree": 1}
        if model is not None:
            self._init_state()

    def _init_state(self):
        if self._host_offload_requested:
            # fsdp_plugin.cpu_offload / DeepSpeed offload_optimizer: optimizer
            # state lives in pinned host memory between steps and rides
            # explicit transfers inside the update program.
            from .parallel.host_offload import host_memory_kind, host_offload

            if host_memory_kind() is None:
                import warnings

                warnings.warn(
                    "cpu_offload requested but this backend exposes no host "
                    "memory space; optimizer state stays in device memory."
                )
                self._host_offload_requested = False
            else:
                self.tx = host_offload(self.tx)
        self.opt_state = self.tx.init(self.model.params)
        self._build_update_fn()

    def _norm_ndp(self) -> Optional[int]:
        """Static dp-chunking degree for the canonical global norm — set on
        any mesh with active data-parallel axes so the eager update, the
        fused step and the ZeRO fused step all reduce in the same association
        (see ``_update_body``); None on dp=1 meshes keeps the legacy path."""
        mesh = getattr(self.accelerator_state, "mesh", None)
        if mesh is None:
            return None
        from .parallel.zero import supported, zero_degree

        if not supported(mesh)[0]:
            # Model-axis meshes keep the legacy norm (ZeRO can't run there,
            # and the chunked reshape would fight fsdp/tp layouts).
            return None
        ndp = zero_degree(mesh)
        return ndp if ndp > 1 else None

    def _build_update_fn(self):
        body = partial(_update_body, self.tx.update, norm_ndp=self._norm_ndp())
        if self._host_offload_requested:
            if jax.default_backend() == "tpu":
                # The carried state must come back in host memory: pin the out
                # shardings so the donated pinned_host buffers are reused
                # instead of clashing with default device-placed outputs.
                opt_sh = jax.tree_util.tree_map(
                    lambda x: x.sharding if isinstance(x, jax.Array) else None,
                    self.opt_state,
                )
                self._update_fn = jax.jit(
                    body,
                    donate_argnums=(0, 1),
                    out_shardings=(None, opt_sh, None, None),
                )
            else:
                # CPU smoke path: the backend cannot execute D2H placement
                # inside jit (the state silently returns in device memory —
                # numerics identical); donating the pinned_host input against
                # a device-kind output would crash, so no donation here.
                self._update_fn = jax.jit(body)
        else:
            # Same donation contract as the legacy module-level _update_step
            # (params + opt state); per-optimizer so the static norm_ndp and
            # this optimizer's tx ride the closure.
            self._update_fn = jax.jit(body, donate_argnums=(0, 1))

    # -- torch-optimizer-shaped surface -------------------------------------

    @property
    def param_groups(self):
        if self.torch_optimizer is not None:
            return self.torch_optimizer.param_groups
        return [{"lr": self.learning_rate}]

    @property
    def learning_rate(self) -> Optional[float]:
        if self.opt_state is not None and hasattr(self.opt_state, "hyperparams"):
            lr = self.opt_state.hyperparams.get("learning_rate")
            return float(lr) if lr is not None else self.initial_lr
        return self.initial_lr

    def set_learning_rate(self, lr: float):
        if self.opt_state is not None and hasattr(self.opt_state, "hyperparams"):
            self.opt_state.hyperparams["learning_rate"] = jnp.asarray(lr, jnp.float32)
        # Keep the torch-visible surface consistent: user code (and the
        # reference's checkpoint-resume asserts) reads the lr back through
        # ``optimizer.param_groups[0]["lr"]``, which lives on the shadow torch
        # optimizer — a torch scheduler's load_state_dict does NOT write it.
        # ONLY when the groups share one lr: per-group schedules are advanced
        # by the torch scheduler's own step(), and overwriting distinct group
        # lrs with lr[0] would collapse them onto group 0's schedule.
        if self.torch_optimizer is not None:
            groups = self.torch_optimizer.param_groups
            if len({float(g["lr"]) for g in groups}) <= 1:
                for group in groups:
                    group["lr"] = lr

    def zero_grad(self, set_to_none: bool = True):
        """Clear accumulated gradients — only when a sync step just happened
        (reference ``optimizer.py:112``: no-op during accumulation)."""
        if self.gradient_state.sync_gradients and self.model is not None:
            self.model._clear_grads()

    def step(self, closure=None):
        if not self.gradient_state.sync_gradients:
            self._step_was_skipped = True
            return
        if self.model is None or self.model._accum_grads is None:
            self._step_was_skipped = True
            return
        with _span("optimizer.step"):
            self._apply_update()
        # A completed step is the telemetry heartbeat: step-time histogram,
        # tokens/sec + MFU gauges, HBM gauges, stall-watchdog beat.
        _get_telemetry().record_step()

    def _apply_update(self):
        _get_telemetry().count_dispatch()  # jitted optax update program
        grads = self.model._consume_grads()
        from .resilience import faultinject

        if faultinject.nan_armed():
            poison = faultinject.grad_poison_scale(self._step_count + 1)
            if poison is not None:
                grads = jax.tree_util.tree_map(lambda g: g * poison, grads)
        clip_norm = self._clip_norm if self._clip_norm_once is None else self._clip_norm_once
        clip_value = self._clip_value if self._clip_value_once is None else self._clip_value_once
        self._clip_norm_once = None
        self._clip_value_once = None
        if self._update_fn is None and self.tx is not None:
            # Rebuilt lazily after unpickle (the jitted closure doesn't pickle).
            self._build_update_fn()
        if self._update_fn is not None:
            new_params, self.opt_state, gnorm, health_norm = self._update_fn(
                self.model.params,
                self.opt_state,
                grads,
                jnp.asarray(clip_norm, jnp.float32),
                jnp.asarray(clip_value, jnp.float32),
            )
        else:
            new_params, self.opt_state, gnorm, health_norm = _update_step(
                self.tx.update,
                self.model.params,
                self.opt_state,
                grads,
                jnp.asarray(clip_norm, jnp.float32),
                jnp.asarray(clip_value, jnp.float32),
            )
        self.model._set_params(new_params)
        self._last_grad_norm = gnorm
        self._last_health_norm = health_norm
        self._step_was_skipped = False
        self._step_count += 1
        if self.torch_optimizer is not None:
            # Keep the shadow's step bookkeeping in sync: torch LR schedulers
            # warn "scheduler.step() before optimizer.step()" otherwise (the
            # optax path never calls the shadow's step()).  Current torch
            # checks _opt_called; older versions compared _step_count.
            self.torch_optimizer._opt_called = True
            self.torch_optimizer._step_count = getattr(self.torch_optimizer, "_step_count", 0) + 1

    @property
    def step_was_skipped(self) -> bool:
        """Parity: reference ``optimizer_step_was_skipped`` (``accelerator.py:3764``)."""
        return self._step_was_skipped

    # Pickling (reference tests/test_optimizer.py:26): the optax transform is
    # a closure (unpicklable) and the model holds compiled steps — both drop;
    # the transform rebuilds from the picklable shadow torch optimizer, and
    # the model re-pairs at the next prepare() (same contract as Accelerator).
    def __getstate__(self):
        state = {
            k: v
            for k, v in self.__dict__.items()
            if k not in ("tx", "model", "_update_fn")
        }
        # Jitted update (a closure over tx.update) is unpicklable; it rebuilds
        # lazily in _apply_update after the next prepare() re-pairs a model.
        state["_update_fn"] = None
        state["opt_state"] = jax.device_get(self.opt_state) if self.opt_state is not None else None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self.model = None
        if self.torch_optimizer is not None:
            from .utils.torch_bridge import convert_optimizer

            self.tx, _ = convert_optimizer(self.torch_optimizer)
        else:
            self.tx = None

    def state_dict(self) -> dict:
        return {
            "opt_state": jax.device_get(self.opt_state),
            "step_count": self._step_count,
            "initial_lr": self.initial_lr,
        }

    def load_state_dict(self, state_dict: dict):
        target = self.opt_state
        loaded = state_dict["opt_state"]
        # Restore with the live opt-state's shardings — but ONLY where the
        # live leaf is meaningfully placed (spans >1 device, or lives in a
        # non-default memory space like pinned_host).  A fresh ``tx.init``
        # leaves scalar leaves (optax's ``count``) as UNCOMMITTED
        # single-device arrays whose placement the next update's jit resolves
        # against the params; ``device_put``-committing them to the init
        # device pins them to device 0 and a resumed run on a multi-device
        # mesh then fails jit placement ("incompatible devices") on its very
        # first step.
        flat_t, treedef = jax.tree_util.tree_flatten(target)
        flat_l = jax.tree_util.tree_leaves(loaded)
        placed = []
        for t, l in zip(flat_t, flat_l):
            sharding = getattr(t, "sharding", None) if isinstance(t, jax.Array) else None
            pinned = False
            if sharding is not None and getattr(sharding, "memory_kind", None) is not None:
                try:
                    default_kind = next(iter(sharding.device_set)).default_memory().kind
                except Exception:
                    default_kind = None
                pinned = default_kind is not None and sharding.memory_kind != default_kind
            if sharding is not None and (len(sharding.device_set) > 1 or pinned):
                placed.append(jax.device_put(jnp.asarray(l), sharding))
            else:
                placed.append(l)
        self.opt_state = jax.tree_util.tree_unflatten(treedef, placed)
        self._step_count = state_dict.get("step_count", 0)

    def __repr__(self):
        return f"AcceleratedOptimizer({self.tx.__class__.__name__}, lr={self.learning_rate})"
