"""Fused train step: ONE jitted, buffer-donated dispatch per optimizer step.

The eager hot loop pays three Python→XLA dispatch sites per micro-batch
(the fused forward+backward jit, the host-side gradient scale/accumulate,
and the jitted optax update at the window boundary) — ``3 × accum_steps``
dispatches per optimizer step, with the device idling on host work between
each.  ``accelerator.make_train_step(model, optimizer)`` collapses the whole
window into one compiled program:

- forward + backward for every micro-batch (``lax.scan`` over the stacked
  micro-batch window when ``gradient_accumulation_steps > 1``),
- gradient accumulation (same ``g * (1/accum)`` scaling and addition order
  as the eager ``backward()`` path, so numerics are bit-exact),
- optional value/global-norm clipping and the optax update — literally the
  eager path's ``_update_body``, traced into the same program.

Params and optimizer state are donated, so the update is in-place in device
memory and the gradient window never materializes on the host.

``zero=True`` (or ``ACCELERATE_TPU_ZERO=1``) swaps the window's gradient
engine for the ZeRO cross-replica sharded update (``parallel/zero.py``):
per-device forward+backward under a manual dp region, per-leaf
reduce-scatter instead of the monolithic gradient all-reduce, the clip +
optax update on the local shard (opt state lives dp-sharded in HBM between
steps), and one params all-gather per window — still a single dispatch, and
bit-exact with the unsharded step on power-of-two dp degrees.

Pipeline parallelism composes the same way: on a pp mesh the prepared
model's forward IS the compiled pipeline scan (the torch-bridge pipelined
lowering, or ``parallel.pipeline.pipeline_llama_model`` for the native
flagship path), so the fused step wraps the whole microbatch schedule —
gpipe or interleaved — plus backward, clipping, the health gate and the
optax update in ONE donated dispatch per optimizer step.  ``pp_active`` /
``pp_degree`` record that the built program pipelines (the observability
twin of ``zero_active``); ZeRO requests on a pp mesh keep their existing
warning-fallback (``zero.supported`` declines model axes).
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp

from ..telemetry import get_telemetry as _get_telemetry
from ..telemetry import span as _span

__all__ = ["TrainStep", "make_train_step"]


def _as_args_kwargs(batch):
    """One micro-batch → the (args, kwargs) the prepared model is called with:
    mappings become keyword arguments (the ``model(**batch)`` shape), tuples
    positional, anything else a single positional argument.  (An accumulation
    WINDOW is a ``list`` — only lists are unpacked by ``__call__``, so a
    tuple micro-batch is never mistaken for a window.)"""
    if isinstance(batch, Mapping):
        return (), dict(batch)
    if isinstance(batch, tuple):
        return batch, {}
    return (batch,), {}


class TrainStep:
    """Callable returned by :meth:`Accelerator.make_train_step`.

    ``step_fn(batch)`` runs one full optimizer step from one micro-batch
    (``accum_steps == 1``); ``step_fn([b1, ..., bN])`` (or ``step_fn(b1, ...,
    bN)``) runs the whole N-micro-batch accumulation window in the same single
    dispatch.  Returns the micro-batch loss (scalar when ``accum_steps == 1``,
    else the per-micro-batch loss vector) — bit-exact with the eager
    ``model(...)`` / ``backward()`` / ``optimizer.step()`` sequence.

    The wrapped model/optimizer stay the source of truth: parameters and
    optimizer state are read from them at every call and written back after,
    so checkpointing (``save_state``/``load_state``/``resume_from_latest``),
    LR scheduling and ``check_preemption()`` step boundaries keep working
    unchanged around the fused loop.
    """

    def __init__(
        self,
        accelerator,
        model,
        optimizer,
        accum_steps: Optional[int] = None,
        clip_norm: Optional[float] = None,
        clip_value: Optional[float] = None,
        zero=None,
    ):
        from ..accelerator import PreparedModel
        from ..optimizer import AcceleratedOptimizer

        if not isinstance(model, PreparedModel):
            raise TypeError(
                "make_train_step needs the PreparedModel returned by prepare(); "
                f"got {type(model).__name__}"
            )
        if not isinstance(optimizer, AcceleratedOptimizer):
            raise TypeError(
                "make_train_step needs the AcceleratedOptimizer returned by "
                f"prepare(); got {type(optimizer).__name__}"
            )
        if optimizer.model is not model:
            raise ValueError(
                "optimizer is not paired with this model — prepare them together "
                "(the optax state is built from the model's sharded params)"
            )
        self.accelerator = accelerator
        self.model = model
        self.optimizer = optimizer
        self.accum_steps = int(
            accum_steps
            if accum_steps is not None
            else accelerator.gradient_accumulation_steps
        )
        if self.accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {self.accum_steps}")
        # Persistent clips for this step fn; None defers to the optimizer's
        # (dialect-configured) persistent clips.  One-shot arms from
        # ``accelerator.clip_grad_{norm,value}_`` still win for one call.
        self.clip_norm = clip_norm
        self.clip_value = clip_value
        self.last_grad_norm = None
        # Pre-clip global grad norm of the last call, forced non-finite when
        # the in-program health gate skipped the update (loss or grads went
        # NaN/Inf) — what HealthGuard.check() reads.  Device scalar; floating
        # it is the caller's sync.
        self.last_health_norm = None
        self.step_count = 0
        # Python-side dispatch tally (telemetry-independent; the
        # ``pipeline.dispatches`` counter is the observable twin).
        self.dispatch_count = 0
        self._jit = None
        self._introspect_pending = True
        self._poison_armed = False  # resolved at trace time in _build_jit
        # ZeRO sharded weight update (parallel/zero.py): resolved here (arg >
        # ACCELERATE_TPU_ZERO env), eligibility-checked against the mesh at
        # _build_jit.  ``zero_active`` is the observable truth of which
        # program was built.
        from ..parallel.zero import ZeROConfig

        self.zero_config = ZeROConfig.resolve(zero)
        self.zero_active = False
        # pp observability: a fused step built on a pp mesh runs the whole
        # pipeline schedule (microbatch scan + backward + update) inside its
        # one dispatch.  The schedule itself lives in the prepared model's
        # forward; these fields are the perf gate's / bench's truth of what
        # was built (the zero_active pattern).
        mesh = getattr(accelerator, "mesh", None)
        self.pp_degree = int(dict(mesh.shape).get("pp", 1)) if mesh is not None else 1
        self.pp_active = self.pp_degree > 1

    # -- program construction -------------------------------------------------

    def _resolve_zero(self):
        """Eligibility-check the requested ZeRO config against the live mesh;
        arms ``zero_active`` and (on TPU) the overlap scheduler flags."""
        from ..parallel import zero as zero_mod

        if not self.zero_config.enabled:
            return
        ok, reason = zero_mod.supported(self.accelerator.mesh)
        if not ok:
            import warnings

            warnings.warn(
                f"ZeRO sharded update requested but unsupported here: {reason}. "
                "Falling back to the replicated fused update."
            )
            return
        self.zero_active = True
        if self.zero_config.overlap_effective:
            zero_mod.enable_overlap_flags()

    def _build_jit(self):
        if self._jit is not None:
            return
        from ..optimizer import _update_body
        from ..parallel import zero as zero_mod
        from ..resilience import faultinject

        self._resolve_zero()
        model = self.model
        mesh = self.accelerator.mesh
        tx_update = self.optimizer.tx.update
        accum = self.accum_steps
        scale = 1.0 / accum
        # Canonical-norm chunking degree: set on any ZeRO-capable mesh so
        # eager / fused / fused+ZeRO clip with the same reduction association
        # (optimizer._update_body) — ZeRO on or off.  Meshes with active
        # model axes keep the legacy norm: ZeRO can't run there, and chunked
        # reshapes of fsdp/tp-sharded gradients would invite resharding.
        ndp = zero_mod.zero_degree(mesh) if zero_mod.supported(mesh)[0] else 1
        norm_ndp = ndp if ndp > 1 else None
        # Trace-time fork: only a NaN-fault-armed process carries the poison
        # scalar in its program signature — production programs are untouched.
        # Either way the window stays ONE dispatch (the health-smoke proof).
        poison_armed = self._poison_armed = faultinject.nan_armed()
        # DDP comm-hook parity: the eager path casts each scaled micro-grad
        # to the sync dtype (bf16 under fp16/bf16 hooks) before accumulating
        # (PreparedModel._accumulate); the fused window must do the same or
        # switching to make_train_step silently changes numerics.
        sync_dtype = model._grad_sync_dtype

        def _scaled(g):
            s = g * scale
            if sync_dtype is not None and jnp.issubdtype(s.dtype, jnp.floating):
                s = s.astype(sync_dtype)
            return s

        def _loss_and_grads(params, batch):
            args, kwargs = batch

            def lossf(p):
                out = model._forward(p, args, kwargs)
                loss = out["loss"] if isinstance(out, dict) else out[0]
                return jnp.asarray(loss, jnp.float32).mean()

            with jax.named_scope("loss_grad"):
                return jax.value_and_grad(lossf)(params)

        if self.zero_active:
            grads_and_losses = self._build_zero_grads_fn(_loss_and_grads, _scaled)
            # Where the updated param shards gather back to: each leaf's live
            # sharding (replicated over dp on a pure-dp mesh).
            from jax.sharding import NamedSharding, PartitionSpec

            gather_sh = jax.tree_util.tree_map(
                lambda p: p.sharding
                if isinstance(p, jax.Array) and isinstance(p.sharding, NamedSharding)
                else NamedSharding(mesh, PartitionSpec()),
                model.params,
            )
        else:
            grads_and_losses = None
            gather_sh = None

        def step(params, opt_state, batches, clip_norm, clip_value, *fault):
            if grads_and_losses is not None:
                # ZeRO: per-device fwd/bwd + per-leaf reduce-scatter inside a
                # manual dp region; grads come back dp-SHARDED and the update
                # below runs on the local shard only.
                grads, losses = grads_and_losses(params, batches)
            elif accum == 1:
                loss, grads = _loss_and_grads(params, batches[0])
                # Eager parity: backward() accumulates grads * (1/accum) —
                # at accum == 1 the scale is exactly 1.0 (a no-op multiply).
                grads = jax.tree_util.tree_map(_scaled, grads)
                losses = loss
            else:
                stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)

                def body(acc, micro):
                    loss, grads = _loss_and_grads(params, micro)
                    # Same op order as the eager accumulation buffer:
                    # scale (and sync-dtype-cast) each micro-grad, then add
                    # (0 + g*s == g*s bitwise, so the zeros init matches
                    # "first assign").
                    acc = jax.tree_util.tree_map(
                        lambda a, g: a + _scaled(g), acc, grads
                    )
                    return acc, loss

                def _zeros_like_accum(p):
                    dtype = p.dtype
                    if sync_dtype is not None and jnp.issubdtype(dtype, jnp.floating):
                        dtype = sync_dtype
                    return jnp.zeros(jnp.shape(p), dtype)

                zeros = jax.tree_util.tree_map(_zeros_like_accum, params)
                grads, losses = jax.lax.scan(body, zeros, stacked)
            if poison_armed:
                # In-program fault injection: grads *= grad_scale (1.0 or NaN)
                # rides the existing dispatch instead of adding one.
                grads = jax.tree_util.tree_map(lambda g: g * fault[0], grads)
            # Health gate: the update must also zero out when any micro-loss
            # went non-finite — grads usually follow the loss, but an Inf loss
            # with (pathologically) finite grads must not slip an update in.
            losses_ok = jnp.all(jnp.isfinite(jnp.asarray(losses)))
            new_params, new_opt_state, gnorm, health_norm = _update_body(
                tx_update, params, opt_state, grads, clip_norm, clip_value,
                health_ok=losses_ok, norm_ndp=norm_ndp,
            )
            if grads_and_losses is not None:
                # All-gather: the dp-sharded updated params return to each
                # replica's layout for the next forward (the param-bytes
                # all-gather of the ZeRO ledger signature).
                new_params = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, new_params, gather_sh
                )
            return new_params, new_opt_state, losses, gnorm, health_norm

        donate = (0, 1)
        out_shardings = None
        if self.zero_active:
            # Re-place the live opt state onto its dp shards (host-offloaded
            # leaves keep their pinned-host kind: shard *then* offload), and
            # pin the carried-state outputs there via out_shardings.
            opt = self.optimizer
            opt.opt_state, _ = zero_mod.shard_opt_state(opt.opt_state, mesh)
            opt_sh = zero_mod.opt_state_shardings(opt.opt_state, mesh)
            # Donate params ONLY.  Donating params AND opt state together into
            # the shard_map program segfaulted the XLA CPU runtime after a few
            # steps on the jaxlib this was written against (0.4.x).  On the
            # installed 0.9.0 it does not reproduce (PR 21: tests/test_zero.py
            # and a 40-step soak on eight virtual CPU devices, both donated);
            # the restriction stays until the wider donation has run on a chip.
            # The un-donated opt-state copy is dp-fold smaller under ZeRO than
            # the replicated state it replaces, so the transient costs less
            # HBM than the feature saves.
            donate = (0,)
            param_sh = jax.tree_util.tree_map(
                lambda x: x.sharding
                if isinstance(x, jax.Array)
                and isinstance(getattr(x, "sharding", None), jax.sharding.NamedSharding)
                else None,
                model.params,
            )
            out_shardings = (param_sh, opt_sh, None, None, None)
        elif self.optimizer._host_offload_requested:
            if jax.default_backend() == "tpu":
                # Pinned-host opt state must come back pinned (same contract
                # as the eager update, optimizer.py:_init_state).
                opt_sh = jax.tree_util.tree_map(
                    lambda x: x.sharding if isinstance(x, jax.Array) else None,
                    self.optimizer.opt_state,
                )
                out_shardings = (None, opt_sh, None, None, None)
            else:
                # CPU smoke path: donating a pinned_host input against a
                # device-kind output crashes; donate params only.
                donate = (0,)
        if out_shardings is not None:
            self._jit = jax.jit(step, donate_argnums=donate, out_shardings=out_shardings)
        else:
            self._jit = jax.jit(step, donate_argnums=donate)
        # Manifest observability: record the layout the carried opt state
        # will have from now on (checkpointing threads it into manifest.json).
        self.optimizer._opt_state_layout = zero_mod.opt_state_layout(
            mesh, self.zero_active
        )
        # HBM ledger: the train state's long-lived reservations, computed
        # from the live trees' per-device sharded bytes AFTER ZeRO placement
        # (so the sharded opt state charges each chip its shard, and
        # host-offloaded moments land under host_bytes, not HBM).  The
        # ledger stores integers only — no reference survives to fight the
        # donated-buffer lifetimes.
        try:
            from ..telemetry.memledger import get_memory_ledger

            ledger = get_memory_ledger()
            ledger.register(
                "train.params",
                tree=self.model.params,
                detail={"zero_active": self.zero_active},
            )
            ledger.register(
                "train.opt_state",
                tree=self.optimizer.opt_state,
                detail={"zero_active": self.zero_active},
            )
        except Exception:
            pass

    def _build_zero_grads_fn(self, _loss_and_grads, _scaled):
        """Build the manual-dp gradient engine of the ZeRO step: a shard_map
        over the whole mesh in which each device runs forward+backward on its
        LOCAL micro-batch shard, ``psum_scatter``s every gradient leaf over
        the dp axes (the reduce-scatter — emitted per leaf, so the XLA
        latency-hiding scheduler can overlap each leaf's collective with the
        remaining backward), and accumulates accum windows on the local shard
        (one reduce-scatter per micro keeps the replica-sum-then-micro-sum
        association of the eager/fused paths — bit-exactness over comms
        volume; the scatter is still half an all-reduce per micro and the
        gather happens once per window).

        Returns ``grads_and_losses(params, batches) -> (shard_grads, losses)``
        where ``shard_grads`` is the dp-sharded global gradient tree and
        ``losses`` matches the unsharded step's shape (scalar, or [accum]).
        """
        from jax.sharding import PartitionSpec as P

        from ..parallel import zero as zero_mod
        from ..parallel.sharding import manual_region

        mesh = self.accelerator.mesh
        model = self.model
        accum = self.accum_steps
        axes = zero_mod.zero_axes(mesh)
        degree = zero_mod.zero_degree(mesh)
        psum_axes = axes if len(axes) > 1 else axes[0]
        axis_entry = axes if len(axes) > 1 else axes[0]
        # 1/degree un-scales the per-lane loss seed (each lane differentiates
        # its LOCAL mean; the global mean is the lane-mean mean).  Exactly a
        # power of two on pow2 dp degrees — where the ZeRO step is bit-exact
        # against the unsharded one (docs/usage_guides/performance.md).
        lane_scale = 1.0 / degree
        params = model.params
        pspecs = jax.tree_util.tree_map(
            lambda p: zero_mod.shard_spec(tuple(jnp.shape(p)), axes, degree), params
        )

        def batch_spec(leaf):
            # Batch leaves are batch-major (dim 0) by the loader contract
            # (_GlobalBatchPlacer shards dim 0 of every ndim>=1 leaf).  A
            # non-divisible or scalar leaf stays replicated: every lane sees
            # the full value — identical math, no silent slicing.
            if hasattr(leaf, "ndim") and leaf.ndim >= 1 and leaf.shape[0] % degree == 0 and leaf.shape[0] > 0:
                return P(*((axis_entry,) + (None,) * (leaf.ndim - 1)))
            return P()

        def scatter(g):
            d = zero_mod.shard_dim(tuple(g.shape), degree)
            if d is None:
                # Unshardable leaf (no dim divisible by the dp degree): plain
                # psum — it stays replicated, and its update is replicated
                # too (same rule the norm chunking and opt-state placement
                # use, so all three agree).
                return jax.lax.psum(g, psum_axes)
            return jax.lax.psum_scatter(g, psum_axes, scatter_dimension=d, tiled=True)

        def one_micro(p, batch):
            # Per-device: fwd+bwd on the local lane, then the per-leaf
            # reduce-scatter, then the exact-pow2 lane unscale — giving each
            # device precisely the replica-summed global-mean gradient SHARD
            # the unsharded path's all-reduce would have given it in full.
            loss, grads = _loss_and_grads(p, batch)
            shards = jax.tree_util.tree_map(scatter, grads)
            shards = jax.tree_util.tree_map(lambda g: g * lane_scale, shards)
            return shards, loss

        def wrapped(p, *micros):
            if accum == 1:
                shards, loss = one_micro(p, micros[0])
                shards = jax.tree_util.tree_map(_scaled, shards)
                losses = loss
            else:
                stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *micros)

                def body(acc, micro):
                    shards, loss = one_micro(p, micro)
                    # Eager-order accumulation on the SHARD: replica-sum
                    # (the scatter) first, then scale/cast, then add — the
                    # same per-element association as the unsharded window.
                    acc = jax.tree_util.tree_map(
                        lambda a, g: a + _scaled(g), acc, shards
                    )
                    return acc, loss

                sync_dtype = model._grad_sync_dtype

                def _zeros_shard(leaf):
                    dtype = leaf.dtype
                    if sync_dtype is not None and jnp.issubdtype(dtype, jnp.floating):
                        dtype = sync_dtype
                    return jnp.zeros(
                        zero_mod.shard_shape(tuple(leaf.shape), degree), dtype
                    )

                zeros = jax.tree_util.tree_map(_zeros_shard, params)
                shards, losses = jax.lax.scan(body, zeros, stacked)
            # Lane losses ride out stacked on a leading dp dim; the caller
            # means over lanes (== the global mean, bit-exactly so when the
            # per-lane element count is a power of two).
            losses = jnp.expand_dims(jnp.asarray(losses), 0)
            return shards, losses

        lane_losses_spec = (
            P(axis_entry) if accum == 1 else P(axis_entry, None)
        )

        def grads_and_losses(params, batches):
            in_specs = (
                jax.tree_util.tree_map(lambda _: P(), params),
            ) + tuple(
                jax.tree_util.tree_map(batch_spec, b) for b in batches
            )
            with manual_region():
                shards, lane_losses = jax.shard_map(
                    wrapped,
                    mesh=mesh,
                    in_specs=in_specs,
                    out_specs=(pspecs, lane_losses_spec),
                    check_vma=False,
                )(params, *batches)
            losses = jnp.mean(lane_losses, axis=0)
            if accum == 1:
                losses = jnp.squeeze(losses)
            return shards, losses

        return grads_and_losses

    def _maybe_introspect(self, jit_args):
        """First-call AOT capture of the fused program
        (``ACCELERATE_TPU_INTROSPECT=1``): cost/memory analysis, comms ledger
        and resharding lint flow through the same ``capture()`` hook the
        eager fused step uses — the one-dispatch program is observable too."""
        if not self._introspect_pending:
            return
        self._introspect_pending = False
        from ..telemetry import introspect as _introspect

        if not _introspect.enabled_from_env():
            return
        _introspect.capture(
            self._jit,
            jit_args,
            name=f"{self.model._program_label}.train_step",
            mesh=self.accelerator.mesh,
            declared_specs=self.model._param_specs,
            count_in_step=True,
        )

    # -- execution ------------------------------------------------------------

    def _window(self, batches) -> tuple:
        """Validate one call's micro-batches and normalize each to the
        ``(args, kwargs)`` the prepared model is called with."""
        from ..accelerator import _torch_to_jax_tree

        # Only a LIST unpacks as the accumulation window: a tuple is a valid
        # single micro-batch (positional model args) and must not be split
        # into per-element "micro-batches".
        if len(batches) == 1 and isinstance(batches[0], list):
            batches = tuple(batches[0])
        if len(batches) != self.accum_steps:
            raise ValueError(
                f"fused train step was built for {self.accum_steps} micro-batch"
                f"{'es' if self.accum_steps > 1 else ''} per optimizer step but "
                f"received {len(batches)} — pass the whole accumulation window "
                "in one call as a LIST of micro-batches (a tuple is treated as "
                "one positional-args micro-batch)."
            )
        return tuple(_as_args_kwargs(_torch_to_jax_tree(b)) for b in batches)

    def _jit_args(self, batches: tuple, clip_norm, clip_value) -> tuple:
        jit_args = (
            self.model.params,
            self.optimizer.opt_state,
            batches,
            jnp.asarray(clip_norm if clip_norm is not None else -1.0, jnp.float32),
            jnp.asarray(clip_value if clip_value is not None else -1.0, jnp.float32),
        )
        if self._poison_armed:
            from ..resilience import faultinject

            poison = faultinject.grad_poison_scale(self.optimizer._step_count + 1)
            jit_args = jit_args + (
                jnp.asarray(1.0 if poison is None else poison, jnp.float32),
            )
        return jit_args

    def lower(self, *batches):
        """Lower the fused program for this window without running it (the
        ``jax.jit(f).lower(...)`` idiom): ``.compile()`` the result to read
        the executable's ``as_text()`` / ``memory_analysis()`` — what kernels
        and collectives the step really holds.  Nothing is donated."""
        batches = self._window(batches)
        self._build_jit()
        return self._jit.lower(*self._jit_args(batches, None, None))

    def __call__(self, *batches):
        batches = self._window(batches)
        self._build_jit()
        opt = self.optimizer
        # Clip resolution mirrors the eager update: one-shot arms win once,
        # then this step fn's persistent clips, then the optimizer's.
        clip_norm = (
            opt._clip_norm_once
            if opt._clip_norm_once is not None
            else (self.clip_norm if self.clip_norm is not None else opt._clip_norm)
        )
        clip_value = (
            opt._clip_value_once
            if opt._clip_value_once is not None
            else (self.clip_value if self.clip_value is not None else opt._clip_value)
        )
        opt._clip_norm_once = None
        opt._clip_value_once = None
        jit_args = self._jit_args(batches, clip_norm, clip_value)
        self._maybe_introspect(jit_args)
        try:
            with _span("pipeline.train_step"):
                new_params, new_opt_state, losses, gnorm, health_norm = self._jit(*jit_args)
        except Exception as e:
            # Params/opt-state are DONATED: an execution failure (e.g.
            # RESOURCE_EXHAUSTED mid-step) may have consumed the buffers the
            # model/optimizer still reference.  Trace-time failures leave
            # them intact (donation only consumes at execution) — in that
            # case re-raise as-is and the step is safely retryable.
            leaves = jax.tree_util.tree_leaves((self.model.params, opt.opt_state))
            consumed = any(
                x.is_deleted() for x in leaves
                if isinstance(x, jax.Array) and hasattr(x, "is_deleted")
            )
            if consumed:
                raise RuntimeError(
                    "fused train step failed AFTER its donated parameter/"
                    "optimizer buffers were consumed; in-process model state "
                    "is unrecoverable. Do not retry the step (e.g. via "
                    "find_executable_batch_size) — restore from the latest "
                    "checkpoint (accelerator.resume_from_latest / load_state) "
                    "or rebuild via prepare()."
                ) from e
            raise
        # Write-back: the model/optimizer stay the source of truth for
        # checkpointing, schedulers and any interleaved eager steps.
        self.model._set_params(new_params)
        self.model._clear_grads()
        opt.opt_state = new_opt_state
        opt._last_grad_norm = gnorm
        opt._last_health_norm = health_norm
        self.last_health_norm = health_norm
        opt._step_was_skipped = False
        opt._step_count += 1
        if opt.torch_optimizer is not None:
            opt.torch_optimizer._opt_called = True
            opt.torch_optimizer._step_count = (
                getattr(opt.torch_optimizer, "_step_count", 0) + 1
            )
        # A fused call IS a sync step — schedulers gate on this flag.
        opt.gradient_state._set_sync_gradients(True)
        self.last_grad_norm = gnorm
        self.step_count += 1
        self.dispatch_count += 1
        tel = _get_telemetry()
        tel.count_dispatch()
        tel.record_step()
        return losses


def make_train_step(
    accelerator,
    model,
    optimizer,
    accum_steps: Optional[int] = None,
    clip_norm: Optional[float] = None,
    clip_value: Optional[float] = None,
    zero=None,
) -> TrainStep:
    """Build a :class:`TrainStep` (the function behind
    :meth:`Accelerator.make_train_step`).  ``zero`` opts into the
    cross-replica sharded weight update (``parallel/zero.py``): ``True`` /
    ``False`` / a :class:`~accelerate_tpu.parallel.zero.ZeROConfig`; ``None``
    defers to ``ACCELERATE_TPU_ZERO``."""
    return TrainStep(
        accelerator,
        model,
        optimizer,
        accum_steps=accum_steps,
        clip_norm=clip_norm,
        clip_value=clip_value,
        zero=zero,
    )
