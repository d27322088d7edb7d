"""The ``Accelerator`` façade — L4.

Parity target: reference ``src/accelerate/accelerator.py`` (3860 LoC): ``prepare``
(``accelerator.py:1292``), ``backward`` (2437), ``accumulate`` (1124),
``clip_grad_norm_`` (2565), ``gather_for_metrics`` (2686), ``save_state``/
``load_state`` (3191/3357), ``autocast`` (…), trigger flags (2471).

TPU-native redesign (SURVEY §7): the reference keeps the user's eager torch loop
and hides engines behind per-object wrappers; here ``prepare()`` lowers the torch
model to a pure JAX function and the imperative loop drives *compiled* steps:

- ``model(**batch)`` with labels → ONE jitted fused forward+backward
  (``value_and_grad``); gradients are stashed, outputs returned lazily.
- ``model(x)`` + external torch criterion → outputs are torch tensors wired into
  torch.autograd via a bridge Function whose backward calls a jitted JAX vjp —
  user-land torch ops differentiate in torch, the model differentiates in XLA.
- ``backward(loss)`` accumulates gradients (scaled 1/accum_steps,
  reference ``accelerator.py:2459``); ``optimizer.step()`` applies the optax
  update when ``sync_gradients`` — observable semantics identical to the
  reference's no_sync/accumulate contract.
- Data-parallel reduction is not an explicit collective anywhere: batches are
  global arrays over the mesh, so XLA emits the reduction inside the step.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import os
import warnings
from typing import Any, Callable, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp

from .data_loader import DataLoaderDispatcher, DataLoaderShard, prepare_data_loader, skip_first_batches
from .optimizer import AcceleratedOptimizer
from .scheduler import AcceleratedScheduler
from .state import AcceleratorState, GradientState
from .telemetry import get_telemetry as _get_telemetry
from .telemetry import maybe_enable_from_env as _telemetry_from_env
from .telemetry import span as _span
from .utils.dataclasses import (
    DataLoaderConfiguration,
    DistributedType,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    KwargsHandler,
    ParallelismConfig,
    ProfileKwargs,
    ProjectConfiguration,
    RNGType,
)
from .utils.imports import is_torch_available
from .utils.operations import (
    convert_to_fp32,
    gather,
    gather_object,
    pad_across_processes,
    recursively_apply,
    reduce,
    to_jax,
    to_numpy,
)

__all__ = ["Accelerator", "JaxModel", "PreparedModel"]


class JaxModel:
    """Native-JAX model handle for ``prepare()``: a pure ``apply(params, *args,
    **kwargs)`` plus its params pytree (and optional partition rules)."""

    def __init__(self, apply_fn: Callable, params: Any, partition_rules=None, buffers: Any = None):
        self.apply_fn = apply_fn
        self.params = params
        self.buffers = buffers if buffers is not None else {}
        self.partition_rules = partition_rules


class _LazyOutputs:
    """Model outputs materialized to torch lazily, field by field (keeps logits on
    device unless the user actually reads them)."""

    def __init__(self, tree: Any, model: "PreparedModel"):
        object.__setattr__(self, "_tree", tree)
        object.__setattr__(self, "_model", model)
        object.__setattr__(self, "_cache", {})

    def _materialize(self, key, value):
        cache = object.__getattribute__(self, "_cache")
        if key not in cache:
            cache[key] = _jax_to_torch(value)
            model = object.__getattribute__(self, "_model")
            if key in ("loss", 0) and model is not None:
                model._tag_loss(cache[key])
        return cache[key]

    def __getattr__(self, name):
        tree = object.__getattribute__(self, "_tree")
        if isinstance(tree, dict) and name in tree:
            return self._materialize(name, tree[name])
        raise AttributeError(name)

    def __getitem__(self, key):
        tree = object.__getattribute__(self, "_tree")
        if isinstance(tree, dict):
            if isinstance(key, int):
                key = list(tree.keys())[key]
            return self._materialize(key, tree[key])
        return self._materialize(key, tree[key])

    def keys(self):
        tree = object.__getattribute__(self, "_tree")
        return tree.keys() if isinstance(tree, dict) else range(len(tree))

    def to_tuple(self):
        return tuple(self[k] for k in self.keys())

    def __repr__(self):
        tree = object.__getattribute__(self, "_tree")
        keys = list(tree.keys()) if isinstance(tree, dict) else f"tuple[{len(tree)}]"
        return f"_LazyOutputs({keys})"


def _local_numpy(x: jax.Array) -> np.ndarray:
    """Host copy of the PROCESS-LOCAL portion of a jax.Array.

    Fully-addressable arrays fetch whole.  Multi-process global arrays
    cannot be fetched (jax raises); each process instead assembles its own
    addressable shards — DDP semantics: rank-local batch rows in, rank-local
    outputs back.  Replicated copies dedup by slice; a single varying axis
    (the batch/data dim) concatenates in index order, which is also the
    layout ``jax.make_array_from_process_local_data`` expects when the
    backward rebuilds the global cotangent."""
    if x.is_fully_addressable:
        return np.asarray(jax.device_get(x))
    seen: dict = {}
    for sh in x.addressable_shards:
        key = tuple((sl.start or 0, sl.stop) for sl in sh.index)
        seen.setdefault(key, np.asarray(sh.data))
    if len(seen) == 1:
        return next(iter(seen.values()))
    keys = sorted(seen)
    varying = [i for i in range(len(keys[0])) if len({k[i] for k in keys}) > 1]
    if len(varying) != 1:
        raise NotImplementedError(
            "process-local assembly of an array sharded on multiple axes "
            f"({varying}) is not supported on the torch-bridge boundary"
        )
    return np.concatenate([seen[k] for k in keys], axis=varying[0])


def _jax_to_torch(x):
    if not isinstance(x, jax.Array):
        return x
    import torch

    arr = _local_numpy(x)
    if not arr.flags.writeable:
        # torch.from_numpy on a read-only view warns (and writing through the
        # tensor would be UB); jax.device_get returns read-only arrays.
        arr = arr.copy()
    return torch.from_numpy(arr)


def _torch_to_jax_tree(tree):
    return recursively_apply(to_jax, tree)


class PreparedModel:
    """The object ``prepare(model)`` hands back: callable like the torch module,
    backed by sharded params + jitted JAX execution."""

    def __init__(
        self,
        apply_fn: Callable,
        params: Any,
        buffers: Any,
        accelerator: "Accelerator",
        original_module=None,
    ):
        self._apply_fn = apply_fn
        self.params = params
        self.buffers = buffers
        self.accelerator = accelerator
        self.module = original_module
        self.training = True
        self._accum_grads = None
        self._pending = None  # (loss_jax, grads) from the latest fused call
        self._tagged_losses: dict[int, Any] = {}
        self._mode: Optional[str] = None  # "fused" | "bridge", decided on first call
        policy = accelerator.state.dtype_policy
        self._compute_dtype = jnp.dtype(policy.compute_dtype) if policy.compute_dtype else None
        self._fp8_recipe = policy.fp8_recipe if policy.fp8 else None
        # DDP comm-hook analog: fp16/bf16 hooks compress the cross-replica
        # gradient traffic; here the accumulated/synced gradient pytree is held
        # in that dtype (bf16 on TPU for both — fp16 grads overflow without a
        # scaler and bf16 is the hardware-native reduced type).
        ddp = getattr(accelerator, "ddp_handler", None)
        self._grad_sync_dtype = (
            jnp.bfloat16 if ddp is not None and ddp.comm_hook in ("fp16", "bf16") else None
        )
        self._jit_fused = None
        self._jit_fwd = None
        self._jit_vjp = None
        # PartitionSpec tree prepare_model declared for self.params — the
        # "what was intended" side of the resharding lint.
        self._param_specs = None
        self._introspect_pending = True
        self._introspect_modes = None  # captured-program keys once enabled
        # Telemetry program label; prepare_model makes it unique per model so
        # two prepared models don't overwrite each other's introspection
        # report or measured-FLOPs entry (both are keyed by name).
        self._program_label = "model"

    # -- torch-like mode switches -------------------------------------------

    def train(self, mode: bool = True):
        self.training = mode
        return self

    def eval(self):
        return self.train(False)

    def parameters(self):
        return jax.tree_util.tree_leaves(self.params)

    def num_parameters(self) -> int:
        return int(sum(np.prod(np.shape(p)) for p in self.parameters()))

    # -- internals -----------------------------------------------------------

    def _cast(self, tree):
        if self._compute_dtype is None or self._compute_dtype == jnp.float32:
            return tree
        return jax.tree_util.tree_map(
            lambda x: x.astype(self._compute_dtype)
            if isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jnp.floating)
            else x,
            tree,
        )

    def _forward(self, params, args, kwargs):
        if self._fp8_recipe is not None:
            # Read at trace time: the compiled step bakes in fp8 matmuls.
            from .ops.fp8 import fp8_autowrap

            with fp8_autowrap(self._fp8_recipe):
                out = self._apply_fn(self._cast(params), self.buffers, *args, **kwargs)
        else:
            out = self._apply_fn(self._cast(params), self.buffers, *args, **kwargs)
        return convert_to_fp32(out) if self._compute_dtype not in (None, jnp.float32) else out

    def _build_jits(self):
        if self._jit_fused is None:

            @jax.jit
            def fused(params, args, kwargs):
                def lossf(p):
                    out = self._forward(p, args, kwargs)
                    loss = out["loss"] if isinstance(out, dict) else out[0]
                    return jnp.asarray(loss, jnp.float32).mean(), out

                (loss, out), grads = jax.value_and_grad(lossf, has_aux=True)(params)
                return loss, out, grads

            @jax.jit
            def fwd(params, args, kwargs):
                return self._forward(params, args, kwargs)

            @jax.jit
            def vjp_params(params, args, kwargs, cotangents):
                _, pullback = jax.vjp(lambda p: self._forward(p, args, kwargs), params)
                return pullback(cotangents)[0]

            self._jit_fused, self._jit_fwd, self._jit_vjp = fused, fwd, vjp_params

    def _pick_mode(self, args, kwargs) -> str:
        """Fused when the model's output structure contains a scalar loss leaf
        (dict['loss'] or scalar first tuple element); bridge otherwise."""
        out_shape = jax.eval_shape(lambda p: self._forward(p, args, kwargs), self.params)
        if isinstance(out_shape, dict) and "loss" in out_shape:
            return "fused"
        if isinstance(out_shape, (tuple, list)) and len(out_shape) and out_shape[0].shape == ():
            return "fused"
        return "bridge"

    def _maybe_introspect(self, args, kwargs):
        """Once-per-program AOT inspection of the compiled step this call
        will run (``ACCELERATE_TPU_INTROSPECT=1``): cost/memory analysis,
        comms ledger, resharding lint against the specs prepare_model
        declared.  Captures the fused training step and the eval forward
        independently (an eval-first warmup must not swallow the training
        step's capture).  Costs one extra AOT compile per captured program;
        with the flag unset the first call resolves the env once and every
        later call is a single attribute check — nothing is lowered."""
        if not self._introspect_pending:
            return
        from .telemetry import introspect as _introspect

        if self._introspect_modes is None:
            if not _introspect.enabled_from_env():
                self._introspect_pending = False
                return
            self._introspect_modes = set()
        fused = self.training and self._mode == "fused"
        key = "fused_step" if fused else "forward"
        if key in self._introspect_modes:
            return
        self._introspect_modes.add(key)
        _introspect.capture(
            self._jit_fused if fused else self._jit_fwd,
            (self.params, args, kwargs),
            name=f"{self._program_label}.{key}",
            mesh=self.accelerator.mesh,
            declared_specs=self._param_specs,
            # Only the fused train step runs once per optimizer step; an eval
            # forward (or bridge-mode partial) must not skew measured MFU.
            count_in_step=fused,
        )

    def __call__(self, *args, **kwargs):
        args = _torch_to_jax_tree(args)
        kwargs = _torch_to_jax_tree(kwargs)
        self._build_jits()
        if self.training and self._mode is None:
            self._mode = self._pick_mode(args, kwargs)
        self._maybe_introspect(args, kwargs)
        if self.training and self._mode == "fused":
            _get_telemetry().count_dispatch()  # eager fused fwd+bwd program
            loss, out, grads = self._jit_fused(self.params, args, kwargs)
            self._pending = (loss, grads)
            return _LazyOutputs(out if isinstance(out, (dict, tuple, list)) else {"loss": loss}, self)
        if self.training:
            return self._bridge_forward(args, kwargs)
        out = self._jit_fwd(self.params, args, kwargs)
        if isinstance(out, (dict, tuple, list)):
            return _LazyOutputs(out, None)
        return _jax_to_torch(out)

    # fused-mode bookkeeping --------------------------------------------------

    def _tag_loss(self, torch_loss):
        if self._pending is None:
            return
        key = id(torch_loss)
        entry = {"pending": self._pending, "consumed": False}
        self._tagged_losses[key] = entry
        self._pending = None
        # Make the materialized loss a DIFFERENTIABLE leaf: torch ops derived
        # from it (loss / n, loss + aux, ...) build a real autograd graph, and
        # backward() on the derived tensor delivers d(derived)/d(loss) here —
        # the chain-rule factor the jax-side grads must be scaled by.  This
        # widens fused mode to "any torch graph OF the loss scalar" (bridge
        # mode already covers graphs of the logits).  Torch-parity side effect:
        # the loss requires grad, exactly like a torch criterion's output —
        # log it with float(loss) / loss.item() / loss.detach(), not
        # np.asarray(loss).
        import torch

        if isinstance(torch_loss, torch.Tensor) and torch_loss.dtype.is_floating_point:
            torch_loss.requires_grad_(True)
            model = self

            def _route_grad(grad):
                if entry["consumed"]:
                    # Torch parity: a second backward through the same forward
                    # must not silently drop the gradient.
                    raise RuntimeError(
                        "Trying to backward through the same prepared-model forward a "
                        "second time: re-run the forward before calling backward again."
                    )
                entry["consumed"] = True
                # Release both references — the dict entry AND the pending
                # pytree held by this closure (a retained loss tensor keeps the
                # hook alive, which must not pin a model-sized grad tree).
                model._tagged_losses.pop(key, None)
                pending = entry["pending"]
                entry["pending"] = None
                if grad.numel() != 1:
                    raise RuntimeError(
                        "Fused-mode losses are scalars, so backward(gradient=...) with a "
                        f"non-scalar cotangent (shape {tuple(grad.shape)}) cannot be routed "
                        "to the jax-side gradients. Reduce the loss to a scalar before "
                        "backward, or use bridge mode for per-element cotangents."
                    )
                model._accumulate(pending[1], float(grad.reshape(())))

            torch_loss.register_hook(_route_grad)

    def _grads_for_loss(self, torch_loss):
        entry = self._tagged_losses.pop(id(torch_loss), None)
        if entry is None or entry["consumed"]:
            return None
        entry["consumed"] = True
        pending = entry["pending"]
        entry["pending"] = None  # the hook closure must not pin the grads
        return pending

    def _accumulate(self, grads, scale: float):
        _get_telemetry().count_dispatch()  # host-side gradient scale
        scaled = jax.tree_util.tree_map(lambda g: g * scale, grads)
        if self._grad_sync_dtype is not None:
            scaled = jax.tree_util.tree_map(
                lambda g: g.astype(self._grad_sync_dtype) if jnp.issubdtype(g.dtype, jnp.floating) else g,
                scaled,
            )
        if self._accum_grads is None:
            self._accum_grads = scaled
        else:
            _get_telemetry().count_dispatch()  # host-side gradient merge
            self._accum_grads = jax.tree_util.tree_map(jnp.add, self._accum_grads, scaled)

    def _consume_grads(self):
        g = self._accum_grads
        self._accum_grads = None
        return g

    def _clear_grads(self):
        self._accum_grads = None
        self._tagged_losses.clear()
        self._pending = None

    def _set_params(self, params):
        self.params = params

    # bridge mode -------------------------------------------------------------

    def _bridge_forward(self, args, kwargs):
        import torch

        model = self
        out_struct = {}

        class _Bridge(torch.autograd.Function):
            @staticmethod
            def forward(ctx, dummy):
                out = model._jit_fwd(model.params, args, kwargs)
                flat, treedef = jax.tree_util.tree_flatten(out)
                out_struct["treedef"] = treedef
                torch_out = tuple(_jax_to_torch(f) for f in flat)
                # Keep each output's sharding: on multi-process clusters the
                # torch side sees only the LOCAL rows, and the backward must
                # rebuild the GLOBAL cotangent from each process's local grad.
                # ``scaled``: True only when the torch side actually received
                # a local SLICE (data-sharded output) — those cotangents sum
                # across ranks inside the spmd vjp and carry the DDP 1/P.
                # Replicated global outputs (full copy on every rank) have no
                # cross-rank summation to cancel and must NOT be shrunk.
                out_struct["avals"] = [
                    (
                        f.shape,
                        f.dtype,
                        None if f.is_fully_addressable else f.sharding,
                        (not f.is_fully_addressable) and tuple(t.shape) != tuple(f.shape),
                    )
                    for f, t in zip(flat, torch_out)
                ]
                return torch_out

            @staticmethod
            def backward(ctx, *grad_outputs):
                def as_global(g, shape, dtype, sharding, scaled):
                    if g is None:
                        cot = jnp.zeros(shape, dtype)
                        if sharding is not None:
                            cot = jax.device_put(cot, sharding)
                        return cot
                    arr = to_numpy(g).astype(dtype)
                    if sharding is None:
                        return jnp.asarray(arr)
                    if scaled:
                        # Local rows -> global array (inverse of _local_numpy).
                        # DDP semantics: each rank computed a MEAN loss over
                        # its local rows, and ranks' gradients are AVERAGED —
                        # the spmd vjp sums contributions across the data
                        # axis, so the per-rank cotangent carries the 1/P.
                        # (Divide-then-recast: numpy promotes bf16/fp16 under
                        # true division, and the vjp needs the exact dtype.)
                        from .state import PartialState

                        arr = (arr / PartialState().num_processes).astype(dtype, copy=False)
                    return jax.make_array_from_process_local_data(sharding, arr)

                cotangents = [
                    as_global(g, s, d, sh, sc)
                    for g, (s, d, sh, sc) in zip(grad_outputs, out_struct["avals"])
                ]
                cot_tree = jax.tree_util.tree_unflatten(out_struct["treedef"], cotangents)
                grads = model._jit_vjp(model.params, args, kwargs, cot_tree)
                model._accumulate(grads, 1.0)
                return torch.zeros(())

        dummy = torch.zeros((), requires_grad=True)
        flat_out = _Bridge.apply(dummy)
        tree = jax.tree_util.tree_unflatten(
            out_struct["treedef"], list(flat_out)
        )
        return tree

    def state_dict(self) -> dict:
        """Flat numpy state dict (reference ``get_state_dict`` shape).  A
        pipelined bridged model's stacked block leaves are unstacked back to
        torch per-block names so checkpoints stay loadable by torch/HF and by
        pp=1 runs."""
        flat = _flatten_tree(jax.device_get(self.params))
        flat.update({f"buffers.{k}": v for k, v in _flatten_tree(jax.device_get(self.buffers)).items()})
        lowered = getattr(self, "_lowered", None)
        if lowered is not None and hasattr(lowered, "unstack_state_dict"):
            flat = lowered.unstack_state_dict(flat)
        return flat

    def load_state_dict(self, state_dict: dict):
        lowered = getattr(self, "_lowered", None)
        if lowered is not None and hasattr(lowered, "restack_state_dict"):
            state_dict = lowered.restack_state_dict(state_dict)
        flat = _flatten_tree(self.params)
        new = {}
        for k, v in flat.items():
            if k not in state_dict:
                raise KeyError(f"Missing parameter {k} in state_dict")
            arr = jnp.asarray(to_numpy(state_dict[k]), dtype=v.dtype)
            new[k] = jax.device_put(arr, v.sharding) if hasattr(v, "sharding") else arr
        self.params = _unflatten_tree(new, self.params)


class _RemovableHandle:
    """Minimal ``torch.utils.hooks.RemovableHandle`` equivalent (id +
    weak-registry pop) so hook registration stays usable without torch —
    sibling facade methods guard their torch imports the same way."""

    _next_id = 0

    def __init__(self, registry):
        import weakref

        self._registry_ref = weakref.ref(registry)
        self.id = _RemovableHandle._next_id
        _RemovableHandle._next_id += 1

    def remove(self) -> None:
        registry = self._registry_ref()
        if registry is not None:
            registry.pop(self.id, None)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.remove()


def _flatten_tree(tree, prefix="") -> dict:
    out = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten_tree(v, f"{prefix}{k}." if not prefix else f"{prefix}{k}."))
        return out
    if isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(_flatten_tree(v, f"{prefix}{i}."))
        return out
    out[prefix[:-1] if prefix.endswith(".") else prefix] = tree
    return out


def _unflatten_tree(flat: dict, like):
    if isinstance(like, dict):
        return {
            k: _unflatten_tree(
                {kk[len(k) + 1 :]: vv for kk, vv in flat.items() if kk == k or kk.startswith(k + ".")},
                v,
            )
            if isinstance(v, (dict, list, tuple))
            else flat[k]
            for k, v in like.items()
        }
    if isinstance(like, (list, tuple)):
        return type(like)(
            _unflatten_tree(
                {kk[len(str(i)) + 1 :]: vv for kk, vv in flat.items() if kk.startswith(f"{i}.")}, v
            )
            if isinstance(v, (dict, list, tuple))
            else flat[str(i)]
            for i, v in enumerate(like)
        )
    return flat[""]


class Accelerator:
    """Single façade over state, mesh, data, model, optimizer, checkpointing.

    Constructor parity: reference ``Accelerator.__init__`` (``accelerator.py:
    270-605``) — same keyword surface where meaningful on TPU; engine-specific
    kwargs (deepspeed_plugin, megatron_lm_plugin) are accepted as config dialects
    in later rounds.
    """

    def __init__(
        self,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: Optional[str] = None,
        gradient_accumulation_steps: int = 1,
        cpu: bool = False,
        dataloader_config: Optional[DataLoaderConfiguration] = None,
        log_with=None,
        project_dir: Optional[str] = None,
        project_config: Optional[ProjectConfiguration] = None,
        gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None,
        step_scheduler_with_optimizer: bool = True,
        kwargs_handlers: Optional[list[KwargsHandler]] = None,
        rng_types: Optional[list[Union[str, RNGType]]] = None,
        fsdp_plugin: Optional[FullyShardedDataParallelPlugin] = None,
        parallelism_config: Optional[ParallelismConfig] = None,
        pp_plugin=None,
        deepspeed_plugin=None,
        megatron_lm_plugin=None,
        even_batches: bool = True,
        dispatch_batches: Optional[bool] = None,
        use_seedable_sampler: bool = False,
    ):
        # Engine config dialects (SURVEY §7 item 14): a DeepSpeed or Megatron
        # plugin is translated onto the GSPMD mesh instead of handed to an
        # external engine — explicit fsdp_plugin/parallelism_config win.
        if deepspeed_plugin is not None and megatron_lm_plugin is not None:
            raise ValueError("Pass either deepspeed_plugin or megatron_lm_plugin, not both")
        # Launcher env contract (reference utils/launch.py:329, :310): the worker
        # reconstructs the active dialect from env alone.
        if deepspeed_plugin is None and megatron_lm_plugin is None:
            from .utils.environment import parse_flag_from_env

            if parse_flag_from_env("ACCELERATE_USE_DEEPSPEED"):
                from .utils.deepspeed import DeepSpeedPlugin

                ds_config = os.environ.get("ACCELERATE_DEEPSPEED_CONFIG_FILE")
                deepspeed_plugin = DeepSpeedPlugin(hf_ds_config=ds_config)
            elif parse_flag_from_env("ACCELERATE_USE_MEGATRON_LM"):
                from .utils.megatron import MegatronLMPlugin

                megatron_lm_plugin = MegatronLMPlugin()
        # Multi-model DS support (reference accelerator.py + state.py:906-953):
        # a dict of named plugins registers them all; the FIRST is active.
        ds_plugins = None
        if isinstance(deepspeed_plugin, dict):
            if not deepspeed_plugin:
                raise ValueError("deepspeed_plugin dict must not be empty")
            from .utils.deepspeed import DeepSpeedPlugin

            for key, value in deepspeed_plugin.items():
                if not isinstance(value, DeepSpeedPlugin):
                    raise TypeError(
                        f"deepspeed_plugin[{key!r}] must be a DeepSpeedPlugin, got "
                        f"{type(value).__name__} (raw DS config dicts go through "
                        "DeepSpeedPlugin(hf_ds_config=...))"
                    )
            ds_plugins = dict(deepspeed_plugin)
            deepspeed_plugin = next(iter(ds_plugins.values()))
        self._deepspeed_plugin = deepspeed_plugin
        self.megatron_lm_plugin = megatron_lm_plugin
        dialect = deepspeed_plugin or megatron_lm_plugin
        if dialect is not None:
            import jax

            n_devices = jax.device_count()
            if parallelism_config is None:
                parallelism_config = dialect.to_parallelism_config(n_devices)
            if fsdp_plugin is None:
                fsdp_plugin = dialect.to_fsdp_plugin()
        if deepspeed_plugin is not None:
            if mixed_precision is None:
                mixed_precision = deepspeed_plugin.mixed_precision
            if gradient_accumulation_steps == 1:
                gradient_accumulation_steps = deepspeed_plugin.gradient_accumulation_steps
            deepspeed_plugin.select()
        if project_config is not None:
            self.project_configuration = project_config
        else:
            self.project_configuration = ProjectConfiguration(project_dir=project_dir)
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)

        if gradient_accumulation_plugin is None:
            env_steps = int(os.environ.get("ACCELERATE_GRADIENT_ACCUMULATION_STEPS", 1))
            steps = gradient_accumulation_steps if gradient_accumulation_steps != 1 else env_steps
            gradient_accumulation_plugin = GradientAccumulationPlugin(num_steps=steps)

        self.dataloader_config = dataloader_config or DataLoaderConfiguration(
            split_batches=split_batches,
            dispatch_batches=dispatch_batches,
            even_batches=even_batches,
            use_seedable_sampler=use_seedable_sampler,
        )

        self.state = AcceleratorState(
            mixed_precision=mixed_precision,
            cpu=cpu,
            parallelism_config=parallelism_config,
            fsdp_plugin=fsdp_plugin,
            pp_plugin=pp_plugin,
            _from_accelerator=True,
        )
        if dialect is not None:
            # Reference parity: the dialect rewrites distributed_type ON THE
            # STATE singleton (``state.py:952-976``) so direct readers agree.
            self.state.deepspeed_plugin = deepspeed_plugin
            if deepspeed_plugin is not None:
                self.state.deepspeed_plugins = ds_plugins or {"default": deepspeed_plugin}
            self.state.megatron_lm_plugin = megatron_lm_plugin
            self.state.distributed_type = (
                DistributedType.DEEPSPEED if deepspeed_plugin is not None else DistributedType.MEGATRON_LM
            )
        self.gradient_state = GradientState(gradient_accumulation_plugin=gradient_accumulation_plugin)
        self.device_placement = device_placement
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer
        self.rng_types = rng_types or ["generator"]
        self.step = 0
        self._models: list[PreparedModel] = []
        self._optimizers: list[AcceleratedOptimizer] = []
        self._schedulers: list[AcceleratedScheduler] = []
        self._dataloaders: list = []
        self._custom_objects: list = []
        # save_state/load_state pre-hooks (reference accelerator.py:3054-3118):
        # registered callables run before state is written/read.
        self._save_state_pre_hooks: "OrderedDict" = collections.OrderedDict()
        self._load_state_pre_hooks: "OrderedDict" = collections.OrderedDict()
        self.flag_tensor = None
        # Resilience: no guard (and no signal handlers, no per-step cost)
        # unless enable_preemption_handling() opts in.
        self._preemption_guard = None
        # Numerical health: no host-side policy runs unless
        # enable_health_guard() opts in (the in-program zero-delta gate on
        # non-finite updates is always on — it rides the existing dispatch).
        self._health_guard = None
        # Elastic resume record: what the last resume_from_latest() actually
        # did (resharded? recomputed skip geometry?) — ElasticResumeInfo.
        self.last_resume_info = None
        self._pending_checkpoint_finalize = None
        self.trackers: list = []
        self.log_with = log_with if isinstance(log_with, (list, tuple)) else ([log_with] if log_with else [])

        # kwargs handlers → named slots (reference accelerator.py:413-450); at
        # most one of each kind.
        from .utils.dataclasses import (
            AutocastKwargs,
            DistributedDataParallelKwargs,
            DistributedInitKwargs,
            FP8RecipeKwargs,
            GradScalerKwargs,
        )

        self.ddp_handler = None
        self.scaler_handler = None
        self.init_handler = None
        self.autocast_handler = None
        self.profile_handler = None
        self.fp8_recipe_handler = None
        _slots = {
            DistributedDataParallelKwargs: "ddp_handler",
            GradScalerKwargs: "scaler_handler",
            DistributedInitKwargs: "init_handler",
            AutocastKwargs: "autocast_handler",
            ProfileKwargs: "profile_handler",
            FP8RecipeKwargs: "fp8_recipe_handler",
        }
        for handler in kwargs_handlers or []:
            if not isinstance(handler, KwargsHandler):
                raise ValueError(f"Unsupported kwargs handler: {handler!r}")
            slot = _slots.get(type(handler))
            if slot is None:
                raise ValueError(f"Unsupported kwargs handler type: {type(handler).__name__}")
            if getattr(self, slot) is not None:
                raise ValueError(f"You can only pass one {type(handler).__name__} in `kwargs_handlers`.")
            setattr(self, slot, handler)
        if self.fp8_recipe_handler is not None and hasattr(self.state, "dtype_policy"):
            # Recipe kwargs override the policy default (reference fp8 plumbing).
            self.state.dtype_policy.fp8_recipe = self.fp8_recipe_handler
        # Observability is env-opt-in (ACCELERATE_TPU_TELEMETRY=1): enabled
        # here so env-only runs get spans/metrics/watchdog with no code change.
        _telemetry_from_env()
        # Persistent XLA compilation cache is default-ON (pipeline/
        # compile_cache.py): repeated runs load compiled executables instead
        # of recompiling.  JAX_COMPILATION_CACHE_DIR places it;
        # ACCELERATE_TPU_COMPILE_CACHE= (empty) disables; hits surface as
        # the jit.cache_hits counter.
        from .pipeline.compile_cache import enable_compile_cache

        enable_compile_cache()
        # ZeRO sharded weight update (ACCELERATE_TPU_ZERO=1): arm the XLA
        # latency-hiding scheduler flags before the TPU backend boots so the
        # per-leaf grad reduce-scatters overlap remaining backward compute.
        from .parallel.zero import maybe_enable_from_env as _zero_flags_from_env

        _zero_flags_from_env()

    # -- state passthroughs (reference properties) ---------------------------

    @property
    def distributed_type(self) -> DistributedType:
        return self.state.distributed_type

    @property
    def num_processes(self) -> int:
        return self.state.num_processes

    @property
    def process_index(self) -> int:
        return self.state.process_index

    @property
    def local_process_index(self) -> int:
        return self.state.local_process_index

    @property
    def device(self):
        return self.state.device

    @property
    def mesh(self):
        return self.state.mesh

    @property
    def is_main_process(self) -> bool:
        return self.state.is_main_process

    @property
    def is_local_main_process(self) -> bool:
        return self.state.is_local_main_process

    @property
    def is_last_process(self) -> bool:
        return self.state.is_last_process

    @property
    def mixed_precision(self) -> str:
        return self.state.mixed_precision

    @property
    def project_dir(self):
        return self.project_configuration.project_dir

    @property
    def sync_gradients(self) -> bool:
        return self.gradient_state.sync_gradients

    @sync_gradients.setter
    def sync_gradients(self, value: bool):
        # Reference accelerator.py mutable-state contract
        # (tests/test_accelerator.py:191): writes flow to the GradientState.
        self.gradient_state.sync_gradients = value

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, value: int):
        self.gradient_state.plugin_kwargs.update({"num_steps": value})

    @property
    def use_distributed(self) -> bool:
        return self.state.use_distributed

    def print(self, *args, **kwargs):
        self.state.print(*args, **kwargs)

    def wait_for_everyone(self):
        self.state.wait_for_everyone()

    @contextlib.contextmanager
    def main_process_first(self):
        with self.state.main_process_first():
            yield

    @contextlib.contextmanager
    def local_main_process_first(self):
        with self.state.local_main_process_first():
            yield

    def on_main_process(self, func=None):
        return self.state.on_main_process(func)

    def on_local_main_process(self, func=None):
        return self.state.on_local_main_process(func)

    def on_process(self, func=None, process_index=None):
        return self.state.on_process(func, process_index)

    def on_last_process(self, func):
        """Run only on the last process (reference ``accelerator.py:930``)."""
        return self.state.on_last_process(func)

    def on_local_process(self, func=None, local_process_index=None):
        """Run only on the given local process index (reference
        ``accelerator.py:975``)."""
        return self.state.on_local_process(func, local_process_index)

    # -- dataloader-config passthrough properties (reference accelerator.py
    # exposes each knob directly on the façade) ------------------------------

    @property
    def split_batches(self) -> bool:
        return self.dataloader_config.split_batches

    @property
    def dispatch_batches(self):
        return self.dataloader_config.dispatch_batches

    @property
    def even_batches(self) -> bool:
        return self.dataloader_config.even_batches

    @even_batches.setter
    def even_batches(self, value: bool):
        self.dataloader_config.even_batches = value

    @property
    def use_seedable_sampler(self) -> bool:
        return self.dataloader_config.use_seedable_sampler

    @property
    def use_stateful_dataloader(self) -> bool:
        return getattr(self.dataloader_config, "use_stateful_dataloader", False)

    @property
    def non_blocking(self) -> bool:
        return getattr(self.dataloader_config, "non_blocking", False)

    @property
    def logging_dir(self):
        return self.project_configuration.logging_dir

    @property
    def is_fsdp2(self) -> bool:
        """Reference distinguishes FSDP1/FSDP2 engines; both map onto the one
        GSPMD design here (single predicate lives on the state)."""
        return self.state.is_fsdp2

    @property
    def deepspeed_plugin(self):
        """The ACTIVE DeepSpeed plugin — reads through the state so a
        ``state.select_deepspeed_plugin(...)`` switch is immediately visible
        to every facade consumer (prepare's fill_auto, grad clipping)."""
        state = self.__dict__.get("state")
        if state is not None:
            active = state.__dict__.get("deepspeed_plugin")
            if active is not None:
                return active
        return self.__dict__.get("_deepspeed_plugin")

    @property
    def _dialect_grad_clip(self):
        """Gradient-clipping value of the ACTIVE engine dialect (follows
        plugin selection, unlike a value captured at __init__)."""
        dialect = self.deepspeed_plugin or self.megatron_lm_plugin
        return dialect.gradient_clipping if dialect is not None else None

    @property
    def fp8_backend(self) -> Optional[str]:
        """Reference returns the fp8 engine in use ("TE"/"MSAMP"/"AO"); here
        the one backend is XLA's scaled-matmul path (ops/fp8.py)."""
        return "XLA" if self.mixed_precision == "fp8" else None

    @property
    def optimizer_step_was_skipped(self) -> bool:
        """Whether the last ``optimizer.step()`` was skipped (overflow /
        accumulation) — reference ``accelerator.py:2530``."""
        return any(getattr(opt, "step_was_skipped", False) for opt in self._optimizers)

    def save(self, obj, f, safe_serialization: bool = False):
        """Save ``obj`` on the main process only (reference
        ``accelerator.py:2905``; every-node saves follow
        ``ProjectConfiguration.save_on_each_node``)."""
        from .utils.other import save

        save(
            obj,
            f,
            save_on_each_node=getattr(self.project_configuration, "save_on_each_node", False),
            safe_serialization=safe_serialization,
        )

    def unscale_gradients(self, optimizer=None):
        """Reference ``accelerator.py:2370``: unscale fp16 AMP gradients.  The
        optax path carries no loss scaler (bf16 needs none); gradients are
        already true-scale, so this is a deliberate no-op kept for API parity.
        """

    def trigger_sync_in_backward(self, model):
        """Reference ``accelerator.py:2061``: force DDP grad sync on the next
        backward inside a ``no_sync`` window.  Sync here is bookkeeping (grads
        accumulate in the buffer until ``sync_gradients`` flips), so arm the
        flag directly."""
        self.gradient_state._set_sync_gradients(True)

    def verify_device_map(self, model) -> bool:
        """True when the model was dispatched with a multi-tier device map
        (reference ``accelerator.py:3479`` — such models must not be wrapped
        for distributed training)."""
        if not is_torch_available():
            return False  # no torch module can carry a device map
        import torch

        if not isinstance(model, torch.nn.Module):
            return False
        for module in model.modules():
            device_map = getattr(module, "hf_device_map", None)
            if device_map is not None and len(set(device_map.values())) > 1:
                return True
        return False

    def lomo_backward(self, loss, learning_rate: float):
        """Reference ``accelerator.py:2580`` (lomo-optim's fused
        backward+step), implemented natively: compute gradients and fold them
        into the parameters with one jitted, donated SGD update — no optimizer
        state is ever allocated and the gradient tree dies inside the fused
        update, which is LOMO's memory-saving contract.  Under
        ``accumulate()`` the update happens at the sync boundary (gradients
        accumulate as usual until then)."""
        # backward() routes the loss to exactly one model; update ONLY that
        # one — other prepared models may hold accumulated grads for their own
        # optimizers (multi-model setups must not get a stray SGD step).
        before = [m._accum_grads for m in self._models]
        self.backward(loss)
        if not self.sync_gradients:
            return
        for model, prior in zip(self._models, before):
            if model._accum_grads is prior:
                continue
            grads = model._consume_grads()
            if grads is None:
                continue
            model._set_params(
                _lomo_sgd_update(model.params, grads, jnp.asarray(learning_rate))
            )

    def split_between_processes(self, inputs, apply_padding: bool = False):
        return self.state.split_between_processes(inputs, apply_padding)

    # -- prepare -------------------------------------------------------------

    @_span("accelerator.prepare")
    def prepare(self, *args, device_placement=None):
        """Prepare model/optimizer/dataloader/scheduler objects for the mesh.

        Parity: reference ``accelerator.py:1292`` — order is preserved, every
        object routed by type.  Torch optimizers must be prepared together with
        (after) their model, mirroring the reference's FSDP requirement
        (``accelerator.py:1384-1398``).
        """
        import torch

        from .utils.deepspeed import DummyOptim, DummyScheduler

        prepared = []
        # Pass 1: everything except optimizers/schedulers (model must exist first).
        staged: dict[int, Any] = {}
        for i, obj in enumerate(args):
            if isinstance(obj, torch.nn.Module) or isinstance(obj, JaxModel):
                staged[i] = self.prepare_model(obj)
            elif isinstance(obj, torch.utils.data.DataLoader) or isinstance(
                obj, (DataLoaderShard, DataLoaderDispatcher)
            ):
                staged[i] = self.prepare_data_loader(obj)
        if self.deepspeed_plugin is not None:
            # Resolve "auto" DS-config fields against the prepared dataloaders
            # (reference _prepare_deepspeed accelerator.py:1837-1863).
            micro_bs = next(
                (dl.batch_size for dl in self._dataloaders if getattr(dl, "batch_size", None)),
                None,
            )
            self.deepspeed_plugin.fill_auto(
                train_micro_batch_size_per_gpu=micro_bs, num_devices=self.num_processes
            )
        dummy_realized: dict[int, Any] = {}  # id(DummyOptim) -> real torch optimizer
        for i, obj in enumerate(args):
            if i in staged:
                continue
            if isinstance(obj, DummyOptim):
                # "Optimizer comes from the DS config": materialize the AdamW the
                # DS engine would have built (reference utils/deepspeed.py:325).
                real = torch.optim.AdamW(obj.params, lr=obj.lr, weight_decay=obj.weight_decay)
                dummy_realized[id(obj)] = real
                staged[i] = self.prepare_optimizer(real)
            elif isinstance(obj, torch.optim.Optimizer):
                staged[i] = self.prepare_optimizer(obj)
            elif _is_optax_tx(obj):
                staged[i] = self.prepare_optimizer(obj)
        for i, obj in enumerate(args):
            if i in staged:
                continue
            if isinstance(obj, DummyScheduler):
                real_opt = dummy_realized.get(id(obj.optimizer))
                if real_opt is None and isinstance(obj.optimizer, torch.optim.Optimizer):
                    real_opt = obj.optimizer
                if real_opt is None:
                    raise ValueError(
                        "DummyScheduler's optimizer must be the DummyOptim (or torch "
                        "optimizer) passed to the same prepare() call"
                    )
                if obj.lr_scheduler_callable is not None:
                    sched = obj.lr_scheduler_callable(real_opt)
                else:
                    # DS WarmupLR semantics: linear warmup then constant.
                    warm = max(int(obj.warmup_num_steps or 0), 0)
                    sched = torch.optim.lr_scheduler.LambdaLR(
                        real_opt, lambda step: min(1.0, (step + 1) / warm) if warm else 1.0
                    )
                staged[i] = self.prepare_scheduler(sched)
            elif _is_scheduler_like(obj):
                staged[i] = self.prepare_scheduler(obj)
            else:
                staged[i] = obj  # passthrough, reference behavior
        prepared = [staged[i] for i in range(len(args))]
        return prepared[0] if len(prepared) == 1 else tuple(prepared)

    @_span("accelerator.prepare_model")
    def prepare_model(self, model, device_placement=None, evaluation_mode: bool = False):
        """Lower + shard a model (reference ``prepare_model`` ``accelerator.py:1468``)."""
        from .parallel.sharding import make_param_specs, shard_params

        if isinstance(model, PreparedModel):
            return model
        if isinstance(model, JaxModel):
            apply_fn = lambda p, b, *a, **k: model.apply_fn(p, *a, **k)
            params, buffers, rules = model.params, model.buffers, model.partition_rules
            original = None
        else:
            from .utils.torch_bridge import TorchLoweringError, lower_module

            rules = None
            lowered = None
            pp = dict(self.mesh.shape).get("pp", 1)
            if pp > 1:
                # Reference capability: the Megatron engine pipelines any model
                # it wraps (utils/megatron_lm.py:1034-1055).  Native analog:
                # stack the module's repeated-block chain into the compiled
                # GPipe scan.  Modules without pipelineable structure fall back
                # to plain GSPMD — loudly, so pp_degree is never silently inert.
                from jax.sharding import PartitionSpec as _P

                from .utils.torch_bridge import lower_module_pipelined

                pp_plugin = self.state.pp_plugin
                mb = getattr(pp_plugin, "num_micro_batches", 1) or 1
                try:
                    lowered = lower_module_pipelined(
                        model,
                        pp,
                        num_micro_batches=mb,
                        schedule=getattr(pp_plugin, "schedule", "gpipe") or "gpipe",
                        virtual_stages=getattr(pp_plugin, "virtual_stages", 1) or 1,
                    )
                    rules = [(r"\._stacked\.", _P("pp"))]
                except TorchLoweringError as e:
                    warnings.warn(
                        f"pp={pp} requested but this torch module cannot be "
                        f"pipelined ({e}); it will run GSPMD-sharded WITHOUT a "
                        "microbatch pipeline schedule — pp_degree buys no "
                        "pipelining for this model. Restructure the repeated "
                        "blocks into a ModuleList/Sequential linear chain to "
                        "enable the compiled GPipe schedule."
                    )
            if lowered is None:
                lowered = lower_module(model)
            apply_fn = lowered.apply
            params, buffers = lowered.params, lowered.buffers
            original = model

        specs = make_param_specs(params, self.mesh, self.state.fsdp_plugin, rules=rules)
        params = shard_params(params, self.mesh, specs)
        buffers = jax.tree_util.tree_map(lambda b: jax.device_put(jnp.asarray(b)), buffers)
        prepared = PreparedModel(apply_fn, params, buffers, self, original_module=original)
        # The declared shardings are the lint's ground truth: the inspector
        # compares what enters the compiled step against these.
        prepared._param_specs = specs
        prepared._program_label = f"model{len(self._models)}"
        if original is not None:
            # Keep the lowering handle: a pipelined lowering stores stacked
            # block params, and state_dict/unwrap must translate back to torch
            # per-block names (PipelinedLoweredModule.unstack_state_dict).
            prepared._lowered = lowered
        if evaluation_mode:
            prepared.eval()
        prepared._is_accelerate_prepared = True
        self._models.append(prepared)
        return prepared

    def prepare_data_loader(self, data_loader, device_placement=None, slice_fn_for_dispatch=None):
        if isinstance(data_loader, (DataLoaderShard, DataLoaderDispatcher)):
            self._dataloaders.append(data_loader)
            return data_loader
        cfg = self.dataloader_config
        prepared = prepare_data_loader(
            data_loader,
            device=self.device,
            split_batches=cfg.split_batches,
            put_on_device=device_placement if device_placement is not None else self.device_placement,
            rng_types=self.rng_types,
            dispatch_batches=cfg.dispatch_batches,
            even_batches=cfg.even_batches,
            slice_fn_for_dispatch=slice_fn_for_dispatch,
            use_seedable_sampler=cfg.use_seedable_sampler,
            data_seed=cfg.data_seed,
            non_blocking=cfg.non_blocking,
            use_stateful_dataloader=cfg.use_stateful_dataloader,
            mesh=self.mesh,
            output_type="torch",  # user-land torch ops (criteria/metrics) work
            # unchanged; the jitted model picks up `._atpu_jax` with no re-transfer
            static_shape_tail=getattr(cfg, "static_shape_tail", False),
            prefetch_to_device=getattr(cfg, "prefetch_to_device", 0),
        )
        prepared._is_accelerate_prepared = True
        self._dataloaders.append(prepared)
        return prepared

    def prepare_optimizer(self, optimizer, device_placement=None):
        import torch

        if isinstance(optimizer, AcceleratedOptimizer):
            return optimizer
        if not self._models:
            raise ValueError(
                "Prepare the model before (or together with) its optimizer — the optax "
                "state is built from the sharded parameters (the reference imposes the "
                "same model+optimizer pairing for FSDP, accelerator.py:1384-1398)."
            )
        model = self._models[-1]
        # Honor the offload knobs: fsdp_plugin.cpu_offload and the DeepSpeed
        # dialect's offload_optimizer both mean "optimizer state in host
        # memory" — wired through parallel/host_offload (pinned_host placement
        # + in-step transfers).
        host_off = bool(
            getattr(getattr(self.state, "fsdp_plugin", None), "cpu_offload", False)
        ) or (
            getattr(
                getattr(self.state, "deepspeed_plugin", None),
                "offload_optimizer_device",
                None,
            )
            in ("cpu", "nvme")
        )
        if isinstance(optimizer, torch.optim.Optimizer):
            # Pair by PARAMETER IDENTITY, not recency: with several models under
            # one Accelerator (reference test_ds_multiple_model.py), each torch
            # optimizer holds references to its own model's parameters — pairing
            # with _models[-1] would route every optimizer's step to the last
            # prepared model.
            opt_param_ids = {id(p) for g in optimizer.param_groups for p in g["params"]}
            for candidate in reversed(self._models):
                original = getattr(candidate, "module", None)
                if original is not None and any(
                    id(p) in opt_param_ids for p in original.parameters()
                ):
                    model = candidate
                    break
            from .utils.torch_bridge import convert_optimizer

            tx, lr = convert_optimizer(optimizer)
            prepared = AcceleratedOptimizer(
                tx, model=model, torch_optimizer=optimizer, initial_lr=lr,
                host_offload_state=host_off,
            )
        else:
            prepared = AcceleratedOptimizer(optimizer, model=model, host_offload_state=host_off)
        if self._dialect_grad_clip is not None and float(self._dialect_grad_clip) > 0:
            # DS/Megatron configs carry gradient_clipping; the engines applied it
            # automatically, so the dialect must too (reference utils/deepspeed.py
            # fills "gradient_clipping" into the engine config).  DeepSpeed's
            # documented disabled value is 0.0 — which must NOT arm the clip
            # (the jitted update treats 0 as "zero the grads", torch parity for
            # the explicit clip_grad_norm_(0) call only).
            prepared._clip_norm = float(self._dialect_grad_clip)
        prepared._is_accelerate_prepared = True
        self._optimizers.append(prepared)
        return prepared

    def prepare_scheduler(self, scheduler):
        if isinstance(scheduler, AcceleratedScheduler):
            return scheduler
        opts = self._optimizers or []
        prepared = AcceleratedScheduler(
            scheduler,
            opts,
            step_with_optimizer=self.step_scheduler_with_optimizer,
            split_batches=self.dataloader_config.split_batches,
        )
        prepared._is_accelerate_prepared = True
        self._schedulers.append(prepared)
        return prepared

    # -- training loop surface ------------------------------------------------

    def make_train_step(
        self,
        model,
        optimizer,
        accum_steps: Optional[int] = None,
        clip_norm: Optional[float] = None,
        clip_value: Optional[float] = None,
        zero=None,
    ):
        """Build the fused train step: ONE jitted, buffer-donated callable
        running forward+backward, gradient accumulation over the micro-batch
        window (``lax.scan`` when ``accum_steps > 1``), optional clipping and
        the optax update — one Python→XLA dispatch per optimizer step instead
        of ``3 × accum_steps`` on the eager ``backward()``/``step()`` path,
        with bit-exact numerics (see ``docs/usage_guides/performance.md``).

        ``model``/``optimizer`` are the prepared pair from :meth:`prepare`;
        they remain the source of truth (params/opt-state written back every
        call), so ``save_state``/``resume_from_latest``, LR schedulers and
        :meth:`check_preemption` step boundaries keep working unchanged::

            step_fn = accelerator.make_train_step(model, optimizer)
            for batch in loader:          # accum_steps == 1
                loss = step_fn(batch)
            for window in windows:        # accum_steps == N: list of N batches
                losses = step_fn(window)

        ``zero`` opts into the ZeRO-style cross-replica sharded weight update
        (``parallel/zero.py``: reduce-scatter grads, update the local shard,
        all-gather params — dp-fold less opt-state HBM per chip and half the
        grad-sync bandwidth); ``None`` defers to ``ACCELERATE_TPU_ZERO=1``.
        """
        from .pipeline.train_step import make_train_step as _make

        return _make(
            self,
            model,
            optimizer,
            accum_steps=accum_steps,
            clip_norm=clip_norm,
            clip_value=clip_value,
            zero=zero,
        )

    def prepare_serving(
        self,
        apply_cached,
        init_cache,
        params,
        config,
        serving=None,
        **serving_kwargs,
    ):
        """Build a continuous-batching serving engine over a model family's
        cached-decode pair (``serving/engine.py``): a paged/block KV cache
        shared by every in-flight request, an admission queue with LIFO
        preemption under block pressure, bounded chunked prefill interleaved
        with decode, and ONE fused jitted decode dispatch per step over the
        active slots — greedy outputs token-identical to the offline
        ``generate_loop`` per request.  Per-request SLO metrics (TTFT,
        inter-token latency, queue wait) publish through the telemetry
        registry as the ``serving.*`` families; completions emit
        ``serving.request_complete`` events the flight recorder mirrors.

        The engine is production-robust out of the box: bound the queue with
        ``max_queue_depth`` (overload sheds with a typed
        ``AdmissionRejected``), set default TTFT/total deadlines
        (``default_ttft_deadline_ms`` / ``default_deadline_ms``), quarantine
        NaN-poisoned requests via in-program detection, and arm the
        crash-recovery write-ahead journal with ``journal_path`` (a
        SIGKILLed engine's successor rebuilds its queue via
        ``recover_from_journal`` and finishes token-identically) — see
        "Overload & failure handling" in ``docs/usage_guides/serving.md``.

        ``apply_cached``/``init_cache`` are a family's cached-inference pair
        (``models/{gpt2,llama,mixtral}.py`` — fp or int8 KV).  On a
        multi-device mesh ``params`` that do not already live on the mesh's
        devices are replicated over it (jit refuses arguments committed to
        other devices than the installed mesh's, and a tree left on device 0
        would be exactly that); params the caller sharded over the mesh stay
        as placed.  Geometry comes from a
        :class:`~accelerate_tpu.serving.ServingConfig` (or its fields as
        keyword arguments)::

            engine = accelerator.prepare_serving(
                gpt2.apply_cached, gpt2.init_cache, params, cfg,
                max_slots=8, num_blocks=256, block_size=16,
            )
            rid = engine.submit(prompt_tokens, max_new_tokens=64)
            outputs = engine.run()

        See ``docs/usage_guides/serving.md``.
        """
        from .serving import ServingConfig, ServingEngine

        if serving is not None and serving_kwargs:
            raise ValueError("pass either a ServingConfig or its fields, not both")
        if serving is None:
            serving = ServingConfig(**serving_kwargs)
        mesh = self.state.mesh
        if mesh.size > 1:
            from .parallel.sharding import replicated

            mesh_devices = set(mesh.devices.flat)
            on_mesh = replicated(mesh)
            params = jax.tree_util.tree_map(
                lambda x: x
                if isinstance(x, jax.Array) and x.sharding.device_set == mesh_devices
                else jax.device_put(x, on_mesh),
                params,
            )
        engine = ServingEngine(apply_cached, init_cache, params, config, serving=serving)
        # Graceful drain: an installed PreemptionGuard (enable_preemption_
        # handling) makes the engine stop admission and requeue-journal the
        # in-flight requests when the preemption signal arrives, instead of
        # dying mid-dispatch with work in the queue.
        if self._preemption_guard is not None:
            engine.install_preemption_guard(self._preemption_guard)
        return engine

    @_span("accelerator.backward")
    def backward(self, loss, **kwargs):
        """Accumulate gradients for ``loss`` (reference ``accelerator.py:2437``)."""
        scale = 1.0 / self.gradient_accumulation_steps
        if is_torch_available():
            import torch

            if isinstance(loss, torch.Tensor):
                for model in self._models:
                    pending = model._grads_for_loss(loss)
                    if pending is not None:
                        _, grads = pending
                        model._accumulate(grads, scale)
                        return
                if not loss.requires_grad:
                    raise RuntimeError(
                        "accelerator.backward() received a torch tensor with no autograd "
                        "graph and no prepared-model tag. Pass the loss returned by the "
                        "model (outputs.loss), a torch expression derived from it, or a "
                        "loss computed from model outputs with torch ops."
                    )
                # Torch autograd flows into the jax side: through the bridge
                # vjp (bridge mode) or the tagged-loss grad hooks (fused mode
                # with a derived loss), scaled by the accumulation factor.
                (loss * scale).backward(**kwargs)
                return
        if isinstance(loss, jax.Array):
            for model in self._models:
                if model._pending is not None:
                    _, grads = model._pending
                    model._pending = None
                    model._accumulate(grads, scale)
                    return
        raise RuntimeError(
            "accelerator.backward() could not associate this loss with a prepared "
            "model's forward pass. Pass the loss object returned by the model "
            "(outputs.loss) or compute it from model outputs with torch ops."
        )

    def _do_sync(self):
        if self.gradient_state.sync_with_dataloader and self.gradient_state.end_of_dataloader:
            self.step = 0
            self.gradient_state._set_sync_gradients(True)
        else:
            self.step += 1
            self.gradient_state._set_sync_gradients(
                (self.step % self.gradient_accumulation_steps) == 0
            )

    @contextlib.contextmanager
    def accumulate(self, *models):
        """Parity: reference ``accelerator.py:1124``."""
        self._do_sync()
        if self.gradient_state.sync_each_batch:
            self.gradient_state._set_sync_gradients(True)
        yield

    @contextlib.contextmanager
    def no_sync(self, model=None):
        """Reference ``accelerator.py:1009``: skip grad sync.  GSPMD has no per-step
        sync to skip (accumulation happens in the grad buffer), so this only flips
        the bookkeeping flag."""
        old = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(False)
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(old)

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables, even_batches=None):
        """Reference ``accelerator.py:1169``: torch Join for uneven inputs.  The
        Join sync itself is a warn-noop here (uneven inputs cannot reach the
        mesh — even_batches/padding guarantee shape; same behavior the
        reference has on XLA), but the ``even_batches`` override keeps its
        reference semantics: prepared MAP-STYLE dataloaders temporarily switch
        their batch sampler's even_batches inside the context (restored on
        exit); iterable loaders warn, as in the reference."""
        warnings.warn(
            "join_uneven_inputs is a no-op on the TPU backend: batches are equalized "
            "by even_batches/padding before reaching the mesh."
        )
        overridden: list = []
        iterable_seen = False
        # Reference parity (accelerator.py:1251): at a single process the whole
        # context is a nullcontext — no override, no map-style warning (the
        # single-process prepare path keeps the plain torch BatchSampler, which
        # has no even_batches knob).
        if even_batches is not None and self.num_processes > 1:
            for dl in self._dataloaders:
                sampler = getattr(dl, "batch_sampler", None)
                if sampler is not None and hasattr(sampler, "even_batches"):
                    overridden.append((sampler, sampler.even_batches))
                    sampler.even_batches = even_batches
                else:
                    iterable_seen = True
            if iterable_seen:
                warnings.warn(
                    "Overriding even_batches is only supported for map-style datasets; "
                    "iterable dataloaders keep their behavior."
                )
        try:
            yield
        finally:
            for sampler, prev in overridden:
                sampler.even_batches = prev

    # Pickling (reference test_distributed_data_loop.py test_pickle_accelerator):
    # prepared objects hold compiled steps / device arrays / live loaders —
    # process-local by nature.  The pickle carries the CONFIG (plugins, state
    # singletons via their own reducers); handles re-register on prepare().
    _UNPICKLABLE_ATTRS = (
        "_models", "_optimizers", "_schedulers", "_dataloaders", "trackers",
        "_save_state_pre_hooks", "_load_state_pre_hooks",
    )

    def __getstate__(self):
        out = {k: v for k, v in self.__dict__.items() if k not in self._UNPICKLABLE_ATTRS}
        return out

    def __setstate__(self, state):
        self.__dict__.update(state)
        for attr in self._UNPICKLABLE_ATTRS:
            fresh = collections.OrderedDict() if attr.endswith("_pre_hooks") else []
            setattr(self, attr, fresh)

    def unwrap_model(self, model, keep_fp32_wrapper: bool = True, keep_torch_compile: bool = True):
        """Return the original torch module with CURRENT trained weights copied in
        (reference ``extract_model_from_parallel`` + ``get_state_dict`` contract)."""
        if isinstance(model, PreparedModel):
            if model.module is not None:
                import torch

                flat = _flatten_tree(jax.device_get(model.params))
                lowered = getattr(model, "_lowered", None)
                if lowered is not None and hasattr(lowered, "unstack_state_dict"):
                    flat = lowered.unstack_state_dict(flat)
                # np.array(copy) — device_get hands back read-only views that
                # torch.from_numpy warns about.
                sd = {k: torch.from_numpy(np.array(v)) for k, v in flat.items()}
                model.module.load_state_dict(sd, strict=False)
                return model.module
            return model
        from .utils.other import extract_model_from_parallel

        return extract_model_from_parallel(
            model, keep_fp32_wrapper=keep_fp32_wrapper, keep_torch_compile=keep_torch_compile
        )

    def clip_grad_norm_(self, parameters=None, max_norm: float = 1.0, norm_type: float = 2.0):
        """Arm global-norm clipping for the next optimizer step (one-shot, like
        the reference's in-place call ``accelerator.py:2565``) and return the
        current accumulated grad norm."""
        import optax

        for opt in self._optimizers:
            opt._clip_norm_once = float(max_norm)
        for model in self._models:
            if model._accum_grads is not None:
                return _jax_to_torch(optax.global_norm(model._accum_grads))
        return None

    def clip_grad_value_(self, parameters=None, clip_value: float = 1.0):
        """Arm elementwise gradient clipping for the next optimizer step
        (one-shot; reference ``accelerator.py:2630``.  The reference disallows
        this under FSDP/DeepSpeed — here it composes with any sharding, since
        the clip is fused into the jitted update)."""
        for opt in self._optimizers:
            opt._clip_value_once = float(clip_value)

    # -- collectives / metrics ------------------------------------------------

    def gather(self, tensor):
        return gather(tensor)

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """Gather + drop even-batches duplicate samples (reference
        ``accelerator.py:2686``, dedup at 2730-2754)."""
        try:
            recursively_apply(lambda x: x, input_data, error_on_other_type=True)
            all_tensors = True
        except TypeError:
            all_tensors = False
        object_mode = not all_tensors or use_gather_object
        if object_mode:
            # Reference semantics (operations.py:440): each process contributes
            # its LIST of samples; the gather flattens one level, so the result
            # is the concatenated sample list — not a list of per-process
            # batches.
            data = gather_object(
                input_data if isinstance(input_data, (list, tuple)) else [input_data]
            )
        else:
            data = self.gather(input_data)
            pad = getattr(self.gradient_state, "device_pad_rows", 0)
            batch_rows = getattr(self.gradient_state, "device_batch_rows", 0)
            if pad and batch_rows:
                # Drop the rows the device placer appended to make this batch
                # shard-divisible.  The gather concatenates per-process blocks
                # along dim 0, and every process pads its own tail, so the
                # duplicates sit at the end of each block.  Only tensors whose
                # gathered leading dim matches the padded batch are trimmed —
                # a [C] per-class vector or [C, C] confusion matrix gathered
                # mid-epoch passes through untouched.
                n_proc = self.num_processes

                def _drop_pad(t):
                    if getattr(t, "ndim", 0) == 0 or t.shape[0] != n_proc * batch_rows:
                        return t
                    kept = t.reshape(n_proc, batch_rows, *t.shape[1:])[:, : batch_rows - pad]
                    return kept.reshape(n_proc * (batch_rows - pad), *t.shape[1:])

                data = recursively_apply(_drop_pad, data)

        try:
            if self.gradient_state.end_of_dataloader and self.gradient_state.remainder > 0:
                if object_mode:
                    # Flat sample list: plain slice (recursively_apply would
                    # descend into the samples themselves).
                    return data[: self.gradient_state.remainder]

                def _truncate(t):
                    return t[: self.gradient_state.remainder]

                return recursively_apply(_truncate, data)
            return data
        except Exception:
            return data

    def reduce(self, tensor, reduction="sum", scale=1.0):
        return reduce(tensor, reduction, scale)

    def pad_across_processes(self, tensor, dim=0, pad_index=0, pad_first=False):
        return pad_across_processes(tensor, dim, pad_index, pad_first)

    # -- trigger flags (coordinated early stop) -------------------------------

    def set_trigger(self):
        """Reference ``accelerator.py:2471``."""
        self.flag_tensor = np.array([1])

    def check_trigger(self) -> bool:
        """Reference ``accelerator.py:2497``: any-process trigger check."""
        flag = self.flag_tensor if self.flag_tensor is not None else np.array([0])
        total = reduce(flag, reduction="sum")
        if int(np.asarray(total)[0]) >= 1:
            self.flag_tensor = None
            return True
        return False

    # -- precision context ----------------------------------------------------

    @contextlib.contextmanager
    def autocast(self, autocast_handler=None):
        """bf16 compute is baked into the compiled step (dtype policy), so the
        context is a no-op marker (reference ``accelerator.py autocast``)."""
        yield

    @contextlib.contextmanager
    def profile(self, profile_handler=None):
        """Capture a device trace for the enclosed block.

        Parity: reference ``accelerator.py:3705-3762`` (torch.profiler → Chrome
        trace per rank).  Here: ``jax.profiler`` → perfetto/xplane dump under
        ``<output_trace_dir>/profile_<rank>`` when a `ProfileKwargs` with
        ``output_trace_dir`` is given (the ``ACCELERATE_TPU_TRACE_DIR`` env
        var is the argument-free form); otherwise the trace is collected and
        dropped (useful for warm-up parity with the reference's schedule).
        """
        import shutil
        import tempfile

        handler = profile_handler or self.profile_handler or ProfileKwargs()
        out_dir = handler.output_trace_dir or os.environ.get("ACCELERATE_TPU_TRACE_DIR")
        keep = out_dir is not None
        if not keep:
            out_dir = tempfile.mkdtemp(prefix="atpu_profile_")
        os.makedirs(out_dir, exist_ok=True)
        trace_dir = os.path.join(out_dir, f"profile_{self.process_index}")
        jax.profiler.start_trace(trace_dir)
        try:
            yield None
        finally:
            jax.profiler.stop_trace()
            if not keep:
                shutil.rmtree(out_dir, ignore_errors=True)

    # -- persistence (full impl in checkpointing.py) --------------------------

    def register_save_state_pre_hook(self, hook: Callable):
        """Register ``hook(models, weights, output_dir)`` to run inside
        ``save_state`` before anything is written (reference
        ``accelerator.py:3054``).  Returns a removable handle."""
        handle = _RemovableHandle(self._save_state_pre_hooks)
        self._save_state_pre_hooks[handle.id] = hook
        return handle

    def register_load_state_pre_hook(self, hook: Callable):
        """Register ``hook(models, input_dir)`` to run inside ``load_state``
        before weights are restored (reference ``accelerator.py:3118``).
        Returns a removable handle."""
        handle = _RemovableHandle(self._load_state_pre_hooks)
        self._load_state_pre_hooks[handle.id] = hook
        return handle

    def save_state(self, output_dir: Optional[str] = None, **save_model_func_kwargs):
        from .checkpointing import save_accelerator_state

        return save_accelerator_state(self, output_dir, **save_model_func_kwargs)

    def load_state(self, input_dir: Optional[str] = None, **load_model_func_kwargs):
        from .checkpointing import load_accelerator_state

        return load_accelerator_state(self, input_dir, **load_model_func_kwargs)

    def register_for_checkpointing(self, *objects):
        for obj in objects:
            if not (hasattr(obj, "state_dict") and hasattr(obj, "load_state_dict")):
                raise ValueError(
                    f"Object {obj} must expose state_dict/load_state_dict to be registered."
                )
            self._custom_objects.append(obj)

    def save_model(self, model, save_directory, max_shard_size="10GB", safe_serialization=True):
        from .checkpointing import save_model_weights

        return save_model_weights(
            model, save_directory, safe_serialization=safe_serialization, max_shard_size=max_shard_size
        )

    def get_state_dict(self, model, unwrap: bool = True):
        if isinstance(model, PreparedModel):
            return model.state_dict()
        return model.state_dict()

    def skip_first_batches(self, dataloader, num_batches: int = 0):
        return skip_first_batches(dataloader, num_batches)

    def wait_for_checkpoint(self):
        """Block until any in-flight async checkpoint writes
        (``save_state(async_save=True)``) are durable on disk.  The join runs
        under the resilience retry policy and a failed async save re-raises
        here with a clear error (instead of dying silently with its thread);
        for verified saves this also runs the deferred manifest + atomic
        rename that publishes the checkpoint."""
        from .checkpointing import finalize_async_checkpoint

        finalize_async_checkpoint(self)

    # -- resilience (full impl in resilience/) --------------------------------

    def enable_preemption_handling(self, save_dir: Optional[str] = None, signals=None, coordinated=None):
        """Install a :class:`~accelerate_tpu.resilience.PreemptionGuard` for
        this process (idempotent).  ``save_dir`` is where
        :meth:`check_preemption` writes the final verified checkpoint (default:
        the project's automatic checkpoint naming).  Returns the guard."""
        from .resilience import PreemptionGuard

        if self._preemption_guard is None and save_dir is None and not (
            self.project_configuration.automatic_checkpoint_naming
        ):
            # Fail at INSTALL time, not at signal delivery — discovering the
            # missing save target inside the preemption path would kill the
            # run with a traceback exactly when the final checkpoint matters.
            # (A re-enable of an already-installed guard keeps its target, so
            # the idempotent second call never trips this.)
            raise ValueError(
                "enable_preemption_handling needs a checkpoint target: pass "
                "save_dir=, or enable ProjectConfiguration("
                "automatic_checkpoint_naming=True)."
            )
        if self._preemption_guard is None:
            kwargs = {}
            if signals is not None:
                kwargs["signals"] = signals
            self._preemption_guard = PreemptionGuard(coordinated=coordinated, **kwargs)
            self._preemption_guard.install()
        if save_dir is not None:
            self._preemption_guard.save_dir = save_dir
        return self._preemption_guard

    def check_preemption(self, save_dir: Optional[str] = None, step: Optional[int] = None) -> bool:
        """Call once per step at the step boundary.  Returns True when the
        fleet agreed a preemption signal arrived — after writing ONE final
        verified checkpoint (to ``save_dir``, the guard's configured dir, or
        automatic naming) so the caller can break out of the loop and exit
        cleanly.  ``step`` is recorded in the checkpoint manifest for
        :meth:`resume_from_latest`.  Without an installed guard this is a
        single attribute check (plus the env-armed fault-injection tick)."""
        from .resilience import faultinject, fleet

        # Step-loop heartbeat for the FleetSupervisor (no-op unless the
        # supervisor armed $ACCELERATE_TPU_HEARTBEAT_DIR): beaten HERE, from
        # the main thread, so a rank wedged in a dead collective stops
        # beating and the supervisor can kill the fleet instead of hanging.
        fleet.maybe_beat(step if step is not None else self.step)
        if faultinject.armed():
            faultinject.tick(step if step is not None else self.step)
        guard = self._preemption_guard
        if guard is None or not guard.should_stop():
            return False
        if not guard.final_checkpoint_saved:
            target = save_dir or guard.save_dir
            from .telemetry import get_telemetry, span as _tspan

            with _tspan("resilience.final_checkpoint"):
                self.save_state(target, step=step)
            guard.final_checkpoint_saved = True
            tel = get_telemetry()
            if tel.enabled:
                tel.registry.counter("resilience.preempt_checkpoints").inc()
                tel.event("resilience.preempt_checkpoint", step=step)
            from .logging import get_logger

            get_logger(__name__).warning(
                f"preemption checkpoint written (step={step}); exiting cleanly"
            )
        return True

    def resume_from_latest(self, checkpoint_dir: Optional[str] = None, verify: bool = True):
        """Auto-resume: restore the newest *manifest-complete* checkpoint
        under ``checkpoint_dir`` (default: ``<project_dir>/checkpoints``),
        skipping torn partials from crashed saves.  Restores model/optimizer/
        scheduler/RNG/dataloader position via ``load_state`` and returns the
        step recorded at save time (``save_state(..., step=N)`` /
        ``check_preemption(step=N)``), 0 when the checkpoint carries no step,
        or None when no complete checkpoint exists.

        **Elastic**: a checkpoint saved under a different topology (mesh
        shape, world size, ZeRO layout) legally lands on the current mesh —
        the manifest's topology record is validated leaf-by-leaf, every leaf
        re-places onto the live sharding (GSPMD relayout), RNG streams fold
        for new ranks, and the ``skip_first_batches`` count is recomputed for
        the live global-batch split.  Pipeline stage-count changes are
        rejected with :class:`~accelerate_tpu.resilience.ElasticTopologyError`.
        Details of what happened land on ``self.last_resume_info``
        (:class:`~accelerate_tpu.resilience.elastic.ElasticResumeInfo`);
        legacy topology-less checkpoints resume on a warned best-effort path
        identical to the pre-elastic behavior."""
        from .resilience import elastic
        from .resilience.manifest import find_latest_complete, read_manifest

        root = checkpoint_dir or os.path.join(self.project_dir or ".", "checkpoints")
        ckpt = find_latest_complete(root)
        if ckpt is None:
            return None
        manifest = read_manifest(ckpt) or {}
        topology = manifest.get(elastic.TOPOLOGY_KEY)
        step = manifest.get("step")
        resumed_step = int(step) if step is not None else 0

        plan = None
        skip_batches = None
        if topology is None:
            from .logging import get_logger

            get_logger(__name__).warning(
                f"checkpoint {ckpt!r} carries no topology record (pre-elastic "
                "save): resuming best-effort, assuming it was saved under the "
                "current mesh — cross-topology state cannot be validated."
            )
        else:
            # Plan + validate + recompute the loader geometry BEFORE anything
            # is restored: an illegal reshape (pp change, leaf mismatch,
            # non-divisible global-batch split) must fail with the live state
            # untouched.  load_state re-runs plan/validate cheaply (pure
            # metadata) so direct load_state callers get the same guard.
            plan = elastic.plan_resume(topology, self)
            elastic.validate_leaves(topology, self)
            live_gb = None
            for dl in self._dataloaders:
                try:
                    live_gb = int(dl.total_batch_size)
                except Exception:
                    live_gb = None
                break
            # Same-geometry resumes keep the stateful-loader/sampler position
            # restored by load_state — only a changed global batch needs the
            # recomputed skip (whole-epoch math is the caller's loop).
            if plan.saved_global_batch is not None and live_gb is not None and (
                plan.saved_global_batch != live_gb
            ):
                skip_batches = elastic.recompute_skip_batches(
                    resumed_step, plan.saved_global_batch, live_gb
                )
        self.load_state(ckpt, verify=verify)
        # Automatic naming must not overwrite the checkpoint we just resumed
        # from on the next save.
        tail = os.path.basename(ckpt).rsplit("_", 1)[-1]
        if os.path.basename(ckpt).startswith("checkpoint_") and tail.isdigit():
            self.project_configuration.iteration = int(tail) + 1
        self.last_resume_info = elastic.ElasticResumeInfo(
            step=resumed_step,
            checkpoint=ckpt,
            plan=plan,
            legacy=topology is None,
            skip_batches=skip_batches,
        )
        return resumed_step

    def enable_health_guard(
        self,
        optimizer=None,
        dataloader=None,
        max_skips: int = 3,
        max_rewinds: int = 2,
        lr_backoff: Optional[float] = None,
        checkpoint_dir: Optional[str] = None,
        quarantine_after: int = 2,
        quarantine_log: Optional[str] = None,
    ):
        """Install a :class:`~accelerate_tpu.resilience.HealthGuard`: NaN/Inf
        loss+gradient detection inside the jitted step (the anomalous update
        is gated to a zero delta in-program — no extra dispatch), plus the
        host-side policy: skip up to ``max_skips`` consecutive anomalous
        steps, then rewind to the newest manifest-complete checkpoint under
        ``checkpoint_dir`` (via :meth:`resume_from_latest`, with an optional
        ``lr_backoff`` multiplier), raising ``NumericalDivergenceError``
        after ``max_rewinds``.  A batch that produces a non-finite step
        ``quarantine_after`` times is quarantined: fingerprinted by (epoch,
        batch index), logged to JSONL next to the telemetry trace, and
        skipped by the dataloader on replay.  ``optimizer``/``dataloader``
        default to the prepared ones.  Call :meth:`check_health` once per
        step.  Returns the guard."""
        from .resilience.health import HealthGuard

        if optimizer is None:
            optimizer = self._optimizers[-1] if self._optimizers else None
        if dataloader is None:
            dataloader = self._dataloaders[0] if self._dataloaders else None
        self._health_guard = HealthGuard(
            self,
            optimizer=optimizer,
            dataloader=dataloader,
            max_skips=max_skips,
            max_rewinds=max_rewinds,
            lr_backoff=lr_backoff,
            checkpoint_dir=checkpoint_dir,
            quarantine_after=quarantine_after,
            quarantine_log=quarantine_log,
        )
        return self._health_guard

    def enable_flight_recorder(self, dir: Optional[str] = None, capacity: Optional[int] = None, flush_every: Optional[int] = None):
        """Enable the black-box flight recorder: a bounded ring of per-step
        events (step time, dispatches, compiles, health verdicts, checkpoint
        publishes, preemption signals) flushed to a crash-safe JSONL snapshot
        periodically and on SIGTERM/exit/unhandled-exception, with online
        anomaly detection (``telemetry/flightrec.py``).  Env-only runs get
        the same via ``ACCELERATE_TPU_FLIGHTREC=1``.  Returns the recorder."""
        from .telemetry import flightrec

        return flightrec.enable(dir=dir, capacity=capacity, flush_every=flush_every)

    def check_health(self, step: Optional[int] = None, loss=None):
        """Judge the optimizer step that just completed (call right after
        ``optimizer.step()`` or the fused ``step_fn(batch)``).  Returns a
        :class:`~accelerate_tpu.resilience.HealthVerdict`; on
        ``verdict.rewound`` the caller should reset its step counter to
        ``verdict.resumed_step`` and re-enter its dataloader loop (the
        loader's position was restored with the checkpoint).  A no-op
        healthy verdict when no guard is installed."""
        if self._health_guard is None:
            from .resilience.health import HealthVerdict

            return HealthVerdict()
        return self._health_guard.check(step=step, loss=loss)

    def free_memory(self, *objects):
        """Reference ``accelerator.py:3497``: drop references + clear caches.
        Returns one None per input so callers can overwrite their handles
        (reference release_memory contract)."""
        from .utils.memory import release_memory

        self._models.clear()
        self._optimizers.clear()
        self._schedulers.clear()
        self._dataloaders.clear()
        self.step = 0
        # release_memory's clear_device_cache already runs jax.clear_caches().
        objects = release_memory(*objects)
        return objects

    def clear(self, *objects):
        return self.free_memory(*objects)

    # -- trackers (minimal; full suite in tracking.py) ------------------------

    def init_trackers(self, project_name: str, config=None, init_kwargs=None):
        from .tracking import init_trackers

        self.trackers = init_trackers(self.log_with, project_name, config, init_kwargs, self)

    def log(self, values: dict, step: Optional[int] = None, log_kwargs=None):
        from .tracking import telemetry_rows

        rows = telemetry_rows()
        if rows:
            # Telemetry rides along under its own prefix; the user's keys win
            # on collision.
            values = {**rows, **values}
        for tracker in self.trackers:
            tracker.log(values, step=step)

    def get_tracker(self, name: str, unwrap: bool = False):
        for tracker in self.trackers:
            if getattr(tracker, "name", None) == name:
                return tracker.tracker if unwrap else tracker
        raise ValueError(f"Tracker {name} not found")

    def end_training(self):
        # A deferred verified async save must publish before the run ends —
        # exiting with the manifest+rename pending would strand the final
        # checkpoint in `.tmp` for the next run's rotation to sweep.
        if getattr(self, "_pending_checkpoint_finalize", None) is not None or getattr(
            self, "_async_checkpointers", []
        ):
            self.wait_for_checkpoint()
        for tracker in self.trackers:
            tracker.finish()

    def __repr__(self):
        return f"Accelerator(state={self.state!r})"


@functools.partial(jax.jit, donate_argnums=(0,))
def _lomo_sgd_update(params, grads, lr):
    """Fused SGD fold-in for lomo_backward: params are donated so the update
    is in-place in HBM and the grads tree is dead after the call."""
    return jax.tree_util.tree_map(lambda p, g: p - lr * g.astype(p.dtype), params, grads)


def _is_optax_tx(obj) -> bool:
    import optax

    return isinstance(obj, optax.GradientTransformation)


def _is_scheduler_like(obj) -> bool:
    if callable(obj) and not hasattr(obj, "step"):
        return True
    if is_torch_available():
        import torch

        if isinstance(obj, torch.optim.lr_scheduler.LRScheduler):
            return True
    return hasattr(obj, "step") and hasattr(obj, "get_last_lr")
