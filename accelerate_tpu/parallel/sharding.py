"""GSPMD sharding engine — the TPU-native replacement for FSDP/ZeRO/TP wrappers.

Parity target: the *capability* of reference ``utils/fsdp_utils.py`` (737 LoC),
``FullyShardedDataParallelPlugin`` (``utils/dataclasses.py:1451-2020``) and the
DeepSpeed ZeRO stages (``accelerator.py:1804-2068``): parameter/gradient/optimizer
state sharding with configurable strategy.  Where the reference wraps modules in
engine classes that intercept forward/backward to all-gather and reduce-scatter,
here every parameter simply carries a `NamedSharding` and XLA compiles the same
collectives into the step function:

- FULL_SHARD      -> params, grads and optimizer state sharded on the ``fsdp`` axis
                     (== ZeRO-3; XLA all-gathers weights per layer, reduce-scatters
                     gradients — the exact pattern FSDP implements by hand).
- SHARD_GRAD_OP   -> params replicated, grads/opt-state sharded (== ZeRO-2): the
                     step applies updates on shards then all-gathers params once.
- NO_SHARD        -> plain data parallelism (== DDP).
- HYBRID_SHARD    -> shard within a slice (ici axes), replicate across ``dcn_dp``.

Auto-wrap policy analog: the reference decides *which submodules* get wrapped
(transformer_cls / min_num_params); here the unit is the parameter array —
``min_num_params`` keeps small arrays replicated, which is the same latency
optimization auto-wrap exists for.
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Any, Optional

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..utils.dataclasses import FullyShardedDataParallelPlugin

__all__ = [
    "spec_from_rules",
    "auto_fsdp_spec",
    "make_param_specs",
    "shard_params",
    "replicated",
    "data_sharding",
    "batch_spec",
    "constrain",
    "embed_lookup",
    "manual_region",
]


# Thread-local "inside a shard_map manual region" latch (parallel/zero.py
# traces the model forward/backward under shard_map with every mesh axis
# manual).  with_sharding_constraint on a manual axis is an error there, and
# the constraints are layout hints the manual region has already realized —
# so constrain() becomes a no-op while the latch is set.
_MANUAL = threading.local()


@contextlib.contextmanager
def manual_region():
    """Mark the current (tracing) thread as inside a fully-manual shard_map
    region: :func:`constrain` passes values through untouched."""
    prev = getattr(_MANUAL, "active", False)
    _MANUAL.active = True
    try:
        yield
    finally:
        _MANUAL.active = prev


def in_manual_region() -> bool:
    return getattr(_MANUAL, "active", False)


def _abstract_mesh():
    return jax.sharding.get_abstract_mesh()


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """``with_sharding_constraint`` that no-ops when no global mesh is installed
    (single-device use without an AcceleratorState).  Axes the mesh doesn't have
    are pruned per-dimension rather than dropping the whole constraint, so a
    user-installed mesh with a subset of our named axes still gets the valid
    placement hints."""
    if in_manual_region():
        # Inside the ZeRO shard_map region every mesh axis is manual: the
        # sharding is physically realized by the in/out specs, and a wsc
        # naming a manual axis would be an error.
        return x
    m = _abstract_mesh()
    if m.empty:
        return x

    def prune(dim):
        if dim is None:
            return None
        if isinstance(dim, tuple):
            kept = tuple(a for a in dim if a in m.axis_names)
            return kept if kept else None
        return dim if dim in m.axis_names else None

    pruned = P(*(prune(dim) for dim in spec))
    if all(dim is None for dim in pruned):
        return x
    return jax.lax.with_sharding_constraint(x, pruned)


def embed_lookup(table: jax.Array, input_ids: jax.Array, dtype) -> jax.Array:
    """Embedding lookup that stays efficient under SPMD model sharding.

    A plain gather from a model-sharded table produces an output whose feature
    dim inherits the table's ``fsdp``/``tp`` sharding while its batch dim is
    replicated; re-constraining that onto batch-over-data-axes makes XLA's SPMD
    partitioner emit "Involuntary full rematerialization" (replicate the whole
    [B, S, D] activation, then re-partition — a step-time cliff on the DCN path
    of a multislice mesh).  Expressed as a one-hot matmul, the same lookup
    partitions like every other weight matmul: XLA all-gathers the table shard
    (the standard FSDP pattern) and the output comes out batch-sharded with no
    resharding; the backward becomes an MXU matmul instead of a scatter-add.
    For in-range ids the numerics are exact either way (one nonzero per
    one-hot row); out-of-range ids differ — gather wraps negatives / clamps
    overflow, one-hot returns a zero embedding — both are silent garbage, so
    callers must pass valid ids (the reference's nn.Embedding errors instead).

    Outside a table-sharding mesh the gather is cheaper, so it stays.  The
    gate is the ``fsdp``/``tp`` axis sizes — the only axes whose PARTITION
    rules shard the vocab table.  ``sp``/``ep`` shard activations/experts but
    leave the table replicated, and a gather from a replicated table
    partitions cleanly (output inherits the ids' sharding), so those meshes
    keep the gather: at a 128k vocab the one-hot contraction is ~2*V*D FLOPs
    per token — ≈10% of the 6N step FLOPs — far too much to pay when the
    table is not actually sharded.  The gate is mesh-axis sizes, not the
    table's actual layout, so a config that keeps params replicated on an
    active ``fsdp`` axis (SHARD_GRAD_OP-style) still pays the contraction;
    the table's true sharding is not visible on traced values in
    auto-sharding mode.  Decode paths keep the gather: most call it directly,
    and the trailing-dim-1 guard below catches single-token lookups routed
    through shared embed helpers (a [B, 1, V] one-hot would read the whole
    table per token).
    """
    single_token = input_ids.ndim >= 1 and input_ids.shape[-1] == 1
    m = _abstract_mesh()
    if (
        not single_token
        and any(dict(m.shape).get(a, 1) > 1 for a in ("fsdp", "tp"))
    ):
        one_hot = jax.nn.one_hot(input_ids, table.shape[0], dtype=dtype)
        return one_hot @ table.astype(dtype)
    return table.astype(dtype)[input_ids]


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_spec(mesh: Mesh) -> P:
    """PartitionSpec for a batch dimension: all data-consuming axes."""
    from .mesh import data_axes

    axes = data_axes(mesh)
    return P(axes if axes else None)


def data_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(mesh))


def spec_from_rules(path: str, ndim: int, rules: list[tuple[str, P]]) -> Optional[P]:
    for pattern, spec in rules:
        if re.search(pattern, path):
            if len(spec) > ndim:
                # Rule written for a higher-rank tensor under the same path
                # prefix (e.g. an `embeddings/` matrix rule hitting a norm
                # scale): replicate instead of producing an invalid sharding.
                # Shorter-than-rank specs are legal (trailing dims replicate).
                continue
            return spec
    return None


def _divisible_axis(shape: tuple[int, ...], axis_size: int, taken: set[int]) -> Optional[int]:
    """Largest dim divisible by ``axis_size`` not already sharded."""
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if i not in taken and shape[i] % axis_size == 0 and shape[i] >= axis_size:
            return i
    return None


def auto_fsdp_spec(
    shape: tuple[int, ...],
    mesh: Mesh,
    existing: Optional[P] = None,
    min_size: int = 0,
    axis: str = "fsdp",
) -> P:
    """Assign the ``fsdp`` axis to the best free dimension of a parameter.

    The reference's auto-wrap policy decides which modules to FSDP-wrap
    (``utils/dataclasses.py`` transformer/size policies); the GSPMD analog is
    per-array: arrays under ``min_size`` elements (or with no divisible dim) stay
    replicated on the fsdp axis.
    """
    if axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return existing if existing is not None else P(*([None] * len(shape)))
    n = int(np.prod(shape)) if shape else 0
    spec = list(existing) if existing is not None else [None] * len(shape)
    while len(spec) < len(shape):
        spec.append(None)
    taken = set()
    for i, s in enumerate(spec):
        if s is not None:
            if axis == s or (isinstance(s, tuple) and axis in s):
                return P(*spec)  # already sharded on this axis
            taken.add(i)
    if n < max(min_size, 2) :
        return P(*spec)
    dim = _divisible_axis(shape, mesh.shape[axis], taken)
    if dim is None:
        return P(*spec)
    spec[dim] = axis if spec[dim] is None else (spec[dim], axis)
    return P(*spec)


def _path_str(key_path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in key_path)


def make_param_specs(
    params: Any,
    mesh: Mesh,
    fsdp_plugin: Optional[FullyShardedDataParallelPlugin] = None,
    rules: Optional[list[tuple[str, P]]] = None,
) -> Any:
    """Build the PartitionSpec pytree for a parameter pytree.

    Precedence: explicit ``rules`` (e.g. a model's tensor-parallel table) first,
    then the FSDP strategy fills a free dimension, mirroring how the reference
    composes TP (transformers-provided) with FSDP wrapping.
    """
    shards_params = (
        fsdp_plugin is not None
        and fsdp_plugin.shards_parameters
        and "fsdp" in mesh.axis_names
        and mesh.shape["fsdp"] > 1
    )
    min_size = fsdp_plugin.min_num_params if fsdp_plugin is not None else 0

    def one(key_path, leaf):
        shape = tuple(np.shape(leaf))
        path = _path_str(key_path)
        spec = spec_from_rules(path, len(shape), rules) if rules else None
        if spec is not None:
            # Clip rule specs to mesh axes that are actually active; the plugin
            # strategy owns the fsdp axis — NO_SHARD/SHARD_GRAD_OP keep params
            # replicated on it even when a rule names it.
            def keep(s):
                if s is None:
                    return None
                # Strip inactive axes (and, when the strategy keeps params
                # replicated, the fsdp axis) from the spec entry; tuples keep
                # their remaining members.
                axes = s if isinstance(s, tuple) else (s,)
                kept = tuple(
                    a
                    for a in axes
                    if _axis_active(mesh, a) and (shards_params or a != "fsdp")
                )
                if not kept:
                    return None
                return kept if len(kept) > 1 else kept[0]

            spec = P(
                *[keep(s) for s in (list(spec) + [None] * (len(shape) - len(spec)))][: len(shape)]
            )
        if shards_params:
            spec = auto_fsdp_spec(shape, mesh, existing=spec, min_size=min_size)
        elif spec is None:
            spec = P(*([None] * len(shape)))
        return spec

    return jax.tree_util.tree_map_with_path(one, params)


def _axis_active(mesh: Mesh, axis) -> bool:
    if axis is None:
        return False
    if isinstance(axis, tuple):
        return all(a in mesh.axis_names and mesh.shape[a] > 1 for a in axis)
    return axis in mesh.axis_names and mesh.shape[axis] > 1


def shard_params(params: Any, mesh: Mesh, specs: Any) -> Any:
    """Place a parameter pytree onto the mesh according to ``specs``.

    This is the moment the reference spends in FSDP's ``sync_module_states`` /
    meta-device ``param_init_fn`` machinery (``accelerator.py:1611-1738``) — here
    it is one ``device_put`` per array (XLA slices or broadcasts as needed).
    """
    return jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs
    )
