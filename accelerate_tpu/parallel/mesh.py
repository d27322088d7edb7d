"""Named device-mesh construction — the TPU-native replacement for process groups.

The reference builds torch process groups / DeviceMeshes per engine (e.g.
``TorchTensorParallelPlugin`` ``utils/dataclasses.py:2022-2058``, DeepSpeed AutoTP
``accelerator.py:1817-1830``); here ONE `jax.sharding.Mesh` with named axes carries
every strategy, and XLA compiles collectives onto ICI/DCN links from sharding
annotations alone.

Axis order (outermost-first) = ``ParallelismConfig.AXIS_ORDER``:
``(dcn_dp, dp, fsdp, pp, sp, ep, tp)``.  ``tp`` is innermost so tensor-parallel
collectives (highest frequency, smallest payload latency tolerance) map onto
nearest-neighbor ICI links; ``dcn_dp`` is outermost so only low-frequency gradient
all-reduces cross the data-center network on multislice.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh

from ..telemetry import span as _span
from ..utils.dataclasses import ParallelismConfig

__all__ = ["build_mesh", "mesh_axis_names", "data_axes", "model_axes", "local_mesh_shape"]

# Axes over which the *batch* is sharded (data-consuming axes).
DATA_AXES = ("dcn_dp", "dp", "fsdp")
# Axes over which *weights* may be sharded.
MODEL_AXES = ("fsdp", "pp", "ep", "tp")

def mesh_axis_names() -> tuple[str, ...]:
    return tuple(ParallelismConfig.AXIS_ORDER)


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes that consume distinct data shards (size > 1)."""
    return tuple(a for a in DATA_AXES if a in mesh.axis_names and mesh.shape[a] > 1)


def model_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in MODEL_AXES if a in mesh.axis_names and mesh.shape[a] > 1)


@_span("mesh.build")
def build_mesh(
    cfg: ParallelismConfig,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the global mesh for ``cfg``.

    On real TPU topologies ``jax.make_mesh`` (mesh_utils under the hood) arranges
    devices so that inner axes are ICI-contiguous, and a shape it cannot lay
    out is an error — a plain reshape there would silently drop the ICI-aware
    layout.  The reshape of ``devices`` in enumeration order is kept for an
    explicit ``devices=`` and for the topology-free CPU simulation mesh.
    """
    axis_names = mesh_axis_names()
    shape = tuple(getattr(cfg, a) for a in axis_names)
    # Auto axis types: shardings are GSPMD *hints* (with_sharding_constraint
    # propagates), not the assert semantics of Explicit mode.
    axis_types = (jax.sharding.AxisType.Auto,) * len(axis_names)
    if devices is None:
        if jax.default_backend() != "cpu":
            return jax.make_mesh(shape, axis_names, axis_types=axis_types)
        devices = jax.devices()
    n = int(np.prod(shape))
    if len(devices) < n:
        raise ValueError(f"Need {n} devices for mesh {dict(zip(axis_names, shape))}, have {len(devices)}")
    dev_array = np.asarray(devices[:n]).reshape(shape)
    return Mesh(dev_array, axis_names, axis_types=axis_types)


def local_mesh_shape(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


# The handle of the mesh this module installed with ``jax.set_mesh`` — its
# ``__exit__`` restores whatever was in context before (normally nothing).
# jax keeps that context per thread: install and reset on the same thread (the
# state singletons are built and reset on the main one).
_INSTALLED: Optional[jax.set_mesh] = None


def install_global_mesh(mesh: Mesh) -> None:
    """Install ``mesh`` as the global mesh context so bare-``PartitionSpec``
    sharding constraints inside model code resolve against it.  Replaces a
    mesh installed earlier by this function."""
    global _INSTALLED
    reset_global_mesh()
    _INSTALLED = jax.set_mesh(mesh)


def reset_global_mesh() -> None:
    """Take the installed mesh out of context.  Nothing is left behind: jit
    refuses arguments committed to devices other than the context mesh's, so
    a stale one-device mesh would poison every later multi-device program."""
    global _INSTALLED
    if _INSTALLED is not None:
        _INSTALLED.__exit__(None, None, None)
        _INSTALLED = None
