"""AOT compiled-program inspector: XLA cost/memory analysis, comms ledger,
resharding lint.

PR 1's telemetry answers *when* a step is slow; this module answers *why*:
given a compiled jax program it reports

- ``cost_analysis()`` — FLOPs and bytes accessed by the optimized executable
  (measured cost, not the 6ND estimate);
- ``memory_analysis()`` — the HBM breakdown: argument / output / temp /
  generated-code bytes;
- the **comms ledger** (``hlo_scan``): every collective XLA's SPMD partitioner
  inserted, with byte volumes per mesh axis and an estimated comms/compute
  time ratio;
- the **resharding lint**: arrays entering the step whose live sharding
  differs from what the compiled program expects (each call pays a
  device-to-device resharding copy), and large parameters left
  replicated-by-default on a mesh with active model axes (the
  under-constrained-annotation failure mode of GSPMD propagation).

Default-off.  ``ACCELERATE_TPU_INTROSPECT=1`` hooks it transparently into the
first call of every prepared model's compiled step (one extra AOT compile per
program — the jit cache is not shared with ``lower().compile()``); or call
:func:`inspect_compiled` / :func:`capture` directly.  Reports are written to
the telemetry JSONL sink as ``{"kind": "introspect", ...}`` records when
telemetry is enabled, and surfaced by ``telemetry.report``.
"""

from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Any, Optional

import numpy as np

from .hlo_scan import CommsLedger, scan_hlo
from .metrics import peak_flops_per_chip

__all__ = [
    "ENV_INTROSPECT",
    "ProgramReport",
    "LintFinding",
    "enabled_from_env",
    "inspect_compiled",
    "capture",
    "lint_reshardings",
    "estimate_comms_compute_ratio",
]

ENV_INTROSPECT = "ACCELERATE_TPU_INTROSPECT"

_TRUTHY = {"1", "true", "yes", "on"}

# Per-chip interconnect bandwidth (bytes/s) by device kind — rough ICI figures
# for the comms/compute time ratio ONLY (order-of-magnitude triage, not a
# roofline).  Checked in order; "v5 lite"/"v5e" before "v5" (see
# metrics._PEAK_FLOPS_TABLE).
_ICI_BW_TABLE = (
    ("v5 lite", 1.6e11),
    ("v5e", 1.6e11),
    ("v5p", 4.8e11),
    ("v5", 4.8e11),
    ("v4", 2.4e11),
    ("v6", 3.6e11),
    ("trillium", 3.6e11),
)
_DEFAULT_ICI_BW = 1.0e11

# Params below this byte size are fine replicated (the min_num_params analog:
# sharding tiny arrays costs more in collective latency than it saves in HBM).
_REPLICATED_LINT_MIN_BYTES = 1 << 20

# Count of capture() invocations this process — the "zero overhead when the
# env flag is unset" tests assert this stays 0.
CAPTURE_COUNT = 0


def enabled_from_env() -> bool:
    return os.environ.get(ENV_INTROSPECT, "").strip().lower() in _TRUTHY


def _ici_bandwidth(device=None) -> float:
    try:
        if device is None:
            import jax

            device = jax.devices()[0]
        kind = device.device_kind.lower()
    except Exception:
        return _DEFAULT_ICI_BW
    for key, bw in _ICI_BW_TABLE:
        if key in kind:
            return bw
    return _DEFAULT_ICI_BW


@dataclasses.dataclass
class LintFinding:
    """One resharding-lint warning."""

    kind: str  # "implicit-reshard" | "replicated-by-default"
    path: str  # input pytree path
    message: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ProgramReport:
    """Everything the inspector learned about one compiled program."""

    name: str
    flops: float  # cost_analysis FLOPs (per device, optimized program)
    bytes_accessed: float  # cost_analysis memory traffic
    memory: dict  # argument/output/temp/generated_code bytes (per device)
    ledger: CommsLedger
    comms_compute_ratio: Optional[float]  # est. comm time / compute time
    lint: list  # list[LintFinding]

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "memory": self.memory,
            "comms": self.ledger.to_dict(),
            "comms_compute_ratio": self.comms_compute_ratio,
            "lint": [f.to_dict() for f in self.lint],
        }


def estimate_comms_compute_ratio(
    comm_bytes: float, flops: float, device=None
) -> Optional[float]:
    """Estimated collective-time / compute-time ratio for one program.

    ``comm_bytes / ICI_bw`` over ``flops / peak_flops`` — both per device.  A
    ratio near or above 1 means the step is communication-bound and no kernel
    work will move the roofline; far below 1 means collectives are not the
    bottleneck.  Rough by construction (no overlap modeling, flat per-kind
    cost): use it to rank programs, not to predict step time.
    """
    if not flops or flops <= 0:
        return None
    try:
        peak = peak_flops_per_chip(device)
    except ValueError:  # device not in the peak table: no estimate
        return None
    compute_s = flops / peak
    comm_s = float(comm_bytes) / _ici_bandwidth(device)
    if compute_s <= 0:
        return None
    return comm_s / compute_s


def _cost_analysis(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    return dict(ca) if ca else {}


def _memory_analysis(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for key in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ):
        val = getattr(ma, key, None)
        if val is not None:
            out[key.replace("_size_in_bytes", "_bytes")] = int(val)
    return out


def _spec_of(sharding) -> Optional[tuple]:
    spec = getattr(sharding, "spec", None)
    return tuple(spec) if spec is not None else None


def _is_fully_replicated(sharding, ndim: int) -> bool:
    try:
        return bool(sharding.is_fully_replicated)
    except Exception:
        spec = _spec_of(sharding)
        return spec is None or all(s is None for s in spec)


def _leaf_paths(tree) -> list[str]:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [
        "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        for path, _ in flat
    ]


def lint_reshardings(
    compiled,
    args: tuple,
    mesh=None,
    declared_specs: Any = None,
) -> list[LintFinding]:
    """Compare the shardings of arrays entering a compiled step against what
    the program expects (and, for params, what ``prepare()`` declared).

    ``args`` is the positional-arg tuple the program is called with (the same
    one it was lowered from).  Two findings:

    - **implicit-reshard** — a live input's sharding differs from the compiled
      program's expected input sharding: every call pays a resharding copy
      before the step body runs (the silent device_put GSPMD inserts).
    - **replicated-by-default** — a large (>=1 MiB) floating-point input ends
      up fully replicated although the mesh has active model axes
      (``fsdp``/``tp``/``ep``): nothing constrained it, so propagation fell
      back to replication — the under-constrained-annotation case of
      arXiv:2105.04663.  ``declared_specs`` (the PartitionSpec tree
      ``prepare()`` built, pytree-prefix of ``args[0]``) suppresses this for
      leaves the rules *deliberately* replicate.
    """
    import jax

    findings: list[LintFinding] = []
    try:
        expected, _ = compiled.input_shardings
        # One entry per *argument*, each a pytree of shardings mirroring that
        # argument's structure — flatten to align with the args' leaves.
        expected = jax.tree_util.tree_leaves(expected)
    except Exception:
        return findings
    leaves, _ = jax.tree_util.tree_flatten(args)
    paths = _leaf_paths(args)
    if len(expected) != len(leaves):
        return findings  # donated/pruned args changed the flat arity; bail

    model_axes_active = False
    if mesh is not None:
        model_axes_active = any(
            a in mesh.axis_names and mesh.shape[a] > 1 for a in ("fsdp", "tp", "ep")
        )

    declared_flat = None
    if declared_specs is not None:
        from jax.sharding import PartitionSpec

        try:
            declared_flat = jax.tree_util.tree_leaves(
                declared_specs,
                is_leaf=lambda s: s is None or isinstance(s, PartitionSpec),
            )
        except Exception:
            declared_flat = None

    for i, (leaf, want) in enumerate(zip(leaves, expected)):
        if not isinstance(leaf, jax.Array):
            continue
        path = paths[i] if i < len(paths) else str(i)
        have = leaf.sharding
        ndim = leaf.ndim
        equivalent = True
        try:
            equivalent = have.is_equivalent_to(want, ndim)
        except Exception:
            equivalent = _spec_of(have) == _spec_of(want)
        if not equivalent:
            findings.append(
                LintFinding(
                    kind="implicit-reshard",
                    path=path,
                    message=(
                        f"input {path!r} arrives as {_spec_of(have)} but the "
                        f"compiled step wants {_spec_of(want)} — every call "
                        "pays a resharding copy before the step runs. "
                        "device_put it onto the expected sharding once (or fix "
                        "the producing op's constraint)."
                    ),
                )
            )
            continue
        # Under-constrained check: large floating leaf, fully replicated, on a
        # mesh that could shard it — unless the declared spec says replicate.
        if not model_axes_active:
            continue
        if not np.issubdtype(np.dtype(leaf.dtype), np.floating):
            continue
        if leaf.size * leaf.dtype.itemsize < _REPLICATED_LINT_MIN_BYTES:
            continue
        if not _is_fully_replicated(want, ndim):
            continue
        if declared_flat is not None and i < len(declared_flat):
            spec = declared_flat[i]
            if spec is not None and any(s is not None for s in tuple(spec)):
                # Declared sharded but compiled replicated — propagation
                # dropped the annotation; that IS the finding.
                findings.append(
                    LintFinding(
                        kind="implicit-reshard",
                        path=path,
                        message=(
                            f"param {path!r} was declared {tuple(spec)} but the "
                            "compiled program runs it fully replicated — the "
                            "sharding annotation was lost before partitioning."
                        ),
                    )
                )
                continue
            if spec is not None:
                continue  # deliberately replicated by the rules: no finding
        findings.append(
            LintFinding(
                kind="replicated-by-default",
                path=path,
                message=(
                    f"input {path!r} ({leaf.size * leaf.dtype.itemsize} bytes) is "
                    "fully replicated on a mesh with active model axes — no "
                    "sharding rule constrained it, so GSPMD propagation fell "
                    "back to replication. Add a partition rule (or an "
                    "auto-fsdp spec) if this array should be sharded."
                ),
            )
        )
    return findings


def inspect_compiled(
    compiled,
    name: str = "program",
    mesh=None,
    args: Optional[tuple] = None,
    declared_specs: Any = None,
    device=None,
) -> ProgramReport:
    """Build a :class:`ProgramReport` from a ``jax.stages.Compiled`` — pure
    analysis, never executes the program."""
    cost = _cost_analysis(compiled)
    flops = float(cost.get("flops", 0.0) or 0.0)
    bytes_accessed = float(cost.get("bytes accessed", 0.0) or 0.0)
    memory = _memory_analysis(compiled)
    if memory:
        # Feed the HBM ledger's conservation contract: temp/scratch +
        # generated-code bytes are memory the *program* owns — neither a
        # registered live array nor unattributed residue (argument/output
        # bytes ARE live arrays and would double-count).
        from .memledger import get_memory_ledger

        get_memory_ledger().note_program_bytes(
            name,
            int(memory.get("temp_bytes", 0)) + int(memory.get("generated_code_bytes", 0)),
        )
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    ledger = scan_hlo(hlo, mesh)
    ratio = estimate_comms_compute_ratio(ledger.total_bytes, flops, device)
    lint = (
        lint_reshardings(compiled, args, mesh, declared_specs)
        if args is not None
        else []
    )
    return ProgramReport(
        name=name,
        flops=flops,
        bytes_accessed=bytes_accessed,
        memory=memory,
        ledger=ledger,
        comms_compute_ratio=ratio,
        lint=lint,
    )


def capture(
    jitted,
    args: tuple,
    name: str = "program",
    mesh=None,
    declared_specs: Any = None,
    warn: bool = True,
    count_in_step: bool = True,
) -> Optional[ProgramReport]:
    """AOT lower+compile ``jitted`` on ``args`` and inspect the result.

    The transparent hook behind ``ACCELERATE_TPU_INTROSPECT=1``: writes the
    report to the telemetry sink (when telemetry is enabled), feeds the
    measured FLOPs into the MFU collector, and emits each lint finding as a
    Python warning.  Never raises — introspection must not take down the
    training step it is observing.

    ``count_in_step``: whether this program runs once per training step and
    should therefore count toward the measured-cost MFU (the fused train
    step does; an eval forward or a bridge-mode partial program does not —
    summing those would systematically skew ``step.mfu``).
    """
    global CAPTURE_COUNT
    CAPTURE_COUNT += 1
    try:
        compiled = jitted.lower(*args).compile()
        report = inspect_compiled(
            compiled, name=name, mesh=mesh, args=args, declared_specs=declared_specs
        )
    except Exception as e:  # pragma: no cover - backend-specific failures
        warnings.warn(f"introspection of {name!r} failed: {e}")
        return None
    _publish(report, count_in_step=count_in_step)
    if warn:
        for finding in report.lint:
            warnings.warn(f"[resharding lint] {finding.message}")
    return report


def _publish(report: ProgramReport, count_in_step: bool = True) -> None:
    """Write the report into the telemetry stream and the MFU collector."""
    from .core import get_telemetry

    tel = get_telemetry()
    if report.flops > 0:
        # Measured-cost MFU: the step timer prefers the summed analyzed FLOPs
        # of the inspected step programs over any static 6ND estimate.
        if count_in_step:
            tel.step_timer.record_measured_flops(report.name, report.flops)
        tel.registry.gauge(f"introspect.{report.name}.flops").set(report.flops)
    if report.ledger.total_bytes:
        tel.registry.gauge(f"introspect.{report.name}.comms_bytes").set(
            report.ledger.total_bytes
        )
    if not tel.enabled:
        return
    tel.write({"kind": "introspect", **report.to_dict()})
