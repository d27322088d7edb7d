"""Dependency-free observability for the training hot path.

Three pillars (see ``docs/usage_guides/telemetry.md``):

- **trace spans** — ``span("name")`` context-manager/decorator: wall-time,
  process index and nesting to a per-process JSONL file, mirrored into the
  profiler's trace (``annotate``, the profiler-only half) for Perfetto/XPlane
  dumps;
- **metrics registry** — counters/gauges/histograms with built-in collectors
  for step time, jit compile count/time (cache-miss detection via
  ``jax.monitoring``), tokens/sec, achieved-MFU, and device HBM bytes;
- **stall watchdog** — warns with a full thread dump when no step completes
  within a configurable deadline;
- **compiled-program introspection** — XLA cost/memory analysis, the
  per-program collective-communication ledger, and the resharding lint
  (``ACCELERATE_TPU_INTROSPECT=1``; see ``introspect.py`` /
  ``docs/package_reference/introspect.md``);
- **flight recorder + anomaly sentinel** — a bounded ring of per-step events
  flushed crash-safe on SIGTERM/exit/crash, with online rolling-median
  anomaly detection and a one-shot profiler capture
  (``ACCELERATE_TPU_FLIGHTREC=1``; see ``flightrec.py`` / ``sentinel.py`` /
  ``docs/package_reference/flightrec.md``);
- **HBM ledger** — per-subsystem memory attribution with a per-device
  conservation contract, OOM forensics (ranked-ledger postmortems into the
  flight recorder) and serving-headroom gauges (``memledger.py`` /
  ``docs/package_reference/memledger.md``);
- **goodput accounting + metrics export** — the wall-clock attribution
  ledger (every second classified into exactly one category, with a
  conservation invariant; ``ACCELERATE_TPU_GOODPUT=1``), fleet straggler
  aggregation (min-over-hosts goodput), and a Prometheus text-exposition
  endpoint / atomic snapshot (``ACCELERATE_TPU_METRICS_PORT`` /
  ``..._SNAPSHOT``; see ``goodput.py`` / ``export.py`` /
  ``docs/package_reference/goodput.md``).

Default-off: enable with ``ACCELERATE_TPU_TELEMETRY=1`` or
``telemetry.enable()``.  Summarize a run with
``python -m accelerate_tpu.telemetry.report <dir>``.
"""

from .core import (
    ENV_DIR,
    ENV_ENABLE,
    ENV_STALL_TIMEOUT,
    Telemetry,
    disable,
    enable,
    enabled,
    get_telemetry,
    maybe_enable_from_env,
)
from .metrics import (
    CompileWatcher,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    StepTimer,
    collect_hbm,
    peak_flops_per_chip,
    ridge_rows,
)
from .flightrec import FlightRecorder, get_flight_recorder
from .hlo_scan import CollectiveOp, CommsLedger, parse_collectives, scan_hlo
from .profile_scan import (
    ProfileReport as TraceProfileReport,
    analyze_trace_dir,
    analyze_trace_file,
)
from .export import MetricsExporter, render_prometheus
from .goodput import FleetAggregator, GoodputLedger
from .memledger import MemoryLedger, get_memory_ledger, tree_device_bytes
from .sentinel import AnomalySentinel
from .timeline import Timeline, TraceEvent, TraceParseError
from .introspect import (
    ENV_INTROSPECT,
    LintFinding,
    ProgramReport,
    capture,
    inspect_compiled,
    lint_reshardings,
)
from .spans import annotate, span
from .watchdog import StallWatchdog, thread_dump

__all__ = [
    "Telemetry",
    "get_telemetry",
    "enabled",
    "enable",
    "disable",
    "maybe_enable_from_env",
    "annotate",
    "span",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "StepTimer",
    "CompileWatcher",
    "collect_hbm",
    "peak_flops_per_chip",
    "ridge_rows",
    "StallWatchdog",
    "thread_dump",
    # flight recorder + anomaly sentinel
    "FlightRecorder",
    "get_flight_recorder",
    "AnomalySentinel",
    "ENV_ENABLE",
    "ENV_DIR",
    "ENV_STALL_TIMEOUT",
    # compiled-program introspection
    "ENV_INTROSPECT",
    "ProgramReport",
    "LintFinding",
    "CollectiveOp",
    "CommsLedger",
    "inspect_compiled",
    "capture",
    "lint_reshardings",
    "parse_collectives",
    "scan_hlo",
    # HBM ledger (per-subsystem memory attribution + OOM forensics)
    "MemoryLedger",
    "get_memory_ledger",
    "tree_device_bytes",
    # goodput accounting + metrics export
    "GoodputLedger",
    "FleetAggregator",
    "MetricsExporter",
    "render_prometheus",
    # trace-driven performance attribution
    "TraceProfileReport",
    "analyze_trace_dir",
    "analyze_trace_file",
    "Timeline",
    "TraceEvent",
    "TraceParseError",
]
