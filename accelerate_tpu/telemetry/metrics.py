"""Metrics registry: counters, gauges, histograms + built-in collectors.

Dependency-free by design (stdlib + jax only, and jax is touched lazily): the
registry must be constructible before any backend client exists, and a snapshot
must serialize straight into the JSONL sink or a tracker ``log()`` call.

Built-in collectors cover the signals the ROADMAP's perf work needs to prove
wins on ``bench.py``'s MFU metric:

- ``StepTimer`` — wall-time between completed optimizer steps, tokens/sec and
  an achieved-MFU estimate against the per-chip peak-FLOPs table (the same
  table ``bench.py`` uses).
- ``CompileWatcher`` — counts XLA backend compiles via ``jax.monitoring``
  duration events; every backend compile is a jit cache miss, so a moving
  count mid-training is the recompile signal GSPMD runs must not have.
- ``collect_hbm`` — live/peak device HBM bytes via ``device.memory_stats()``.
"""

from __future__ import annotations

import bisect
import collections
import threading
import time
from typing import Optional

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "StepTimer",
    "CompileWatcher",
    "collect_hbm",
    "peak_flops_per_chip",
    "ridge_rows",
]

# jax.monitoring key emitted once per compile REQUEST that missed the in-memory
# jit cache — a real backend compile, or (with the duration of the load) an
# executable the persistent cache returned.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# jax.monitoring event recorded once per persistent-compilation-cache hit
# (an executable deserialized instead of compiled).
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Counter:
    """Monotonic counter."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def inc(self, n: int = 1):
        self.value += n


class Gauge:
    """Last-value-wins scalar."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = None

    def set(self, value):
        self.value = float(value)


class Histogram:
    """Streaming distribution: exact count/sum/min/max plus a bounded window of
    recent observations for percentile estimates, and exact per-bucket counts
    over fixed bounds so the Prometheus exporter (``export.py``) can render a
    true ``_bucket``/``_sum``/``_count`` triplet over ALL observations, not
    just the recent window."""

    __slots__ = ("name", "count", "total", "min", "max", "last", "_recent", "bucket_counts")

    WINDOW = 1024
    # Exposition bucket upper bounds.  The registry's histograms are
    # millisecond-scale latencies (step time, TTFT, compile ms), so the
    # bounds span sub-ms to a minute; an implicit +Inf bucket catches the
    # rest.  Unit-free values (tokens/s) still render correctly — bucket
    # placement is just coarser.
    BOUNDS = (
        1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
        1000.0, 2500.0, 5000.0, 10000.0, 30000.0, 60000.0,
    )

    def __init__(self, name: str):
        self.name = name
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self.last = None
        self._recent = collections.deque(maxlen=self.WINDOW)
        self.bucket_counts = [0] * (len(self.BOUNDS) + 1)

    def observe(self, value):
        value = float(value)
        self.count += 1
        self.total += value
        self.last = value
        self.min = value if self.min is None else min(self.min, value)
        self.max = value if self.max is None else max(self.max, value)
        self._recent.append(value)
        self.bucket_counts[bisect.bisect_left(self.BOUNDS, value)] += 1

    def over_threshold_fraction(self, threshold: float) -> Optional[float]:
        """Fraction of the RECENT window strictly above ``threshold`` (the
        SLO burn-rate input; None before any observation)."""
        if not self._recent:
            return None
        over = sum(1 for v in self._recent if v > threshold)
        return over / len(self._recent)

    def summary(self) -> dict:
        if self.count == 0:
            return {"count": 0}
        data = sorted(self._recent)

        def pct(q):
            return data[min(int(q * len(data)), len(data) - 1)]

        return {
            "count": self.count,
            "mean": self.total / self.count,
            "min": self.min,
            "max": self.max,
            "last": self.last,
            "p50": pct(0.50),
            "p95": pct(0.95),
        }


class MetricsRegistry:
    """Name → metric store with get-or-create accessors and a flat snapshot."""

    def __init__(self):
        self._metrics: dict = {}
        self._lock = threading.Lock()

    def _get_or_create(self, name: str, cls):
        with self._lock:
            metric = self._metrics.get(name)
            if metric is None:
                metric = self._metrics[name] = cls(name)
            elif not isinstance(metric, cls):
                raise TypeError(
                    f"metric {name!r} already registered as {type(metric).__name__}"
                )
            return metric

    def counter(self, name: str) -> Counter:
        return self._get_or_create(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get_or_create(name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get_or_create(name, Histogram)

    def peek(self, name: str):
        """Read a metric WITHOUT creating it (None when absent) — for readers
        like the flight recorder that must not materialize metrics the
        instrumented path never touched."""
        with self._lock:
            return self._metrics.get(name)

    def reset(self):
        with self._lock:
            self._metrics.clear()

    def snapshot(self) -> dict:
        """Flat ``{name: scalar}`` view: counters/gauges as-is, histograms
        exploded into ``name.count/.mean/.p50/.p95/.max/.last``."""
        out: dict = {}
        with self._lock:
            metrics = list(self._metrics.values())
        for metric in metrics:
            if isinstance(metric, Histogram):
                for k, v in metric.summary().items():
                    if v is not None:
                        out[f"{metric.name}.{k}"] = v
            elif metric.value is not None:
                out[metric.name] = metric.value
        return out


# ---------------------------------------------------------------------------
# Built-in collectors
# ---------------------------------------------------------------------------

# Per-chip bf16 peak FLOP/s and HBM bytes/s by device kind, checked in order
# (the table bench.py's MFU math uses — kept here so the live MFU gauge and the
# benchmark can never disagree; the bandwidth stands beside the peak because
# the two together say where a matmul stops being bound by its weights' bytes,
# :func:`ridge_rows`).  "v5 lite"/"v5e" before "v5" so the lite chip does not
# match the v5p row.  Sources: Google Cloud's TPU documentation (v5e: 197
# TFLOP/s, 819 GB/s; v5p: 459, 2,765; v4: 275, 1,200; v6e: 918, 1,640).
_PEAK_FLOPS_TABLE = (
    ("v5 lite", 197e12, 819e9),
    ("v5e", 197e12, 819e9),
    ("v5p", 459e12, 2765e9),
    ("v5", 459e12, 2765e9),
    ("v4", 275e12, 1200e9),
    ("v6", 918e12, 1640e9),
    ("trillium", 918e12, 1640e9),
)


def _peaks(device_kind: str) -> Optional[tuple]:
    kind = device_kind.lower()
    for key, flops, hbm in _PEAK_FLOPS_TABLE:
        if key in kind:
            return flops, hbm
    return None


def peak_flops_per_chip(device=None) -> float:
    """bf16 peak FLOP/s for one chip of ``device``'s kind (default: device 0).
    A device kind the table does not list is a ``ValueError``: a utilization
    against a guessed peak is not a measurement."""
    if device is None:
        import jax

        device = jax.devices()[0]
    peaks = _peaks(device.device_kind)
    if peaks is None:
        raise ValueError(
            f"no peak FLOP/s known for device kind {device.device_kind!r} "
            f"(table: {[row[0] for row in _PEAK_FLOPS_TABLE]})"
        )
    return peaks[0]


def ridge_rows(device_kind: str) -> Optional[float]:
    """Rows up to which a dense matmul against two-byte weights is bound by the
    weights' bytes on a chip of this kind: ``n`` rows against ``[d, f]`` bf16
    weights are ``2 n d f`` FLOPs over ``2 d f`` bytes, so the two times meet at
    ``n`` = peak FLOP/s / HBM bytes/s (240 on a v5e).  ``None`` for a kind the
    table lacks (a CPU among them): the caller keeps what it did without one."""
    peaks = _peaks(device_kind)
    return None if peaks is None else peaks[0] / peaks[1]


def collect_hbm(registry: MetricsRegistry, device=None) -> dict:
    """Record device memory gauges across EVERY local device (or just
    ``device`` when given): worst-device live/peak bytes and the fleet-min
    headroom (``bytes_limit - bytes_in_use`` over all devices — the binding
    constraint, since the first chip to fill kills the whole SPMD program).

    ``hbm.stats_available`` is always published (1/0) so a dashboard can
    tell "no data" (CPU builds return no ``memory_stats()``)
    from "zero bytes"; the byte gauges only exist where stats do.
    """
    try:
        if device is not None:
            devices = [device]
        else:
            import jax

            devices = list(jax.local_devices())
    except Exception:
        return {}
    in_use, peak, headroom = [], [], []
    for d in devices:
        try:
            stats = d.memory_stats() or None
        except Exception:
            stats = None
        if not stats:
            continue
        if "bytes_in_use" in stats:
            in_use.append(int(stats["bytes_in_use"]))
            limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
            if limit:
                headroom.append(int(limit) - int(stats["bytes_in_use"]))
        if "peak_bytes_in_use" in stats:
            peak.append(int(stats["peak_bytes_in_use"]))
    available = bool(in_use or peak)
    registry.gauge("hbm.stats_available").set(1 if available else 0)
    out = {"hbm.stats_available": 1 if available else 0}
    if not available:
        return {}
    if in_use:
        registry.gauge("hbm.bytes_in_use").set(max(in_use))
        out["hbm.bytes_in_use"] = max(in_use)
    if peak:
        registry.gauge("hbm.peak_bytes").set(max(peak))
        out["hbm.peak_bytes"] = max(peak)
    if headroom:
        registry.gauge("hbm.fleet_min_headroom_bytes").set(min(headroom))
        out["hbm.fleet_min_headroom_bytes"] = min(headroom)
    return out


class StepTimer:
    """Wall-time between completed optimizer steps → step-time histogram,
    tokens/sec and achieved-MFU gauges (when configured with the workload's
    per-step token/FLOP counts)."""

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self.tokens_per_step: Optional[float] = None
        self.flops_per_step: Optional[float] = None
        # Per-program analyzed FLOPs from the compiled-program inspector
        # (introspect.py).  When the user never configured a static estimate,
        # their sum IS the per-step FLOP count — measured-cost MFU.
        self.measured_flops: dict = {}
        self._last: Optional[float] = None

    def configure(self, tokens_per_step=None, flops_per_step=None):
        if tokens_per_step is not None:
            self.tokens_per_step = float(tokens_per_step)
        if flops_per_step is not None:
            self.flops_per_step = float(flops_per_step)

    def record_measured_flops(self, program: str, flops: float):
        """Register the XLA-analyzed FLOPs of one compiled program in the step
        (called by the inspector; latest capture per program name wins).
        NOTE: ``cost_analysis`` FLOPs are PER DEVICE (the SPMD-partitioned
        module), unlike ``configure(flops_per_step=)``'s global estimate —
        the MFU math normalizes the two differently."""
        self.measured_flops[program] = float(flops)

    @property
    def effective_flops_per_step(self) -> Optional[float]:
        """Explicit static estimate if configured, else the summed analyzed
        cost of every inspected step program — measured beats assumed."""
        if self.flops_per_step:
            return self.flops_per_step
        if self.measured_flops:
            return sum(self.measured_flops.values())
        return None

    def reset(self):
        self._last = None
        self.measured_flops.clear()

    def step(self) -> Optional[float]:
        """Mark one completed step; returns the step duration in seconds (None
        for the first step — there is no prior boundary to measure from)."""
        now = time.perf_counter()
        self.registry.counter("step.count").inc()
        dt = None
        if self._last is not None:
            dt = now - self._last
            self.registry.histogram("step.time_ms").observe(dt * 1e3)
            if self.tokens_per_step:
                self.registry.gauge("step.tokens_per_sec").set(self.tokens_per_step / dt)
            try:
                if self.flops_per_step:
                    # Global static estimate: normalize by the whole fleet.
                    import jax

                    peak = peak_flops_per_chip() * jax.device_count()
                    self.registry.gauge("step.mfu").set(self.flops_per_step / dt / peak)
                elif self.measured_flops:
                    # Analyzed cost is per device (SPMD module): per-chip peak
                    # only — the same value as global MFU under symmetric SPMD.
                    flops = sum(self.measured_flops.values())
                    self.registry.gauge("step.mfu").set(
                        flops / dt / peak_flops_per_chip()
                    )
            except ValueError:
                pass  # device not in the peak table: publish no MFU
        self._last = now
        return dt


class CompileWatcher:
    """Standalone compile counter: registers ``jax.monitoring`` listeners and
    tallies compile requests (``count``/``total_ms``) and, among them, the
    ones the persistent cache answered (``cache_hits``) between construction
    and ``stop()``.

    jax has no per-listener unregister, so the listener stays installed but
    goes inert after ``stop()`` — construct sparingly (one per process is the
    intended shape; the telemetry singleton uses its own listener)."""

    def __init__(self):
        self.count = 0
        self.total_ms = 0.0
        self.cache_hits = 0
        self._active = True
        from jax import monitoring

        def _on_duration(event, duration, **kwargs):
            if self._active and event == COMPILE_EVENT:
                self.count += 1
                self.total_ms += duration * 1e3

        def _on_event(event, **kwargs):
            if self._active and event == CACHE_HIT_EVENT:
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)

    def stop(self):
        self._active = False
