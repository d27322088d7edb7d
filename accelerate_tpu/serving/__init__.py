"""Production serving layer: continuous batching over a paged KV cache.

Three pieces (see ``docs/usage_guides/serving.md``):

- **blocks** — a fixed-size-block KV pool with a free-list allocator and
  per-request block tables, so heterogeneous sequence lengths stop tiling
  HBM to the maximum context (``blocks.py``);
- **scheduler** — the continuous-batching request scheduler: admission
  queue, slot map, LIFO preemption under block pressure
  (``scheduler.py``);
- **engine** — the serving engine itself: one fused jitted decode step
  over the in-flight batch per tick plus bounded chunked prefill, with
  per-request SLO metrics (TTFT, inter-token latency, queue wait)
  published through the telemetry registry (``engine.py``); the jitted
  programs and how a dispatch reads the pool, paged or through a dense
  view as the family decides (``programs.py``).

Entry point: :meth:`accelerate_tpu.Accelerator.prepare_serving`, or
construct :class:`ServingEngine` directly from a model family's
``apply_cached``/``init_cache`` pair.

Robustness layer (overload shedding, request deadlines, poison-request
quarantine, crash-recovery journal): ``engine.py`` + ``journal.py``, proven
under fire by the seeded serving chaos campaign (``serving/chaos.py``,
``make serving-chaos-smoke``).

KV survivability layer (``host_blocks > 0``): a host-DRAM second tier for
the paged pool (``blocks.HostBlockPool``) — preemption demotes the
victim's blocks and re-admission promotes them back (zero re-prefill
dispatches), cold prefix chains spill on LRU eviction, and admission
demotes proactively under the memory-headroom watermark; proven by the
tiered chaos campaign (``make tiering-chaos-smoke``) and the perf-gate
tiering row. See ``docs/usage_guides/serving.md`` ("KV tiering & memory
pressure").

Observability layer (per-request phase traces, tail-latency blame
decomposition, Chrome-trace export, live ``/debug`` endpoints):
``tracing.py`` + the metrics HTTP server, walked through in
``docs/usage_guides/serving.md`` ("Tracing a slow request") and specified
in ``docs/package_reference/serving_tracing.md``.
"""

from .blocks import (
    BlockAllocator,
    BlockOutOfMemory,
    HostBlockPool,
    PagedKVCache,
    PrefixCache,
)
from .drafter import DraftModelDrafter, NgramDrafter
from .engine import (
    AdmissionRejected,
    CompletedRequest,
    ServingConfig,
    ServingEngine,
)
from .journal import JournalError, ServingJournal
from .scheduler import Request, RequestState, Scheduler
from .tracing import (
    RequestTrace,
    ServingTracer,
    export_chrome_trace,
    load_serving_traces,
    stitch_traces,
    summarize_traces,
)

__all__ = [
    "AdmissionRejected",
    "BlockAllocator",
    "BlockOutOfMemory",
    "HostBlockPool",
    "PagedKVCache",
    "PrefixCache",
    "CompletedRequest",
    "DraftModelDrafter",
    "JournalError",
    "NgramDrafter",
    "Request",
    "RequestState",
    "RequestTrace",
    "Scheduler",
    "ServingConfig",
    "ServingEngine",
    "ServingJournal",
    "ServingTracer",
    "export_chrome_trace",
    "load_serving_traces",
    "stitch_traces",
    "summarize_traces",
]
