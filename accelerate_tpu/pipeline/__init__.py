"""Overlapped execution pipeline — the "as fast as the hardware allows" layer.

Two coupled pieces (see ``docs/usage_guides/performance.md``):

- **async device prefetch** (``prefetch.py``) — a background thread performs
  the sharded ``device_put`` of the next 1-2 batches while the current step
  computes, so H2D transfer leaves the critical path.  Wired into the
  prepared dataloaders via ``DataLoaderConfiguration(prefetch_to_device=N)``
  or ``ACCELERATE_TPU_PREFETCH=N``.
- **fused train step** (``train_step.py``) — ``accelerator.make_train_step
  (model, optimizer)`` returns ONE jitted, buffer-donated callable doing
  forward+backward, gradient accumulation (``lax.scan``), optional clipping
  and the optax update: one Python→XLA dispatch per optimizer step instead
  of ``3 × accum_steps`` on the eager ``backward()``/``step()`` path, with
  bit-exact numerics.

Plus the **persistent XLA compilation cache** (``compile_cache.py``),
default-on and placed by ``JAX_COMPILATION_CACHE_DIR`` so repeated runs skip
the multi-minute warmup compile entirely, and the **CPU-tier perf-regression
gate** (``perf_gate.py``, ``make perf-gate``) that asserts the fused-path
invariants — 1 dispatch/step, the fused-vs-eager speedup, bounded
host-blocked time — against a committed baseline inside tier-1, so the
wins above cannot silently rot between chip runs.
"""

from .compile_cache import (
    DEFAULT_COMPILE_CACHE_DIR,
    ENV_COMPILE_CACHE,
    enable_compile_cache,
)
from .prefetch import (
    ENV_PREFETCH,
    DevicePrefetcher,
    cached_sharding,
    prefetch_depth_from_env,
    sharding_cache_info,
)
from .train_step import TrainStep, make_train_step

# perf_gate is intentionally NOT imported here: it pulls in torch/numpy probe
# machinery that the hot-path import of accelerate_tpu.pipeline must not pay
# for.  Use `python -m accelerate_tpu.pipeline.perf_gate` or import it
# directly (accelerate_tpu.pipeline.perf_gate).

__all__ = [
    "DevicePrefetcher",
    "cached_sharding",
    "sharding_cache_info",
    "prefetch_depth_from_env",
    "ENV_PREFETCH",
    "TrainStep",
    "make_train_step",
    "enable_compile_cache",
    "ENV_COMPILE_CACHE",
    "DEFAULT_COMPILE_CACHE_DIR",
]
