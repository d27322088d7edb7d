"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's `debug_launcher` strategy (reference ``launchers.py:268`` —
N CPU processes with gloo) translated to JAX: one process, 8 virtual CPU devices via
``--xla_force_host_platform_device_count``, so every mesh/sharding semantics test
runs without TPU hardware (SURVEY §4 "Implication for our build").
"""

import os

# Must be set before the CPU backend client is created.
os.environ["JAX_PLATFORMS"] = "cpu"
# Checkpoint tests assert write ORDERING (manifest-last atomic publish), not
# power-loss durability; per-file fsync on the CI filesystem costs real
# wall-clock across the suite's many save_state calls.
os.environ.setdefault("ACCELERATE_TPU_CHECKPOINT_FSYNC", "0")
# The persistent compilation cache is default-ON for real runs; the suite
# compiles thousands of tiny programs and must stay hermetic (no cross-run
# state, no per-program disk writes).  Tests of the cache itself place it in
# a tmpdir explicitly.
os.environ.setdefault("ACCELERATE_TPU_COMPILE_CACHE", "")
# Flight recorder hermeticity: the sentinel's one-shot jax.profiler capture
# must never fire inside the suite (it would drop trace dumps and fight other
# profiler tests), and any stray enable writes its snapshot under a tmpdir,
# not the checkout.  Tests of the recorder pass dir= explicitly.
os.environ.setdefault("ACCELERATE_TPU_SENTINEL_PROFILE", "0")
import tempfile as _tempfile

os.environ.setdefault(
    "ACCELERATE_TPU_FLIGHTREC_DIR",
    _tempfile.mkdtemp(prefix="atpu_test_flightrec_"),
)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import contextlib  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402


def without_apply_paged(family):
    """``family.apply_cached`` as a function of this module, which has no
    ``apply_paged``: the serving engine chooses its back end from the family
    alone, so this is how a test reaches the dense back end with a paged
    family's weights and oracle."""

    def apply_cached(params, input_ids, config, cache):
        return family.apply_cached(params, input_ids, config, cache)

    return apply_cached


@contextlib.contextmanager
def recorded_spans():
    """Every span the serving engine opens through ``telemetry.annotate``
    while the context is open, without a profiler session: a list of objects
    with ``name``, ``meta`` (the keywords, and what ``set_metadata`` added),
    ``start`` / ``end`` (``time.monotonic``) and ``parent`` (the span open
    around it, or None), in the order they were opened."""
    from accelerate_tpu.serving import engine

    spans, stack = [], []

    class Span:
        def __init__(self, name, **meta):
            self.name, self.meta, self.start, self.end, self.parent = name, meta, None, None, None

        def __enter__(self):
            self.parent = stack[-1] if stack else None
            stack.append(self)
            spans.append(self)
            self.start = time.monotonic()
            return self

        def __exit__(self, *exc):
            self.end = time.monotonic()
            stack.pop()
            return False

        def set_metadata(self, **meta):
            self.meta.update(meta)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(engine, "annotate", Span)
        yield spans


@pytest.fixture(autouse=True)
def _block_tables_from_one_block_up(monkeypatch):
    """The tiny engines of these suites bucket their block tables from one
    block up, as every engine did until PR 31, so that they keep crossing
    table widths at contexts of tens of rows.  The production floor
    (``serving/programs.py:MIN_TABLE_ROWS``, 256 rows) would hold them all at
    their one widest table; ``tests/test_serving_programs.py`` holds it."""
    from accelerate_tpu.serving import programs

    monkeypatch.setattr(programs, "MIN_TABLE_ROWS", 1)


@pytest.fixture(autouse=True)
def _reset_singletons():
    """Reference parity: ``AccelerateTestCase.tearDown`` (``test_utils/testing.py:
    610-621``) resets the three state singletons between tests — and takes the
    mesh ``AcceleratorState`` installed out of context, whoever cleared the
    state dict: an array a later fixture commits under a leftover mesh is
    refused by every jit that runs under another one."""
    yield
    from accelerate_tpu.parallel.mesh import reset_global_mesh
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    reset_global_mesh()
    assert jax.sharding.get_abstract_mesh().empty


_COMPLETED = {"n": 0}


@pytest.fixture(autouse=True)
def _periodic_jax_cache_clear():
    """Clear the jit/compilation caches every 150 tests.  A full-suite run
    accumulates thousands of compiled programs in one process (~6.5 GB RSS
    by the 90% mark), at which point XLA's CPU compiler has been observed
    to segfault inside backend_compile_and_load on an otherwise-green test
    (reproduced twice at the same suite position; the test passes in
    isolation and in earlier, smaller suite runs).  Bounding the cache
    trades a few recompiles for not crossing that cliff."""
    yield
    _COMPLETED["n"] += 1
    if _COMPLETED["n"] % 150 == 0:
        jax.clear_caches()
