"""chipbench's own tests run on one CPU device with no persistent compile cache.

    python -m pytest chipbench/tests -q

They are not part of the repo's tier-1 run.  Nothing they time is a device metric.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("ACCELERATE_TPU_COMPILE_CACHE", "")
os.environ.setdefault("ACCELERATE_TPU_SENTINEL_PROFILE", "0")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

TEST_BENCHMARK = os.path.join(HERE, "data", "BENCHMARK.json")
CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
# run_cell looks the device's kind up in the cell's peaks; a test gives its made-up device made-up peaks
CPU_PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11}


def load_test_cell(run, workload):
    cell = run.load_cell(workload, TEST_BENCHMARK)
    cell["peaks"] = dict(cell["peaks"], cpu=CPU_PEAKS)
    return cell


@pytest.fixture(autouse=True)
def _reset_singletons():
    yield
    from accelerate_tpu.parallel.mesh import reset_global_mesh
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    reset_global_mesh()


@pytest.fixture(scope="session")
def run():
    import run as run_mod

    return run_mod


@pytest.fixture(scope="session")
def qwen2(run):
    return run.load_module("families", "qwen2")


@pytest.fixture(scope="session")
def tiny_cfg(run):
    return run.load_json(os.path.join(HERE, "data", "configs", "tiny-qwen2.json"))


def real_cfg(run, name):
    return run.load_json(os.path.join(BENCH, "configs", name + ".json"))
