"""The trace reduction on a trace recorded on the chip in PR 24 (two steps of
qwen2.5-1.5b.train_2x2048 on a TPU v5e, operation names shortened): the same
busy share, per-program time and top operations every time.  The numbers below
describe the fixture; they are not a benchmark result."""

import os

import pytest

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "fixtures", "train_2x2048.tpu_v5e.raw.json.gz")


@pytest.fixture(scope="module")
def trace(run):
    return run.load_module("", "trace")


def test_interval_arithmetic(trace):
    merged = trace.merge_intervals([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert merged == [(0, 3), (5, 6)] and trace.intervals_total(merged) == 4
    assert trace.clip_intervals(merged, 2, 5.5) == [(2, 3), (5, 5.5)]


def test_self_time_of_nested_operations(trace):
    events = [(0.0, 10.0, "%while.1 = x"), (1.0, 2.0, "%a = x"), (4.0, 3.0, "%b = x"), (4.5, 1.0, "%c = x"), (12.0, 1.0, "%d = x")]
    ops = {o[0]: o for o in trace.device_ops(events, [(0.0, 11.0, "jit_f"), (11.5, 2.0, "jit_g")], "dev")}
    assert ops["while.1"][5] == pytest.approx(5.0) and ops["b"][5] == pytest.approx(2.0) and ops["c"][5] == 1.0
    assert ops["a"][1] == "jit_f" and ops["d"][1] == "jit_g"


def test_names(trace):
    full = '%closed_call.8 = (bf16[2,12,2048,128]{3,2,1,0}) custom-call(bf16[2] %x), custom_call_target="tpu_custom_call"'
    assert trace.short_name(full) == "closed_call.8" + trace.MOSAIC_MARK
    assert trace.short_name('%c.7 = bf16[2] custom-call(bf16[1] %s), custom_call_target="ConcatBitcast"') == "c.7"
    assert trace.program_name("jit_step(7279325104279172636)") == "jit_step"


def test_fixture_reduces_the_same_every_time(trace):
    raw = trace.load_raw(FIXTURE)
    first, second = trace.reduce(raw), trace.reduce(trace.load_raw(FIXTURE))
    assert first == second
    assert first["window_s"] == pytest.approx(0.8)
    assert first["busy_s"] / first["window_s"] == pytest.approx(0.99994, abs=2e-5)
    assert first["program_s"]["jit_step"] == pytest.approx(first["busy_s"], rel=1e-4)
    assert first["executions"]["jit_step"] == 2
    top = [name for name, _ in first["device_ops"]]
    assert top[:3] == ["convolution_multiply_fusion.2", "fusion.419", "fusion.418"] and len(top) == 10
    mosaic = {k: v for k, v in first["op_s"].items() if k.endswith(trace.MOSAIC_MARK)}
    assert len(mosaic) == 4  # flash forward, its recomputation, and the two backward kernels
    assert sum(mosaic.values()) / first["busy_s"] == pytest.approx(0.109, abs=0.005)
    assert first["idle_gaps"][0][0] == trace.SHORT_GAPS


def test_gap_is_charged_to_the_span_that_covers_it(trace):
    raw = {
        "ops": [["a", "jit_f", 0.0, 1.0, "dev", 1.0], ["b", "jit_f", 3.0, 1.0, "dev", 1.0]],
        "programs": [["jit_f", 0.0, 4.0, "dev"]],
        "spans": [[trace.TRACED_SPAN, 0.0, 5.0], ["engine.step", 0.5, 2.0], ["submit", 2.5, 0.4]],
    }
    out = trace.reduce(raw)
    assert out["busy_s"] == 2.0 and out["window_s"] == 5.0
    assert dict(out["idle_gaps"]) == {"engine.step": 2.0, trace.NO_SPAN: 1.0}


def test_no_traced_span_no_numbers(trace):
    assert trace.reduce({"ops": [["a", "p", 0.0, 1.0, "dev", 1.0]], "programs": [], "spans": []}) == {}
