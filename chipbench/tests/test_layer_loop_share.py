"""``serve.layer_loop_share`` on the three ticks of qwen2.5-3b.chat_closed16 that PR 26 recorded on a TPU v5e
(``fixtures/chat_closed16.tpu_v5e.program.json.gz``): the parent of PR 27, where the layer scan still cut every
layer's slice out of the pool.  The numbers describe the fixture; they are not a benchmark result."""

import os

import pytest

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "fixtures")
CHAT = os.path.join(FIXTURES, "chat_closed16.tpu_v5e.program.json.gz")
TRAIN = os.path.join(FIXTURES, "train_2x2048.tpu_v5e.raw.json.gz")  # PR 24's: no scope in it
READER = "serve.layer_loop_share"


@pytest.fixture(scope="module")
def pt(run):
    return run.load_module("", "program_trace")


def chat_run(pt):
    window = pt.traced_window(CHAT)
    busy = window[1] - window[0] - sum(b - a for a, b in pt.idle_intervals(CHAT))
    return {"traced": {"raw_path": CHAT, "trace": {"busy_s": busy, "window_s": window[1] - window[0]}}}


def test_layer_loop_share_against_the_fixtures_layers_rows(pt, run):
    ops = pt.load(CHAT)["ops"]
    window = pt.traced_window(CHAT)
    by_hand = sum(
        op[5] for op in ops
        if op[1] in ("jit_prefill", "jit_decode") and op[2] < window[1] and op[2] + op[3] > window[0]
        and pt.innermost_scope(op[6]) == "layers"
    )
    # jit_decode's and jit_prefill's `layers` rows, as test_program_trace.py reads them
    assert by_hand == pytest.approx(0.045852 + 0.046033, abs=4e-6)
    # all of it is the scan's own slicing of the pool: dynamic_slice and squeeze under layers/while/body
    sliced = sum(op[5] for op in ops if op[6].endswith(("layers/while/body/dynamic_slice", "layers/while/body/squeeze")))
    assert sliced == pytest.approx(by_hand, rel=0.02)
    value = run.load_module("readers", READER).read(chat_run(pt))
    assert value == pytest.approx(100 * (0.045852 + 0.046033) / 0.194610, rel=1e-3) and 47.0 < value < 47.5


def test_layer_loop_share_finds_nothing_without_scopes(run):
    trace = run.load_module("", "trace")
    traced = {"raw_path": TRAIN, "trace": trace.reduce(trace.load_raw(TRAIN)), "counters": {"steps": 2}}
    reader = run.load_module("readers", READER)
    assert reader.read({"traced": traced}) is None
    assert reader.read({"traced": {"raw_path": None, "trace": {}}}) is None
