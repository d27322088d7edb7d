"""chipbench/program_trace.py on three ticks of qwen2.5-3b.chat_closed16 recorded
on a TPU v5e in PR 26 (``fixtures/chat_closed16.tpu_v5e.program.json.gz``: every
device operation with its full ``op_name``, the host spans with their keywords,
times from half a millisecond before tick 1019; the ``chipbench.traced`` span is
cut to the three ticks, and the last 0.47 ms of tick 1018 lie inside it).  The
numbers below describe the fixture; they are not a benchmark result."""

import os

import pytest

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "fixtures")
CHAT = os.path.join(FIXTURES, "chat_closed16.tpu_v5e.program.json.gz")
TRAIN = os.path.join(FIXTURES, "train_2x2048.tpu_v5e.raw.json.gz")  # PR 24's: no scope, no program span
PHASES = ["admit", "prefill.build", "prefill.wait", "prefill.emit", "decode.build", "decode.wait", "decode.emit", "publish"]
NEW_READERS = ["serve.kv_pool_share", "serve.idle_build_ms", "serve.idle_readback_ms", "train.recompute_share",
               "train.optimizer_share"]


@pytest.fixture(scope="module")
def pt(run):
    return run.load_module("", "program_trace")


def test_host_spans_of_a_tick(pt):
    ticks = [s for s in pt.host_spans(CHAT, "serving.tick") if s[0] == "serving.tick"]
    assert [s[3]["tick"] for s in ticks] == [1018, 1019, 1020, 1021] and pt.ticks_in_window(CHAT) == 3
    assert ticks[1][3] == {"tick": 1019, "queued": 0, "prefilling": 8, "decoding": 8}
    mine = [s for s in pt.host_spans(CHAT, "serving.tick.") if s[3]["tick"] == 1019]
    assert [s[0] for s in mine] == ["serving.tick." + p for p in PHASES]  # by start, the tick's own order
    assert all(ticks[1][1] <= s[1] and s[2] <= ticks[1][2] for s in mine)
    build = mine[1][3]
    assert build == {"tick": 1019, "request": 77, "start": 288, "rows": 32, "width": 32}
    assert pt.host_spans(CHAT, "no.such") == [] and pt.traced_window(CHAT) == (0.0, pytest.approx(0.211741733))


@pytest.mark.parametrize("op_name,scope", [
    ("jit(decode)/layers/while/body/closed_call/attn/kv_pool/kv_pool.gather/jit(_take)/gather", "kv_pool.gather"),
    ("jit(decode)/layers/while/body/squeeze", "layers"),
    ("jit(step)/loss_grad/transpose(jvp(layers))/while/body/closed_call/checkpoint/rematted_computation/mlp/dot_general", "mlp"),
    ("jit(step)/loss_grad/jvp(head_loss)/jit(log_softmax)/reduce_max", "head_loss"),
    ("jit(step)/optimizer/jit(_where)/select_n", "optimizer"),
    ("jit(decode)/while/body/closed_call/dot_general", "(no scope)"),
    ("", "(no scope)"),
])
def test_innermost_scope(pt, op_name, scope):
    assert pt.innermost_scope(op_name) == scope


def test_scope_seconds_against_sums_by_hand(pt):
    ops = pt.load(CHAT)["ops"]
    decode = pt.scope_seconds(CHAT, "jit_decode")
    by_hand = sum(op[5] for op in ops if op[1] == "jit_decode" and "/kv_pool.gather/" in op[6])
    assert decode["kv_pool.gather"] == [pytest.approx(by_hand), 0.0] and by_hand == pytest.approx(0.050744, abs=2e-6)
    assert decode["layers"][0] == pytest.approx(0.045852, abs=2e-6)  # the scan's own slices of the pool, not under kv_pool
    assert sum(row[0] for row in decode.values()) == pytest.approx(sum(op[5] for op in ops if op[1] == "jit_decode"))
    prefill = pt.scope_seconds(CHAT, "jit_prefill")
    assert prefill["layers"][0] == pytest.approx(0.046033, abs=2e-6) and prefill["mlp"][0] == pytest.approx(0.019635, abs=2e-6)
    assert pt.scope_seconds(CHAT, "jit_step") is None and pt.scope_seconds(TRAIN, "jit_step") is None


def test_idle_under_against_sums_by_hand(pt, run):
    trace = run.load_module("", "trace")
    gaps = pt.idle_intervals(CHAT)
    idle = sum(b - a for a, b in gaps)
    assert idle == pytest.approx(0.017132, abs=2e-6) and pt.idle_under(CHAT, [trace.TRACED_SPAN]) == pytest.approx(idle)
    waits = pt.host_spans(CHAT, "serving.tick.decode.wait")
    by_hand = sum(max(0.0, min(b, w[2]) - max(a, w[1])) for a, b in gaps for w in waits)
    assert pt.idle_under(CHAT, ["serving.tick.decode.wait"]) == pytest.approx(by_hand) == pytest.approx(0.008627, abs=2e-6)
    phases = sum(pt.idle_under(CHAT, ["serving.tick." + p]) for p in PHASES)
    assert phases <= pt.idle_under(CHAT, ["serving.tick"]) <= idle  # what is left lies between the phases, and between ticks
    assert idle - phases < 1e-3 * 3
    assert pt.idle_under(CHAT, ["no.such"]) is None and pt.idle_under(TRAIN, ["serving.tick"]) is None


def chat_run(pt):
    window = pt.traced_window(CHAT)
    busy = window[1] - window[0] - sum(b - a for a, b in pt.idle_intervals(CHAT))
    return {"traced": {"raw_path": CHAT, "trace": {"busy_s": busy, "window_s": window[1] - window[0]}}}


def test_readers_on_the_chat_fixture(pt, run):
    values = {name: run.load_module("readers", name).read(chat_run(pt)) for name in NEW_READERS}
    assert values["serve.kv_pool_share"] == pytest.approx(100 * (0.050744 + 0.000020 + 0.000982 + 0.000029) / 0.194610, rel=1e-3)
    assert values["serve.idle_build_ms"] == pytest.approx(1e3 * 0.000280 / 3, rel=2e-3)  # admit alone: the builds hold none
    assert values["serve.idle_readback_ms"] == pytest.approx(1e3 * (0.008627 + 0.000862 + 0.006550 + 0.000175) / 3, rel=1e-3)
    assert values["train.recompute_share"] is None and values["train.optimizer_share"] is None  # no jit_step here


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_find_nothing_in_a_trace_without_scopes_or_spans(run, name):
    trace = run.load_module("", "trace")
    traced = {"raw_path": TRAIN, "trace": trace.reduce(trace.load_raw(TRAIN)), "counters": {"steps": 2}}
    assert run.load_module("readers", name).read({"traced": traced}) is None
    assert run.load_module("readers", name).read({"traced": {"raw_path": None, "trace": {}}}) is None


def test_xplane_reader_on_a_cpu_trace(pt, tmp_path):
    """The wire-format reader against ``jax.profiler.ProfileData`` on a trace made here: the same annotation,
    its keywords, its times; a CPU trace has no device plane, so the readers' functions find nothing."""
    import jax
    from jax.profiler import ProfileData

    trace = pt.trace_module()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.TRACED_SPAN):
        with jax.profiler.TraceAnnotation("serving.tick", tick=7, queued=-2, label="x"):
            jax.block_until_ready(jax.numpy.ones((8, 8)) @ jax.numpy.ones((8, 8)))
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    (name, start, end, meta), = pt.host_spans(path, "serving.tick")
    theirs = [e for p in ProfileData.from_file(path).planes for line in p.lines for e in line.events if e.name == "serving.tick"]
    assert len(theirs) == 1 and meta == {"tick": 7, "queued": -2, "label": "x"}
    assert start == pytest.approx(theirs[0].start_ns * 1e-9, abs=1e-9) and end - start == pytest.approx(theirs[0].duration_ns * 1e-9, abs=1e-9)
    assert pt.load(path)["ops"] == [] and pt.scope_seconds(path, "jit_") is None and pt.idle_under(path, ["serving.tick"]) is None
