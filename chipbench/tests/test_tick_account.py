"""chipbench/tick_account.py and the five readers of PR 36: on a span list
written by hand, on a CPU trace of a tiny engine (the pattern of
``test_xplane_reader_on_a_cpu_trace``), on the fixture PR 36 recorded on a TPU
v5e (``fixtures/agent_closed16.tick_account.tpu_v5e.program.json.gz``: a few
ticks of kanana-2-30b-a3b.agent_closed16, cut like the others), and ``None`` on
the four fixtures recorded before the program wrote the tick's record.  The
numbers describe the spans; none is a benchmark result."""

import gzip
import json
import os

import pytest

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "fixtures")
OLD = [os.path.join(FIXTURES, f"{name}.tpu_v5e.program.json.gz")
       for name in ("chat_closed16", "agent_closed16", "agent_closed32", "blocks_closed32")]
NEW = os.path.join(FIXTURES, "agent_closed16.tick_account.tpu_v5e.program.json.gz")
READERS = {"serve.host_busy_share": "host_busy_share", "serve.row_fill": "row_fill", "serve.width_forced_share": "width_forced_share",
           "serve.turn_wait_share": "turn_wait_share", "serve.pipelined_share": "pipelined_share"}


@pytest.fixture(scope="module")
def account(run):
    return run.load_module("", "tick_account")


def read_all(run, path):
    return {name: run.load_module("readers", name).read({"traced": {"raw_path": path}}) for name in READERS}


# ---------------------------------------------------------------------------
# a span list written by hand
# ---------------------------------------------------------------------------


def tick(n, start, end, **record):
    return ["serving.tick", start, end, dict({"tick": n, "queued": 0, "prefilling": 1, "decoding": 2}, **record)]


def part(n, name, t0, t1, **meta):
    return ["serving.tick." + name, t0, t1, dict(meta, tick=n)]


def record(rows_live, rows_computed, width, width_lanes, mixed=0, pipelined=1, settles=0):
    return dict(rows_live=rows_live, rows_computed=rows_computed, width=width, width_lanes=width_lanes, mixed=mixed,
                pipelined=pipelined, settles=settles)


# Four ticks, 10 ms apart, the traced span over the first three (the fourth only closes the third's period).
# Tick 1: a chunk alone, not pipelined (no read).  Tick 2: mixed, the chunk forces the width; reads tick 1 for 4 ms; a
# stats() between ticks 2 and 3 reads tick 2 for 0.2 ms, in tick 2's period.  Tick 3: lanes alone, nothing in flight
# to read, then a settle (reads itself, 6 ms).  First tokens: one in tick 2's prefill.emit, fifteen in tick 3's decode.emit.
HAND_SPANS = [
    ["chipbench.traced", 0.0995, 0.1295, {}],
    tick(1, 0.100, 0.103, **record(20, 48, 4, 0, pipelined=0)),
    part(1, "admit", 0.1000, 0.1002, admitted=1),
    part(1, "prefill.build", 0.1002, 0.1005, request=7, start=0, rows=20),
    part(1, "decode.build", 0.1005, 0.1006),
    part(1, "prefill.wait", 0.1006, 0.1020, live=0, width=4),
    part(1, "launch", 0.1010, 0.1018, program="decode_chunk", fresh=0),
    tick(2, 0.110, 0.118, **record(2 + 32, 48, 8, 4, mixed=1)),
    part(2, "decode.wait", 0.1105, 0.1160, live=2, width=8),
    part(2, "launch", 0.1110, 0.1115, program="decode_chunk", fresh=0),
    part(2, "read", 0.1120, 0.1160, of=1),
    part(2, "prefill.emit", 0.1160, 0.1170, request=7, first_token=1, first_tokens=1, held_ticks=1, own_ticks=1),
    part(2, "decode.wait", 0.1192, 0.1194, settle="stats"),
    part(2, "read", 0.1192, 0.1194, of=2, settle="stats"),  # between the ticks: in tick 2's period
    tick(3, 0.120, 0.129, **record(3, 16, 4, 4, pipelined=0, settles=1)),
    part(3, "decode.wait", 0.1205, 0.1220, live=3, width=4),
    part(3, "launch", 0.1210, 0.1215, program="decode", fresh=0),
    part(3, "decode.wait", 0.1225, 0.1286, settle="idle"),
    part(3, "read", 0.1226, 0.1286, of=3, settle="idle"),
    part(3, "decode.emit", 0.1286, 0.1289, tokens=24, first_tokens=15, held_ticks=47, own_ticks=29),
    tick(4, 0.130, 0.131, **record(3, 16, 4, 4, pipelined=0)),
]


def write_spans(path, spans, programs=None):
    op = ["fusion.1", "jit_decode", 0.1019, 0.0001, "/device:TPU:0", 0.0001, 0]  # one device operation: not a CPU trace
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump({"op_names": ["jit(decode)/mlp/dot_general"], "ops": [op], "spans": spans}, f)
    if programs is not None:  # trace.py's raw lists, as CHIPBENCH_KEEP_RAW leaves them beside the program's
        with gzip.open(path[: -len(".program.json.gz")], "wt", encoding="utf-8") as f:
            json.dump({"ops": [], "programs": programs, "spans": []}, f)
    return path


@pytest.fixture()
def hand(tmp_path):
    programs = [  # the reads end 30, 20 and 50 us after their programs; the programs start 850, 4,990 and 200 us after their launches
        ["jit_decode_chunk", 0.10185, 0.11597 - 0.10185, "/device:TPU:0"],
        ["jit_decode_chunk", 0.11599, 0.11938 - 0.11599, "/device:TPU:0"],
        ["jit_decode", 0.12120, 0.12855 - 0.12120, "/device:TPU:0"],
        ["jit_other", 0.1, 0.001, "/device:TPU:0"], ["jit_decode", 0.0, 1.0, "/device:TPU:1"],
    ]
    return write_spans(str(tmp_path / "hand.program.json.gz"), HAND_SPANS, programs)


def test_the_rows_of_a_span_list_written_by_hand(account, hand):
    rows = account.ticks(hand)
    assert [r["tick"] for r in rows] == [1, 2, 3]  # the fourth starts outside the traced span
    assert [r["period_s"] for r in rows] == [pytest.approx(0.010)] * 3
    assert [r["read_s"] for r in rows] == [0.0, pytest.approx(0.004 + 0.0002), pytest.approx(0.006)]
    assert rows[0]["phase_s"] == pytest.approx({"admit": 0.0002, "prefill.build": 0.0003, "decode.build": 0.0001, "launch": 0.0008,
                                                "wait": 0.0014 - 0.0008, "tick": 0.003 - 0.0002 - 0.0003 - 0.0001 - 0.0014})
    assert rows[2]["phase_s"]["wait"] == pytest.approx(0.0015 + 0.0061 - 0.0005 - 0.0060)  # both waits less their children
    # the tick's self time: its span less the wait and the emit that start in it; the settle between ticks 2 and 3 is not tick 2's
    assert rows[1]["phase_s"]["tick"] == pytest.approx(0.008 - 0.0055 - 0.001) and rows[2]["phase_s"]["tick"] == pytest.approx(0.009 - 0.0015 - 0.0061 - 0.0003)


def test_the_five_readings_of_a_span_list_written_by_hand(account, run, hand):
    values = read_all(run, hand)
    assert values["serve.host_busy_share"] == pytest.approx(100 * (0.030 - 0.0042 - 0.006) / 0.030)
    assert values["serve.row_fill"] == pytest.approx(100 * (20 + 34 + 3) / (48 + 48 + 16))
    assert values["serve.width_forced_share"] == pytest.approx(50.0)  # of ticks 2 and 3 (tick 1 has no lane), tick 2
    assert values["serve.turn_wait_share"] == pytest.approx(100 * (1 - 30 / 48))
    assert values["serve.pipelined_share"] == pytest.approx(100 / 3)
    assert account.first_tokens(hand) == {"first_tokens": 16, "held_ticks": 48, "own_ticks": 30}


def test_fewer_than_sixteen_first_tokens_are_no_reading(account, tmp_path):
    spans = [s for s in HAND_SPANS if s[0] != "serving.tick.prefill.emit"]
    path = write_spans(str(tmp_path / "few.program.json.gz"), spans)
    assert account.first_tokens(path)["first_tokens"] == 15 and account.turn_wait_share(path) is None
    assert account.row_fill(path) is not None and account.clock_bracket(path) is None  # no executions beside it


def test_the_clock_bracket_of_a_span_list_written_by_hand(account, hand):
    low, high, matched = account.clock_bracket(hand)
    assert matched == 3 and low == pytest.approx(-20e-6, abs=1e-9) and high == pytest.approx(200e-6, abs=1e-9)
    assert account.executions(hand) == [(0.10185, pytest.approx(0.11597)), (0.11599, pytest.approx(0.11938)), (0.1212, pytest.approx(0.12855))]


def test_the_table_prints_the_rows_and_the_readings(account, hand, capsys):
    account.table(hand)
    out = capsys.readouterr().out.splitlines()
    assert out[0].split()[:12] == ["tick", "period", "host", "admit", "build", "tables", "launch", "booking", "read", "emit", "publish", "between"]
    assert len(out) == 1 + 3 + 1 + 5 + 1 + 1 and out[4].split()[0] == "mean"
    assert out[1].split()[:12] == ["1", "10.000", "10.000", "0.200", "0.400", "1.000", "0.800", "0.600", "0.000", "0.000", "0.000", "7.000"]
    assert out[2].split()[:9] == ["2", "10.000", "5.800", "0.000", "0.000", "1.500", "0.500", "1.000", "4.200"]
    assert out[5].startswith("serve.host_busy_share = 66.0") and out[-1].startswith("device clock less host clock: between -20.0 and 200.0 us")


# ---------------------------------------------------------------------------
# the fixture recorded on a TPU v5e (PR 36)
# ---------------------------------------------------------------------------


def test_the_readers_on_the_recorded_fixture(account, run):
    """Ticks 5692-5695 of a traced kanana run (times from half a millisecond before tick 5692, the traced span cut to
    the four ticks; tick 5691 keeps its tail and tick 5696 only closes the last period): four mixed ticks of 13 or 14
    lanes and a chunk, in three of which the chunk's table (64 blocks) is wider than the lanes' (32); tick 5695's read
    yields one first token, of a prompt whose 22 chunks rode in the 22 ticks it was held."""
    rows = account.ticks(NEW)
    assert [r["tick"] for r in rows] == [5692, 5693, 5694, 5695]
    assert [(r["rows_live"], r["rows_computed"], r["width"], r["width_lanes"]) for r in rows] == [
        (45, 48, 64, 32), (45, 48, 64, 32), (36, 48, 64, 32), (46, 48, 64, 64)]
    assert all((r["mixed"], r["pipelined"], r["settles"]) == (1, 1, 0) for r in rows)
    assert all(set(r) == {"tick", "queued", "prefilling", "decoding", *account.RECORD, "mixed", "settles", "start", "end", "period_s",
                          "read_s", "phase_s"} for r in rows)  # no time rides on the span: the times are the spans' own
    micros = [{name: round(1e6 * seconds) for name, seconds in r["phase_s"].items()} for r in rows]
    assert micros[0] == {"admit": 33, "prefill.build": 30, "decode.build": 66, "launch": 1568, "read": 7170, "prefill.emit": 242,
                         "decode.emit": 183, "publish": 48, "wait": 127, "tick": 154}
    assert [(m["tick"], m["wait"], m["launch"]) for m in micros[1:]] == [(204, 88, 1763), (176, 153, 1720), (193, 84, 1815)]
    assert all(r["period_s"] > r["end"] - r["start"] for r in rows)
    values = read_all(run, NEW)
    assert values["serve.host_busy_share"] == pytest.approx(100 * (1 - sum(r["read_s"] for r in rows) / sum(r["period_s"] for r in rows)))
    assert values["serve.host_busy_share"] == pytest.approx(28.335, abs=1e-3)
    assert values["serve.row_fill"] == pytest.approx(100 * 172 / 192) and values["serve.width_forced_share"] == 75.0
    assert values["serve.pipelined_share"] == 100.0 and values["serve.turn_wait_share"] is None  # one first token: no reading
    assert account.first_tokens(NEW) == {"first_tokens": 1, "held_ticks": 22, "own_ticks": 22}
    pt = account.program_trace()
    reads = pt.host_spans(NEW, account.READ)
    assert [s[3] for s in reads] == [{"tick": t, "of": t - 1} for t in range(5691, 5697)]
    waits = pt.host_spans(NEW, "serving.tick.decode.wait")
    launches = pt.host_spans(NEW, account.LAUNCH)
    for child in reads + launches:
        assert sum(w[1] <= child[1] and child[2] <= w[2] and w[3]["tick"] == child[3]["tick"] for w in waits) == 1
    by_tick = {w[3]["tick"]: w for w in waits}
    assert all(0 <= launch[1] - by_tick[launch[3]["tick"]][1] < 20e-6 for launch in launches)  # the wait opens at the launch
    assert account.clock_bracket(NEW) is None  # the executions are trace.py's lists, which no fixture keeps beside it


# ---------------------------------------------------------------------------
# traces recorded before the program wrote the tick's record
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("reader", sorted(READERS))
@pytest.mark.parametrize("path", OLD, ids=[os.path.basename(p).split(".")[0] for p in OLD])
def test_the_readers_find_nothing_in_the_older_fixtures(account, run, path, reader):
    assert run.load_module("readers", reader).read({"traced": {"raw_path": path}}) is None
    assert run.load_module("readers", reader).read({"traced": {"raw_path": None}}) is None
    assert account.ticks(path) is None and account.first_tokens(path) is None and account.clock_bracket(path) is None


# ---------------------------------------------------------------------------
# a CPU trace of a tiny engine
# ---------------------------------------------------------------------------


def test_the_readers_on_a_cpu_trace_of_a_tiny_engine(account, run, tmp_path):
    """Twenty ticks of a tiny engine inside the traced span, one more after it (so that the last has a period): the
    helper's readings against the engine's own records of those ticks, the spans nested as the engine nests them."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import gpt2
    from accelerate_tpu.serving import ServingConfig, ServingEngine

    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
    engine = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, gpt2.init_params(cfg, jax.random.key(0)), cfg,
        serving=ServingConfig(block_size=4, num_blocks=64, max_slots=4, max_blocks_per_seq=16, prefill_chunk=8, prefix_cache=False),
    )
    prompts = [list(range(1, 1 + n)) for n in (5, 21, 9, 12, 7, 10)]
    for prompt in prompts:  # a first round leaves every table width compiled
        engine.submit(prompt, 6)
    engine.run()
    engine.pop_finished()
    for prompt in prompts:
        engine.submit(prompt, 6)
    pt = account.program_trace()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(pt.trace_module().TRACED_SPAN):
        records = []
        for _ in range(20):
            engine.step()
            records.append(dict(engine._tick))
    engine.step()
    jax.profiler.stop_trace()
    path = pt.trace_module().find_xplane(str(tmp_path))
    rows = account.ticks(path)
    assert [r["tick"] for r in rows] == [r["tick"] for r in records] and len(rows) == 20
    for row, rec in zip(rows, records):
        assert (row["rows_live"], row["rows_computed"], row["width"], row["mixed"]) == (rec["rows_live"], rec["rows_computed"], rec["width"] or 0, rec["mixed"])
        assert row["end"] - row["start"] <= row["period_s"] and 0 <= row["read_s"] < row["period_s"]
        assert row["phase_s"]["tick"] > 0 and row["phase_s"]["wait"] >= 0 and abs(row["end"] - row["start"] - rec["total_ms"] / 1e3) < 0.05
    assert read_all(run, path) == dict.fromkeys(READERS)  # no device plane: the readers give a CPU run no reading
    values = {name: getattr(account, function)(path) for name, function in READERS.items()}
    assert values["serve.row_fill"] == pytest.approx(100 * sum(r["rows_live"] for r in records) / sum(r["rows_computed"] for r in records))
    assert 0 < values["serve.row_fill"] < 100 and 0 < values["serve.host_busy_share"] <= 100
    with_lanes = [r for r in records if r["live"]]
    assert values["serve.width_forced_share"] == pytest.approx(100 * sum(r["width"] > r["width_lanes"] for r in with_lanes) / len(with_lanes))
    dispatched = [r for r in records if r["rows_computed"]]  # the last ticks of the span find nothing left to dispatch
    assert len(dispatched) < len(records) and not records[0]["pipelined"]  # and nothing was in flight when it opened
    assert values["serve.pipelined_share"] == pytest.approx(100 * (len(dispatched) - 1) / len(dispatched))
    firsts = account.first_tokens(path)
    assert firsts["first_tokens"] == len(prompts) and firsts["held_ticks"] > firsts["own_ticks"] >= len(prompts)
    assert values["serve.turn_wait_share"] is None  # six first tokens are no reading
    # launch and read lie inside a wait, the wait inside its tick
    spans = pt.host_spans(path, "serving.tick")
    for name, start, end, meta in spans:
        if name in (account.LAUNCH, account.READ):
            wait = [s for s in spans if s[0].endswith(".wait") and s[1] <= start and end <= s[2]]
            assert len(wait) == 1 and wait[0][3]["tick"] == meta["tick"]
    assert account.clock_bracket(path) is None  # a CPU trace has no device plane: no execution to match
