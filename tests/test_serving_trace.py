"""Per-request serving traces (serving/tracing.py): the conservation
invariant (phases partition admission→terminal wall time, residual exposed),
blame decomposition naming the injected phase, Chrome-trace export
round-tripping through telemetry/timeline.py, JSONL persistence with
last-record-wins + torn-tail tolerance, cross-life stitching by journal tag
(SIGKILL subprocess proof), and the engine-side bucket-compile attribution
that works even with tracing off."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from conftest import recorded_spans

from accelerate_tpu import telemetry
from accelerate_tpu.models import gpt2
from accelerate_tpu.serving import ServingConfig, ServingEngine
from accelerate_tpu.serving.tracing import (
    RequestTrace,
    decompose_blame,
    export_chrome_trace,
    format_trace_block,
    load_serving_traces,
    stitch_traces,
    summarize_traces,
)
from accelerate_tpu.telemetry.timeline import build_timeline, load_trace_events


@pytest.fixture(scope="module")
def gpt2_setup():
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
    params = gpt2.init_params(cfg, jax.random.key(0))
    return cfg, params


def _engine(cfg, params, trace=True, trace_dir=None, **overrides):
    kw = dict(block_size=4, num_blocks=32, max_slots=2, max_blocks_per_seq=8,
              prefill_chunk=8, trace=trace, trace_dir=trace_dir)
    kw.update(overrides)
    return ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(**kw),
    )


# ---------------------------------------------------------------------------
# The conservation invariant (unit: no engine, synthetic clock)
# ---------------------------------------------------------------------------


def test_cursor_makes_intervals_a_partition():
    """add() clamps every interval's start to the cursor and advances it, so
    intervals are disjoint and ordered NO MATTER what start times callers
    pass — conservation is structural, not a property of polite callers."""
    t = RequestTrace(1, "t", arrival=100.0, prompt_len=4, max_new=8)
    t.add("queue_wait", 100.5)
    t.add("prefill", 100.8, start=100.2)       # overlapping start: clamped
    t.add("decode", 101.0, start=99.0)         # before arrival: clamped
    t.add("preempted", 100.9, start=100.9)     # end < cursor: zero-dur marker
    t.add("requeued_wait", 101.4)
    for prev, cur in zip(t.intervals, t.intervals[1:]):
        assert cur.start >= prev.end
    t.finish = 101.5
    window = t.window_ms()
    attributed = sum(t.phase_ms().values())
    assert abs(window - attributed - t.unattributed_ms()) < 1e-9
    assert t.unattributed_ms() == pytest.approx(100.0)  # the 101.4→101.5 gap
    assert t.phase_ms()["queue_wait"] == pytest.approx(500.0)


def test_blame_floor_dominance_and_quarantine():
    # Quarantine outranks everything, including a huge queue wait.
    assert decompose_blame({"queue_wait": 900.0}, 1000.0, "quarantined") == "quarantine"
    # Dominant badput phase above the 10%-of-window floor.
    assert decompose_blame(
        {"queue_wait": 400.0, "requeued_wait": 100.0, "decode": 500.0}, 1000.0
    ) == "queue_wait"
    # Goodput phases (prefill/decode) are never blamed, however large.
    assert decompose_blame({"decode": 990.0, "queue_wait": 5.0}, 1000.0) == "none"
    # Below the floor: immaterial badput is "none", not noise-blame.
    assert decompose_blame({"compile_in_path": 50.0, "decode": 950.0}, 1000.0) == "none"
    # The absolute 1 ms floor guards tiny windows.
    assert decompose_blame({"queue_wait": 0.4, "decode": 0.2}, 0.8) == "none"
    assert decompose_blame({"queue_wait": 3.0, "decode": 0.2}, 4.0) == "queue_wait"


# ---------------------------------------------------------------------------
# Chrome export / JSONL persistence / stitching (unit: synthetic traces)
# ---------------------------------------------------------------------------


def _synthetic_trace(rid, tag, arrival, phases, slot=0):
    """phases: [(name, dur_s, meta)] laid end to end from arrival."""
    t = RequestTrace(rid, tag, arrival=arrival, prompt_len=3, max_new=4)
    cur = arrival
    for name, dur, meta in phases:
        cur += dur
        t.add(name, cur, **meta)
    t.finish = cur
    t.status = "ok"
    t.blame = decompose_blame(t.phase_ms(), t.window_ms(), "ok")
    return t


def test_chrome_export_roundtrips_through_timeline(tmp_path):
    now = time.monotonic()
    traces = [
        _synthetic_trace(0, "a", now, [
            ("queue_wait", 0.1, {}),
            ("prefill", 0.02, {"slot": 0, "chunk": 0}),
            ("decode", 0.3, {"slot": 0, "co_batch": 2, "ticks": 7}),
        ]),
        _synthetic_trace(1, None, now + 0.05, [
            ("queue_wait", 0.01, {}),
            ("compile_in_path", 0.4, {"slot": 1, "kind": "decode", "width": 4}),
        ]),
    ]
    for path in (str(tmp_path / "t.trace.json"), str(tmp_path / "t.trace.json.gz")):
        export_chrome_trace(path, traces)
        tl = build_timeline(load_trace_events(path), source=path)
        # Serving events are host-side bookkeeping, never device ops.
        assert tl.host_events and not tl.events
        tracks = set(tl.tracks().values())
        assert "serving engine slots/slot 0" in tracks
        assert "serving requests/req 0 [a]" in tracks
        names = {ev.name for ev in tl.host_events}
        assert {"queue_wait", "decode", "compile_in_path"} <= names
        # Request-track events carry the request id and phase in args-derived
        # names; slot tracks mirror them as r<rid>/<phase>.
        assert any(ev.name == "r0/decode" for ev in tl.host_events)


def test_load_last_record_wins_and_tolerates_torn_tail(tmp_path):
    path = tmp_path / "serving_trace_111_ab.jsonl"
    rec_inflight = {"kind": "serving_trace", "rid": 5, "tag": "x",
                    "status": "inflight", "arrival_wall": 10.0,
                    "duration_ms": 50.0, "phase_ms": {"queue_wait": 50.0},
                    "unattributed_ms": 0.0}
    rec_final = dict(rec_inflight, status="ok", duration_ms=80.0,
                     blame="queue_wait")
    with open(path, "w") as f:
        f.write(json.dumps(rec_inflight) + "\n")
        f.write(json.dumps({"kind": "other"}) + "\n")      # foreign record
        f.write(json.dumps(rec_final) + "\n")
        f.write('{"kind": "serving_trace", "rid": 9, "sta')  # torn tail
    records = load_serving_traces(str(tmp_path))
    assert len(records) == 1
    assert records[0]["status"] == "ok" and records[0]["duration_ms"] == 80.0
    assert records[0]["source"] == path.name
    # A direct file path loads too.
    assert load_serving_traces(str(path))[0]["rid"] == 5


def test_stitch_joins_lives_by_tag_with_recovery_gap():
    victim = {"kind": "serving_trace", "rid": 0, "tag": "job", "status": "inflight",
              "arrival_wall": 1000.0, "duration_ms": 200.0,
              "phase_ms": {"queue_wait": 10.0, "decode": 190.0},
              "unattributed_ms": 0.0}
    successor = {"kind": "serving_trace", "rid": 7, "tag": "job", "status": "ok",
                 "arrival_wall": 1000.5, "duration_ms": 100.0,
                 "phase_ms": {"journal_recovery": 0.0, "prefill": 40.0,
                              "decode": 60.0},
                 "unattributed_ms": 0.0, "recovered_from": 0}
    untagged = dict(victim, tag=None, rid=3)
    stitched = stitch_traces([successor, victim, untagged])
    assert len(stitched) == 1
    st = stitched[0]
    assert st["tag"] == "job" and st["lives"] == 2 and st["status"] == "ok"
    # Gap between the victim's last trace end (1000.2) and the successor's
    # arrival (1000.5) is the recovery dead time.
    assert st["journal_recovery_ms"] == pytest.approx(300.0, abs=1.0)
    assert st["total_ms"] == pytest.approx(600.0, abs=1.0)
    assert st["conservation_ok"], st
    # A single-life tag with no recovery marker does not stitch.
    assert stitch_traces([victim]) == []
    summary = summarize_traces([victim, successor])
    assert summary["requests"] == 1 and summary["inflight"] == 1
    assert summary["stitched"] == stitched
    block = "\n".join(format_trace_block(summary))
    assert "stitched tag 'job'" in block and "conservation ok" in block


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------


def test_kill_switch_and_config_override(gpt2_setup, monkeypatch, tmp_path):
    cfg, params = gpt2_setup
    monkeypatch.setenv("ACCELERATE_TPU_SERVING_TRACE", "0")
    assert _engine(cfg, params, trace=None).tracer is None
    eng = _engine(cfg, params, trace=True, trace_dir=str(tmp_path))
    assert eng.tracer is not None  # explicit config beats the env
    monkeypatch.delenv("ACCELERATE_TPU_SERVING_TRACE")
    assert _engine(cfg, params, trace=None).tracer is not None  # default-on
    # Idle-engine introspection payloads have their shape without dispatching.
    assert eng.debug_requests() == []
    blocks = eng.debug_blocks()
    assert blocks["used"] == 0 and blocks["free"] == blocks["capacity"]
    assert blocks["occupancy"] == 0.0 and blocks["slots"] == {}
    with pytest.raises(RuntimeError, match="tracing"):
        _engine(cfg, params, trace=False).export_chrome_trace(
            str(tmp_path / "no.json")
        )


def test_conservation_and_blame_under_queue_pressure_and_preemption(
    gpt2_setup, tmp_path
):
    """Acceptance criterion: a seeded mix with forced preemption and queue
    pressure keeps every completed request's phase sum within epsilon of its
    wall window, and blames the requests whose slowness was injected on the
    injected phase."""
    cfg, params = gpt2_setup
    eng = _engine(cfg, params, trace=True, trace_dir=str(tmp_path))
    rng = np.random.default_rng(0)

    def prompt(n):
        return list(rng.integers(0, cfg.vocab_size, size=n))

    # Warm every bucket width the scenario can hit (prefill widths 2-8,
    # decode widths 1-8) so scenario blame is the injected phase, not
    # compile_in_path (see serving/trace_smoke.py for the width math).
    eng.submit(prompt(3), 6, tag="w-short")
    eng.run(max_ticks=500)
    for i in range(2):
        eng.submit(prompt(12), 18, tag=f"w{i}")
    eng.submit(prompt(20), 4, tag="w-long")
    eng.run(max_ticks=500)

    # Injected queue delay: 120 ms between submit and the first tick.
    rid_queue = eng.submit(prompt(6), 12, tag="slow-queue")
    time.sleep(0.12)
    for _ in range(3):
        eng.step()
    # Injected preemption: evict mid-decode, hold requeued 120 ms.
    rid_preempt = eng.submit(prompt(6), 12, tag="slow-preempt")
    for _ in range(6):
        eng.step()
    victim = [idx for idx, s in eng.sched.slots.items()
              if s.request.id == rid_preempt]
    assert victim, "preemption target never reached a slot"
    eng.sched.preempt_slot(victim[0])
    time.sleep(0.12)
    eng.run(max_ticks=1000)

    by_rid = {t.rid: t for t in eng.tracer.completed}
    assert len(by_rid) == 6
    for t in by_rid.values():
        window = t.window_ms()
        attributed = sum(t.phase_ms().values())
        resid = t.unattributed_ms()
        assert abs(window - attributed - resid) < 1e-6, (t.rid, window, attributed)
        assert 0.0 <= resid <= max(5.0, 0.05 * window), (t.rid, resid, window)
    assert by_rid[rid_queue].blame == "queue_wait", by_rid[rid_queue].phase_ms()
    assert by_rid[rid_preempt].blame == "requeued_wait", (
        by_rid[rid_preempt].phase_ms()
    )
    assert any(iv.phase == "preempted" for iv in by_rid[rid_preempt].intervals)
    assert eng.tracer.blame_counts.get("queue_wait", 0) >= 1
    assert eng.tracer.blame_counts.get("requeued_wait", 0) >= 1
    assert eng.stats()["trace_blame"] == eng.tracer.blame_counts
    # The terminal records persisted; the offline summary agrees on blame.
    summary = summarize_traces(load_serving_traces(str(tmp_path)))
    assert summary["requests"] == 6
    assert summary["by_blame"].get("queue_wait", 0) >= 1


def test_bucket_compile_event_and_width_gauge_without_tracing(
    gpt2_setup, tmp_path
):
    """Satellite: per-width jit-cache-miss attribution must not depend on
    tracing — with the tracer OFF, the engine still emits a
    serving.bucket_compile event per fresh width and publishes the
    serving.decode_bucket_width gauge."""
    cfg, params = gpt2_setup
    tel = telemetry.enable(dir=str(tmp_path))
    try:
        eng = _engine(cfg, params, trace=False)
        assert eng.tracer is None
        eng.submit([1, 2, 3, 4, 5], 6)
        eng.run(max_ticks=200)
        assert tel.registry.gauge("serving.decode_bucket_width").value >= 1
        assert eng.stats()["decode_bucket_widths"], "no decode width recorded"
        assert eng.stats()["trace_blame"] is None
    finally:
        telemetry.disable()
    events = []
    for fname in os.listdir(tmp_path):
        if not fname.endswith(".jsonl"):
            continue
        with open(tmp_path / fname) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") == "event" and rec.get("name") == "serving.bucket_compile":
                    events.append(rec)
    assert events, "no serving.bucket_compile event landed in telemetry"
    assert {e["dispatch"] for e in events} <= {"decode_chunk", "decode"}  # the program that met the width first
    assert all(isinstance(e["width"], int) for e in events)


# ---------------------------------------------------------------------------
# Spans inside the tick (telemetry.annotate) and the slow-tick record
# ---------------------------------------------------------------------------

TICK_PHASES = ["admit", "prefill.build", "prefill.wait", "prefill.emit",
               "decode.build", "decode.wait", "launch", "read", "decode.emit", "publish"]
# a tick with a chunk and a live decoder: both builds, then ONE dispatch (under decode.wait: its launch, and the read
# of the tick before, are the wait's two children), then both emits
MIXED_TICK_PHASES = ["admit", "prefill.build", "decode.build", "decode.wait", "launch", "read", "prefill.emit",
                     "decode.emit", "publish"]
TICK_ACCOUNT = {"rows_live", "rows_computed", "width", "width_lanes", "width_window", "mixed", "pipelined", "settles"}


def _busy_engine(cfg, params, **overrides):
    """An engine in mid-flight with its programs warm: one request decoding,
    one with prompt chunks still to prefill, and the tick in flight a mixed
    one, so a tick runs every phase (its emits are of the tick before it)."""
    eng = _engine(cfg, params, prefix_cache=False, **overrides)
    for _ in range(2):  # the first pair runs to its end and leaves every table width compiled
        eng.run()
        eng.submit(list(range(1, 6)), 24)
        eng.submit(list(range(1, 25)), 4)
    eng.step()  # the short prompt's one chunk
    eng.step()  # the long prompt's first chunk rides with the first decode; reads the tick before back
    return eng


def test_tick_spans_in_a_profiler_session(gpt2_setup, tmp_path):
    """A profiler session round three ticks, each reading back a tick with a
    chunk and a live decoder, holds the spans of a mixed tick: nested in its
    serving.tick, in the tick's own order (one wait: the launch of this tick
    and the read-back of the one before; the emits are that tick's), children
    sharing the parent's ``tick``, the counts the issue's table gives as their
    stats.  The long prompt's third and last chunk rides in the second tick
    traced: the third builds no chunk and emits that one's first token."""
    import glob

    from jax.profiler import ProfileData

    cfg, params = gpt2_setup
    eng = _busy_engine(cfg, params)
    first = eng.ticks + 1
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        eng.step()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
        for plane in ProfileData.from_file(path).planes for line in plane.lines for e in line.events
        if e.name.startswith("serving.tick")
    )
    ticks = [e for e in events if e[2] == "serving.tick"]
    assert [e[3]["tick"] for e in ticks] == [first, first + 1, first + 2]
    assert set(ticks[0][3]) == {"tick", "queued", "prefilling", "decoding"} | TICK_ACCOUNT
    assert ticks[0][3]["prefilling"] == 1 and ticks[0][3]["decoding"] == 1
    live_before, prefilled = 1, None  # of the tick in flight when the session opened
    for i, (start, end, _, stats) in enumerate(ticks):
        children = [e for e in events if e[2] != "serving.tick" and e[3]["tick"] == stats["tick"]]
        assert [e[2] for e in children] == ["serving.tick." + p for p in MIXED_TICK_PHASES]
        assert all(start <= e[0] and e[1] <= end for e in children)
        wait, launch, read = children[3:6]
        assert wait[0] <= launch[0] and launch[1] <= read[0] and read[1] <= wait[1]  # the wait's two children
        phases = children[:4] + children[6:]
        assert all(a[1] <= b[0] for a, b in zip(phases, phases[1:]))  # one after the other
        by_name = {e[2].removeprefix("serving.tick."): e[3] for e in children}
        assert by_name["launch"] == {"tick": stats["tick"], "program": "decode_chunk" if i < 2 else "decode", "fresh": 0}
        assert by_name["read"] == {"tick": stats["tick"], "of": stats["tick"] - 1}  # no experts, no settle
        assert (stats["mixed"], stats["pipelined"], stats["settles"]) == (int(i < 2), 1, 0)
        assert by_name["admit"]["admitted"] == 0
        assert set(by_name["prefill.build"]) == ({"tick", "request", "start", "rows"} if i < 2 else {"tick"})
        prefilled = by_name["prefill.build"].get("request", prefilled)
        assert by_name["prefill.emit"]["request"] == prefilled
        assert by_name["prefill.emit"]["first_token"] == int(i == 2)
        assert by_name["decode.build"]["live"] == by_name["decode.wait"]["live"] == (1 if i < 2 else 2)
        assert set(by_name["decode.wait"]) == {"tick", "live", "width"} and by_name["decode.wait"]["width"] >= 1
        assert by_name["decode.emit"]["tokens"] == live_before  # the emits are of the tick dispatched before this one
        live_before = by_name["decode.build"]["live"]


# One run of two requests under the span recorder, worked by hand (block_size 4, max_slots 2, prefill_chunk 8, tables
# from one block up).  The short request (5-token prompt, 24 new) and the long one (24-token prompt, 4 new) are admitted
# in tick 1.  Tick 1: the short prompt's one chunk alone.  Ticks 2-4: the long prompt's three chunks ride with the short
# request's lane; the chunk's padded extent needs 2, 4 and 6 blocks, the lane 2: in ticks 3 and 4 the chunk forces the
# width.  Ticks 5-7: both lanes (the long one owns 7 blocks: width 8).  Ticks 8-24: the short lane alone.  Tick 24
# dispatches the last token, reads tick 23 and, nothing being left, settles ("idle"): a second read, of itself.
ACCOUNT_TICKS = {  # tick: (rows_live, rows_computed, width, width_lanes, mixed, pipelined, settles)
    1: (5, 10, 2, 0, 0, 0, 0), 2: (9, 10, 2, 2, 1, 1, 0), 3: (9, 10, 4, 2, 1, 1, 0), 4: (9, 10, 8, 2, 1, 1, 0),
    5: (2, 2, 8, 8, 0, 1, 0), 7: (2, 2, 8, 8, 0, 1, 0), 8: (1, 2, 4, 4, 0, 1, 0), 24: (1, 2, 8, 8, 0, 1, 1),
}


@pytest.fixture(scope="module")
def accounted(gpt2_setup):
    from accelerate_tpu.serving import programs

    cfg, params = gpt2_setup
    with recorded_spans() as spans, pytest.MonkeyPatch.context() as patch:
        patch.setattr(programs, "MIN_TABLE_ROWS", 1)  # conftest's, which a module's fixture is built ahead of
        eng = _engine(cfg, params, prefix_cache=False)
        rids = [eng.submit(list(range(1, 6)), 24), eng.submit(list(range(1, 25)), 4)]
        records = []
        while not eng.sched.idle():
            eng.step()
            records.append(dict(eng._tick, phase_ms=dict(eng._tick["phase_ms"])))
        stats = eng.stats()
    ticks = {s.meta["tick"]: s for s in spans if s.name == "serving.tick"}
    done = {c.id: c for c in eng.pop_finished()}
    return {"spans": spans, "ticks": ticks, "records": {r["tick"]: r for r in records}, "stats": stats,
            "done": [done[rid] for rid in rids]}


@pytest.mark.parametrize("tick", sorted(ACCOUNT_TICKS))
def test_the_tick_span_carries_the_dispatchs_rows_and_widths(accounted, tick):
    meta = accounted["ticks"][tick].meta
    assert set(meta) == {"tick", "queued", "prefilling", "decoding"} | TICK_ACCOUNT
    assert all(type(meta[k]) is int for k in TICK_ACCOUNT)
    names = ("rows_live", "rows_computed", "width", "width_lanes", "mixed", "pipelined", "settles")
    assert tuple(meta[k] for k in names) == ACCOUNT_TICKS[tick]


def test_launch_and_read_are_phases_of_their_own_and_the_phases_still_sum_to_the_tick(accounted):
    assert len(accounted["ticks"]) == 24
    tails, edges = [], []
    for tick, span in accounted["ticks"].items():
        meta, record = span.meta, accounted["records"][tick]
        assert abs(sum(record["phase_ms"].values()) - record["total_ms"]) < 1e-6
        assert set(record["phase_ms"]) <= set(TICK_PHASES) and record["phase_ms"]["launch"] > 0
        tails.append((span.end - span.start) * 1e3 - record["total_ms"])
        children = [s for s in accounted["spans"] if s.parent is span]
        waits = [s for s in children if s.name.endswith(".wait")]
        inside = [s for s in accounted["spans"] if s.parent in waits]
        assert [s.name for s in inside] == ["serving.tick.launch"] + ["serving.tick.read"] * (len(inside) - 1)
        assert waits[0].start <= inside[0].start  # the wait opens at the launch: the tables are filled before it
        reads = [s for s in inside if s.name == "serving.tick.read"]
        assert len(reads) == meta["pipelined"] + meta["settles"] and ("read" in record["phase_ms"]) == bool(reads)
        edges.append(abs(record["phase_ms"].get("read", 0.0) - sum(s.end - s.start for s in reads) * 1e3))
    # The record's clock starts at step()'s first line, a few lines before the span opens, and the span closes after the
    # record: the tracer's end of tick lies between (0.3 ms in all on a machine that does not pause; judged by the
    # median, a loaded worker may stall in any one tick).  The read's phase is the time inside its spans but for the
    # clock reads either side.
    middle = lambda values: sorted(values)[len(values) // 2]  # noqa: E731
    assert -0.3 < middle(tails) < 0.3 and middle(edges) < 0.1


def test_a_settle_adds_a_second_read_with_its_reason(accounted):
    last = accounted["ticks"][24]
    reads = [s for s in accounted["spans"] if s.name == "serving.tick.read" and s.meta["tick"] == 24 and s.end <= last.end]
    assert [(s.meta["of"], s.meta.get("settle")) for s in reads] == [(23, None), (24, "idle")]
    assert [s.parent.name for s in reads] == ["serving.tick.decode.wait"] * 2 and reads[1].parent.meta["settle"] == "idle"
    assert last.meta["settles"] == 1 and accounted["records"][24]["settle"] == "idle"
    assert all(t.meta["settles"] == 0 for n, t in accounted["ticks"].items() if n != 24)
    outside = [s for s in accounted["spans"] if s.name == "serving.tick.read" and s.start > last.end]
    assert outside == [] and accounted["stats"]["settles"] == {"idle": 1}  # nothing was in flight for stats() to read


def test_a_settle_between_two_ticks_leaves_the_closed_record_alone(gpt2_setup):
    """``stats()`` between two ticks reads the tick in flight back and emits it under spans of their own; the record of
    the tick before it is closed: no phase, no ``settle`` and no count is booked into it after its end."""
    cfg, params = gpt2_setup
    eng = _busy_engine(cfg, params)
    closed = eng._tick
    before = dict(closed, phase_ms=dict(closed["phase_ms"]))
    with recorded_spans() as spans:
        settles = eng.stats()["settles"]
    assert [s.name for s in spans] == ["serving.tick." + p for p in ("decode.wait", "read", "prefill.emit", "decode.emit")]
    assert spans[1].parent is spans[0] and spans[1].meta == {"tick": eng.ticks, "of": eng.ticks, "settle": "stats"}
    assert settles["stats"] >= 1 and eng._tick is closed and closed == before
    eng.step()  # the next tick finds nothing in flight: not pipelined, and its own record is whole
    assert not eng._tick["pipelined"] and abs(sum(eng._tick["phase_ms"].values()) - eng._tick["total_ms"]) < 1e-6


def test_the_first_tokens_account_of_a_one_token_family(accounted):
    """The short prompt's chunk is dispatched in the tick that admits it: held one tick, its own.  The long prompt is
    admitted in the same tick and prefills in ticks 2-4: held four ticks, three of them its own."""
    emits = [s for s in accounted["spans"] if s.name == "serving.tick.prefill.emit"]
    firsts = [s for s in emits if s.meta["first_token"]]
    done = accounted["done"]
    assert [(s.meta["tick"], s.meta["request"]) for s in firsts] == [(2, done[0].id), (5, done[1].id)]
    assert [(s.meta["first_tokens"], s.meta["held_ticks"], s.meta["own_ticks"]) for s in firsts] == [(1, 1, 1), (1, 4, 3)]
    assert all(set(s.meta) == {"tick", "request", "first_token", "first_tokens", "held_ticks", "own_ticks"} for s in firsts)
    others = [s for s in emits if not s.meta["first_token"]]
    assert others and all(set(s.meta) == {"tick", "request", "first_token"} for s in others)
    assert all("first_tokens" not in s.meta for s in accounted["spans"] if s.name == "serving.tick.decode.emit")


def test_the_counters_of_stats_equal_the_spans_sums(accounted):
    ticks, stats = accounted["ticks"].values(), accounted["stats"]
    assert sum(t.meta["rows_live"] for t in ticks) == 5 + 3 * 9 + 3 * 2 + 17
    assert sum(t.meta["rows_computed"] for t in ticks) == 4 * 10 + 20 * 2
    assert sum(t.meta["width"] > t.meta["width_lanes"] > 0 for t in ticks) == 2
    assert stats["mixed_dispatches"] == sum(t.meta["mixed"] for t in ticks) == 3
    assert stats["pipelined_ticks"] == sum(t.meta["pipelined"] for t in ticks) == 23


def test_slow_ticks_keep_the_slowest_and_say_which_phase(gpt2_setup, tmp_path, monkeypatch):
    """The tracer keeps the engine's eight slowest ticks, slowest first, each
    with its phases (summing to the tick) and the decode dispatch's shape; a
    tick held up in admission shows its time under ``admit``; ticks that met
    a fresh table width are the compile_in_path phase's and are left out."""
    from accelerate_tpu.serving.tracing import SLOW_TICKS

    cfg, params = gpt2_setup
    assert _engine(cfg, params, trace=False).stats()["slow_ticks"] is None
    eng = _busy_engine(cfg, params, trace_dir=str(tmp_path))
    admit = eng.sched.admit
    held = eng.ticks + 2

    def slow_admit(now):
        if eng.ticks == held:
            time.sleep(0.25)  # long enough to stay among the eight slowest whatever six loaded workers do to the others
        return admit(now)

    monkeypatch.setattr(eng.sched, "admit", slow_admit)
    for _ in range(4):
        eng.step()
    slow = eng.stats()["slow_ticks"]
    assert len(slow) == SLOW_TICKS == 8
    assert [t["total_ms"] for t in slow] == sorted((t["total_ms"] for t in slow), reverse=True)
    by_tick = {t["tick"]: t for t in slow}
    assert by_tick[held]["pipelined"] is True and by_tick[held]["settle"] is None
    for t in slow:
        assert set(t) == {"tick", "total_ms", "phase_ms", "live", "prefilling", "width", "mixed", "pipelined", "settle", "gc_count"}
        assert isinstance(t["pipelined"], bool) and t["settle"] in (None, "idle")  # "idle": the last tick of a warm-up run
        before = by_tick.get(t["tick"] - 1)
        if before is not None and t["settle"] is None:  # a tick's emits are of the tick dispatched before it
            assert before["mixed"] == ("prefill.emit" in t["phase_ms"] and "decode.emit" in t["phase_ms"])
        assert abs(sum(t["phase_ms"].values()) - t["total_ms"]) < 1.0
        assert set(t["phase_ms"]) <= set(TICK_PHASES) and len(t["gc_count"]) == 3
    # asserted on the held tick's own record, not on its rank: under a loaded machine any other tick may stall for longer
    mine = by_tick[held]
    assert mine["phase_ms"]["admit"] >= 250.0 and mine["phase_ms"]["admit"] == max(mine["phase_ms"].values())
    assert mine["total_ms"] - mine["phase_ms"]["admit"] < mine["phase_ms"]["admit"]
    assert mine["live"] >= 1 and mine["width"] >= 1
    assert 1 not in [t["tick"] for t in slow]  # the first tick compiled both programs: by far the slowest, and left out
    assert eng.stats()["ticks"] - 1 > SLOW_TICKS
    eng.tracer.flush()
    with open(eng.tracer.path) as f:
        lines = [json.loads(line) for line in f]
    assert [r for r in lines if r["kind"] == "slow_ticks"][-1]["ticks"] == slow
    assert all(r["kind"] == "serving_trace" for r in load_serving_traces(str(tmp_path)))


def test_sigkill_trace_stitches_across_engine_lives(gpt2_setup, tmp_path):
    """Satellite (extends the PR 14 chaos proof): a SIGKILLed engine's
    periodic in-flight snapshots plus the successor's terminal records
    stitch under one journal tag — two lives, a journal_recovery phase, and
    conservation across the stitch."""
    cfg, params = gpt2_setup
    jp = str(tmp_path / "journal.json")
    tdir = str(tmp_path)

    script = f"""
import os, signal
import jax, jax.numpy as jnp
from accelerate_tpu.models import gpt2
from accelerate_tpu.serving import ServingConfig, ServingEngine

cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
params = gpt2.init_params(cfg, jax.random.key(0))
eng = ServingEngine(
    gpt2.apply_cached, gpt2.init_cache, params, cfg,
    serving=ServingConfig(block_size=4, num_blocks=40, max_slots=2,
                          prefill_chunk=8, max_blocks_per_seq=8,
                          journal_path={jp!r}, trace=True, trace_dir={tdir!r}),
)
eng.submit([5, 6, 7, 8, 9, 10], 8, tag="life0")
eng.submit([11, 12, 13], 8, tag="life1")
for _ in range(3):
    eng.step()
os.kill(os.getpid(), signal.SIGKILL)  # no drain, no flush, no atexit
"""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "ACCELERATE_TPU_COMPILE_CACHE": "",
                "ACCELERATE_TPU_SENTINEL_PROFILE": "0",
                "ACCELERATE_TPU_SERVING_TRACE_FLUSH_EVERY": "1"})
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == -signal.SIGKILL, (proc.returncode, proc.stderr)
    victim_records = load_serving_traces(tdir)
    assert {r["tag"] for r in victim_records} == {"life0", "life1"}
    assert all(r["status"] == "inflight" for r in victim_records)

    succ = _engine(cfg, params, trace=True, trace_dir=tdir,
                   num_blocks=40, journal_path=jp)
    mapping = succ.recover_from_journal()
    assert len(mapping) == 2
    succ.run(max_ticks=500)
    assert {c.tag for c in succ.pop_finished()} == {"life0", "life1"}
    # Successor traces carry the recovery marker and the predecessor's id.
    for t in succ.tracer.completed:
        assert t.recovered_from is not None
        assert any(iv.phase == "journal_recovery" for iv in t.intervals)

    stitched = {s["tag"]: s for s in stitch_traces(load_serving_traces(tdir))}
    assert set(stitched) == {"life0", "life1"}
    for tag, st in stitched.items():
        assert st["lives"] == 2, (tag, st)
        assert st["status"] == "ok"
        assert "journal_recovery" in st["phase_ms"], st
        assert st["journal_recovery_ms"] > 0.0
        assert st["conservation_ok"], (
            f"{tag}: conservation error {st['conservation_error_ms']} ms "
            f"over {st['total_ms']} ms"
        )
    # The report renders the stitch offline from the files alone.
    block = "\n".join(format_trace_block(
        summarize_traces(load_serving_traces(tdir))
    ))
    assert "stitched tag 'life0'" in block
    assert "serving traces (per-request blame)" in block


def test_report_cli_renders_trace_block(tmp_path, capsys):
    """telemetry.report picks the trace JSONL up from a run dir (human and
    --json) with no engine or jax state present."""
    from accelerate_tpu.telemetry import report

    rec = {"kind": "serving_trace", "rid": 2, "tag": "r", "status": "ok",
           "arrival_wall": 5.0, "duration_ms": 42.0, "blame": "queue_wait",
           "phase_ms": {"queue_wait": 30.0, "decode": 12.0},
           "unattributed_ms": 0.0, "phases": []}
    with open(tmp_path / "serving_trace_7_aa.jsonl", "w") as f:
        f.write(json.dumps(rec) + "\n")
    assert report.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "serving traces (per-request blame) — 1 completed" in out
    assert "blame: queue_wait 1" in out
    assert report.main([str(tmp_path), "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["serving_traces"]["requests"] == 1
    assert payload["serving_traces"]["by_blame"] == {"queue_wait": 1}
