"""Goodput accounting (telemetry/goodput.py): the attribution ledger's
precedence sweep and conservation invariant, the health-skip/rewind replay
reclassification, fault markers, offline replay, the live telemetry wiring,
the fleet aggregator's straggler naming + min-over-hosts goodput, and the
report integration (human block + stable --json key).
"""

import json
import time

import pytest

from accelerate_tpu import telemetry
from accelerate_tpu.telemetry import get_telemetry, goodput, span
from accelerate_tpu.telemetry import report as telemetry_report
from accelerate_tpu.telemetry.goodput import (
    CATEGORIES,
    FleetAggregator,
    GoodputLedger,
    ledger_from_records,
    summary_from_records,
)
from accelerate_tpu.telemetry.sentinel import AnomalySentinel


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.disable()
    get_telemetry().registry.reset()
    get_telemetry().step_timer.reset()
    goodput.detach()
    yield
    telemetry.disable()
    goodput.detach()


EPS = 1e-9


def _span_record(name, t_end, dur_s, **fields):
    return {"kind": "span", "name": name, "t": t_end, "dur_ms": dur_s * 1e3, **fields}


def _event(name, t, **fields):
    return {"kind": "event", "name": name, "t": t, **fields}


def _check_conservation(summary):
    assert abs(summary["conservation_error_s"]) < 1e-6, summary
    assert all(v >= -EPS for v in summary["seconds"].values()), summary
    assert summary["attributed_s"] <= summary["elapsed_s"] + 1e-6


# ---------------------------------------------------------------------------
# Ledger unit semantics
# ---------------------------------------------------------------------------


def test_single_categories_and_idle_complement():
    led = GoodputLedger(start_t=100.0)
    led.observe_record(_span_record("pipeline.train_step", 101.0, 0.5))
    led.observe_record(_span_record("checkpoint.save_state", 102.0, 0.25))
    led.observe_record(_span_record("dataloader.next_batch", 103.0, 0.125))
    s = led.summary(now=104.0)
    assert s["elapsed_s"] == pytest.approx(4.0)
    assert s["seconds"]["productive"] == pytest.approx(0.5)
    assert s["seconds"]["checkpoint"] == pytest.approx(0.25)
    assert s["seconds"]["input_wait"] == pytest.approx(0.125)
    assert s["seconds"]["idle"] == pytest.approx(4.0 - 0.875)
    assert s["goodput_fraction"] == pytest.approx(0.5 / 4.0, abs=1e-6)
    _check_conservation(s)


def test_precedence_compile_inside_train_step_wins():
    """The first step's trace+compile happens INSIDE the train-step span: the
    overlap must be compile badput, counted once."""
    led = GoodputLedger(start_t=0.0)
    led.observe_record(_span_record("pipeline.train_step", 10.0, 10.0))
    led.observe_record({"kind": "compile", "t": 8.0, "dur_ms": 6000.0})
    s = led.summary(now=10.0)
    assert s["seconds"]["compile"] == pytest.approx(6.0)
    assert s["seconds"]["productive"] == pytest.approx(4.0)
    assert s["seconds"]["idle"] == pytest.approx(0.0)
    _check_conservation(s)


def test_nested_checkpoint_spans_do_not_double_count():
    led = GoodputLedger(start_t=0.0)
    # health.rewind wraps checkpoint.load_state — same category, one second.
    led.observe_record(_span_record("health.rewind", 2.0, 1.0))
    led.observe_record(_span_record("checkpoint.load_state", 1.9, 0.8))
    s = led.summary(now=2.0)
    assert s["seconds"]["checkpoint"] == pytest.approx(1.0)
    _check_conservation(s)


def test_health_skip_reclassifies_the_step_it_judged():
    led = GoodputLedger(start_t=0.0)
    led.observe_record(_span_record("pipeline.train_step", 1.0, 1.0))
    led.observe_record(_event("health.skip", 1.01, step=1))
    led.observe_record(_span_record("pipeline.train_step", 2.0, 0.5))
    s = led.summary(now=2.0)
    assert s["seconds"]["rewind_replay"] == pytest.approx(1.0)
    assert s["seconds"]["productive"] == pytest.approx(0.5)
    assert s["markers"]["rewind_replay"] == 1
    _check_conservation(s)


def test_rewind_arms_replay_budget():
    """A rewind from step 5 to checkpoint step 2 means the next 3 steps are
    re-runs — badput even though they compute; the 4th is new ground."""
    led = GoodputLedger(start_t=0.0)
    led.observe_record(_event("health.rewind", 0.5, step=5, resumed_step=2))
    for i in range(4):
        led.observe_record(_span_record("pipeline.train_step", 1.0 + i, 0.5))
    s = led.summary(now=5.0)
    assert s["seconds"]["rewind_replay"] == pytest.approx(1.5)
    assert s["seconds"]["productive"] == pytest.approx(0.5)
    _check_conservation(s)


def test_preempt_epoch_claims_post_signal_remainder():
    led = GoodputLedger(start_t=0.0)
    led.observe_record(_span_record("pipeline.train_step", 1.0, 1.0))
    led.observe_record(_event("resilience.preempt_signal", 2.0, signum=15))
    # The final checkpoint after the signal is still checkpoint time...
    led.observe_record(_span_record("resilience.final_checkpoint", 3.0, 0.5))
    s = led.summary(now=4.0)
    assert s["seconds"]["checkpoint"] == pytest.approx(0.5)
    # ...idle before the signal stays idle, the drain after it is preempt.
    assert s["seconds"]["idle"] == pytest.approx(1.0)
    assert s["seconds"]["preempt"] == pytest.approx(1.5)
    assert s["markers"]["preempt"] == 1
    _check_conservation(s)


def test_retry_waits_split_by_label():
    led = GoodputLedger(start_t=0.0)
    led.observe_record(
        _event("resilience.retry", 1.0, label="checkpoint.publish", wait_s=0.5,
               error="OSError: disk")
    )
    led.observe_record(
        _event("resilience.retry", 3.0, label="device.alloc", wait_s=0.25,
               error="TimeoutError: busy")
    )
    led.observe_record(
        _event("resilience.gave_up", 4.0, label="alloc",
               error="non-retryable: RuntimeError: RESOURCE_EXHAUSTED: oom")
    )
    s = led.summary(now=5.0)
    assert s["seconds"]["checkpoint"] == pytest.approx(0.5)
    assert s["seconds"]["device_acquire"] == pytest.approx(0.25)
    assert s["markers"]["checkpoint"] == 1
    assert s["markers"]["device_acquire"] == 2  # the retry + the RE give-up
    _check_conservation(s)


def test_background_categories_cannot_be_claimed():
    led = GoodputLedger()
    with pytest.raises(ValueError):
        led.note_interval("idle", 0.0, 1.0)
    with pytest.raises(ValueError):
        led.note_interval("preempt", 0.0, 1.0)


def test_conservation_under_randomized_overlap():
    import random

    rnd = random.Random(0)
    led = GoodputLedger(start_t=0.0)
    for _ in range(300):
        cat = CATEGORIES[rnd.randrange(6)]
        t0 = rnd.uniform(0.0, 50.0)
        led.note_interval(cat, t0, t0 + rnd.uniform(0.0, 3.0))
    s = led.summary(now=50.0)  # some intervals extend past the window: clipped
    _check_conservation(s)
    assert s["elapsed_s"] == pytest.approx(50.0)


def test_compaction_matches_uncompacted_sweep(monkeypatch):
    import random

    rnd = random.Random(1)
    records = []
    for i in range(400):
        name = ("pipeline.train_step", "checkpoint.save_state", "dataloader.next_batch")[i % 3]
        t0 = rnd.uniform(0.0, 100.0)
        records.append(_span_record(name, t0 + rnd.uniform(0.0, 2.0), rnd.uniform(0.0, 2.0)))
    records.sort(key=lambda r: r["t"])

    def build():
        led = GoodputLedger(start_t=0.0)
        for r in records:
            led.observe_record(r)
        return led

    plain = build().summary(now=200.0)
    monkeypatch.setattr(GoodputLedger, "COMPACT_AT", 32)
    monkeypatch.setattr(GoodputLedger, "COMPACT_MARGIN_S", 0.0)
    compacting = build()
    # Interleave mid-run summaries so compaction actually folds the prefix.
    compacting.summary(now=120.0)
    compacted = compacting.summary(now=200.0)
    assert len(compacting._intervals) <= 64  # the fold actually happened
    for name in CATEGORIES:
        assert compacted["seconds"][name] == pytest.approx(
            plain["seconds"][name], abs=1e-6
        ), name
    _check_conservation(compacted)


def test_offline_replay_matches_live_order():
    records = [
        _span_record("pipeline.train_step", 1.0, 0.5),
        _event("health.skip", 1.01, step=1),
        _span_record("pipeline.train_step", 2.0, 0.5),
        {"kind": "metrics", "t": 2.5, "snapshot": {}},
    ]
    s = summary_from_records(records)
    assert s["elapsed_s"] == pytest.approx(2.0)  # earliest span START .. last t
    assert s["seconds"]["rewind_replay"] == pytest.approx(0.5)
    assert s["seconds"]["productive"] == pytest.approx(0.5)
    assert summary_from_records([]) is None
    assert ledger_from_records([{"kind": "span"}]) is None  # no timestamps


# ---------------------------------------------------------------------------
# Live wiring through the telemetry singleton
# ---------------------------------------------------------------------------


def test_attached_ledger_classifies_live_spans_and_publishes(tmp_path):
    tel = telemetry.enable(dir=str(tmp_path))
    led = goodput.attach()
    with span("pipeline.train_step"):
        time.sleep(0.03)
    with span("checkpoint.save_state"):
        time.sleep(0.02)
    tel.record_step()  # publishes goodput.* gauges
    snap = tel.registry.snapshot()
    assert snap["goodput.productive_s"] >= 0.02
    assert snap["goodput.checkpoint_s"] >= 0.01
    assert 0.0 <= snap["goodput.fraction"] <= 1.0
    assert snap["goodput.elapsed_s"] > 0
    _check_conservation(led.summary())


def test_env_attach_and_disable_detaches(tmp_path, monkeypatch):
    monkeypatch.setenv("ACCELERATE_TPU_GOODPUT", "1")
    telemetry.enable(dir=str(tmp_path))
    assert goodput.get_ledger() is not None
    telemetry.disable()
    assert goodput.get_ledger() is None
    # The final snapshot written on disable carries the ledger gauges.
    records = telemetry_report.load_records(str(tmp_path))
    snapshot = [r for r in records if r.get("kind") == "metrics"][-1]["snapshot"]
    assert "goodput.fraction" in snapshot


def test_disabled_telemetry_feeds_no_ledger(tmp_path):
    led = goodput.attach()
    with span("pipeline.train_step"):
        time.sleep(0.01)
    assert led.summary()["seconds"]["productive"] == 0.0


# ---------------------------------------------------------------------------
# Fleet aggregation
# ---------------------------------------------------------------------------


def _fake_gather(n_hosts, slow_host=None, fractions=None):
    """A gather_fn that splices fake peers around the local payload."""

    def gather(items):
        local = items[0]
        out = []
        for h in range(n_hosts):
            if h == local["host"]:
                out.append(local)
                continue
            durs = [100.0] * len(local["durs"])
            if h == slow_host:
                durs = [250.0] * len(local["durs"])
            out.append({
                "host": h,
                "durs": durs,
                "goodput_fraction": (fractions or {}).get(h, 0.8),
            })
        return out

    return gather


def test_fleet_aggregator_cadence_and_straggler_naming(tmp_path):
    tel = telemetry.enable(dir=str(tmp_path))
    sentinel = AnomalySentinel(window=32, warmup=4, straggler_factor=1.5)
    agg = FleetAggregator(
        sentinel=sentinel, every=4,
        gather_fn=_fake_gather(4, slow_host=2, fractions={2: 0.4}),
        host=0,
    )
    reports = [agg.on_step(100.0, telemetry=tel) for _ in range(16)]
    gathers = [r for r in reports if r is not None]
    assert len(gathers) == 4  # every 4th call, not every call
    final = gathers[-1]
    assert final["hosts"] == 4
    assert [s["host"] for s in final["stragglers"]] == [2]
    assert final["stragglers"][0]["ratio"] >= 2.0
    # min-over-hosts: host 2's 0.4 beats everyone's 0.8 (local has no ledger
    # attached, so its fraction is None and is excluded).
    assert final["fleet_fraction"] == pytest.approx(0.4)
    snap = tel.registry.snapshot()
    assert snap["goodput.fleet_hosts"] == 4
    assert snap["goodput.straggler_count"] == 1
    assert snap["goodput.fleet_fraction"] == pytest.approx(0.4)
    events = [
        json.loads(line)
        for line in open(tel.jsonl_path)
        if "sentinel.straggler" in line
    ]
    assert events and events[-1]["host"] == 2


def test_record_step_drives_installed_aggregator(tmp_path):
    tel = telemetry.enable(dir=str(tmp_path))
    agg = FleetAggregator(
        sentinel=AnomalySentinel(window=32, warmup=2),
        every=2, gather_fn=_fake_gather(2), host=0,
    )
    tel.install_fleet_aggregator(agg)
    for _ in range(5):
        tel.record_step()
        time.sleep(0.002)
    # record_step skips the first step (no duration yet): 4 timed steps at
    # cadence 2 = 2 gathers.
    assert agg.last_report is not None
    assert agg.last_report["hosts"] == 2


def test_local_goodput_fraction_travels_with_the_gather(tmp_path):
    telemetry.enable(dir=str(tmp_path))
    led = goodput.attach()
    led.note_interval("productive", led.start_t, led.start_t + 0.5)
    seen = {}

    def gather(items):
        seen.update(items[0])
        return list(items)

    agg = FleetAggregator(sentinel=AnomalySentinel(), every=1, gather_fn=gather, host=0)
    agg.on_step(10.0)
    assert seen["goodput_fraction"] is not None and seen["goodput_fraction"] > 0


# ---------------------------------------------------------------------------
# Report integration
# ---------------------------------------------------------------------------


def _run_and_load(tmp_path):
    tel = telemetry.enable(dir=str(tmp_path))
    with span("pipeline.train_step"):
        time.sleep(0.02)
    tel.event("resilience.retry", label="checkpoint.publish", attempt=1,
              wait_s=0.01, error="OSError: x")
    telemetry.disable()
    return telemetry_report.load_records(str(tmp_path))


def test_report_human_block_renders_ledger(tmp_path):
    records = _run_and_load(tmp_path)
    out = telemetry_report.format_report(telemetry_report.summarize(records))
    assert "goodput ledger" in out
    assert "productive" in out
    assert "conservation error" in out


def test_report_json_carries_stable_goodput_key(tmp_path, capsys):
    _run_and_load(tmp_path)
    rc = telemetry_report.main([str(tmp_path), "--json"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    gp = payload["goodput"]
    assert gp is not None
    assert set(gp["seconds"]) == set(CATEGORIES)
    assert abs(gp["conservation_error_s"]) < 1e-6
    assert gp["markers"].get("checkpoint", 0) >= 1
    # ...and the goodput dict is NOT duplicated inside the telemetry block.
    assert "goodput" not in payload["telemetry"]


def test_straggler_recovery_emits_clear_and_ages_out_of_report(tmp_path):
    """A host named straggler once must NOT be reported forever: when a later
    gather no longer names it, the aggregator emits cleared=True and the
    report drops the row."""
    tel = telemetry.enable(dir=str(tmp_path))
    sentinel = AnomalySentinel(window=8, warmup=4, straggler_factor=1.5)
    state = {"slow": 2}

    def gather(items):
        local = items[0]
        out = [local]
        for h in (1, 2):
            dur = 300.0 if h == state["slow"] else 100.0
            out.append({"host": h, "durs": [dur] * len(local["durs"]),
                        "goodput_fraction": 0.8})
        return out

    agg = FleetAggregator(sentinel=sentinel, every=4, gather_fn=gather, host=0)
    for _ in range(8):
        agg.on_step(100.0, telemetry=tel)
    assert [s["host"] for s in agg.last_report["stragglers"]] == [2]
    # Host 2 recovers; its fast steps age the rolling median back down.
    state["slow"] = None
    for _ in range(16):
        agg.on_step(100.0, telemetry=tel)
    assert agg.last_report["stragglers"] == []
    telemetry.disable()
    records = telemetry_report.load_records(str(tmp_path))
    summary = telemetry_report.summarize(records)
    assert summary["stragglers"][-1].get("cleared") is True
    assert "STRAGGLER" not in telemetry_report.format_report(summary)


def test_attached_context_restores_previous_ledger(tmp_path):
    """A probe's scoped ledger (perf-gate goodput arm) must not destroy the
    host run's attached ledger."""
    telemetry.enable(dir=str(tmp_path))
    host_ledger = goodput.attach()
    with goodput.attached() as probe_ledger:
        assert goodput.get_ledger() is probe_ledger
        assert probe_ledger is not host_ledger
    assert goodput.get_ledger() is host_ledger


def test_skip_reclassification_survives_compaction_split(monkeypatch):
    """The health.skip reclassification holds an OBJECT reference: a
    compaction that rebuilds (and even splits) the interval list between the
    span and its skip event must still flip the right interval."""
    monkeypatch.setattr(GoodputLedger, "COMPACT_AT", 2)
    monkeypatch.setattr(GoodputLedger, "COMPACT_MARGIN_S", 0.0)
    led = GoodputLedger(start_t=0.0)
    led.observe_record(_span_record("dataloader.next_batch", 1.0, 0.5))
    led.observe_record(_span_record("checkpoint.save_state", 2.0, 0.5))
    # The step span [9, 11] straddles the compaction boundary below.
    led.observe_record(_span_record("pipeline.train_step", 11.0, 2.0))
    led.summary(now=10.0)  # compacts up to 10.0, splitting the step interval
    led.observe_record(_event("health.skip", 11.01, step=1))
    s = led.summary(now=12.0)
    # The kept right half [10, 11] flipped to rewind_replay; the folded left
    # half [9, 10] legitimately stays productive (documented degradation —
    # in practice skips land milliseconds after their span, inside the
    # margin, so nothing has folded yet).
    assert s["seconds"]["rewind_replay"] == pytest.approx(1.0)
    assert s["seconds"]["productive"] == pytest.approx(1.0)
    _check_conservation(s)


def test_record_step_publish_is_cadence_gated(tmp_path):
    tel = telemetry.enable(dir=str(tmp_path))
    led = goodput.attach()
    calls = {"n": 0}
    orig = led.publish

    def counting_publish(registry, now=None):
        calls["n"] += 1
        return orig(registry, now=now)

    led.publish = counting_publish
    for _ in range(20):
        tel.record_step()
    # First step publishes (gauges exist early), then every 16th.
    assert calls["n"] == 2
    assert "goodput.fraction" in tel.registry.snapshot()


def test_report_renders_stragglers(tmp_path):
    tel = telemetry.enable(dir=str(tmp_path))
    with span("pipeline.train_step"):
        time.sleep(0.01)
    tel.event("sentinel.straggler", host=3, median_ms=250.0,
              fleet_median_ms=100.0, ratio=2.5)
    telemetry.disable()
    records = telemetry_report.load_records(str(tmp_path))
    out = telemetry_report.format_report(telemetry_report.summarize(records))
    assert "STRAGGLER host 3" in out and "2.5x" in out
