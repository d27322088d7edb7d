"""CPU-tier perf-regression gate (pipeline/perf_gate.py): the committed
baseline parses, the evaluate() thresholds cut both ways, the real probe
passes the gate on CPU inside tier-1, and the degrade knob demonstrably
fails it — the proof the gate can actually catch a fused-path rot.
"""

import json

import pytest

from accelerate_tpu import telemetry
from accelerate_tpu.pipeline.perf_gate import (
    DEFAULT_BASELINE_PATH,
    evaluate,
    load_baseline,
    run_gate,
    run_pp_probe,
    run_probe,
    run_serving_probe,
    run_spec_probe,
    run_tiering_probe,
)


@pytest.fixture(autouse=True)
def _telemetry_off():
    yield
    telemetry.disable()


def _passing_measurements():
    return {
        "fused_vs_eager_ratio": 2.0,
        "dispatches_per_step": 1.0,
        "fused_host_blocked_ms_per_step": 2.0,
        "goodput_productive_frac": 0.3,
        "goodput_conservation_error_s": 0.0,
        "train_state_bytes_per_chip": 200000,
    }


def test_baseline_is_committed_and_parses():
    baseline = load_baseline()
    assert baseline["max_dispatches_per_step"] == 1.0
    assert baseline["min_fused_vs_eager_ratio"] > 1.0
    assert baseline["max_fused_host_blocked_ms_per_step"] > 0
    assert baseline["probe"]["accum"] >= 2  # the contrast the ratio floor assumes


def test_evaluate_passes_clean_measurements():
    assert evaluate(_passing_measurements(), load_baseline()) == []


def test_evaluate_fails_each_threshold():
    baseline = load_baseline()
    m = dict(_passing_measurements(), dispatches_per_step=6.0)
    assert any("dispatches" in f for f in evaluate(m, baseline))
    m = dict(_passing_measurements(), fused_vs_eager_ratio=1.0)
    assert any("ratio" in f for f in evaluate(m, baseline))
    m = dict(_passing_measurements(), fused_host_blocked_ms_per_step=500.0)
    assert any("host-blocked" in f for f in evaluate(m, baseline))


@pytest.mark.slow  # ~50s; `make test` runs the standalone 3-epoch gate
# (perf-gate target) on every invocation, so the in-suite run duplicated
# that coverage inside the bounded tier-1 budget.
def test_gate_passes_on_cpu(capsys):
    """The real gate as a pytest test: perf regressions in the fused
    pipeline fail `make test` even when no TPU answers (ROADMAP item 5) —
    via this test in the full run and the perf-gate Make target either way.
    Two timed epochs instead of the standalone gate's three."""
    assert run_gate(probe_kwargs={"epochs": 2}) == 0
    out = capsys.readouterr().out
    line = next(l for l in out.splitlines() if l.startswith("{"))
    measurements = json.loads(line)["perf_gate"]
    assert measurements["dispatches_per_step"] == 1.0


def test_gate_fails_when_fused_path_degraded(monkeypatch):
    """Forcing the fused arm onto the eager loop must trip the gate — the
    dispatches/step integer jumps to 3 x accum, immune to timing noise."""
    monkeypatch.setenv("ACCELERATE_TPU_PERF_GATE_DEGRADE", "eager")
    measurements = run_probe(accum=2, steps=4, dim=64, batch=8, epochs=1, prefetch=0, pp=False, serving=False)
    assert measurements["probe"]["degrade"] == "eager"
    assert measurements["dispatches_per_step"] == 6.0
    failures = evaluate(measurements, load_baseline())
    assert any("dispatches" in f for f in failures)


def _passing_zero_measurements():
    return dict(
        _passing_measurements(),
        zero_active=True,
        zero_vs_eager_ratio=2.0,
        zero_dispatches_per_step=1.0,
        zero_host_blocked_ms_per_step=2.0,
        zero_exposed_collective_frac=0.5,
    )


def test_evaluate_zero_row_thresholds():
    baseline = load_baseline()
    assert evaluate(_passing_zero_measurements(), baseline) == []
    m = dict(_passing_zero_measurements(), zero_active=False)
    assert any("silently fell back" in f for f in evaluate(m, baseline))
    m = dict(_passing_zero_measurements(), zero_dispatches_per_step=12.0)
    assert any("ZeRO dispatches" in f for f in evaluate(m, baseline))
    m = dict(_passing_zero_measurements(), zero_vs_eager_ratio=1.0)
    assert any("ZeRO-vs-eager" in f for f in evaluate(m, baseline))
    # Single-device probe: the arm was skipped — no zero judgments at all.
    m = dict(_passing_measurements(), zero_active=None)
    assert evaluate(m, baseline) == []


def test_evaluate_overlap_row_thresholds():
    """The exposed-collective row (PR 8): too-exposed fails, a missing audit
    number fails LOUDLY (a broken capture is a broken check), and the
    single-device skip still applies."""
    baseline = load_baseline()
    assert baseline["max_exposed_collective_frac"] < 1.0
    m = dict(_passing_zero_measurements(), zero_exposed_collective_frac=1.0)
    assert any("exposed-collective fraction" in f for f in evaluate(m, baseline))
    m = dict(_passing_zero_measurements(), zero_exposed_collective_frac=None,
             zero_profile_error="trace analysis exploded")
    failures = evaluate(m, baseline)
    assert any("unchecked" in f and "exploded" in f for f in failures)
    m = dict(_passing_measurements(), zero_active=None)
    assert evaluate(m, baseline) == []


def test_gate_fails_when_zero_silently_falls_back(monkeypatch):
    """ACCELERATE_TPU_PERF_GATE_DEGRADE=zero-fallback runs the ZeRO arm with
    the replicated update — the zero_active tripwire must fail the gate."""
    monkeypatch.setenv("ACCELERATE_TPU_PERF_GATE_DEGRADE", "zero-fallback")
    measurements = run_probe(accum=2, steps=4, dim=64, batch=8, epochs=1, prefetch=0, pp=False, serving=False)
    assert measurements["zero_active"] is False
    failures = evaluate(measurements, load_baseline())
    assert any("silently fell back" in f for f in failures)


@pytest.mark.slow
def test_gate_fails_when_overlap_stripped(monkeypatch):
    """ACCELERATE_TPU_PERF_GATE_DEGRADE=no-overlap scans the ZeRO arm's trace
    with the concurrent-compute credit disabled (what stripping the TPU
    latency-hiding flags does at runtime): exposed frac hits 1.0 by
    construction and the overlap row must fail the gate.  Probe-level
    self-test; the cheap evaluate()-level row tests run in tier-1."""
    monkeypatch.setenv("ACCELERATE_TPU_PERF_GATE_DEGRADE", "no-overlap")
    measurements = run_probe(accum=2, steps=4, dim=64, batch=8, epochs=1, prefetch=0, pp=False, serving=False)
    assert measurements["zero_exposed_collective_frac"] == 1.0
    failures = evaluate(measurements, load_baseline())
    assert any("exposed-collective fraction" in f for f in failures)


# ---------------------------------------------------------------------------
# goodput row (PR 13): wall-clock attribution ledger audit
# ---------------------------------------------------------------------------


def test_evaluate_goodput_row_thresholds():
    """The goodput row: a too-low productive fraction fails, a MISSING number
    fails loudly (the overlap-row convention: a broken audit is a broken
    check), and a blown conservation residual fails the ledger itself."""
    baseline = load_baseline()
    assert 0 < baseline["min_goodput_productive_frac"] < 1
    assert baseline["max_goodput_conservation_error_s"] > 0
    assert evaluate(_passing_measurements(), baseline) == []
    m = dict(_passing_measurements(), goodput_productive_frac=0.01)
    assert any("goodput productive fraction" in f for f in evaluate(m, baseline))
    m = dict(_passing_measurements(), goodput_productive_frac=None)
    assert any("goodput audit produced no number" in f for f in evaluate(m, baseline))
    m = dict(_passing_measurements(), goodput_conservation_error_s=0.5)
    assert any("conservation error" in f for f in evaluate(m, baseline))


def test_gate_fails_when_badput_degraded(monkeypatch):
    """ACCELERATE_TPU_PERF_GATE_DEGRADE=badput sleeps between the goodput
    arm's steps (pure idle badput) — the productive-fraction floor must fail
    the gate, and the ledger must still conserve."""
    monkeypatch.setenv("ACCELERATE_TPU_PERF_GATE_DEGRADE", "badput")
    measurements = run_probe(accum=2, steps=4, dim=64, batch=8, epochs=1, prefetch=0, pp=False, serving=False)
    baseline = load_baseline()
    assert measurements["goodput_productive_frac"] < baseline["min_goodput_productive_frac"]
    assert abs(measurements["goodput_conservation_error_s"]) <= (
        baseline["max_goodput_conservation_error_s"]
    )
    failures = evaluate(measurements, baseline)
    assert any("goodput productive fraction" in f for f in failures)


# ---------------------------------------------------------------------------
# pp row (PR 11): fused pipeline-parallel step + interleaved schedule
# ---------------------------------------------------------------------------


def _passing_pp_measurements():
    return dict(
        _passing_measurements(),
        pp_dispatches_per_step=1.0,
        pp_interleaved_active=True,
        pp_interleaved_vs_gpipe_ratio=1.1,
        pp_gpipe_ticks=5,
        pp_interleaved_ticks=9,
    )


def test_evaluate_pp_row_thresholds():
    baseline = load_baseline()
    assert baseline["max_pp_dispatches_per_step"] == 1.0
    assert baseline["require_pp_interleaved"] is True
    assert baseline["min_interleaved_vs_gpipe_ratio"] > 0
    assert evaluate(_passing_pp_measurements(), baseline) == []
    m = dict(_passing_pp_measurements(), pp_interleaved_active=False)
    assert any("fell back to gpipe" in f for f in evaluate(m, baseline))
    m = dict(_passing_pp_measurements(), pp_dispatches_per_step=9.0)
    assert any("pp dispatches" in f for f in evaluate(m, baseline))
    m = dict(_passing_pp_measurements(), pp_interleaved_vs_gpipe_ratio=0.4)
    assert any("interleaved-vs-gpipe" in f for f in evaluate(m, baseline))
    # Single-device probe: the pp arm was skipped — no pp judgments at all.
    assert evaluate(_passing_measurements(), baseline) == []


@pytest.mark.slow
def test_pp_probe_fused_one_dispatch_and_interleaved_wins_ticks():
    """The real pp probe: the fused pipeline-parallel train
    step must be exactly 1 dispatch per optimizer step for BOTH schedules,
    the interleaved schedule must actually build (tick count v*M + S - 1 <
    the gpipe-equal-work v*(M+S-1)), and the analytic bubble must shrink."""
    row = run_pp_probe(steps=3)
    assert row["pp_dispatches_per_step"] == 1.0
    assert row["pp_gpipe_dispatches_per_step"] == 1.0
    assert row["pp_active"] is True
    assert row["pp_interleaved_active"] is True
    v, M, S = row["pp_virtual_stages"], row["pp_micro_batches"], row["pp_degree"]
    assert row["pp_gpipe_ticks"] == M + S - 1
    assert row["pp_interleaved_ticks"] == v * M + S - 1 < v * (M + S - 1)
    assert row["pp_analytic_bubble_interleaved"] < row["pp_analytic_bubble_gpipe"]
    assert evaluate(
        dict(_passing_measurements(), **row), load_baseline()
    ) == []


@pytest.mark.slow
def test_pp_row_fails_when_gpipe_only_degraded(monkeypatch):
    """ACCELERATE_TPU_PERF_GATE_DEGRADE=gpipe-only runs the interleaved arm
    on the gpipe schedule — the pp_interleaved_active tripwire must fail the
    row (the proof the gate catches a silently-degraded schedule)."""
    monkeypatch.setenv("ACCELERATE_TPU_PERF_GATE_DEGRADE", "gpipe-only")
    row = run_pp_probe(steps=2)
    assert row["pp_interleaved_active"] is False
    failures = evaluate(dict(_passing_measurements(), **row), load_baseline())
    assert any("fell back to gpipe" in f for f in failures)


# ---------------------------------------------------------------------------
# serving row (PR 15): a paged family is served paged, one decode dispatch a tick
# ---------------------------------------------------------------------------


def _passing_serving_measurements():
    return dict(
        _passing_measurements(),
        serving_decode_dispatches_per_tick=1.0,
        serving_paged_active=True,
        serving_pool_bytes_per_chip=655360,
    )


def test_evaluate_serving_row_thresholds():
    baseline = load_baseline()
    assert baseline["require_serving_paged"] is True
    assert baseline["max_serving_decode_dispatches_per_tick"] == 1.0
    assert "min_paged_vs_dense_ratio" not in baseline  # no CPU steps/s ratio stands in this row
    assert evaluate(_passing_serving_measurements(), baseline) == []
    m = dict(_passing_serving_measurements(), serving_paged_active=False)
    assert any("fell back to the dense" in f for f in evaluate(m, baseline))
    m = dict(_passing_serving_measurements(), serving_decode_dispatches_per_tick=2.0)
    assert any("dispatches/tick" in f for f in evaluate(m, baseline))
    m = dict(_passing_serving_measurements(), serving_pool_bytes_per_chip=None)
    assert any("serving pool audit produced no number" in f for f in evaluate(m, baseline))
    # the row was skipped entirely: no serving judgments at all
    assert evaluate(_passing_measurements(), baseline) == []


# ---------------------------------------------------------------------------
# spec row (PR 19): speculative draft-then-verify vs plain greedy decode
# ---------------------------------------------------------------------------


def _passing_spec_measurements():
    return dict(
        _passing_serving_measurements(),
        serving_spec_vs_greedy_itl_ratio=1.1,
        serving_spec_acceptance_rate=0.9,
        serving_spec_tokens_per_dispatch=3.0,
        serving_spec_active=True,
        serving_spec_token_identical=True,
    )


def test_evaluate_spec_row_thresholds():
    """The spec row cuts three ways: the active tripwire (silent fallback to
    greedy), token identity (accept/rewind contract), and the ITL ratio floor
    (verify window slower per token than the single-token program).  The
    integer tripwires carry exactness — the CPU ratio floor sits below the
    noise band on purpose (see the baseline's _comment)."""
    baseline = load_baseline()
    assert baseline["require_spec_active"] is True
    assert 0 < baseline["min_spec_vs_greedy_itl_ratio"] < 1.0
    assert evaluate(_passing_spec_measurements(), baseline) == []
    m = dict(_passing_spec_measurements(), serving_spec_active=False)
    assert any("serving_spec_active is False" in f for f in evaluate(m, baseline))
    m = dict(_passing_spec_measurements(), serving_spec_token_identical=False)
    assert any("accept/rewind contract" in f for f in evaluate(m, baseline))
    m = dict(_passing_spec_measurements(), serving_spec_vs_greedy_itl_ratio=0.5)
    assert any("stopped beating" in f for f in evaluate(m, baseline))
    # spec arm never ran: no spec judgments at all
    assert evaluate(_passing_serving_measurements(), baseline) == []


@pytest.mark.slow
def test_spec_row_fails_when_no_spec_degraded(monkeypatch):
    """ACCELERATE_TPU_PERF_GATE_DEGRADE=no-spec runs the spec arm with
    spec_tokens=0 — plain greedy masquerading as the speculative config.
    The serving_spec_active tripwire must fail the row; note the measured
    ratio typically stays ABOVE the floor here (greedy vs greedy ~1.0+,
    and the floor is 0.9), which is exactly why the tripwire exists: the
    ratio floor alone can never catch a silent fallback.  Probe-level
    self-test; the cheap evaluate()-row tests run in tier-1."""
    monkeypatch.setenv("ACCELERATE_TPU_PERF_GATE_DEGRADE", "no-spec")
    row = run_spec_probe(max_new=16)
    assert row["serving_spec_active"] is False
    assert row["serving_spec_tokens_per_dispatch"] <= 1.0
    failures = evaluate(dict(_passing_measurements(), **row), load_baseline())
    assert any("serving_spec_active is False" in f for f in failures)


@pytest.mark.slow
def test_spec_probe_wins_and_matches_greedy():
    """The real spec probe on CPU: drafts are accepted (the n-gram drafter
    engages on the pure-pattern prompts from the first tick), more than one
    token lands per slot-dispatch, outputs are token-identical to the greedy
    arm, and the full row passes the committed gate."""
    row = run_spec_probe(max_new=24)
    assert row["serving_spec_active"] is True
    assert row["serving_spec_acceptance_rate"] > 0.5
    assert row["serving_spec_tokens_per_dispatch"] > 1.5
    assert row["serving_spec_token_identical"] is True
    failures = evaluate(dict(_passing_measurements(), **row), load_baseline())
    spec_failures = [f for f in failures if "spec" in f]
    assert spec_failures == []


# ---------------------------------------------------------------------------
# tiering row (PR 20): migrated preempt-resume vs the re-prefill fallback
# ---------------------------------------------------------------------------


def _passing_tiering_measurements():
    return dict(
        _passing_spec_measurements(),
        serving_migrated_vs_reprefill_ratio=1.4,
        serving_tiering_active=True,
        serving_tiering_token_identical=True,
        serving_tier_migrations=4,
        serving_tier_fallback_reprefills=0,
    )


def test_evaluate_tiering_row_thresholds():
    """The tiering row cuts three ways: the active tripwire (a preempted
    request silently re-prefilling instead of promoting its host-demoted
    blocks), token identity across the HBM->host->HBM round trip, and the
    migrated-vs-re-prefill resume ratio floor.  The tripwires carry the
    exactness — the CPU ratio floor sits below the noise band on purpose
    (see the baseline's _comment)."""
    baseline = load_baseline()
    assert baseline["require_tiering_active"] is True
    assert 0 < baseline["min_migrated_resume_vs_reprefill_ratio"] < 1.0
    assert evaluate(_passing_tiering_measurements(), baseline) == []
    m = dict(_passing_tiering_measurements(), serving_tiering_active=False)
    assert any(
        "serving_tiering_active is False" in f for f in evaluate(m, baseline)
    )
    m = dict(_passing_tiering_measurements(), serving_tiering_token_identical=False)
    assert any(
        "round trip corrupted KV state" in f for f in evaluate(m, baseline)
    )
    m = dict(_passing_tiering_measurements(), serving_migrated_vs_reprefill_ratio=0.5)
    assert any("stopped beating re-prefilling" in f for f in evaluate(m, baseline))
    # tiering arm never ran: no tiering judgments at all
    assert evaluate(_passing_spec_measurements(), baseline) == []


@pytest.mark.slow
def test_tiering_row_fails_when_no_tiering_degraded(monkeypatch):
    """ACCELERATE_TPU_PERF_GATE_DEGRADE=no-tiering builds the tiered arm
    with host_blocks=0 — re-prefill resume masquerading as the tiered
    config.  The serving_tiering_active tripwire must fail the row; the
    measured ratio typically stays NEAR 1.0 here (re-prefill vs re-prefill)
    while the floor is 0.9, which is exactly why the tripwire exists: the
    ratio floor alone can never catch a silent fallback.  Probe-level
    self-test; the cheap evaluate()-row tests run in tier-1."""
    monkeypatch.setenv("ACCELERATE_TPU_PERF_GATE_DEGRADE", "no-tiering")
    row = run_tiering_probe(cycles=2)
    assert row["serving_tiering_active"] is False
    failures = evaluate(dict(_passing_measurements(), **row), load_baseline())
    assert any("serving_tiering_active is False" in f for f in failures)


@pytest.mark.slow
def test_tiering_probe_wins_and_stays_token_identical():
    """The real tiering probe on CPU: promotions land with zero fallback
    re-prefills, outputs survive the HBM->host->HBM round trip
    token-identically, and the full row passes the committed gate."""
    row = run_tiering_probe(cycles=2)
    assert row["serving_tiering_active"] is True
    assert row["serving_tiering_token_identical"] is True
    assert row["serving_tier_migrations"] >= 2
    assert row["serving_tier_fallback_reprefills"] == 0
    failures = evaluate(dict(_passing_measurements(), **row), load_baseline())
    tier_failures = [f for f in failures if "tier" in f or "migrated" in f]
    assert tier_failures == []


# ---------------------------------------------------------------------------
# memory row (PR 17): per-chip byte ceilings from the HBM ledger
# ---------------------------------------------------------------------------


def test_evaluate_memory_row_thresholds():
    """The memory row: a bloated train state fails, a MISSING number fails
    loudly (the overlap-row convention: a deleted registration hook is a
    broken check, not an un-gated pass), and the serving-pool ceiling is
    judged only when the serving arm ran."""
    baseline = load_baseline()
    assert baseline["max_train_state_bytes_per_chip"] > 0
    assert baseline["max_serving_pool_bytes_per_chip"] > 0
    assert evaluate(_passing_measurements(), baseline) == []
    m = dict(_passing_measurements(), train_state_bytes_per_chip=10**9)
    assert any("train-state footprint" in f for f in evaluate(m, baseline))
    m = dict(_passing_measurements(), train_state_bytes_per_chip=None)
    assert any(
        "memory audit produced no number" in f for f in evaluate(m, baseline)
    )
    m = dict(_passing_serving_measurements(), serving_pool_bytes_per_chip=10**9)
    assert any("serving KV pool" in f for f in evaluate(m, baseline))
    m = dict(_passing_serving_measurements(), serving_pool_bytes_per_chip=None)
    assert any(
        "serving pool audit produced no number" in f for f in evaluate(m, baseline)
    )
    # No serving arm: the pool ceiling makes no judgment at all.
    assert evaluate(_passing_measurements(), baseline) == []


@pytest.mark.slow
def test_gate_fails_when_memory_bloated(monkeypatch):
    """ACCELERATE_TPU_PERF_GATE_DEGRADE=mem-bloat registers four live extra
    parameter copies under perf_gate.bloat — the per-chip train-state ceiling
    must fail the gate (the proof the memory row judges real bytes).  Runs at
    the baseline's dim=128 geometry: the ceiling was committed against it.
    Probe-level self-test (full probe, ~40s); the cheap evaluate()-level
    memory-row tests run in tier-1."""
    monkeypatch.setenv("ACCELERATE_TPU_PERF_GATE_DEGRADE", "mem-bloat")
    measurements = run_probe(
        accum=2, steps=4, dim=128, batch=8, epochs=1, prefetch=0,
        pp=False, serving=False,
    )
    baseline = load_baseline()
    assert (
        measurements["train_state_bytes_per_chip"]
        > baseline["max_train_state_bytes_per_chip"]
    )
    failures = evaluate(measurements, baseline)
    assert any("train-state footprint" in f for f in failures)


@pytest.mark.slow
def test_serving_probe_reports_exact_pool_bytes():
    """The serving arm's pool measurement is exact allocation arithmetic
    (num_blocks x block rows x layer K/V), committed in the baseline — and
    must stay under its ceiling.  Probe-level;
    `make perf-gate` judges the same number against the baseline every run."""
    baseline = load_baseline()
    row = run_serving_probe(decode_ticks=4)
    assert row["serving_pool_bytes_per_chip"] == 655360
    assert row["serving_pool_bytes_per_chip"] <= baseline["max_serving_pool_bytes_per_chip"]
