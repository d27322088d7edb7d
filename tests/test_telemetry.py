"""Telemetry subsystem: spans, metrics registry, collectors, stall watchdog,
hot-path instrumentation, report CLI, and the profile() trace-dir env var.

Everything runs default-OFF: the first test class asserts the disabled fast
path writes nothing; the rest enable telemetry into tmp dirs and verify the
JSONL stream and registry contents.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest
import torch
from torch.utils.data import DataLoader

from accelerate_tpu import telemetry
from accelerate_tpu.telemetry import (
    CompileWatcher,
    MetricsRegistry,
    StallWatchdog,
    annotate,
    get_telemetry,
    peak_flops_per_chip,
    span,
)
from accelerate_tpu.telemetry import report as telemetry_report


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Telemetry state is process-global; every test STARTS from a clean
    disabled singleton (enable() resets the registry but disable() keeps it
    for the final snapshot, so metrics from an earlier module — e.g. the
    test_flightrec steps — would otherwise leak into the disabled-by-default
    assertions here) and leaves it disabled."""
    telemetry.disable()
    get_telemetry().registry.reset()
    get_telemetry().step_timer.reset()
    yield
    telemetry.disable()
    # configure()'s constants outlive reset(): left set, they decide the next
    # test file's effective_flops_per_step on the same xdist worker
    timer = get_telemetry().step_timer
    timer.tokens_per_step = timer.flops_per_step = None


def _read_jsonl(tel):
    with open(tel.jsonl_path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------------------
# Disabled fast path
# ---------------------------------------------------------------------------


def test_disabled_by_default_and_writes_nothing(tmp_path):
    assert not telemetry.enabled()
    with span("should_not_record"):
        pass
    tel = get_telemetry()
    assert tel._file is None
    assert tel.registry.snapshot() == {}


def test_record_step_noop_when_disabled():
    tel = get_telemetry()
    tel.record_step()
    assert tel.registry.snapshot() == {}


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


def test_span_nesting_depth_and_path(tmp_path):
    tel = telemetry.enable(dir=str(tmp_path))
    with span("outer"):
        time.sleep(0.01)
        with span("inner", detail="x"):
            pass
    records = [r for r in _read_jsonl(tel) if r["kind"] == "span"]
    inner, outer = records[0], records[1]  # inner exits (and writes) first
    assert inner["name"] == "inner" and inner["depth"] == 1
    assert inner["path"] == "outer/inner"
    assert inner["attrs"] == {"detail": "x"}
    assert outer["name"] == "outer" and outer["depth"] == 0
    assert outer["dur_ms"] >= 10
    assert "proc" in outer and "t" in outer


def test_span_decorator_and_exception_flag(tmp_path):
    tel = telemetry.enable(dir=str(tmp_path))

    @span("decorated")
    def work(x):
        return x + 1

    assert work(1) == 2
    assert work(2) == 3

    with pytest.raises(ValueError):
        with span("failing"):
            raise ValueError("boom")

    records = [r for r in _read_jsonl(tel) if r["kind"] == "span"]
    names = [r["name"] for r in records]
    assert names.count("decorated") == 2
    failing = next(r for r in records if r["name"] == "failing")
    assert failing["error"] == "ValueError"
    # Registry mirrors every span into a histogram.
    assert tel.registry.snapshot()["span.decorated_ms.count"] == 2


def _profiled_events(trace_dir, name):
    """Host events called ``name`` in the one profile under ``trace_dir``: (stats, duration_ns)."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return [
        (dict(e.stats), e.duration_ns)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for e in line.events
        if e.name == name
    ]


@pytest.mark.parametrize("make,enabled,records", [
    (annotate, False, 0), (annotate, True, 0), (span, False, 0), (span, True, 1),
])
def test_annotate_is_the_profiler_half_of_span(tmp_path, make, enabled, records):
    """Both show in a profiler session whatever telemetry's state is (annotate
    with its keywords as the event's stats); a JSONL record and a histogram
    come from span alone, and only with telemetry on."""
    tel = telemetry.enable(dir=str(tmp_path / "tel")) if enabled else get_telemetry()
    jax.profiler.start_trace(str(tmp_path / "prof"))
    with make("probe.scope", detail=3):
        pass
    jax.profiler.stop_trace()
    ((stats, duration_ns),) = _profiled_events(str(tmp_path / "prof"), "probe.scope")
    assert duration_ns > 0 and stats == ({"detail": 3} if make is annotate else {})
    if not enabled:
        assert tel._file is None and tel.registry.snapshot() == {}
        return
    spans = [r for r in _read_jsonl(tel) if r["kind"] == "span"]
    assert [r["name"] for r in spans] == ["probe.scope"] * records
    assert ("span.probe.scope_ms.count" in tel.registry.snapshot()) == bool(records)


def test_span_enabled_mid_flight_records_nothing_for_open_context(tmp_path):
    """A span entered while disabled must not write on exit, even if telemetry
    turned on mid-context (enablement is checked at __enter__)."""
    s = span("early")
    s.__enter__()
    tel = telemetry.enable(dir=str(tmp_path))
    s.__exit__(None, None, None)
    assert all(r["kind"] != "span" for r in _read_jsonl(tel))


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.5)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.histogram("h").observe(v)
    snap = reg.snapshot()
    assert snap["c"] == 5
    assert snap["g"] == 2.5
    assert snap["h.count"] == 4
    assert snap["h.mean"] == 2.5
    assert snap["h.min"] == 1.0 and snap["h.max"] == 4.0
    assert snap["h.last"] == 4.0
    assert 2.0 <= snap["h.p50"] <= 3.0
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("c")


def test_peak_flops_unknown_device_is_an_error():
    class _Dev:
        device_kind = "TPU v5 lite"

    assert peak_flops_per_chip(_Dev()) == 197e12
    # The CPU test mesh is not in the table: no default peak to hide behind.
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        peak_flops_per_chip()


def test_step_timer_tokens_and_mfu(tmp_path):
    tel = telemetry.enable(dir=str(tmp_path))
    tel.step_timer.configure(tokens_per_step=1000, flops_per_step=1e9)
    tel.record_step()
    time.sleep(0.02)
    tel.record_step()
    snap = tel.registry.snapshot()
    assert snap["step.count"] == 2
    assert snap["step.time_ms.count"] == 1  # first step has no prior boundary
    assert snap["step.time_ms.last"] >= 20
    assert snap["step.tokens_per_sec"] > 0
    # ...and no MFU against a guessed peak: the CPU is not in the table.
    assert "step.mfu" not in snap


# ---------------------------------------------------------------------------
# Compile (jit cache-miss) detection
# ---------------------------------------------------------------------------


def test_forced_recompile_detection(tmp_path):
    tel = telemetry.enable(dir=str(tmp_path))
    counter = tel.registry.counter("jit.compiles")

    @jax.jit
    def f(x):
        return x * 2 + 1

    f(jnp.ones((3,))).block_until_ready()
    after_first = counter.value
    assert after_first >= 1  # first call compiles

    f(jnp.ones((3,))).block_until_ready()
    assert counter.value == after_first  # cache hit: no compile event

    f(jnp.ones((5,))).block_until_ready()  # new shape forces a recompile
    assert counter.value > after_first

    records = _read_jsonl(tel)
    compile_recs = [r for r in records if r["kind"] == "compile"]
    assert len(compile_recs) == counter.value
    assert all(r["dur_ms"] > 0 for r in compile_recs)
    assert tel.registry.snapshot()["jit.compile_ms.count"] == counter.value


def test_compile_watcher_standalone():
    watcher = CompileWatcher()

    @jax.jit
    def g(x):
        return x - 1

    g(jnp.ones((7,))).block_until_ready()
    assert watcher.count >= 1
    assert watcher.total_ms > 0
    n = watcher.count
    watcher.stop()
    g(jnp.ones((9,))).block_until_ready()
    assert watcher.count == n  # inert after stop()


# ---------------------------------------------------------------------------
# Stall watchdog
# ---------------------------------------------------------------------------


def test_watchdog_fires_once_per_stall_with_thread_dump(tmp_path):
    tel = telemetry.enable(dir=str(tmp_path))
    dog = StallWatchdog(0.05, telemetry=tel, poll_s=0.01)
    dog.start()
    try:
        time.sleep(0.25)
        assert dog.stall_count == 1  # one warning per episode, not per poll
        dog.beat()  # progress re-arms it
        time.sleep(0.02)
        assert dog.stall_count == 1
        time.sleep(0.25)
        assert dog.stall_count == 2
    finally:
        dog.stop()
    stalls = [r for r in _read_jsonl(tel) if r["kind"] == "stall"]
    assert len(stalls) == 2
    assert stalls[0]["deadline_s"] == 0.05
    # The dump carries the stalled (main) thread's actual stack.
    assert "test_telemetry" in stalls[0]["threads"]
    assert tel.registry.snapshot()["stall.count"] == 2


def test_watchdog_rejects_nonpositive_deadline():
    with pytest.raises(ValueError):
        StallWatchdog(0)


def test_watchdog_armed_via_enable(tmp_path):
    tel = telemetry.enable(dir=str(tmp_path), stall_timeout_s=120)
    assert tel.watchdog is not None
    assert tel.watchdog.deadline_s == 120
    telemetry.disable()
    assert tel.watchdog is None


# ---------------------------------------------------------------------------
# Hot-path instrumentation through the Accelerator facade
# ---------------------------------------------------------------------------


def _collate(samples):
    return {
        "x": torch.tensor([s["x"] for s in samples]),
        "y": torch.tensor([s["y"] for s in samples]),
    }


def _train_two_steps(tmp_path):
    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.test_utils import RegressionDataset, RegressionModelWithLoss

    # split_batches: the global batch IS batch_size (16 samples / 8 = 2 steps
    # regardless of the 8-device test mesh's shard count).
    accelerator = Accelerator(split_batches=True)
    ds = RegressionDataset(length=16)
    dl = DataLoader(list(ds), batch_size=8, collate_fn=_collate)
    model = RegressionModelWithLoss()
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    model, opt, dl = accelerator.prepare(model, opt, dl)
    for batch in dl:
        out = model(x=batch["x"], y=batch["y"])
        accelerator.backward(out.loss)
        opt.step()
        opt.zero_grad()
    return accelerator


def test_training_hot_paths_emit_spans_and_step_metrics(tmp_path):
    tel = telemetry.enable(dir=str(tmp_path / "runs"))
    acc = _train_two_steps(tmp_path)
    records = _read_jsonl(tel)
    names = {r["name"] for r in records if r["kind"] == "span"}
    assert {"mesh.build", "accelerator.prepare", "accelerator.prepare_model",
            "accelerator.backward", "optimizer.step", "dataloader.next_batch"} <= names
    # prepare_model nests under prepare.
    pm = next(r for r in records if r.get("name") == "accelerator.prepare_model")
    assert pm["path"] == "accelerator.prepare/accelerator.prepare_model"
    snap = tel.registry.snapshot()
    assert snap["step.count"] == 2
    assert snap["dataloader.batches"] == 2
    assert snap["jit.compiles"] >= 1  # the fused train step compiled

    ckpt = str(tmp_path / "ckpt")
    acc.save_state(ckpt)
    acc.load_state(ckpt)
    names = {r["name"] for r in _read_jsonl(tel) if r["kind"] == "span"}
    assert {"checkpoint.save_state", "checkpoint.load_state"} <= names


def test_env_flag_enables_via_accelerator(tmp_path, monkeypatch):
    from accelerate_tpu.accelerator import Accelerator

    monkeypatch.setenv("ACCELERATE_TPU_TELEMETRY", "1")
    monkeypatch.setenv("ACCELERATE_TPU_TELEMETRY_DIR", str(tmp_path / "env_dir"))
    assert not telemetry.enabled()
    Accelerator()
    assert telemetry.enabled()
    assert get_telemetry().dir == str(tmp_path / "env_dir")


def test_disable_flushes_final_metrics_snapshot(tmp_path):
    tel = telemetry.enable(dir=str(tmp_path))
    tel.registry.counter("demo").inc(7)
    path = tel.jsonl_path
    telemetry.disable()
    with open(path) as f:
        records = [json.loads(line) for line in f if line.strip()]
    snap = next(r for r in records if r["kind"] == "metrics")["snapshot"]
    assert snap["demo"] == 7


def test_tracker_bridge_telemetry_rows(tmp_path):
    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.tracking import GeneralTracker, telemetry_rows

    assert telemetry_rows() == {}  # disabled → trackers see nothing extra

    tel = telemetry.enable(dir=str(tmp_path))
    tel.registry.counter("step.count").inc(3)
    tel.registry.gauge("hbm.demo").set(5)

    class Recorder(GeneralTracker):
        name = "recorder"
        requires_logging_directory = False

        def __init__(self):
            self.records = []

        def store_init_configuration(self, values):
            pass

        def log(self, values, step=None, **kwargs):
            self.records.append((step, dict(values)))

    rec = Recorder()
    acc = Accelerator(log_with=[rec])
    acc.init_trackers("proj")
    acc.log({"loss": 1.0, "telemetry/step.count": -1}, step=0)
    step, values = rec.records[0]
    assert values["loss"] == 1.0
    assert values["telemetry/step.count"] == -1  # user keys win on collision
    assert values["telemetry/hbm.demo"] == 5  # registry rows ride along


# ---------------------------------------------------------------------------
# Report CLI
# ---------------------------------------------------------------------------


def test_report_summarizes_run_dir(tmp_path, capsys):
    run_dir = str(tmp_path / "run")
    tel = telemetry.enable(dir=run_dir)
    with span("train_step"):
        with span("forward"):
            pass
    with span("train_step"):
        pass
    tel.write({"kind": "compile", "dur_ms": 12.5})
    telemetry.disable()

    assert telemetry_report.main([run_dir]) == 0
    out = capsys.readouterr().out
    assert "train_step" in out and "forward" in out
    assert "compiles: 1 (12.5 ms total)" in out
    assert "final metrics snapshot" in out

    summary = telemetry_report.summarize(telemetry_report.load_records(run_dir))
    assert summary["spans"]["train_step"]["count"] == 2
    assert summary["spans"]["forward"]["depth"] == 1
    assert summary["compiles"] == 1


def test_report_missing_path_errors():
    assert telemetry_report.main(["/nonexistent/telemetry"]) == 1


def test_report_skips_torn_lines(tmp_path):
    f = tmp_path / "telemetry_p0.jsonl"
    f.write_text('{"kind": "span", "name": "a", "dur_ms": 1.0, "depth": 0}\n{"kind": "sp')
    records = telemetry_report.load_records(str(tmp_path))
    assert len(records) == 1


# ---------------------------------------------------------------------------
# profile() trace-dir env var (satellite)
# ---------------------------------------------------------------------------


def test_profile_honors_trace_dir_env(tmp_path, monkeypatch):
    from accelerate_tpu.accelerator import Accelerator

    out_dir = str(tmp_path / "traces")
    monkeypatch.setenv("ACCELERATE_TPU_TRACE_DIR", out_dir)
    acc = Accelerator()
    with acc.profile():
        jnp.ones((4,)).block_until_ready()
    trace_dir = os.path.join(out_dir, "profile_0")
    assert os.path.isdir(trace_dir)
    assert any(files for _, _, files in os.walk(trace_dir)), "no trace artifacts written"


# ---------------------------------------------------------------------------
# Stable names on the device side: named scopes in the jitted steps, kernel names
# ---------------------------------------------------------------------------


def _op_names(lowered):
    """Every ``op_name`` of the compiled program, as one text (what a device trace shows per operation)."""
    import re

    return "\n".join(sorted(set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))))


def _lowered_train_step():
    import optax

    from accelerate_tpu import Accelerator, JaxModel
    from accelerate_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(remat=True, remat_policy="nothing")
    params = llama.init_params(cfg, jax.random.key(0))

    def apply_fn(params, input_ids):
        return {"loss": llama.loss_fn(params, {"input_ids": input_ids}, cfg)}

    acc = Accelerator()
    model, opt = acc.prepare(JaxModel(apply_fn, params, partition_rules=llama.PARTITION_RULES), optax.adamw(1e-3))
    return acc.make_train_step(model, opt, clip_norm=1.0).lower({"input_ids": jnp.zeros((2, 16), jnp.int32)})


def _lowered_paged_decode():
    import numpy as np

    from accelerate_tpu.models import llama
    from accelerate_tpu.serving import ServingConfig, ServingEngine

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    eng = ServingEngine(
        llama.apply_cached, llama.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=16, max_slots=2, max_blocks_per_seq=4, prefill_chunk=8),
    )
    assert eng.decode_path == "paged"
    return eng.programs.decode.lower(
        params, eng.cache.pool, np.zeros((2, 2), np.int32), np.zeros((2,), np.int32), np.zeros((2, 1), np.int32),
        np.zeros((2,), np.int32), np.zeros((3,), np.int32), np.zeros((2,), np.int32),
    )


@pytest.mark.parametrize("lower,scopes", [
    (_lowered_train_step, [
        "loss_grad/jvp(embed)", "loss_grad/jvp(layers)/", "loss_grad/transpose(jvp(layers))/", "/attn/attn.qkv/",
        "/attn/attn.core/", "/attn/attn.out/", "/mlp/", "loss_grad/jvp(head_loss)/", "jit(step)/clip/",
        "jit(step)/optimizer/", "/checkpoint/rematted_computation/attn/", "/checkpoint/rematted_computation/mlp/",
    ]),
    (_lowered_paged_decode, [
        "jit(decode)/embed/", "jit(decode)/layers/", "/attn/attn.qkv/", "/attn/kv_pool/kv_pool.gather/", "/attn/attn.core/",
        "/attn/attn.out/", "/mlp/", "jit(decode)/head/", "jit(decode)/kv_pool.write/",
    ]),
])
def test_jitted_steps_carry_their_scopes(lower, scopes):
    """What chipbench's scope metrics read by name (PERF.md section 3): each
    scope stands in the ``op_name``s of the compiled program, autodiff's and
    remat's wrappers round it as the readers expect them."""
    names = _op_names(lower())
    missing = [s for s in scopes if s not in names]
    assert not missing, missing


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"])
def test_pallas_kernels_carry_their_names(kernel):
    from accelerate_tpu.ops import pallas_attention as pa

    q = jnp.ones((1, 128, 2, 64))
    jaxpr = jax.make_jaxpr(jax.grad(lambda x: pa.pallas_attention(x, x, x, block_size=128, interpret=True).sum()))(q)
    assert f"name={kernel}\n" in str(jaxpr) or f"name={kernel} " in str(jaxpr)
