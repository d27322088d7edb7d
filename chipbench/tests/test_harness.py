"""The harness end to end at the tiny preset on the CPU: both drivers, the
shape of the last line, the device gate, and `correct` under planted faults.

The chip is looked for by ``run.find_device``; these tests skip that look and
drive the rest of a run (``run.run_cell``) with a made-up device record.
Nothing timed here is a device metric."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, CPU_DEVICE, ROOT, TEST_BENCHMARK, load_test_cell

TRAIN, CHAT = "tiny-qwen2.train_tiny", "tiny-qwen2.chat_tiny"


def run_cell(run, workload, seed, trace=False, seconds=1.0):
    return run.run_cell(load_test_cell(run, workload), seed, seconds, trace, CPU_DEVICE)


def check_line(result, cell):
    assert list(result)[:3] == ["correct", "attempted", "failed"] and list(result)[-1] == "checks"
    assert {"metrics", "device"} <= set(result)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float), name
    for c in result["checks"].values():
        assert {"value", "limit"} <= set(c)
    json.dumps(result)


@pytest.mark.parametrize("workload,metrics", [
    (TRAIN, {"train_tokens_per_s", "setup_s"}),
    (CHAT, {"serve_tokens_per_s", "ttft_p90_ms", "itl_p95_ms", "setup_s"}),
])
def test_run_end_to_end(run, workload, metrics):
    result = run_cell(run, workload, 2**31 + 11)
    check_line(result, workload)
    assert set(result["metrics"]) == metrics
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert all(v["value"] > 0 for v in result["metrics"].values())


def test_traced_run_reads_the_real_cells_readers(run):
    """The real BENCHMARK.json's per-layer readers on a CPU trace of the tiny
    serving cell: every reader returns a number or nothing, never raises."""
    cell = load_test_cell(run, CHAT)
    real = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell["per_layer"] = [m for m in real["per_layer"] if m["name"].startswith("serve.")]
    result = run.run_cell(cell, 5, 1.5, True, CPU_DEVICE)
    check_line(result, CHAT)
    assert {"busy_s", "window_s"} <= set(result["device"]) and result["device"]["busy_s"] > 0
    assert {"serve.mfu", "serve.decode_fill", "serve.tick_ms", "serve.prefill_tick_share",
            "serve.compiles_in_window", "serve.device_idle", "serve.decode_roofline"} == set(result["metrics"])
    assert result["metrics"]["serve.compiles_in_window"]["value"] == 0.0
    assert len(result["breakdown"]["device_ops"]) <= 10 and result["breakdown"]["idle_gaps"]
    assert not os.path.exists(os.path.join(run.TRACE_DIR, CHAT))


def test_every_metric_has_a_reader(run):
    real = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in real["per_layer"]:
        assert callable(run.load_module("readers", m["name"]).read)


@pytest.mark.parametrize("kind", ["cpu", "TPU v9 unheard-of"])
def test_no_chip_no_result(run, monkeypatch, kind):
    import jax

    class Dev:
        platform = "cpu" if kind == "cpu" else "tpu"
        device_kind = kind

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(SystemExit) as e:
        run.find_device(1, run.load_json(os.path.join(BENCH, "peaks.json")))
    assert e.value.code not in (0, None)


def test_command_fails_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "qwen2.5-1.5b.train_2x2048", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
    )
    assert out.returncode != 0 and out.stdout.strip() == "" and "needs a TPU" in out.stderr


# -- the timed path broken underneath: `correct` has to come out false ---------


def test_fault_state_unchanged(run, monkeypatch):
    """A step that returns its loss and leaves its state as it was."""
    from accelerate_tpu import Accelerator

    real = Accelerator.make_train_step

    def broken(self, model, optimizer, **kw):
        step = real(self, model, optimizer, **kw)

        def call(batch):
            import jax

            saved = jax.tree_util.tree_map(lambda x: x + 0, (model.params, optimizer.opt_state))
            loss = step(batch)
            model._set_params(saved[0])
            optimizer.opt_state = saved[1]
            return loss

        return call

    monkeypatch.setattr(Accelerator, "make_train_step", broken)
    result = run_cell(run, TRAIN, 21)
    assert result["correct"] is False
    assert result["checks"]["change_norm_gap"]["value"] > result["checks"]["change_norm_gap"]["limit"]


def test_fault_half_batch(run, monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    from accelerate_tpu import Accelerator

    real = Accelerator.make_train_step

    def broken(self, model, optimizer, **kw):
        step = real(self, model, optimizer, **kw)
        return lambda batch: step({k: v[: v.shape[0] // 2] for k, v in batch.items()})

    monkeypatch.setattr(Accelerator, "make_train_step", broken)
    result = run_cell(run, TRAIN, 22)
    assert result["correct"] is False
    assert result["checks"]["grad_norm_gap"]["value"] > 10 * result["checks"]["grad_norm_gap"]["limit"]


def test_fault_token_altered(run, monkeypatch):
    """One served token altered where the engine hands its replies over."""
    from accelerate_tpu.serving.engine import ServingEngine

    real = ServingEngine.pop_finished

    def broken(self):
        out = real(self)
        for c in out:
            c.tokens[-2] = (c.tokens[-2] + 1) % 512
        return out

    monkeypatch.setattr(ServingEngine, "pop_finished", broken)
    result = run_cell(run, CHAT, 23)
    assert result["correct"] is False


# -- the control, at a size a test run can hold --------------------------------


def test_control_fp8_fails_the_training_check(run, qwen2):
    """The reference computed in fp8, put in the program's place, reads over the
    tiny cell's limits on the loss and on the first gradient."""
    cell = run.load_cell(TRAIN, TEST_BENCHMARK)
    mod = run.load_module("drivers", "train")
    cfg, opt = cell["config"], {k: v for k, v in cell["config"]["train"]["optimizer"].items() if k != "name"}
    for seed in (31, 32, 33):
        fed = [np.random.default_rng([seed, i]).integers(0, cfg["vocab_size"], (4, 64), dtype=np.int32) for i in range(3)]
        ref = mod.reference_readings(qwen2, cfg, seed, fed, opt, "float32")
        low = mod.reference_readings(qwen2, cfg, seed, fed, opt, "fp8")
        out = mod.compare(low, ref, cell["limits"])
        assert not run.judge(out["checks"]), out
        assert out["checks"]["loss_gap"]["value"] > out["checks"]["loss_gap"]["limit"]


def test_control_fp8_fails_the_serving_check(run, qwen2):
    """At the positions of a served request, the token that the fp8 reference
    puts first lies further below the float32 reference's best than the limit."""
    cell = run.load_cell(CHAT, TEST_BENCHMARK)
    mod = run.load_module("drivers", "serve_closed")
    cfg = cell["config"]

    class Served:
        def __init__(self, seed):
            rng = np.random.default_rng(seed)
            self.prompt_len = 8
            self.tokens = rng.integers(0, cfg["vocab_size"], 256).tolist()

    for seed in (41, 42, 43):
        params = qwen2.seeded_params(cfg, seed)
        gaps = mod.served_gaps(qwen2, cfg, params, [Served(seed), Served(seed + 100)], 248, control="fp8")
        assert len(gaps["control"]) == 496
        assert max(gaps["control"]) > 2 * cell["limits"]["served_logit_gap"]
