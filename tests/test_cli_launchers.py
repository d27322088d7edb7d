"""CLI + launcher tests (parity: reference tests/test_cli.py + launcher suites)."""

import os
import subprocess
import sys

import pytest
import yaml

from accelerate_tpu.commands.config import ClusterConfig, load_config, save_config
from accelerate_tpu.commands.launch import build_env, launch_command_parser


def test_config_roundtrip(tmp_path):
    cfg = ClusterConfig(mixed_precision="bf16", tp=2, use_fsdp=True)
    path = save_config(cfg, str(tmp_path / "cfg.yaml"))
    loaded = load_config(path)
    assert loaded.mixed_precision == "bf16"
    assert loaded.tp == 2
    assert loaded.use_fsdp


def test_launch_parser_and_env():
    parser = launch_command_parser()
    args = parser.parse_args(
        ["--mixed_precision", "bf16", "--tp_size", "2", "--use_fsdp", "--num_machines", "2",
         "--machine_rank", "1", "--main_process_ip", "10.0.0.1", "train.py", "--epochs", "3"]
    )
    assert args.training_script == "train.py"
    assert args.training_script_args == ["--epochs", "3"]
    from accelerate_tpu.commands.launch import _merge

    merged = _merge(args, ClusterConfig())
    env = build_env(merged)
    assert env["ACCELERATE_MIXED_PRECISION"] == "bf16"
    assert env["ACCELERATE_PARALLELISM_TP"] == "2"
    assert env["ACCELERATE_USE_FSDP"] == "1"
    assert env["ACCELERATE_COORDINATOR_ADDRESS"] == "10.0.0.1:29500"
    assert env["ACCELERATE_PROCESS_ID"] == "1"


@pytest.mark.slow  # >10s; overlapping coverage stays in the bounded tier-1 run
def test_cli_help_and_env_command():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # device-independent (and TPU-outage-proof)
    res = subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "env"],
        capture_output=True,
        text=True,
        cwd="/root/repo",
        env=env,
        timeout=180,
    )
    assert res.returncode == 0, res.stderr
    assert "JAX version" in res.stdout
    assert "accelerate_tpu version" in res.stdout
    # Reported in-process (a probe child would need the chip its parent holds).
    assert "- JAX backend: cpu" in res.stdout and "- Device kind: cpu" in res.stdout


def test_merge_weights_roundtrip(tmp_path):
    import numpy as np
    from safetensors.numpy import load_file, save_file

    shard0 = {"w": np.arange(4, dtype=np.float32).reshape(2, 2)}
    shard1 = {"w": (np.arange(4, dtype=np.float32) + 4).reshape(2, 2)}
    save_file(shard0, str(tmp_path / "model_shard_0.safetensors"))
    save_file(shard1, str(tmp_path / "model_shard_1.safetensors"))
    import json

    (tmp_path / "shard_index.json").write_text(json.dumps({"w": {"concat_axis": 0}}))
    out = tmp_path / "merged"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "merge-weights",
         str(tmp_path), str(out)],
        capture_output=True,
        text=True,
        cwd="/root/repo",
        env=env,
        timeout=180,
    )
    assert res.returncode == 0, res.stderr
    merged = load_file(str(out / "model.safetensors"))
    assert merged["w"].shape == (4, 2)


def _run_cluster_worker(worker: str, token: str, timeout: int = 300, nproc: int = 2):
    """Run a debug_workers payload across a real N-process cluster and assert
    it printed ``token`` — shared boilerplate for the cluster smoke tests."""
    code = (
        "from accelerate_tpu.launchers import debug_launcher;"
        f"from accelerate_tpu.test_utils.scripts.debug_workers import {worker};"
        f"debug_launcher({worker}, args=({nproc},), num_processes={nproc});"
        f"print('{token}')"
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, cwd="/root/repo", env=env,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert token in res.stdout


@pytest.mark.slow
def test_debug_launcher_forms_real_cluster():
    """Two OS processes join a jax.distributed cluster and run collectives."""
    _run_cluster_worker("check_cluster_formed", "CLUSTER_OK", timeout=180)


@pytest.mark.slow
def test_debug_launcher_object_collectives():
    _run_cluster_worker("check_object_collectives", "OBJECTS_OK", timeout=180)


@pytest.mark.slow
def test_data_loop_payload_on_two_process_cluster():
    """The full distributed-data-loop payload (even_batches=False, dispatcher
    parity, join_uneven_inputs override, gather_for_metrics completeness,
    stateful mid-epoch resume) across TWO OS processes on a real
    jax.distributed cluster — reference runs the same payload under torchrun
    (test_utils/scripts/test_distributed_data_loop.py)."""
    _run_cluster_worker("run_data_loop_suite", "DATA_LOOP_OK", timeout=300)


@pytest.mark.slow
def test_training_matrix_on_two_process_cluster():
    """The training_check identical-weights matrix across TWO OS processes on
    a real jax.distributed cluster (reference runs test_script.py under
    torchrun) — quick combos: {no-split, split+dispatch} x {sequential,
    seedable}."""
    _run_cluster_worker("run_training_matrix", "TRAIN_MATRIX_OK", timeout=600)


@pytest.mark.slow
def test_local_state_dict_on_two_process_cluster():
    """LOCAL_STATE_DICT across two OS processes: each rank dumps only its
    own shards and restores them exactly (the topology-bound contract)."""
    _run_cluster_worker("run_local_state_dict_roundtrip", "LOCAL_SD_OK", timeout=300)


def test_launch_parent_holds_no_backend_when_it_spawns(tmp_path):
    """One process for each chip: a parent that has touched JAX holds the
    chip, and the child that needs it then fails or hangs.  On one host
    ``accelerate-tpu launch`` starts exactly one child and, at that moment,
    has initialised no backend itself; the child builds its Accelerator."""
    (tmp_path / "three_lines.py").write_text(
        "from accelerate_tpu import Accelerator\n"
        "acc = Accelerator()\n"
        "print('LAUNCHED', acc.state.platform, acc.state.num_devices, flush=True)\n"
    )
    (tmp_path / "spy.py").write_text(
        "import subprocess, sys\n"
        "real_run, spawns = subprocess.run, []\n"
        "def spying_run(cmd, **kw):\n"
        "    import jax._src.xla_bridge as xb\n"
        "    spawns.append(cmd)\n"
        "    print('PARENT_BACKENDS', sorted(xb._backends), flush=True)\n"
        "    return real_run(cmd, **kw)\n"
        "subprocess.run = spying_run\n"
        "from accelerate_tpu.commands.accelerate_cli import main\n"
        "sys.argv = ['accelerate-tpu', 'launch', 'three_lines.py']\n"
        "main()\n"
        "print('SPAWNS', len(spawns), flush=True)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", ACCELERATE_TPU_COMPILE_CACHE="")
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "spy.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=180,
    )
    assert res.returncode == 0, res.stderr[-1500:]
    assert "PARENT_BACKENDS []" in res.stdout, res.stdout
    assert "SPAWNS 1" in res.stdout
    assert "LAUNCHED cpu" in res.stdout


def test_launch_module_flag(tmp_path):
    """accelerate-tpu launch -m pkg.module parity (reference launch --module)."""
    pkg = tmp_path / "fakepkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "payload.py").write_text("import os; print('MODULE_RAN', os.environ.get('ACCELERATE_MIXED_PRECISION'))\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(tmp_path) + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "launch",
         "--mixed_precision", "bf16", "-m", "fakepkg.payload"],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert res.returncode == 0, res.stderr[-1500:]
    assert "MODULE_RAN bf16" in res.stdout


def test_notebook_launcher_max_restarts():
    """Elastic retry on the direct-call path: a function failing twice then
    succeeding completes under max_restarts=2 and fails under 1."""
    from accelerate_tpu.launchers import notebook_launcher

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    assert notebook_launcher(flaky, num_processes=1, max_restarts=2) == "ok"
    assert calls["n"] == 3

    calls["n"] = 0
    with pytest.raises(RuntimeError, match="transient"):
        notebook_launcher(flaky, num_processes=1, max_restarts=1)


def test_hyphen_and_underscore_flags_equivalent():
    """Reference tests/test_cli.py test_hyphen/test_underscore: every
    --foo_bar flag is also accepted as --foo-bar, mixed freely."""
    parser = launch_command_parser()
    a = parser.parse_args(
        ["--num-processes", "4", "--mixed-precision", "bf16", "--use-fsdp", "t.py"]
    )
    b = parser.parse_args(
        ["--num_processes", "4", "--mixed_precision", "bf16", "--use_fsdp", "t.py"]
    )
    c = parser.parse_args(  # mix of both spellings
        ["--num-processes", "4", "--mixed_precision", "bf16", "--use-fsdp", "t.py"]
    )
    for args in (a, b, c):
        assert args.num_processes == 4
        assert args.mixed_precision == "bf16"
        assert args.use_fsdp
        assert args.training_script == "t.py"


@pytest.mark.slow
def test_broadcast_checkpoint_load_on_two_process_cluster():
    """Rank-0-only checkpoint reads: load_checkpoint_in_model with
    broadcast_from_rank0=True across two OS processes — non-main ranks pass a
    nonexistent path and still receive rank-0's weights (reference
    tests/test_load_checkpoint_and_dispatch_with_broadcast.py)."""
    code = (
        "from accelerate_tpu.launchers import debug_launcher;"
        "from accelerate_tpu.test_utils.scripts.debug_workers import ("
        "check_broadcast_checkpoint_load, check_broadcast_load_rank0_failure);"
        "debug_launcher(check_broadcast_checkpoint_load, args=(2,), num_processes=2);"
        "debug_launcher(check_broadcast_load_rank0_failure, args=(2,), num_processes=2);"
        "print('BROADCAST_LOAD_OK')"
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300, cwd="/root/repo", env=env
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "BROADCAST_LOAD_OK" in res.stdout
