"""``chip_smoke.py`` between chip runs.

The script itself needs a TPU.  What can be held true without one: its three
phases run, at a tiny size, on the virtual CPU mesh (eight devices, so this is
the several-chips branch: FSDP spread, collectives, serving under a
multi-device ``Accelerator``); and the script refuses to produce a result
where there is no chip, or nothing of the repo beside it.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def test_phases_run_tiny_on_the_cpu_mesh(capsys):
    from accelerate_tpu import Accelerator, FullyShardedDataParallelPlugin
    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.telemetry import CompileWatcher

    assert jax.device_count() > 1
    cfg = LlamaConfig.tiny(
        max_seq_len=4096, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        remat=True, remat_policy="dots",
    )
    watcher = CompileWatcher()
    with chip_smoke.Phase("kernels", watcher) as phase:  # before any mesh is in context
        chip_smoke.kernels_phase(cfg, phase, interpret=True)
    acc = Accelerator(mixed_precision="bf16", fsdp_plugin=FullyShardedDataParallelPlugin())
    assert dict(acc.mesh.shape)["fsdp"] == jax.device_count()
    with chip_smoke.Phase("trainer", watcher) as phase:
        chip_smoke.trainer_phase(acc, cfg, phase, seq_len=128, steps=4, expect_mosaic=False)
    with chip_smoke.Phase("server", watcher) as phase:
        chip_smoke.server_phase(
            acc, cfg, phase, num_blocks=64, prompt_lens=[72, 8, 100, 20, 33, 50],
            new_tokens=(3, 6), shared_prefix=32,
        )
    watcher.stop()
    facts = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("{"):
            rec = json.loads(line)
            facts[rec["phase"]] = rec
    assert set(facts) == {"trainer", "server", "kernels"}
    assert facts["trainer"]["worst_device_share"] <= 1.2 / jax.device_count()
    assert facts["trainer"]["train_collectives"]["all-gather"][0] > 0
    assert facts["trainer"]["losses"][-1] < facts["trainer"]["losses"][0]
    assert facts["server"]["prefix_hits"] > 0


def test_a_failed_check_fails_the_phase():
    with pytest.raises(SystemExit, match="FAILED: the reason"):
        chip_smoke.check(False, "the reason")


def test_divergence_is_a_tie_only_between_the_references_top_two():
    import numpy as np

    logits = np.zeros(50, np.float32)
    logits[[7, 9, 11]] = [3.7749, 3.7532, 3.70]
    assert chip_smoke.judge_divergence(logits, offline=7, engine=9)["tie"]  # 0.0217 apart
    assert not chip_smoke.judge_divergence(logits, offline=7, engine=11)["tie"]  # close, but third
    assert not chip_smoke.judge_divergence(logits, offline=7, engine=7)["tie"]  # not a divergence
    logits[9] = 3.70  # now second, but 0.075 below the max
    assert not chip_smoke.judge_divergence(logits, offline=7, engine=9)["tie"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu", ACCELERATE_TPU_COMPILE_CACHE="")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env, capture_output=True, text=True, timeout=300
    )


def _result_lines(out: str) -> list:
    return [line for line in out.splitlines() if line.startswith("{") and '"ok"' in line]


def test_no_chip_no_result():
    proc = _run(REPO, "chip_smoke.py")
    assert proc.returncode != 0
    assert proc.stdout.splitlines()[0].startswith('device: {"platform": "cpu"')
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert not _result_lines(proc.stdout)


def test_alone_in_a_directory_without_a_chip_no_result(tmp_path):
    # Half of the contract's lone-directory clause: here the platform gate
    # stops it.  The other half — a chip but nothing of the repo, where the
    # import of accelerate_tpu is what fails — can only be seen on a chip.
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path / "chip_smoke.py")
    proc = _run(str(tmp_path), "chip_smoke.py")
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)
