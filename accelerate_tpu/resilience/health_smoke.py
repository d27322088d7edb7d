"""Health smoke: NaN-poison a CPU training run, prove skip, rewind, and the
1-dispatch invariant.

Run via ``make health-smoke`` (or ``python -m accelerate_tpu.resilience.health_smoke``).
The parent orchestrates three child processes sharing one fused-train-step
recipe (mirror of ``resilience.smoke``'s kill-and-resume proof):

1. **skip** — ``ACCELERATE_TPU_FAULT_NAN_STEP=4`` poisons step 4's gradients;
   the in-program health gate applies a zero delta and the ``HealthGuard``
   absorbs it (``max_skips=3``).  The child asserts the parameters are
   BIT-IDENTICAL across the poisoned step, that the next clean step moves
   them again, and — from the ``pipeline.dispatches`` telemetry counter —
   that the fused step still issued exactly ONE dispatch per optimizer step
   with the guard enabled and the injector armed.
2. **rewind** — ``NAN_STEP=4``/``NAN_COUNT=3`` poisons steps 4-6 with
   ``max_skips=2``: steps 4 and 5 are skipped, the third consecutive anomaly
   at step 6 triggers a rewind to the verified checkpoint saved at step 2
   (``resume_from_latest`` machinery).  The injector fires once per armed
   step, so the replay of steps 3-8 runs clean; their losses are recorded.
3. **resume** — a fresh, uninjected process resumes from the same checkpoint
   and trains to step 8.

The parent asserts the rewind child's post-rewind losses are BIT-EXACT equal
to the clean resume's for every step 3-8 — the end-to-end proof that a
numerics-triggered rewind lands exactly where a clean restart would.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile

STEPS = 8
NAN_STEP = 4
CKPT_STEP = 2

def _params_digest(model) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(jax.device_get(model.params)):
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()


def _build(ckpt_root: str):
    import torch
    from torch.utils.data import DataLoader

    from ..accelerator import Accelerator
    from ..test_utils import RegressionDataset, RegressionModelWithLoss
    from ..test_utils.training import regression_collate
    from ..utils import DataLoaderConfiguration, set_seed

    set_seed(1234)
    accelerator = Accelerator(
        dataloader_config=DataLoaderConfiguration(use_stateful_dataloader=True)
    )
    model = RegressionModelWithLoss()
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    dl = DataLoader(
        list(RegressionDataset(length=16)), batch_size=4, collate_fn=regression_collate
    )
    model, opt, dl = accelerator.prepare(model, opt, dl)
    return accelerator, model, opt, dl


def _train(role: str, ckpt_root: str, out_path: str) -> int:
    import numpy as np

    from .. import telemetry

    tel = telemetry.enable(dir=tempfile.mkdtemp(prefix="atpu_health_smoke_tel_"))
    accelerator, model, opt, dl = _build(ckpt_root)
    guard = accelerator.enable_health_guard(
        max_skips=3 if role == "skip" else 2,
        max_rewinds=2,
        checkpoint_dir=ckpt_root,
    )
    step_fn = accelerator.make_train_step(model, opt)
    dispatches = tel.registry.counter("pipeline.dispatches")

    global_step = 0
    if role == "resume":
        resumed = accelerator.resume_from_latest(ckpt_root)
        assert resumed == CKPT_STEP, f"resume landed on {resumed}, wanted {CKPT_STEP}"
        global_step = resumed

    losses: dict[str, float] = {}
    digests: dict[int, str] = {global_step: _params_digest(model)}
    skipped: list[int] = []
    rewound_at = None
    resumed_step = None
    step_calls = 0
    while global_step < STEPS:
        restart = False
        for batch in dl:
            loss = step_fn(batch)
            step_calls += 1
            verdict = accelerator.check_health(step=global_step + 1)
            if verdict.rewound:
                rewound_at = global_step + 1
                resumed_step = verdict.resumed_step
                # Drop first-pass records past the rewind point: the replay
                # re-records them (and must match a clean resume bit-exactly).
                losses = {s: v for s, v in losses.items() if int(s) <= resumed_step}
                global_step = resumed_step
                restart = True
                break
            global_step += 1
            losses[str(global_step)] = float(np.asarray(loss))
            digests[global_step] = _params_digest(model)
            if verdict.skipped:
                skipped.append(global_step)
            if role == "rewind" and global_step == CKPT_STEP and rewound_at is None:
                accelerator.save_state(
                    os.path.join(ckpt_root, f"step_{CKPT_STEP}"), step=CKPT_STEP
                )
            if global_step >= STEPS:
                break
        if restart:
            continue

    out = {
        "losses": losses,
        "skipped": skipped,
        "rewound_at": rewound_at,
        "resumed_step": resumed_step,
        "dispatches": dispatches.value,
        "step_calls": step_calls,
        "params_identical_across_skip": (
            digests.get(NAN_STEP) == digests.get(NAN_STEP - 1)
            if role == "skip"
            else None
        ),
        "params_moved_after_skip": (
            digests.get(NAN_STEP + 1) != digests.get(NAN_STEP)
            if role == "skip"
            else None
        ),
    }
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0


def _child(role: str, ckpt_root: str, out_path: str, extra_env: dict) -> dict:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    # Hermetic compile cache: shared between this run's children (warm
    # recompiles) but never the checkout's — a child killed mid-write must
    # not be able to tear state later runs deserialize.
    env.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(os.path.dirname(out_path), "xla_cache")
    )
    env.update(extra_env)
    cmd = [
        sys.executable, "-m", "accelerate_tpu.resilience.health_smoke",
        "--role", role, "--ckpt-root", ckpt_root, "--out", out_path,
    ]
    for attempt in (1, 2):
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=600)
        if proc.returncode == 0:
            with open(out_path) as f:
                return json.load(f)
        if proc.returncode < 0 and attempt == 1:
            # Killed by a signal (rc=-11 = the known XLA-CPU
            # backend_compile_and_load segfault under host memory pressure,
            # ROUND5_NOTES "Suite-scale stability") — environmental, not a
            # verdict on the guard; one retry.  A plain rc=1 assert failure
            # is a real failure and is never retried.
            print(
                f"# {role} child killed by signal {-proc.returncode}; retrying once",
                file=sys.stderr,
            )
            continue
        print(proc.stdout)
        print(proc.stderr, file=sys.stderr)
        raise RuntimeError(f"{role} child exited rc={proc.returncode}")


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--role", choices=("skip", "rewind", "resume"), default=None)
    parser.add_argument("--ckpt-root", default=None)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    if args.role is not None:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        return _train(args.role, args.ckpt_root, args.out)

    # -- parent orchestration -------------------------------------------------
    work = tempfile.mkdtemp(prefix="atpu_health_smoke_")

    print(f"# health-smoke: skip run (NaN grads at step {NAN_STEP})", file=sys.stderr)
    skip = _child(
        "skip",
        os.path.join(work, "skip_ckpts"),
        os.path.join(work, "skip.json"),
        {"ACCELERATE_TPU_FAULT_NAN_STEP": str(NAN_STEP)},
    )
    assert skip["skipped"] == [NAN_STEP], f"expected skip at {NAN_STEP}: {skip}"
    assert skip["params_identical_across_skip"] is True, (
        f"poisoned step mutated params: {skip}"
    )
    assert skip["params_moved_after_skip"] is True, (
        f"post-skip clean step applied no update: {skip}"
    )
    # The 1-dispatch invariant, guard enabled + injector armed: exactly one
    # pipeline dispatch per optimizer-step call (PR 4's counter is the proof).
    assert skip["dispatches"] == skip["step_calls"] == STEPS, (
        f"fused step dispatch count broke with the guard on: {skip}"
    )

    ckpt_root = os.path.join(work, "rewind_ckpts")
    print(
        f"# health-smoke: rewind run (NaN grads at steps {NAN_STEP}-{NAN_STEP + 2}, "
        f"max_skips=2, checkpoint at step {CKPT_STEP})",
        file=sys.stderr,
    )
    rewind = _child(
        "rewind",
        ckpt_root,
        os.path.join(work, "rewind.json"),
        {
            "ACCELERATE_TPU_FAULT_NAN_STEP": str(NAN_STEP),
            "ACCELERATE_TPU_FAULT_NAN_COUNT": "3",
        },
    )
    assert rewind["rewound_at"] == NAN_STEP + 2, rewind
    assert rewind["resumed_step"] == CKPT_STEP, rewind
    assert rewind["skipped"] == [NAN_STEP, NAN_STEP + 1], rewind

    from .manifest import find_latest_complete, verify_checkpoint

    ckpt = find_latest_complete(ckpt_root)
    assert ckpt is not None, f"no manifest-complete checkpoint under {ckpt_root}"
    manifest = verify_checkpoint(ckpt)  # raises on torn/corrupt
    assert manifest["step"] == CKPT_STEP, manifest

    print("# health-smoke: clean resume run (fresh process)", file=sys.stderr)
    resume = _child("resume", ckpt_root, os.path.join(work, "resume.json"), {})
    assert resume["skipped"] == [] and resume["rewound_at"] is None, resume

    post = [str(s) for s in range(CKPT_STEP + 1, STEPS + 1)]
    assert len(post) >= 3, "need >= 3 post-rewind steps for the continuation proof"
    for s in post:
        re_loss, cl_loss = rewind["losses"][s], resume["losses"][s]
        assert re_loss == cl_loss, (
            f"post-rewind loss diverged at step {s}: rewind {re_loss!r} != "
            f"clean resume {cl_loss!r}"
        )
    print(
        f"health-smoke OK — step {NAN_STEP} skipped with bit-identical params and "
        f"{skip['dispatches']}/{STEPS} dispatches (1/step), 3x-NaN run rewound to "
        f"step {CKPT_STEP} and replayed steps {post[0]}..{post[-1]} bit-exact vs a "
        "clean resume"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
