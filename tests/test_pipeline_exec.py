"""Overlapped execution pipeline: async device prefetch + fused train step.

Covers the pipeline/ subsystem end to end:

- ``DevicePrefetcher`` unit semantics (ordering, end flag, exception
  propagation, close idempotence);
- prefetch-enabled dataloaders: batch-stream equality vs the synchronous
  path, end-of-epoch flush, ``skip_first_batches`` and stateful-dataloader
  mid-epoch resume;
- the ``(mesh, spec)`` NamedSharding cache on the hot placement path;
- ``make_train_step``: bit-exact losses/params vs the eager
  ``backward()``/``step()`` loop for accum_steps in {1, 4} with clipping
  on/off, the telemetry-counter-backed one-dispatch-per-window proof, LR
  scheduler interop, and checkpoint save/resume round-trips;
- the persistent compilation cache env contract and its telemetry hit
  counter.
"""

import os

import numpy as np
import pytest
import torch
from torch.utils.data import DataLoader

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from accelerate_tpu import Accelerator, telemetry
from accelerate_tpu.data_loader import prepare_data_loader, skip_first_batches
from accelerate_tpu.pipeline import (
    DevicePrefetcher,
    TrainStep,
    cached_sharding,
    make_train_step,
    prefetch_depth_from_env,
    sharding_cache_info,
)
from accelerate_tpu.pipeline import compile_cache as compile_cache_mod
from accelerate_tpu.pipeline.compile_cache import (
    DEFAULT_COMPILE_CACHE_DIR,
    enable_compile_cache,
)
from accelerate_tpu.test_utils import RegressionDataset, RegressionModelWithLoss
from accelerate_tpu.test_utils.training import regression_collate
from accelerate_tpu.utils import DataLoaderConfiguration, ProjectConfiguration, set_seed


@pytest.fixture(autouse=True)
def _telemetry_off():
    yield
    telemetry.disable()


def _reset_singletons():
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()


def _build_training(accum=1, prefetch=0, length=64, batch_size=1, lr=0.1):
    """One deterministic recipe shared by the eager/fused comparisons."""
    _reset_singletons()
    set_seed(1234)
    accelerator = Accelerator(
        gradient_accumulation_steps=accum,
        dataloader_config=DataLoaderConfiguration(prefetch_to_device=prefetch),
    )
    model = RegressionModelWithLoss()
    opt = torch.optim.SGD(model.parameters(), lr=lr)
    dl = DataLoader(
        list(RegressionDataset(length=length)),
        batch_size=batch_size,
        collate_fn=regression_collate,
    )
    model, opt, dl = accelerator.prepare(model, opt, dl)
    return accelerator, model, opt, dl


def _run_eager(accelerator, model, opt, dl, clip_norm=None, epochs=1):
    losses = []
    for _ in range(epochs):
        for batch in dl:
            with accelerator.accumulate(model):
                out = model(x=batch["x"], y=batch["y"])
                accelerator.backward(out.loss)
                if accelerator.sync_gradients and clip_norm is not None:
                    accelerator.clip_grad_norm_(None, clip_norm)
                opt.step()
                opt.zero_grad()
                losses.append(float(out.loss.detach()))
    return losses, model.state_dict()


def _run_fused(accelerator, model, opt, dl, accum, clip_norm=None, epochs=1):
    step_fn = accelerator.make_train_step(model, opt, clip_norm=clip_norm)
    losses = []
    for _ in range(epochs):
        window = []
        for batch in dl:
            window.append(batch)
            if len(window) == accum:
                out = step_fn(window)
                losses.extend(float(x) for x in np.atleast_1d(np.asarray(out)))
                window = []
    return losses, model.state_dict()


# ---------------------------------------------------------------------------
# DevicePrefetcher unit semantics
# ---------------------------------------------------------------------------


def test_prefetcher_preserves_order_and_flags_last():
    out = list(DevicePrefetcher(range(5), lambda x: (x * 10, x), depth=2))
    assert [v for v, _, _ in out] == [0, 10, 20, 30, 40]
    assert [m for _, m, _ in out] == [0, 1, 2, 3, 4]
    assert [last for _, _, last in out] == [False, False, False, False, True]


def test_prefetcher_empty_stream():
    assert list(DevicePrefetcher(iter(()), lambda x: (x, None), depth=1)) == []


def test_prefetcher_single_item_is_last():
    out = list(DevicePrefetcher([7], lambda x: (x, None), depth=2))
    assert out == [(7, None, True)]


def test_prefetcher_propagates_worker_exception_in_position():
    def convert(x):
        if x == 2:
            raise ValueError("boom at 2")
        return x, None

    received = []
    with pytest.raises(ValueError, match="boom at 2"):
        for v, _, _ in DevicePrefetcher(range(5), convert, depth=2):
            received.append(v)
    assert received == [0, 1]


def test_prefetcher_close_is_idempotent_and_stops_worker():
    pf = DevicePrefetcher(range(1000), lambda x: (x, None), depth=1)
    it = iter(pf)
    assert next(it)[0] == 0
    pf.close()
    pf.close()
    assert not pf._thread.is_alive()


def test_prefetcher_rejects_bad_depth():
    with pytest.raises(ValueError):
        DevicePrefetcher(range(3), lambda x: (x, None), depth=0)


def test_prefetch_depth_from_env(monkeypatch):
    monkeypatch.delenv("ACCELERATE_TPU_PREFETCH", raising=False)
    assert prefetch_depth_from_env() == 0
    monkeypatch.setenv("ACCELERATE_TPU_PREFETCH", "2")
    assert prefetch_depth_from_env() == 2
    monkeypatch.setenv("ACCELERATE_TPU_PREFETCH", "junk")
    assert prefetch_depth_from_env() == 0
    monkeypatch.setenv("ACCELERATE_TPU_PREFETCH", "-3")
    assert prefetch_depth_from_env() == 0


# ---------------------------------------------------------------------------
# Prefetch-enabled dataloaders
# ---------------------------------------------------------------------------


def _collect_batches(dl):
    return [
        {k: np.asarray(v.detach() if hasattr(v, "detach") else v) for k, v in b.items()}
        for b in dl
    ]


def _assert_same_stream(a, b):
    assert len(a) == len(b)
    for ba, bb in zip(a, b):
        assert set(ba) == set(bb)
        for k in ba:
            np.testing.assert_array_equal(ba[k], bb[k])


def test_prefetch_loader_yields_identical_stream():
    _reset_singletons()
    data = list(RegressionDataset(length=48))
    base = DataLoader(data, batch_size=2, collate_fn=regression_collate)
    sync = prepare_data_loader(base, prefetch_to_device=0)
    pref = prepare_data_loader(base, prefetch_to_device=2)
    _assert_same_stream(_collect_batches(sync), _collect_batches(pref))


def test_prefetch_end_of_dataloader_flips_before_final_yield():
    _reset_singletons()
    base = DataLoader(
        list(RegressionDataset(length=24)), batch_size=2, collate_fn=regression_collate
    )
    dl = prepare_data_loader(base, prefetch_to_device=2)
    flags = [dl.end_of_dataloader for _ in dl]
    assert flags[:-1] == [False] * (len(flags) - 1)
    assert flags[-1] is True


def test_prefetch_multiple_epochs_and_iteration_counter():
    _reset_singletons()
    base = DataLoader(
        list(RegressionDataset(length=16)), batch_size=2, collate_fn=regression_collate
    )
    dl = prepare_data_loader(base, prefetch_to_device=1)
    first = _collect_batches(dl)
    assert dl.iteration == 1
    second = _collect_batches(dl)
    assert dl.iteration == 2
    _assert_same_stream(first, second)  # sequential sampler: same order


def test_prefetch_env_knob_applies_to_prepared_loader(monkeypatch):
    _reset_singletons()
    base = DataLoader(
        list(RegressionDataset(length=16)), batch_size=2, collate_fn=regression_collate
    )
    dl = prepare_data_loader(base)
    assert dl._effective_prefetch_depth() == 0
    monkeypatch.setenv("ACCELERATE_TPU_PREFETCH", "2")
    assert dl._effective_prefetch_depth() == 2
    # Explicit config wins over the env.
    dl.prefetch_to_device = 1
    assert dl._effective_prefetch_depth() == 1


def test_skip_first_batches_with_prefetch():
    _reset_singletons()
    base = DataLoader(
        list(RegressionDataset(length=32)), batch_size=2, collate_fn=regression_collate
    )
    sync = prepare_data_loader(base, prefetch_to_device=0)
    pref = prepare_data_loader(base, prefetch_to_device=2)
    skipped_sync = skip_first_batches(sync, 3)
    skipped_pref = skip_first_batches(pref, 3)
    assert skipped_pref.prefetch_to_device == 2
    full = _collect_batches(sync)
    _assert_same_stream(_collect_batches(skipped_sync), full[3:])
    _assert_same_stream(_collect_batches(skipped_pref), full[3:])


def test_prefetch_stateful_dataloader_mid_epoch_resume():
    _reset_singletons()

    def fresh(prefetch):
        base = DataLoader(
            list(RegressionDataset(length=32)), batch_size=2, collate_fn=regression_collate
        )
        return prepare_data_loader(
            base, prefetch_to_device=prefetch, use_stateful_dataloader=True
        )

    dl = fresh(prefetch=2)
    seen = []
    state = None
    for i, batch in enumerate(dl):
        seen.append({k: np.asarray(v) for k, v in batch.items()})
        if i == 4:
            state = dl.state_dict()
            break
    assert state == {"batches_yielded": 5, "iteration": 0}

    resumed = fresh(prefetch=2)
    resumed.load_state_dict(state)
    tail = _collect_batches(resumed)
    full = _collect_batches(fresh(prefetch=0))
    _assert_same_stream(tail, full[5:])
    # The skip is consumed: the next epoch runs in full.
    _assert_same_stream(_collect_batches(resumed), full)


def test_prefetch_records_host_blocked_histogram(tmp_path):
    _reset_singletons()
    tel = telemetry.enable(dir=str(tmp_path))
    base = DataLoader(
        list(RegressionDataset(length=16)), batch_size=2, collate_fn=regression_collate
    )
    dl = prepare_data_loader(base, prefetch_to_device=2)
    n = len(_collect_batches(dl))
    hist = tel.registry.histogram("pipeline.host_blocked_ms")
    assert hist.count >= n


def test_dispatcher_prefetch_single_process_stream():
    _reset_singletons()
    base = DataLoader(
        list(RegressionDataset(length=24)), batch_size=2, collate_fn=regression_collate
    )
    sync = prepare_data_loader(base, dispatch_batches=True, prefetch_to_device=0)
    pref = prepare_data_loader(base, dispatch_batches=True, prefetch_to_device=2)
    _assert_same_stream(_collect_batches(sync), _collect_batches(pref))


# ---------------------------------------------------------------------------
# NamedSharding cache
# ---------------------------------------------------------------------------


def test_cached_sharding_returns_same_object():
    _reset_singletons()
    acc = Accelerator()
    spec = PartitionSpec("dp") if "dp" in acc.mesh.shape else PartitionSpec()
    a = cached_sharding(acc.mesh, spec)
    b = cached_sharding(acc.mesh, spec)
    assert a is b
    assert cached_sharding(acc.mesh, PartitionSpec()) is not a or spec == PartitionSpec()


def test_placer_reuses_cached_sharding_across_batches():
    _reset_singletons()
    acc = Accelerator()
    base = DataLoader(
        list(RegressionDataset(length=16)), batch_size=2, collate_fn=regression_collate
    )
    dl = acc.prepare_data_loader(base)
    list(dl)  # first epoch warms the cache
    before = sharding_cache_info()
    list(dl)
    after = sharding_cache_info()
    assert after.misses == before.misses  # steady state: no new NamedSharding builds
    assert after.hits > before.hits


# ---------------------------------------------------------------------------
# Fused train step: bit-exactness + dispatch counts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("accum", [1, 4])
@pytest.mark.parametrize("clip_norm", [None, 1.0])
def test_fused_step_bit_exact_vs_eager(accum, clip_norm):
    acc, model, opt, dl = _build_training(accum=accum)
    eager_losses, eager_params = _run_eager(acc, model, opt, dl, clip_norm=clip_norm)
    acc, model, opt, dl = _build_training(accum=accum)
    fused_losses, fused_params = _run_fused(
        acc, model, opt, dl, accum, clip_norm=clip_norm
    )
    assert len(eager_losses) > 0
    assert eager_losses == fused_losses
    for key in eager_params:
        np.testing.assert_array_equal(eager_params[key], fused_params[key])


@pytest.mark.parametrize("accum", [1, 4])
def test_fused_step_bit_exact_under_comm_hook_sync_dtype(accum):
    """DDP comm-hook parity: the eager path casts each scaled micro-grad to
    bf16 before accumulating; the fused window must reproduce that cast or
    make_train_step silently changes numerics."""
    from accelerate_tpu.utils import DistributedDataParallelKwargs

    def _build():
        _reset_singletons()
        set_seed(1234)
        acc = Accelerator(
            gradient_accumulation_steps=accum,
            kwargs_handlers=[DistributedDataParallelKwargs(comm_hook="bf16")],
        )
        model = RegressionModelWithLoss()
        opt = torch.optim.SGD(model.parameters(), lr=0.1)
        dl = DataLoader(
            list(RegressionDataset(length=32)),
            batch_size=1,
            collate_fn=regression_collate,
        )
        return acc, *acc.prepare(model, opt, dl)

    acc, model, opt, dl = _build()
    assert model._grad_sync_dtype is not None  # the hook actually armed
    eager_losses, eager_params = _run_eager(acc, model, opt, dl)
    acc, model, opt, dl = _build()
    fused_losses, fused_params = _run_fused(acc, model, opt, dl, accum)
    assert eager_losses == fused_losses
    for key in eager_params:
        np.testing.assert_array_equal(eager_params[key], fused_params[key])


def test_fused_step_tuple_batch_is_one_micro_batch():
    """A tuple batch is positional model args — ONE micro-batch, never
    unpacked as the accumulation window (only a list is)."""
    acc, model, opt, dl = _build_training()
    step_fn = acc.make_train_step(model, opt)
    batch = next(iter(dl))
    loss = step_fn((batch["x"], batch["y"]))  # forward(x, y) positionally
    assert np.asarray(loss).shape == ()

    acc, model, opt, dl = _build_training(accum=2)
    step_fn = acc.make_train_step(model, opt)
    it = iter(dl)
    b1, b2 = next(it), next(it)
    # A tuple is NOT a window: 1 micro-batch received where 2 are expected
    # (previously (x, y) was silently split into two "micro-batches").
    with pytest.raises(ValueError, match="received 1"):
        step_fn((b1["x"], b1["y"]))
    losses = step_fn([(b1["x"], b1["y"]), (b2["x"], b2["y"])])
    assert np.asarray(losses).shape == (2,)


def test_fused_step_one_dispatch_per_window_eager_three_per_micro(tmp_path):
    """Acceptance criterion: the telemetry counter proves the fused step
    issues exactly ONE jitted dispatch per accumulation window, vs
    3 x accum_steps dispatch sites on the eager path, with equal losses."""
    ACCUM = 4
    tel = telemetry.enable(dir=str(tmp_path))
    dispatches = tel.registry.counter("pipeline.dispatches")

    acc, model, opt, dl = _build_training(accum=ACCUM, length=64)
    mark = dispatches.value
    eager_losses, _ = _run_eager(acc, model, opt, dl)
    windows = len(eager_losses) // ACCUM
    assert windows >= 2
    assert dispatches.value - mark == 3 * ACCUM * windows
    assert tel.registry.gauge("pipeline.dispatches_per_step").value == 3 * ACCUM

    acc, model, opt, dl = _build_training(accum=ACCUM, length=64)
    mark = dispatches.value
    fused_losses, _ = _run_fused(acc, model, opt, dl, ACCUM)
    assert dispatches.value - mark == windows  # exactly one dispatch per window
    assert tel.registry.gauge("pipeline.dispatches_per_step").value == 1
    assert fused_losses == eager_losses


def test_fused_step_keeps_bf16_state_and_compiles_once():
    """bf16 parameters: the clip scalars (float32 arrays) must not promote the
    gradient tree, and through optax's moments the optimizer state, to
    float32.  Promoted, the state leaves the first step twice as wide — it no
    longer aliases the donated input (at Llama-3.2-1B widths 17.7 GiB live
    instead of 12.4: more than one v5e holds) and the second step compiles
    the whole program again for the wider state."""
    import optax

    from accelerate_tpu import JaxModel
    from accelerate_tpu.telemetry import CompileWatcher

    acc = Accelerator(mixed_precision="bf16")
    params = {"w": jnp.full((16, 16), 0.1, jnp.bfloat16), "b": jnp.zeros((16,), jnp.bfloat16)}

    def apply_fn(p, x):
        return {"loss": jnp.mean((x @ p["w"] + p["b"]) ** 2)}

    model, opt = acc.prepare(JaxModel(apply_fn, params), optax.adamw(1e-2))
    step = acc.make_train_step(model, opt, clip_norm=1.0, clip_value=0.5)
    batch = {"x": jnp.ones((8, 16), jnp.bfloat16)}
    mem = step.lower(batch).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        x.nbytes for x in jax.tree_util.tree_leaves((model.params, opt.opt_state))
    )  # every donated buffer is reused by an output of its own width
    watcher = CompileWatcher()
    float(step(batch))
    warm = watcher.count
    for _ in range(2):
        float(step(batch))
    watcher.stop()
    assert watcher.count == warm, "the fused step compiled again after its first call"
    floating = [
        x.dtype for x in jax.tree_util.tree_leaves((model.params, opt.opt_state))
        if jnp.issubdtype(x.dtype, jnp.floating)
    ]
    assert floating and all(d == jnp.bfloat16 for d in floating), floating


def test_fused_step_window_size_validation():
    acc, model, opt, dl = _build_training(accum=4)
    step_fn = acc.make_train_step(model, opt)
    batch = next(iter(dl))
    with pytest.raises(ValueError, match="4 micro-batch"):
        step_fn(batch)


def test_fused_step_requires_paired_optimizer():
    acc, model, opt, dl = _build_training()
    _reset_singletons()
    set_seed(1)
    acc2 = Accelerator()
    model2 = acc2.prepare_model(RegressionModelWithLoss())
    other_opt = acc2.prepare_optimizer(torch.optim.SGD(model2.module.parameters(), lr=0.1))
    with pytest.raises(ValueError, match="not paired"):
        acc.make_train_step(model, other_opt)


def test_fused_step_scheduler_interop():
    acc, model, opt, dl = _build_training()
    sched = torch.optim.lr_scheduler.StepLR(opt.torch_optimizer, step_size=1, gamma=0.5)
    sched = acc.prepare_scheduler(sched)
    step_fn = acc.make_train_step(model, opt)
    lr0 = opt.param_groups[0]["lr"]
    batch = next(iter(dl))
    step_fn(batch)
    sched.step()
    assert opt.param_groups[0]["lr"] < lr0
    assert opt._step_count == 1
    assert not opt.step_was_skipped


def test_fused_step_one_shot_clip_arm_consumed():
    acc, model, opt, dl = _build_training()
    step_fn = acc.make_train_step(model, opt)
    it = iter(dl)
    acc.clip_grad_norm_(None, 0.5)
    step_fn(next(it))
    # The arm is one-shot: consumed by the fused call.
    assert opt._clip_norm_once is None


def test_train_step_exported_types():
    acc, model, opt, dl = _build_training()
    step_fn = make_train_step(acc, model, opt)
    assert isinstance(step_fn, TrainStep)
    assert isinstance(acc.make_train_step(model, opt), TrainStep)


# ---------------------------------------------------------------------------
# Resilience interop: checkpoint round-trips through the fused step
# ---------------------------------------------------------------------------


def _build_ckpt_training(project_dir):
    _reset_singletons()
    set_seed(1234)
    accelerator = Accelerator(
        project_config=ProjectConfiguration(
            project_dir=str(project_dir), automatic_checkpoint_naming=False
        )
    )
    model = RegressionModelWithLoss()
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    dl = DataLoader(
        list(RegressionDataset(length=64)),
        batch_size=1,
        collate_fn=regression_collate,
    )
    model, opt, dl = accelerator.prepare(model, opt, dl)
    return accelerator, model, opt, dl


def test_fused_step_save_resume_bit_exact_continuation(tmp_path):
    """Satellite: a save_state/resume_from_latest round-trip mid-run through
    make_train_step continues with bit-exact losses."""
    # Reference run: 8 uninterrupted fused steps.
    acc, model, opt, dl = _build_ckpt_training(tmp_path / "ref")
    step_fn = acc.make_train_step(model, opt)
    ref_losses = []
    it = iter(dl)
    for _ in range(8):
        ref_losses.append(float(step_fn(next(it))))

    # Victim run: 4 fused steps, verified checkpoint, stop.
    ckpt_root = tmp_path / "ckpts"
    acc, model, opt, dl = _build_ckpt_training(tmp_path / "victim")
    step_fn = acc.make_train_step(model, opt)
    victim_losses = []
    it = iter(dl)
    for step in range(1, 5):
        victim_losses.append(float(step_fn(next(it))))
    acc.save_state(str(ckpt_root / "checkpoint_4"), step=4, verified=True)
    assert victim_losses == ref_losses[:4]

    # Fresh accelerator resumes from the verified checkpoint and continues.
    acc, model, opt, dl = _build_ckpt_training(tmp_path / "resume")
    resumed_step = acc.resume_from_latest(str(ckpt_root))
    assert resumed_step == 4
    step_fn = acc.make_train_step(model, opt)
    it = iter(dl)
    for _ in range(4):  # dataloader position: skip the consumed batches
        next(it)
    resumed_losses = [float(step_fn(next(it))) for _ in range(4)]
    assert resumed_losses == ref_losses[4:]


def test_fused_step_honors_check_preemption_boundary(tmp_path):
    """check_preemption() at the fused-step boundary writes one final
    verified checkpoint whose params match the live (post-write-back)
    model."""
    from accelerate_tpu.resilience.manifest import find_latest_complete

    acc, model, opt, dl = _build_ckpt_training(tmp_path / "run")
    guard = acc.enable_preemption_handling(save_dir=str(tmp_path / "preempt"))
    try:
        step_fn = acc.make_train_step(model, opt)
        it = iter(dl)
        stopped_at = None
        for step in range(1, 5):
            step_fn(next(it))
            if step == 3:
                guard._flag = True  # simulated signal delivery
            if acc.check_preemption(step=step):
                stopped_at = step
                break
        assert stopped_at == 3
        ckpt = find_latest_complete(str(tmp_path))
        assert ckpt is not None
        live = model.state_dict()
        acc.load_state(ckpt)
        restored = model.state_dict()
        for key in live:
            np.testing.assert_array_equal(live[key], restored[key])
    finally:
        # A leaked installed guard with _flag set is a process-wide landmine:
        # later tests' real SIGTERMs chain into it and its second-delivery
        # branch hard-kills the whole pytest run.
        guard.uninstall()


# ---------------------------------------------------------------------------
# Persistent compilation cache
# ---------------------------------------------------------------------------


@pytest.fixture
def _restore_compile_cache():
    yield
    from jax.experimental.compilation_cache import compilation_cache as _cc

    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    _cc.reset_cache()


def _placed_from_outside(monkeypatch, path) -> str:
    """What a process started with JAX_COMPILATION_CACHE_DIR=path looks like:
    jax read the variable into its own config when it was imported."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(path))
    jax.config.update("jax_compilation_cache_dir", str(path))
    monkeypatch.setenv("ACCELERATE_TPU_COMPILE_CACHE", "on")
    return str(path)


def test_compile_cache_placed_from_outside_is_left_alone(
    tmp_path, monkeypatch, _restore_compile_cache
):
    """JAX_COMPILATION_CACHE_DIR set: our code makes NO update to
    jax_compilation_cache_dir — only the cache-everything settings."""
    placed = _placed_from_outside(monkeypatch, tmp_path / "outside")
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: (updates.append(k), real_update(k, v))[1]
    )
    assert enable_compile_cache() == placed
    assert "jax_compilation_cache_dir" not in updates
    assert "jax_persistent_cache_min_compile_time_secs" in updates
    assert jax.config.jax_compilation_cache_dir == placed


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout(
    monkeypatch, _restore_compile_cache
):
    """Unset: the one fixed, ignored directory inside the checkout — derived
    from the package's __file__, never $HOME, a temp name, a pid or a time."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("ACCELERATE_TPU_COMPILE_CACHE", "on")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert DEFAULT_COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    made_here = not os.path.isdir(DEFAULT_COMPILE_CACHE_DIR)
    try:
        assert enable_compile_cache() == DEFAULT_COMPILE_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == DEFAULT_COMPILE_CACHE_DIR
        assert enable_compile_cache() == DEFAULT_COMPILE_CACHE_DIR  # idempotent
    finally:
        if made_here and not os.listdir(DEFAULT_COMPILE_CACHE_DIR):
            os.rmdir(DEFAULT_COMPILE_CACHE_DIR)
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_disabled_by_empty_env(monkeypatch, _restore_compile_cache):
    monkeypatch.setenv("ACCELERATE_TPU_COMPILE_CACHE", "")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None  # untouched


def test_compile_cache_size_bound(tmp_path, monkeypatch, _restore_compile_cache):
    # Default-on cache must be bounded: 1 GiB LRU unless overridden.
    monkeypatch.delenv("ACCELERATE_TPU_COMPILE_CACHE_MAX_BYTES", raising=False)
    assert compile_cache_mod.compile_cache_max_bytes_from_env() == 1 << 30
    monkeypatch.setenv("ACCELERATE_TPU_COMPILE_CACHE_MAX_BYTES", "12345")
    assert compile_cache_mod.compile_cache_max_bytes_from_env() == 12345
    monkeypatch.setenv("ACCELERATE_TPU_COMPILE_CACHE_MAX_BYTES", "0")
    assert compile_cache_mod.compile_cache_max_bytes_from_env() == -1  # unbounded
    with pytest.warns(UserWarning, match="not an integer"):
        monkeypatch.setenv("ACCELERATE_TPU_COMPILE_CACHE_MAX_BYTES", "lots")
        assert compile_cache_mod.compile_cache_max_bytes_from_env() == -1
    monkeypatch.setenv("ACCELERATE_TPU_COMPILE_CACHE_MAX_BYTES", "54321")
    _placed_from_outside(monkeypatch, tmp_path / "xla_cache")
    assert enable_compile_cache() is not None
    assert jax.config.jax_compilation_cache_max_size == 54321


def test_compile_cache_round_trip_and_hit_counter(tmp_path, monkeypatch, _restore_compile_cache):
    cache_dir = tmp_path / "xla_cache"
    _placed_from_outside(monkeypatch, cache_dir)
    assert enable_compile_cache() == str(cache_dir)
    tel = telemetry.enable(dir=str(tmp_path / "tel"))

    def f(x):
        return x * 3.0 + 1.0

    jax.jit(f)(jnp.arange(8.0)).block_until_ready()
    assert len(os.listdir(cache_dir)) > 0  # executable serialized
    jax.clear_caches()
    before = tel.registry.counter("jit.cache_hits").value
    jax.jit(f)(jnp.arange(8.0)).block_until_ready()
    assert tel.registry.counter("jit.cache_hits").value > before
    # The key holds the library's source: an executable compiled by another
    # source (whose named scopes it would carry into every profile) is no hit.
    from jax._src import cache_key

    assert cache_key.custom_hook().endswith("accelerate_tpu=" + compile_cache_mod.library_digest())
    monkeypatch.setattr(cache_key.custom_hook, "library_digest", "another source")
    jax.clear_caches()
    before, entries = tel.registry.counter("jit.cache_hits").value, len(os.listdir(cache_dir))
    jax.jit(f)(jnp.arange(8.0)).block_until_ready()
    assert tel.registry.counter("jit.cache_hits").value == before
    assert len(os.listdir(cache_dir)) > entries
