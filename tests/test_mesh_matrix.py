"""Composed-mesh parity matrix: loss, per-leaf grads, and one optimizer step.

Every parallelism axis must COMPOSE: on each mixed mesh, the sharded loss,
every gradient leaf, and the parameter delta of one optimizer step must match
the dense single-device run (oracle semantics of reference
``test_utils/scripts/test_sync.py:29-43``, applied to a mesh).  A
mis-specified sharding that only corrupts the backward — e.g. a wrong psum
axis on a grad — fails the grad assertions even when the forward loss agrees.
Covers llama over fsdp/tp/sp/dp/pp mixes and mixtral (MoE) over ep mixes.
"""

import jax
import numpy as np
import pytest

from accelerate_tpu import ParallelismConfig
from accelerate_tpu.models import llama, mixtral
from accelerate_tpu.parallel.sharding import data_sharding, shard_params
from accelerate_tpu.state import AcceleratorState

LLAMA_MESHES = [
    dict(fsdp=2, sp=4),
    dict(fsdp=4, tp=2),
    dict(tp=2, sp=2, dp=2),
    dict(fsdp=2, tp=2, sp=2),
    dict(dp=4, tp=2),
    dict(pp=2, fsdp=2, dp=2),
    # ~13s; tier-1 budget rebalance (PR 18) — pp2xfsdp2xdp2 keeps pp-composed
    # coverage in tier-1, the sp-composed arm runs in `make test`.
    pytest.param(dict(pp=2, sp=2, dp=2), marks=pytest.mark.slow),
]
MIXTRAL_MESHES = [
    dict(ep=2, fsdp=2, dp=2),
    # ~12s; tier-1 budget rebalance (PR 18) — ep2xfsdp2xdp2 keeps ep-composed
    # coverage in tier-1.
    pytest.param(dict(ep=4, tp=2), marks=pytest.mark.slow),
    dict(ep=2, sp=2, dp=2),
]


def _ids(vocab):
    return np.random.default_rng(0).integers(0, vocab, (8, 32)).astype(np.int32)


def _loss_fn(cfg, mesh_axes, family):
    pp = mesh_axes.get("pp", 1)
    if pp > 1:
        from accelerate_tpu.parallel.pipeline import pipeline_llama_loss_fn

        return lambda p, b: pipeline_llama_loss_fn(
            p, b, cfg, num_stages=pp, num_micro_batches=2
        )
    return lambda p, b: family.loss_fn(p, b, cfg)


def _step_fn(loss_fn, tx):
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, grads, jax.tree.map(lambda p, u: p + u, params, updates)

    return jax.jit(step)


def _assert_tree_close(dense_tree, sharded_tree, what, mesh_axes, atol, rtol, max_relnorm):
    """Per-leaf elementwise closeness AND a per-leaf relative-error norm
    ``||d - s|| / ||d||``: a uniformly mis-scaled leaf (wrong psum average —
    every element off by the same factor) passes a loose elementwise check but
    shows up as relnorm ≈ |1 - scale|, far above bf16 noise.  Bounds are set
    ~3x above the measured maxima of the correct implementation on the 8-device
    CPU mesh (llama grads: 1.34e-3 abs / 2.29e-2 relnorm; mixtral: 5.21e-3 /
    7.87e-2 — MoE routing amplifies bf16 noise through the top-k gate)."""
    flat_d, treedef = jax.tree.flatten(dense_tree)
    flat_s = jax.tree.leaves(sharded_tree)
    keys = [str(k) for k, _ in jax.tree_util.tree_flatten_with_path(dense_tree)[0]]
    for key, d, s in zip(keys, flat_d, flat_s):
        d = np.asarray(d, np.float32)
        s = np.asarray(s, np.float32)
        np.testing.assert_allclose(
            d, s, atol=atol, rtol=rtol,
            err_msg=f"{what} leaf {key} diverged on mesh {mesh_axes}",
        )
        relnorm = float(np.linalg.norm(d - s) / (np.linalg.norm(d) + 1e-12))
        assert relnorm < max_relnorm, (
            f"{what} leaf {key} rel-error norm {relnorm:.3e} >= {max_relnorm} on "
            f"mesh {mesh_axes} (uniform mis-scaling?)"
        )


def _run_matrix_case(
    family, cfg, params, ids, dense_ref, mesh_axes, atol_loss, atol_grad, max_relnorm
):
    import optax

    tx = optax.sgd(0.1)
    dense_loss, dense_grads, dense_new = dense_ref

    state = AcceleratorState(parallelism_config=ParallelismConfig(**mesh_axes))
    sp = shard_params(params, state.mesh, family.param_specs(cfg))
    sb = {"input_ids": jax.device_put(ids, data_sharding(state.mesh))}
    step = _step_fn(_loss_fn(cfg, mesh_axes, family), tx)
    loss, grads, new_params = step(sp, tx.init(sp), sb)

    assert abs(float(loss) - dense_loss) < atol_loss, (mesh_axes, float(loss), dense_loss)
    # Backward parity: every grad leaf (a wrong collective shows up here even
    # when the loss matches).
    _assert_tree_close(
        dense_grads, grads, "grad", mesh_axes,
        atol=atol_grad, rtol=5e-2, max_relnorm=max_relnorm,
    )
    # Update parity: the param delta of one optimizer step (sgd lr=0.1 scales
    # grads by 0.1, hence the 10x-tighter atol).  Deltas are computed in numpy
    # — an eager jnp subtract would run under the ambient mesh context against
    # single-device dense arrays.
    _np = lambda t: jax.tree.map(lambda x: np.asarray(x, np.float32), t)
    dense_delta = jax.tree.map(lambda n, p: n - p, _np(dense_new), _np(params))
    sharded_delta = jax.tree.map(lambda n, p: n - p, _np(new_params), _np(sp))
    _assert_tree_close(
        dense_delta, sharded_delta, "update", mesh_axes,
        atol=atol_grad / 10, rtol=5e-2, max_relnorm=max_relnorm,
    )


@pytest.fixture(scope="module")
def llama_dense():
    import optax

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    ids = _ids(cfg.vocab_size)
    tx = optax.sgd(0.1)
    step = _step_fn(lambda p, b: llama.loss_fn(p, b, cfg), tx)
    loss, grads, new_params = step(params, tx.init(params), {"input_ids": jax.numpy.asarray(ids)})
    return cfg, params, ids, (float(loss), jax.device_get(grads), jax.device_get(new_params))


@pytest.mark.parametrize(
    "mesh_axes", LLAMA_MESHES, ids=lambda m: "x".join(f"{k}{v}" for k, v in m.items())
)
def test_llama_mesh_matrix(mesh_axes, llama_dense):
    cfg, params, ids, dense_ref = llama_dense
    _run_matrix_case(
        llama, cfg, params, ids, dense_ref, mesh_axes,
        atol_loss=3e-3, atol_grad=4e-3, max_relnorm=7e-2,
    )


@pytest.fixture(scope="module")
def mixtral_dense():
    import optax

    cfg = mixtral.MixtralConfig.tiny()
    params = mixtral.init_params(cfg, jax.random.key(0))
    ids = _ids(cfg.vocab_size)
    tx = optax.sgd(0.1)
    step = _step_fn(lambda p, b: mixtral.loss_fn(p, b, cfg), tx)
    loss, grads, new_params = step(params, tx.init(params), {"input_ids": jax.numpy.asarray(ids)})
    return cfg, params, ids, (float(loss), jax.device_get(grads), jax.device_get(new_params))


@pytest.mark.parametrize(
    "mesh_axes", MIXTRAL_MESHES, ids=lambda m: "x".join(f"{k}{v}" for k, v in m.items())
)
def test_mixtral_mesh_matrix(mesh_axes, mixtral_dense):
    cfg, params, ids, dense_ref = mixtral_dense
    _run_matrix_case(
        mixtral, cfg, params, ids, dense_ref, mesh_axes,
        atol_loss=5e-3, atol_grad=1.6e-2, max_relnorm=2.5e-1,
    )


# ---------------------------------------------------------------------------
# Comms-ledger invariants (compiled-program introspection)
# ---------------------------------------------------------------------------
#
# The HLO scan is static (a collective inside the layer lax.scan counts once,
# not once per layer), so the exact-byte invariants run at num_layers=1 where
# static == executed; f32 compute so gradient sync bytes == param bytes.


def _ledger_for(mesh_axes, cfg):
    import optax

    from accelerate_tpu.telemetry import inspect_compiled

    state = AcceleratorState(parallelism_config=ParallelismConfig(**mesh_axes))
    sp = shard_params(params := llama.init_params(cfg, jax.random.key(0)),
                      state.mesh, llama.param_specs(cfg))
    sb = {"input_ids": jax.device_put(_ids(cfg.vocab_size), data_sharding(state.mesh))}
    tx = optax.sgd(0.1)
    step = _step_fn(lambda p, b: llama.loss_fn(p, b, cfg), tx)
    compiled = step.lower(sp, tx.init(sp), sb).compile()
    param_bytes = sum(
        int(np.prod(np.shape(l))) * np.dtype(np.asarray(l).dtype).itemsize
        for l in jax.tree.leaves(params)
    )
    return inspect_compiled(compiled, name="llama_step", mesh=state.mesh), param_bytes


def test_ledger_dp_grad_allreduce_matches_param_bytes():
    """On a pure-dp mesh every gradient leaf is all-reduced at full size:
    total dp all-reduce bytes == total param bytes (within 10% — the slack is
    the loss/metric scalars riding the same axis)."""
    import jax.numpy as jnp

    report, param_bytes = _ledger_for(
        dict(dp=8), llama.LlamaConfig.tiny(num_layers=1, dtype=jnp.float32)
    )
    ar = report.ledger.by_kind.get("all-reduce")
    assert ar is not None, f"no all-reduce on the dp mesh: {report.ledger.by_kind}"
    dp_bytes = report.ledger.by_axis.get("dp", 0)
    assert abs(dp_bytes - param_bytes) / param_bytes < 0.10, (
        f"dp all-reduce bytes {dp_bytes} vs param bytes {param_bytes}"
    )
    # Measured cost came along: the analyzed FLOPs replace the 6ND estimate.
    assert report.flops > 0 and report.bytes_accessed > 0


@pytest.mark.slow  # ~12s; tier-1 budget rebalance (PR 18) — `make test` runs it
def test_ledger_fsdp_has_gather_and_grad_sync():
    """An fsdp mesh must show the ZeRO-3 signature: weight all-gathers for
    compute plus a gradient sync (reduce-scatter or all-reduce) on the fsdp
    axis."""
    import jax.numpy as jnp

    report, param_bytes = _ledger_for(
        dict(fsdp=8), llama.LlamaConfig.tiny(num_layers=1, dtype=jnp.float32)
    )
    kinds = set(report.ledger.by_kind)
    assert "all-gather" in kinds, f"no all-gather on the fsdp mesh: {kinds}"
    assert kinds & {"reduce-scatter", "all-reduce"}, f"no grad sync: {kinds}"
    fsdp_bytes = sum(
        b for ax, b in report.ledger.by_axis.items() if "fsdp" in ax.split("+")
    )
    assert fsdp_bytes > 0
