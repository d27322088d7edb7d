"""The plain reference against the program's own forward, loss and gradients at
a tiny Qwen2-shaped preset with non-zero biases, in float32 on the CPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def setting(qwen2, tiny_cfg):
    from accelerate_tpu.models import llama

    cfg = dict(tiny_cfg, torch_dtype="float32")
    params = qwen2.seeded_params(cfg, 2**31 + 5)  # a seed past 32 signed bits
    pcfg = qwen2.program_config(cfg, remat=False, attention_impl="einsum")
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (3, 48), dtype=np.int32)
    return llama, cfg, pcfg, params, ids


def reference_logits(qwen2, cfg, params, ids):
    ref = qwen2.Reference(cfg)
    x = ref.embed(params["embed"], ids)
    for lp in qwen2.unstack(params):
        x = ref.layer(x, lp)
    return qwen2.ref_head(x, params["final_norm"], params["embed"], cfg)


def test_biases_are_not_zero(setting):
    _, _, _, params, _ = setting
    for name in ("bq", "bk", "bv"):
        assert float(jnp.std(params["layers"][name])) > 0.1
    assert float(jnp.max(jnp.abs(params["layers"]["bo"]))) == 0.0


def test_forward_matches_llama_apply(qwen2, setting):
    llama, cfg, pcfg, params, ids = setting
    want = llama.apply(params, jnp.asarray(ids), pcfg)
    got = reference_logits(qwen2, cfg, params, ids)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4


def test_forward_notices_a_dropped_bias(qwen2, setting):
    llama, cfg, pcfg, params, ids = setting
    broken = dict(params, layers=dict(params["layers"], bk=jnp.zeros_like(params["layers"]["bk"])))
    want = llama.apply(broken, jnp.asarray(ids), pcfg)
    got = reference_logits(qwen2, cfg, params, ids)
    assert float(jnp.max(jnp.abs(got - want))) > 1e-2


def test_loss_and_gradients_match_llama(qwen2, setting):
    llama, cfg, pcfg, params, ids = setting
    loss, grads = jax.value_and_grad(lambda p: llama.loss_fn(p, {"input_ids": jnp.asarray(ids)}, pcfg))(params)
    opt = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4}
    state = qwen2.train_state(params)
    out = qwen2.Reference(cfg).train_step(state, ids, opt)
    assert abs(out["loss"] - float(loss)) < 1e-4
    want = {k: float(v) for k, v in qwen2.leaf_sq(grads).items()}
    for name, sq in out["grad_sq"].items():
        assert sq == pytest.approx(want[name], rel=2e-3, abs=1e-12), name


def test_adamw_matches_optax(qwen2, setting):
    import optax

    llama, cfg, pcfg, params, ids = setting
    opt = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4}
    tx = optax.adamw(**opt)
    p, s = params, tx.init(params)
    state = qwen2.train_state(params)
    ref = qwen2.Reference(cfg)
    for step in range(2):
        batch = np.roll(ids, step, axis=1)
        g = jax.grad(lambda q: llama.loss_fn(q, {"input_ids": jnp.asarray(batch)}, pcfg))(p)
        u, s = tx.update(g, s, p)
        p = optax.apply_updates(p, u)
        ref.train_step(state, batch, opt)
    got = qwen2.change_from_seed_sq(cfg, 2**31 + 5, qwen2.state_leaf(state))
    want = qwen2.change_from_seed_sq(cfg, 2**31 + 5, qwen2.tree_leaf(p))
    for name in got:
        assert got[name] == pytest.approx(want[name], rel=2e-2, abs=1e-14), name


def test_same_seed_same_weights(qwen2, tiny_cfg):
    a, b, c = (qwen2.seeded_params(tiny_cfg, s) for s in (7, 7, 8))
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
    assert not bool(jnp.array_equal(a["embed"], c["embed"]))
