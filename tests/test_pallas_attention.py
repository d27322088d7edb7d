"""Pallas flash-attention kernel vs dense einsum reference (fwd + grads).

Runs the kernels through the Pallas interpreter on the CPU mesh — the same
code compiles to Mosaic on a real TPU (bench.py exercises that path).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.ops.pallas_attention import pallas_attention


def _dense_reference(q, k, v, causal=True):
    b, s, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    kf = jnp.repeat(k, g, axis=2)
    vf = jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, kf).astype(jnp.float32) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p.astype(vf.dtype), vf)


@pytest.mark.parametrize("kv_heads", [4, 2])  # MHA and GQA
@pytest.mark.parametrize("causal", [True, False])
def test_pallas_forward_matches_dense(kv_heads, causal):
    b, s, h, d = 2, 256, 4, 64
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv_heads, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv_heads, d), jnp.float32)

    out = pallas_attention(q, k, v, causal=causal, block_size=128, interpret=True)
    ref = _dense_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kv_heads", [4, 2])
def test_pallas_grads_match_dense(kv_heads):
    b, s, h, d = 1, 256, 4, 64
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv_heads, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv_heads, d), jnp.float32)

    def loss_pallas(q, k, v):
        o = pallas_attention(q, k, v, causal=True, block_size=128, interpret=True)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape) * 0.01))

    def loss_ref(q, k, v):
        o = _dense_reference(q, k, v, causal=True)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape) * 0.01))

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-5,
            err_msg=f"grad d{name} mismatch",
        )


def test_pallas_bf16_close_to_f32():
    b, s, h, d = 1, 256, 2, 64
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    out_bf = pallas_attention(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
        causal=True, block_size=128, interpret=True,
    )
    ref = _dense_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out_bf, np.float32), np.asarray(ref), atol=0.05, rtol=0.05
    )


def test_llama_pallas_impl_matches_einsum():
    """Full llama forward with attention_impl="pallas" vs "einsum"."""
    from accelerate_tpu.models import llama

    cfg_kw = dict(num_layers=2, hidden_size=64, intermediate_size=128, dtype=jnp.float32)
    cfg_e = llama.LlamaConfig.tiny(**cfg_kw, attention_impl="einsum")
    cfg_p = llama.LlamaConfig.tiny(**cfg_kw, attention_impl="pallas")
    params = llama.init_params(cfg_e, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 128), 0, cfg_e.vocab_size)

    out_e = llama.apply(params, ids, cfg_e)
    out_p = llama.apply(params, ids, cfg_p)
    np.testing.assert_allclose(
        np.asarray(out_e, np.float32), np.asarray(out_p, np.float32), atol=2e-2, rtol=2e-2
    )


def test_pallas_spmd_on_mesh_matches_dense():
    """shard_map-wrapped kernel on a dp x tp mesh (interpret mode) vs dense."""
    from accelerate_tpu import AcceleratorState, ParallelismConfig
    from accelerate_tpu.ops.pallas_attention import pallas_attention_spmd

    state = AcceleratorState(parallelism_config=ParallelismConfig(dp=4, tp=2))
    mesh = state.mesh
    b, s, h, d = 4, 256, 4, 64
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)

    ref = _dense_reference(q, k, v, causal=True)
    out = jax.jit(
        lambda q, k, v: pallas_attention_spmd(
            q, k, v, mesh=mesh, causal=True, block_size=128, interpret=True
        )
    )(q, k, v)
    AcceleratorState._reset_state()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_pallas_spmd_rejects_sp_mesh():
    from accelerate_tpu import AcceleratorState, ParallelismConfig
    from accelerate_tpu.ops.pallas_attention import pallas_attention_spmd

    state = AcceleratorState(parallelism_config=ParallelismConfig(dp=2, sp=4))
    q = jnp.zeros((2, 64, 4, 16), jnp.float32)
    with pytest.raises(ValueError, match="ring/ulysses"):
        pallas_attention_spmd(q, q, q, mesh=state.mesh, causal=True, interpret=True)
    AcceleratorState._reset_state()


def _sp_mesh():
    # shard_map requires the context mesh to match, so the sp mesh comes from
    # AcceleratorState (which installs it) rather than a raw Mesh.
    from accelerate_tpu import AcceleratorState, ParallelismConfig

    AcceleratorState._reset_state()
    return AcceleratorState(parallelism_config=ParallelismConfig(dp=2, sp=4)).mesh


def _seq_sharded(mesh, *arrays):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    sh = NamedSharding(mesh, P(None, "sp", None, None))
    return tuple(jax.device_put(a, sh) for a in arrays)


@pytest.mark.parametrize("kv_heads", [4, 2])  # MHA and GQA
@pytest.mark.parametrize("causal", [True, False])
def test_pallas_ring_matches_dense(kv_heads, causal):
    """Pallas-per-block ring over a 4-way sp mesh vs the dense reference."""
    from accelerate_tpu.ops.pallas_attention import ring_attention_pallas

    mesh = _sp_mesh()
    b, s, h, d = 2, 512, 4, 64
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv_heads, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv_heads, d), jnp.float32)
    qs, ksh, vs = _seq_sharded(mesh, q, k, v)

    out = ring_attention_pallas(qs, ksh, vs, mesh=mesh, causal=causal, interpret=True)
    ref = _dense_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow  # >10s; overlapping coverage stays in the bounded tier-1 run
def test_pallas_ring_grads_match_dense():
    """Backward ring: dQ local accumulation + dK/dV riding home with their
    chunks must reproduce the dense gradients."""
    from accelerate_tpu.ops.pallas_attention import ring_attention_pallas

    mesh = _sp_mesh()
    b, s, h, d = 2, 512, 4, 64
    ks = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, 2, d), jnp.float32)  # GQA
    v = jax.random.normal(ks[2], (b, s, 2, d), jnp.float32)
    qs, ksh, vs = _seq_sharded(mesh, q, k, v)

    w = jnp.cos(jnp.arange(b * s * h * d).reshape(b, s, h, d) * 0.01)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_pallas(q, k, v, mesh=mesh, interpret=True) * w)

    def loss_ref(q, k, v):
        return jnp.sum(_dense_reference(q, k, v, causal=True) * w)

    gp = jax.grad(loss_ring, argnums=(0, 1, 2))(qs, ksh, vs)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-4,
            err_msg=f"ring grad d{name} mismatch",
        )


def test_pallas_ring_composes_with_dp_axis():
    """Batch stays sharded over dp while the sequence rings over sp."""
    from accelerate_tpu import AcceleratorState, ParallelismConfig
    from accelerate_tpu.ops.pallas_attention import ring_attention_pallas

    state = AcceleratorState(parallelism_config=ParallelismConfig(dp=2, sp=4))
    mesh = state.mesh
    b, s, h, d = 4, 512, 4, 64
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)

    out = jax.jit(
        lambda q, k, v: ring_attention_pallas(q, k, v, mesh=mesh, interpret=True)
    )(q, k, v)
    ref = _dense_reference(q, k, v, causal=True)
    AcceleratorState._reset_state()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_pallas_ring_bf16_close_to_f32_dense():
    """The bench/production dtype: bf16 q/k/v through the pallas ring must
    track the f32 dense reference within bf16 tolerance."""
    from accelerate_tpu.ops.pallas_attention import ring_attention_pallas

    mesh = _sp_mesh()
    b, s, h, d = 2, 512, 4, 64
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, 2, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, 2, d), jnp.float32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    qs, ksh, vs = _seq_sharded(mesh, qb, kb, vb)

    out = ring_attention_pallas(qs, ksh, vs, mesh=mesh, interpret=True)
    ref = _dense_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=0.05, rtol=0.05
    )


def test_pallas_ring_composes_with_tp_axis():
    """Heads shard over tp while the sequence rings over sp: each tp shard
    runs the kernel on its own head slice."""
    from accelerate_tpu import AcceleratorState, ParallelismConfig
    from accelerate_tpu.ops.pallas_attention import ring_attention_pallas

    AcceleratorState._reset_state()
    state = AcceleratorState(parallelism_config=ParallelismConfig(tp=2, sp=4))
    mesh = state.mesh
    b, s, h, d = 2, 512, 4, 64  # 4 heads / tp=2 -> 2 heads per shard
    ks = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)

    out = jax.jit(
        lambda q, k, v: ring_attention_pallas(q, k, v, mesh=mesh, interpret=True)
    )(q, k, v)
    ref = _dense_reference(q, k, v, causal=True)
    AcceleratorState._reset_state()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ulysses_pallas_impl_matches_dense():
    """impl="pallas" inside the ulysses all-to-all body vs dense reference."""
    from accelerate_tpu.ops.ulysses_attention import ulysses_attention

    mesh = _sp_mesh()
    b, s, h, d = 2, 512, 4, 64
    ks = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    qs, ksh, vs = _seq_sharded(mesh, q, k, v)

    out = ulysses_attention(qs, ksh, vs, mesh=mesh, impl="pallas")
    ref = _dense_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_kv_valid_matches_dense(causal):
    """Key-validity masking inside the kernel (round 5): padded batches no
    longer need the scan fallback.  Fully-masked query rows output zeros
    (einsum/ring convention); fwd and grads match the dense reference."""
    b, s, h, d = 2, 256, 4, 64
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, 2, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, 2, d), jnp.float32)
    valid_np = np.ones((b, s), np.int8)
    valid_np[0, 200:] = 0   # right padding
    valid_np[1, :150] = 0   # LEFT padding: rows 0..149 have NO in-causal
    valid = jnp.asarray(valid_np)   # valid key -> fully-masked query rows

    def dense(q, k, v):
        kf = jnp.repeat(k, 2, axis=2)
        vf = jnp.repeat(v, 2, axis=2)
        scores = jnp.einsum("bshd,bthd->bhst", q, kf).astype(jnp.float32) / np.sqrt(d)
        mask = jnp.ones((b, s, s), bool)
        if causal:
            mask = mask & jnp.tril(jnp.ones((s, s), bool))[None]
        mask = mask & valid.astype(bool)[:, None, :]
        scores = jnp.where(mask[:, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhst,bthd->bshd", p.astype(vf.dtype), vf)
        return out * mask.any(-1)[:, :, None, None]  # zero fully-masked rows

    out = pallas_attention(q, k, v, causal=causal, block_size=128, interpret=True,
                           kv_valid=valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense(q, k, v)),
                               atol=2e-5, rtol=2e-5)

    w = jnp.cos(jnp.arange(b * s * h * d).reshape(b, s, h, d) * 0.01)
    gp = jax.grad(
        lambda q, k, v: jnp.sum(
            pallas_attention(q, k, v, causal=causal, block_size=128, interpret=True,
                             kv_valid=valid) * w
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(dense(q, k, v) * w), argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-5,
                                   err_msg=f"masked grad d{name}")


def test_pallas_spmd_padded_batch_on_mesh():
    """kv_valid rides shard_map on a dp x tp mesh."""
    from accelerate_tpu import AcceleratorState, ParallelismConfig
    from accelerate_tpu.ops.pallas_attention import pallas_attention_spmd

    AcceleratorState._reset_state()
    state = AcceleratorState(parallelism_config=ParallelismConfig(dp=4, tp=2))
    b, s, h, d = 4, 256, 4, 64
    ks = jax.random.split(jax.random.key(12), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    valid_np = np.ones((b, s), np.int8)
    valid_np[1, 100:] = 0
    valid = jnp.asarray(valid_np)

    out = jax.jit(
        lambda q, k, v, m: pallas_attention_spmd(
            q, k, v, mesh=state.mesh, causal=True, block_size=128, interpret=True,
            kv_valid=m,
        )
    )(q, k, v, valid)
    ref = pallas_attention(q, k, v, causal=True, block_size=128, interpret=True,
                           kv_valid=valid)
    AcceleratorState._reset_state()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ulysses_pallas_padded_matches_einsum_ring():
    """Padded sp batches through pallas-ulysses equal the einsum ring."""
    from accelerate_tpu.ops.ring_attention import ring_attention
    from accelerate_tpu.ops.ulysses_attention import ulysses_attention

    mesh = _sp_mesh()
    b, s, h, d = 2, 512, 4, 64
    ks = jax.random.split(jax.random.key(13), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    valid_np = np.ones((b, s), np.int8)
    valid_np[0, 400:] = 0
    valid = jnp.asarray(valid_np)
    qs, ksh, vs = _seq_sharded(mesh, q, k, v)
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    vsh = jax.device_put(valid, NamedSharding(mesh, P(None, "sp")))

    out_u = ulysses_attention(qs, ksh, vs, mesh=mesh, kv_valid=vsh, impl="pallas")
    out_r = ring_attention(qs, ksh, vs, mesh=mesh, kv_valid=vsh)
    np.testing.assert_allclose(np.asarray(out_u), np.asarray(out_r), atol=2e-5, rtol=2e-5)


def test_llama_sp_pallas_matches_dense_model():
    """Full llama forward on an sp mesh with attention_impl="pallas" (the
    pallas-in-ring path) vs the single-device einsum model."""
    from accelerate_tpu import AcceleratorState, ParallelismConfig
    from accelerate_tpu.models import llama

    cfg_kw = dict(
        num_layers=2, hidden_size=64, intermediate_size=128, dtype=jnp.float32,
        max_seq_len=512,
    )
    AcceleratorState._reset_state()  # the reference must run without a mesh
    cfg_e = llama.LlamaConfig.tiny(**cfg_kw, attention_impl="einsum")
    params = llama.init_params(cfg_e, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 512), 0, cfg_e.vocab_size)
    out_ref = llama.apply(params, ids, cfg_e)

    AcceleratorState._reset_state()
    AcceleratorState(parallelism_config=ParallelismConfig(dp=2, sp=4))
    cfg_p = llama.LlamaConfig.tiny(**cfg_kw, attention_impl="pallas")
    # Host copies: the reference run committed these to device 0, which would
    # conflict with the 8-device mesh context here.
    params_h = jax.tree_util.tree_map(np.asarray, params)
    out_sp = llama.apply(params_h, np.asarray(ids), cfg_p)
    AcceleratorState._reset_state()
    np.testing.assert_allclose(
        np.asarray(out_ref, np.float32), np.asarray(out_sp, np.float32), atol=2e-2, rtol=2e-2
    )


@pytest.mark.slow  # >10s; overlapping coverage stays in the bounded tier-1 run
def test_llama_padded_batch_pallas_matches_einsum():
    """attention_impl='pallas' with an attention_mask (the padded-batch path
    that round 5 moved INTO the kernel) must match the einsum model: loss
    and gradients."""
    from accelerate_tpu.models import llama

    cfg_kw = dict(num_layers=2, hidden_size=64, intermediate_size=128,
                  dtype=jnp.float32, max_seq_len=128)
    cfg_e = llama.LlamaConfig.tiny(**cfg_kw, attention_impl="einsum")
    cfg_p = llama.LlamaConfig.tiny(**cfg_kw, attention_impl="pallas")
    params = llama.init_params(cfg_e, jax.random.key(0))
    ids = np.random.default_rng(5).integers(0, cfg_e.vocab_size, (2, 128)).astype(np.int32)
    am = np.ones((2, 128), np.int32)
    am[0, 100:] = 0   # right padding
    am[1, :40] = 0    # left padding (empty query rows)
    batch = {"input_ids": jnp.asarray(ids), "attention_mask": jnp.asarray(am)}

    le, ge = jax.value_and_grad(lambda p: llama.loss_fn(p, batch, cfg_e))(params)
    lp, gp = jax.value_and_grad(lambda p: llama.loss_fn(p, batch, cfg_p))(params)
    assert abs(float(le) - float(lp)) < 2e-4, (float(le), float(lp))
    err = jax.tree.reduce(
        max, jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), ge, gp)
    )
    assert err < 5e-4, f"max grad delta {err}"
