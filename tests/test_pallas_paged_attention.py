"""``ops/pallas_paged_attention.py`` (the decoding lanes' attention read where the
pool lies) through the Pallas interpreter on the CPU, against the gathered
path it replaces (``generation.paged_cache_write`` / ``paged_window_write`` and
``llama._attention`` under ``group_positions`` / ``window_group_masks``), and
the rule that says where it runs (``generation.reads_in_place``).  Nothing here
is a time: the kernel's speed is a chip run's (``PERF.md`` section 6, PR 39),
Mosaic's verdict on it ``tests/test_tpu_compile.py``'s."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from accelerate_tpu.models import generation as G
from accelerate_tpu.models import llama
from accelerate_tpu.ops import pallas_paged_attention as K

BS, HD, WINDOW, CHUNK = 16, 128, 40, 8
RING = G.window_ring_blocks(WINDOW, CHUNK, BS)  # 4 blocks: 64 rows
# lanes at 0 (nothing in the pool), inside a block, at a block's last and first rows, and deep in a table of 16 blocks
FULL_STARTS = [0, 1, 15, 16, 33, 100, 255]
# ... and for a ring: inside the first window, exactly at it and one past it, past the ring, wrapped more than once
RING_STARTS = [0, 1, 16, 39, 40, 41, 70, 200]


def case(kv_heads, starts, width, ring, seed=0):
    """(q, k_new, v_new [B, 1, ., HD], the leaves [N, BS, K, HD], tables [B, width], starts [B]) in float32, every
    block of the pool random, the null block 0 among them: a row read that the mask does not admit shows."""
    rng = np.random.default_rng(seed)
    b, h = len(starts), 2 * kv_heads
    n = 1 + b * width
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    tables = np.zeros((b, width), np.int32)  # entries past a lane's end name the null block
    ids = rng.permutation(np.arange(1, n))
    for lane, s in enumerate(starts):
        owned = min(width, -(-(s + 1) // BS))
        tables[lane, :owned] = ids[lane * width : lane * width + owned]
    return (normal(b, 1, h, HD), normal(b, 1, kv_heads, HD), normal(b, 1, kv_heads, HD), normal(n, BS, kv_heads, HD),
            normal(n, BS, kv_heads, HD), jnp.asarray(tables), jnp.asarray(starts, jnp.int32))


def gathered(q, k_new, v_new, pk, pv, tables, starts, ring):
    tokens = jnp.zeros(starts.shape + (1,), jnp.int32)
    positions, masks = G.group_positions(((tokens, tables, starts),), BS)
    if ring:
        masks = G.window_group_masks(((tokens, tables, starts, tables),), positions, BS, WINDOW)
        _, k_ctx = G.paged_window_write(pk, k_new, tables, starts)
        _, v_ctx = G.paged_window_write(pv, v_new, tables, starts)
    else:
        _, k_ctx = G.paged_cache_write(pk, k_new, tables, starts, jnp.float32)
        _, v_ctx = G.paged_cache_write(pv, v_new, tables, starts, jnp.float32)
    with jax.default_matmul_precision("highest"):
        return llama._attention(q, k_ctx, v_ctx, masks[0], q.shape[2] // k_new.shape[2])


@pytest.mark.parametrize("ring", [False, True], ids=["full", "ring"])
@pytest.mark.parametrize("kv_heads", [2, 8])
def test_the_kernel_and_the_merge_are_the_gathered_path(kv_heads, ring):
    starts, width = (RING_STARTS, RING) if ring else (FULL_STARTS, 16)
    q, k_new, v_new, pk, pv, tables, st = case(kv_heads, starts, width, ring)
    want = gathered(q, k_new, v_new, pk, pv, tables, st, ring)
    with jax.default_matmul_precision("highest"):
        got = G.attend_in_place(q, k_new, v_new, pk, pv, tables, st, WINDOW if ring else 0, interpret=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("step_blocks", [1, 3])
def test_a_step_of_any_number_of_blocks_reads_the_same_rows(step_blocks):
    """Steps that end inside a lane's blocks and lanes whose last step is partial, next to lanes with none: the
    double buffer's slots alternate across lanes, and an empty lane starts nothing."""
    q, k_new, v_new, pk, pv, tables, st = case(2, [0, 40, 0, 0, 255, 17, 0], 16, False, seed=1)
    lo, hi = G._admitted(st, 0)
    with jax.default_matmul_precision("highest"):
        whole = K.paged_decode_attention(q[:, 0], pk, pv, tables, lo, hi, interpret=True)
        steps = K.paged_decode_attention(q[:, 0], pk, pv, tables, lo, hi, step_blocks=step_blocks, interpret=True)
    for a, b in zip(whole, steps):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    acc, m, l = whole
    empty = np.asarray(st) == 0  # a lane with no rows: nothing added, the own row alone decides the merge
    assert (np.asarray(acc)[empty] == 0).all() and (np.asarray(l)[empty] == 0).all() and (np.asarray(m)[empty] == K.MASKED).all()


@pytest.mark.parametrize("interpret", ["hlo", "tpu"], ids=["interpret", "interpret-params"])
@pytest.mark.parametrize("step_blocks", [0, 3])
@pytest.mark.parametrize("ring", [False, True], ids=["full", "ring"])
def test_one_lanes_nan_rows_reach_no_other_lane(ring, step_blocks, interpret):
    """The lanes share the double buffer: a lane's last step that ends inside a slot finds there the blocks an earlier
    lane left, another request's rows.  Lane 0's K and V are NaN (they fill a slot, or both at a step of 3); every
    other lane, whose last steps are partial, reads what the gathered path does, its own blocks alone.  Under
    ``pltpu.InterpretParams`` uninitialized memory reads NaN too, so a fresh slot is held to the same."""
    starts, width = ([200, 70, 16, 0, 41, 9], RING) if ring else ([255, 40, 17, 0, 100, 9], 16)
    q, k_new, v_new, pk, pv, tables, st = case(2, starts, width, ring, seed=2)
    poisoned = np.asarray(tables)[0]
    pk, pv = pk.at[poisoned].set(jnp.nan), pv.at[poisoned].set(jnp.nan)
    lo, hi = G._admitted(st, WINDOW if ring else 0)
    mode = True if interpret == "hlo" else pltpu.InterpretParams()
    with jax.default_matmul_precision("highest"):
        acc, m, l = K.paged_decode_attention(q[:, 0], pk, pv, tables, lo, hi, step_blocks=step_blocks, interpret=mode)
        got = K.merge_own_row(acc, m, l, q[:, 0], k_new[:, 0], v_new[:, 0])[:, None]
    want = gathered(q, k_new, v_new, pk, pv, tables, st, ring)
    assert np.isnan(np.asarray(want)[0]).all() and np.isfinite(np.asarray(want)[1:]).all()
    np.testing.assert_allclose(np.asarray(got)[1:], np.asarray(want)[1:], rtol=2e-5, atol=2e-5)


def test_the_rows_read_are_whole_blocks_of_the_admitted_positions():
    st = jnp.asarray(RING_STARTS, jnp.int32)
    # full: positions 0 .. s - 1 in ceil(s / 16) blocks; window 40: s - 39 .. s - 1, the edge blocks whole
    full = sum(-(-s // BS) for s in RING_STARTS) * BS
    window = sum(((s - 1) // BS - max(s - 39, 0) // BS + 1) * BS for s in RING_STARTS if s)
    assert int(G.rows_read_in_place(st, BS)) == full
    assert int(G.rows_read_in_place(st, BS, WINDOW)) == window
    first, blocks = K.lane_blocks(*G._admitted(st, WINDOW), BS)
    assert list(np.asarray(blocks)) == [0, 1, 1, 3, 3, 3, 4, 3]
    assert list(np.asarray(first)) == [0, 0, 0, 0, 0, 0, 1, 10]


def test_the_merge_of_a_lane_with_no_rows_is_its_own_row():
    q = jnp.ones((1, 4, HD), jnp.float32)
    v = jnp.arange(2 * HD, dtype=jnp.float32).reshape(1, 2, HD)
    out = K.merge_own_row(jnp.zeros((1, 4, HD)), jnp.full((1, 4), K.MASKED), jnp.zeros((1, 4)), q, v, v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(jnp.repeat(v, 2, axis=1)))


LEAF = jax.ShapeDtypeStruct((4, 64, BS, 8, HD), jnp.bfloat16)  # Trinity's geometry: K 8 x hd 128, bf16, 32 KB a block


@pytest.mark.parametrize("edge", ["cpu", "rows", "width", "kv-heads", "int8", "int8-pair", "hd64", "k3", "float32", "mesh"])
def test_the_rule_holds_on_both_sides_of_each_edge(monkeypatch, edge):
    wide = 32  # blocks of 32 KB: 1 MB a lane, the narrowest table read in place
    assert not G.reads_in_place(LEAF, 1, wide)  # the CPU: the gathered path
    monkeypatch.setattr(G, "_on_tpu", lambda: True)
    assert G.reads_in_place(LEAF, 1, wide) and G.reads_in_place(LEAF, 1, 1024)
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype)
    if edge == "cpu":
        monkeypatch.setattr(G, "_on_tpu", lambda: False)
        assert not G.reads_in_place(LEAF, 1, wide)
    elif edge == "rows":  # a chunk, a verify window, a block of several rows
        assert not any(G.reads_in_place(LEAF, t, wide) for t in (2, 4, 32))
    elif edge == "width":  # where the probes' gathered path still won or tied: tables of 512 KB a lane, in blocks of 16 or 32
        assert not G.reads_in_place(LEAF, 1, wide - 1) and not G.reads_in_place(LEAF, 1, 16)
        assert G.reads_in_place(sds((4, 64, 32, 8, HD)), 1, 16) and not G.reads_in_place(sds((4, 64, 32, 8, HD)), 1, 15)
        assert G.MIN_IN_PLACE_TABLE_BYTES == wide * BS * 8 * HD * 2
    elif edge == "kv-heads":  # K 2 (8 KB a block) from 128 blocks, where the probe's kernel first won; K 4 (16 KB) from 64
        assert not G.reads_in_place(sds((4, 64, BS, 2, HD)), 1, 127) and G.reads_in_place(sds((4, 64, BS, 2, HD)), 1, 128)
        assert not G.reads_in_place(sds((4, 64, BS, 4, HD)), 1, 63) and G.reads_in_place(sds((4, 64, BS, 4, HD)), 1, 64)
    elif edge == "int8":
        assert not G.reads_in_place(sds(LEAF.shape, jnp.int8), 1, wide)
    elif edge == "int8-pair":  # (codes, scale), as address_paged_pool_by_layer hands an int8 pool over
        assert not G.reads_in_place((sds(LEAF.shape, jnp.int8), sds(LEAF.shape[:-1])), 1, wide)
    elif edge == "hd64":  # the block axis in the lanes: not whole rows of a block (LFM2's heads packed as 512 are latent)
        assert not G.reads_in_place(sds((4, 64, BS, 16, 64)), 1, wide) and G.reads_in_place(sds((4, 64, BS, 16, 128)), 1, wide)
    elif edge == "k3":  # whole tiles at K 1, 2, 4 and multiples of 8 only
        assert not G.reads_in_place(sds((4, 64, BS, 12, HD)), 1, 1024) and G.reads_in_place(sds((4, 64, BS, 16, HD)), 1, wide)
    elif edge == "float32":
        assert not G.reads_in_place(sds(LEAF.shape, jnp.float32), 1, wide)
    elif edge == "mesh":  # pallas_call takes no part in GSPMD's partitioning
        with jax.set_mesh(Mesh(np.asarray(jax.devices()[:2]), ("x",))):
            assert not G.reads_in_place(LEAF, 1, wide)
        with jax.set_mesh(Mesh(np.asarray(jax.devices()[:1]), ("x",))):
            assert G.reads_in_place(LEAF, 1, wide)


def test_the_kernel_module_is_imported_where_it_is_first_asked_for():
    import subprocess
    import sys

    code = ("import sys, jax.numpy as jnp; from accelerate_tpu.models import afmoe, llama, generation; "
            "assert 'accelerate_tpu.ops.pallas_paged_attention' not in sys.modules; "
            "assert 'jax._src.pallas.pallas_call' not in sys.modules; print('ok')")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300,
                         env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip() == "ok", out.stderr[-2000:]


def test_an_engine_reads_its_lanes_in_place_where_the_rule_says_and_counts_the_rows(monkeypatch):
    """``ServingEngine`` over a bf16 ``afmoe`` of hd 128 x K 2 on one device (``Accelerator().prepare_serving`` would
    install the suite's eight-device mesh, under which the rule gathers), four sliding layers and a full one, the ring
    wrapped: on the CPU nothing is read in place; with the rule's backend test answered as on a TPU (and its table term
    lowered to this model) the decoding lanes go through the kernel (the Pallas TPU interpreter) over both kinds of
    pool and the engine counts what it copied.  The tokens themselves are held to the gathered path one level down
    (``tests/test_afmoe.py``: a tie between bf16 logits of random weights may go either way)."""
    from accelerate_tpu.models import afmoe as af
    from accelerate_tpu.serving import ServingConfig, ServingEngine

    c = af.AfmoeConfig.tiny(num_layers=5, layer_types=(af.SLIDING,) * 4 + (af.FULL,), num_dense_layers=1, head_dim=HD,
                            dtype=jnp.bfloat16, param_dtype=jnp.float32)
    params = af.init_params(c, jax.random.key(1))

    def serve():
        engine = ServingEngine(af.apply_cached, af.init_cache, params, c,
                               ServingConfig(block_size=4, num_blocks=48, max_slots=2, max_blocks_per_seq=16, prefill_chunk=4))
        ids = [engine.submit(np.arange(3, 12), 24), engine.submit(np.arange(40, 45), 6)]
        out = engine.run()
        return [len(out[i]) for i in ids], engine.stats()

    lengths, stats = serve()
    assert stats["attn_rows_read"] == 0
    monkeypatch.setattr(G, "_on_tpu", lambda: True)
    monkeypatch.setattr(G, "MIN_IN_PLACE_TABLE_BYTES", 1)  # tables of 16 blocks of 4 rows of K 2: under the chip's term
    with pltpu.force_tpu_interpret_mode():
        got, stats = serve()
    assert got == lengths and stats["attn_rows_read"] > 0 and stats["attn_rows_read"] % 4 == 0
