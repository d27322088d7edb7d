"""``ops/pallas_paged_index.py`` (the decoding lanes' index scores read where the
``ki`` leaf lies) through the Pallas interpreter on the CPU, against the
gathered path it replaces (``generation.gather_paged_context`` with the new row
overlaid, then ``keye_vl2._index_scores``), and the rule that says where it runs
(``keye_vl2.index_reads_in_place``).  Nothing here is a time: the kernel's speed
is a chip run's (``PERF.md`` section 6), Mosaic's verdict on it
``tests/test_tpu_compile.py``'s.  The model's logits and counters with the
kernel are ``tests/test_keye_vl2.py``'s."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from accelerate_tpu.models import generation as G
from accelerate_tpu.models import keye_vl2 as kv
from accelerate_tpu.ops import pallas_paged_index as K

BS, WIDTH, HEADS, TOPK = 16, 16, 4, 32
CONFIG = kv.KeyeVl2Config.tiny(index_num_heads=HEADS, index_topk=TOPK)  # index keys of 64, two layers a row of 128
# idle lanes (position 0, nothing in the pool), a lane under the top-k at a block's first row, inside its first block,
# at its last row, one past it; lanes past the top-k ending inside a block and at the table's last row
STARTS = [0, 1, 15, 16, 17, 0, 40, 100, 255]


def case(slot, seed=0):
    """(queries laid into ``slot``, weights, the lanes' own new rows laid into it, the leaf's view and the tables offset
    to group 1 of two, as ``address_paged_leaf_by_layer`` hands them over, starts): bf16 keys and queries, every block
    of the leaf random, the null block among them, each lane's blocks scattered over the leaf."""
    rng = np.random.default_rng(seed)
    b, n = len(STARTS), 1 + len(STARTS) * WIDTH
    bf16 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    leaf = bf16(2, n, BS, 2 * CONFIG.index_head_dim)
    tables = np.zeros((b, WIDTH), np.int32)  # entries past a lane's end name the null block
    ids = rng.permutation(np.arange(1, n))
    for lane, s in enumerate(STARTS):
        owned = min(WIDTH, s // BS + 1)
        tables[lane, :owned] = ids[lane * WIDTH : lane * WIDTH + owned]
    view, offset = G.address_paged_leaf_by_layer(leaf, jnp.asarray(tables), jnp.int32(1))
    qi = kv._into_slot(bf16(b, 1, HEADS, CONFIG.index_head_dim), slot, CONFIG)
    w = jnp.asarray(rng.standard_normal((b, 1, HEADS)), jnp.float32)
    ki_new = kv._into_slot(bf16(b, 1, CONFIG.index_head_dim), slot, CONFIG)
    return qi, w, ki_new, view, offset, jnp.asarray(STARTS, jnp.int32)


def top(scores, starts):
    seen = jnp.arange(scores.shape[1])[None, :] <= starts[:, None]
    return np.asarray(jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), TOPK)[1])


@pytest.mark.parametrize("interpret", ["hlo", "tpu"], ids=["interpret", "interpret-params"])
@pytest.mark.parametrize("step_blocks", [0, 8])
@pytest.mark.parametrize("slot", [0, 1])
def test_the_kernels_scores_and_selection_are_the_gathered_paths(slot, step_blocks, interpret):
    """Both layers of a packed row, a lane's blocks in one step and in two (a step of 8 blocks, the last one partial
    or whole), under the interpreter whose fresh memory reads NaN too: the scores at every position a lane sees are
    the gathered path's to float32 round-off, ``MASKED`` past it, and ``lax.top_k`` names the same rows."""
    qi, w, ki_new, view, offset, starts = case(slot, seed=slot)
    mode = True if interpret == "hlo" else pltpu.InterpretParams()
    with jax.default_matmul_precision("highest"):
        want = kv._index_scores(qi, w, G._insert_rows(G.gather_paged_context(view, offset), ki_new, starts))[:, 0]
        if step_blocks:  # the kernel alone: the own row is its caller's, held at the default step
            lanes = jnp.arange(len(STARTS))
            got = K.paged_index_scores(qi[:, 0], w[:, 0], view, offset, *G._admitted(starts, 0), step_blocks=step_blocks,
                                       interpret=mode).at[lanes, starts].set(want[lanes, starts])
        else:
            got = kv._index_scores_in_place(qi, w, ki_new, view, offset, starts, mode)
    assert got.shape == want.shape == (len(STARTS), WIDTH * BS) and got.dtype == jnp.float32
    seen = np.arange(WIDTH * BS)[None, :] <= np.asarray(STARTS)[:, None]
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got[seen], want[seen], rtol=1e-5, atol=1e-5 * np.abs(want[seen]).max())
    assert (got[~seen] == K.MASKED).all()
    assert (top(jnp.asarray(got), starts) == top(jnp.asarray(want), starts)).all()


def test_a_lane_sees_only_its_positions_and_copies_whole_rows_of_scores():
    """``lo`` past 0 (the kernel's contract, no caller's today): positions under it are ``MASKED`` though their block
    is copied, the walk starting at a whole row of 128 scores; a lane with ``hi < lo`` copies nothing."""
    qi, w, _, view, offset, _ = case(0, seed=3)
    lo = jnp.asarray([0, 0, 3, 5, 130, 0, 20, 60, 140], jnp.int32)
    hi = jnp.asarray(STARTS, jnp.int32) - 1
    with jax.default_matmul_precision("highest"):
        got = np.asarray(K.paged_index_scores(qi[:, 0], w[:, 0], view, offset, lo, hi, step_blocks=8, interpret=True))
        want = np.asarray(kv._index_scores(qi, w, G.gather_paged_context(view, offset))[:, 0])
    pos = np.arange(WIDTH * BS)[None, :]
    sees = (pos >= np.asarray(lo)[:, None]) & (pos <= np.asarray(hi)[:, None])
    np.testing.assert_allclose(got[sees], want[sees], rtol=1e-5, atol=1e-5 * np.abs(want).max())
    assert (got[~sees] == K.MASKED).all()
    first, blocks = K.lane_walk(lo, hi, BS)
    assert list(np.asarray(first)) == [0, 0, 0, 0, 8, 0, 0, 0, 8]
    assert list(np.asarray(blocks)) == [0, 1, 1, 1, 0, 0, 3, 7, 8]  # 130 > 16: that lane sees nothing


@pytest.mark.parametrize("bs", [3, 48, 256])
def test_a_block_that_is_no_whole_part_of_a_row_of_scores_is_refused(bs):
    args = (jnp.zeros((1, HEADS, 128)), jnp.zeros((1, HEADS)), jnp.zeros((4, bs, 128)), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match="whole part of a row"):
        K.paged_index_scores(*args, interpret=True)


LEAF = jax.ShapeDtypeStruct((2, 4096, BS, 128), jnp.bfloat16)  # the cell's ki leaf: 4 KB a block


@pytest.mark.parametrize("edge", ["cpu", "rows", "width", "block", "lanes", "float32", "mesh"])
def test_the_rule_holds_on_both_sides_of_each_edge(monkeypatch, edge):
    wide = 256  # blocks of 4 KB: 1 MB a lane, the narrowest table scored in place
    assert not kv.index_reads_in_place(LEAF, 1, wide)  # the CPU: the gathered path
    monkeypatch.setattr(G, "_on_tpu", lambda: True)
    assert kv.index_reads_in_place(LEAF, 1, wide) and kv.index_reads_in_place(LEAF, 1, 2048)
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype)
    if edge == "cpu":
        monkeypatch.setattr(G, "_on_tpu", lambda: False)
        assert not kv.index_reads_in_place(LEAF, 1, 2048)
    elif edge == "rows":  # a chunk, a block of several rows
        assert not any(kv.index_reads_in_place(LEAF, t, wide) for t in (2, 4, 32))
    elif edge == "width":  # tables under 1 MB a lane keep the gather
        assert not kv.index_reads_in_place(LEAF, 1, wide - 1) and not kv.index_reads_in_place(LEAF, 1, 128)
        assert kv.index_reads_in_place(sds((2, 64, 32, 128)), 1, 128) and not kv.index_reads_in_place(sds((2, 64, 32, 128)), 1, 127)
        assert G.MIN_IN_PLACE_TABLE_BYTES == wide * BS * 128 * 2
    elif edge == "block":  # whole (16, 128) tiles, a whole part of a row of 128 scores
        assert not any(kv.index_reads_in_place(sds((2, 64, bs, 128)), 1, 4096) for bs in (4, 8, 24, 48, 256))
        assert all(kv.index_reads_in_place(sds((2, 64, bs, 128)), 1, 4096) for bs in (16, 32, 64, 128))
    elif edge == "lanes":  # a row of one index head of 64, or of 96: not whole lanes
        assert not kv.index_reads_in_place(sds((2, 64, BS, 64)), 1, 4096)
        assert not kv.index_reads_in_place(sds((2, 64, BS, 96)), 1, 4096) and kv.index_reads_in_place(sds((2, 64, BS, 256)), 1, wide)
    elif edge == "float32":
        assert not kv.index_reads_in_place(sds(LEAF.shape, jnp.float32), 1, wide)
    elif edge == "mesh":  # pallas_call takes no part in GSPMD's partitioning
        with jax.set_mesh(Mesh(np.asarray(jax.devices()[:2]), ("x",))):
            assert not kv.index_reads_in_place(LEAF, 1, wide)
        with jax.set_mesh(Mesh(np.asarray(jax.devices()[:1]), ("x",))):
            assert kv.index_reads_in_place(LEAF, 1, wide)


def test_the_kernel_module_is_imported_where_it_is_first_asked_for():
    import os
    import subprocess
    import sys

    code = ("import sys; from accelerate_tpu.models import keye_vl2; "
            "assert 'accelerate_tpu.ops.pallas_paged_index' not in sys.modules; "
            "assert 'jax._src.pallas.pallas_call' not in sys.modules; print('ok')")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip() == "ok", out.stderr[-2000:]


def test_an_engine_scores_its_lanes_in_place_where_the_rule_says_and_counts_the_rows(monkeypatch):
    """``ServingEngine`` over a bf16 ``keye_vl2`` in blocks of 16 rows on one device: on the CPU the lanes gather their
    index keys; with the rule's backend test answered as on a TPU (and its table term lowered to this model) the
    decoding lanes score them through the kernel (the Pallas TPU interpreter) and the engine counts what it copied,
    whole blocks of every layer.  The tokens themselves are held to the gathered path one level down
    (``tests/test_keye_vl2.py``: a tie between bf16 logits of random weights may go either way)."""
    from accelerate_tpu.serving import ServingConfig, ServingEngine

    c = kv.KeyeVl2Config.tiny(dtype=jnp.bfloat16)
    params = kv.init_params(c, jax.random.key(1))

    def serve():
        engine = ServingEngine(kv.apply_cached, kv.init_cache, params, c,
                               ServingConfig(block_size=BS, num_blocks=40, max_slots=2, max_blocks_per_seq=16, prefill_chunk=8))
        ids = [engine.submit(np.arange(3, 20), 8), engine.submit(np.arange(40, 45), 4)]
        out = engine.run()
        return [len(out[i]) for i in ids], engine.stats()

    lengths, stats = serve()
    assert stats["attn_rows_read"] == 0
    monkeypatch.setattr(G, "_on_tpu", lambda: True)
    monkeypatch.setattr(G, "MIN_IN_PLACE_TABLE_BYTES", 1)  # tables of 16 blocks of 4 KB: under the chip's term
    with pltpu.force_tpu_interpret_mode():
        got, stats = serve()
    assert got == lengths and stats["attn_rows_read"] > 0 and stats["attn_rows_read"] % (c.num_layers * BS) == 0
