"""Serving subsystem: block allocator round-trips, paged gather/scatter
primitives, the continuous-batching scheduler, and the engine's equivalence
oracle — greedy outputs token-identical to the offline ``generate_loop`` per
request across randomized arrival/length mixes, including under forced
preemption and with the int8 KV cache."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from conftest import without_apply_paged

from accelerate_tpu import telemetry
from accelerate_tpu.models import gpt2
from accelerate_tpu.models.generation import (
    dequantize_kv,
    extract_token_rows,
    gather_block_view,
    gather_paged_context,
    make_paged_pool,
    overlay_new_rows,
    paged_cache_write,
    quantize_kv,
    scatter_token_rows,
)
from accelerate_tpu.serving import (
    AdmissionRejected,
    BlockAllocator,
    BlockOutOfMemory,
    JournalError,
    PrefixCache,
    Request,
    ServingConfig,
    ServingEngine,
    ServingJournal,
)
from accelerate_tpu.serving.blocks import NULL_BLOCK, blocks_for_tokens
from accelerate_tpu.serving.scheduler import RequestState, Scheduler

# the family decides the engine's back end: gpt2 has an ``apply_paged``, the wrapper's module has none
GPT2_APPLY_CACHED = {"paged": gpt2.apply_cached, "dense": without_apply_paged(gpt2)}


@pytest.fixture(autouse=True)
def _telemetry_clean():
    yield
    telemetry.disable()
    telemetry.get_telemetry().registry.reset()
    telemetry.get_telemetry().step_timer.reset()


# ---------------------------------------------------------------------------
# Block allocator
# ---------------------------------------------------------------------------


def test_allocator_alloc_free_round_trip():
    alloc = BlockAllocator(9)  # 8 usable + null
    assert alloc.capacity == 8
    a = alloc.alloc(3)
    b = alloc.alloc(2)
    assert len(set(a) | set(b)) == 5 and NULL_BLOCK not in a + b
    assert alloc.used_blocks == 5 and alloc.free_blocks == 3
    alloc.free(a)
    assert alloc.used_blocks == 2 and alloc.free_blocks == 6
    c = alloc.alloc(6)
    assert alloc.free_blocks == 0
    alloc.free(b + c)
    assert alloc.used_blocks == 0 and alloc.occupancy == 0.0


def test_allocator_oom_grants_nothing():
    alloc = BlockAllocator(5)
    alloc.alloc(3)
    free_before = alloc.free_blocks
    with pytest.raises(BlockOutOfMemory):
        alloc.alloc(2)
    assert alloc.free_blocks == free_before  # no partial grant leaked


def test_allocator_double_free_and_null_free_rejected():
    alloc = BlockAllocator(4)
    blocks = alloc.alloc(2)
    alloc.free(blocks)
    with pytest.raises(ValueError):
        alloc.free([blocks[0]])
    with pytest.raises(ValueError):
        alloc.free([NULL_BLOCK])


def test_allocator_fragmentation_free_round_trips():
    """Interleaved alloc/free churn: any free block serves any request
    (fixed-size blocks have no external fragmentation), so after arbitrary
    churn the full capacity is still allocatable in one grant."""
    alloc = BlockAllocator(17)
    rng = np.random.default_rng(0)
    held = []
    for _ in range(200):
        if held and rng.random() < 0.5:
            idx = rng.integers(len(held))
            alloc.free(held.pop(idx))
        else:
            n = int(rng.integers(1, 4))
            if n <= alloc.free_blocks:
                held.append(alloc.alloc(n))
    for blocks in held:
        alloc.free(blocks)
    whole = alloc.alloc(alloc.capacity)  # one grant takes EVERYTHING back
    assert sorted(whole) == list(range(1, 17))


def test_blocks_for_tokens():
    assert blocks_for_tokens(1, 4) == 1
    assert blocks_for_tokens(4, 4) == 1
    assert blocks_for_tokens(5, 4) == 2
    assert blocks_for_tokens(0, 4) == 0


# ---------------------------------------------------------------------------
# Paged primitives (generation.py)
# ---------------------------------------------------------------------------


def _toy_pool(L=2, N=6, bs=4, K=2, hd=3):
    key = jax.random.key(0)
    return jax.random.normal(key, (L, N, bs, K, hd), jnp.float32)


def test_gather_block_view_layout():
    pool = _toy_pool()
    tables = jnp.asarray([[2, 5, 0], [1, 3, 4]], jnp.int32)  # [S=2, M=3]
    view = gather_block_view(pool, tables)
    assert view.shape == (2, 2, 1, 12, 2, 3)  # [S, L, 1, M*bs, K, hd]
    np.testing.assert_array_equal(
        np.asarray(view[0, :, 0, 0:4]), np.asarray(pool[:, 2])
    )
    np.testing.assert_array_equal(
        np.asarray(view[1, :, 0, 4:8]), np.asarray(pool[:, 3])
    )


def test_scatter_then_gather_round_trip():
    pool = jnp.zeros((2, 6, 4, 2, 3), jnp.float32)
    tables = jnp.asarray([[2, 5, 0], [1, 3, 0]], jnp.int32)
    rows = jax.random.normal(jax.random.key(1), (2, 2, 3, 2, 3), jnp.float32)
    start = jnp.asarray([2, 6], jnp.int32)  # slot 0 spans blocks 2->5
    pool2 = scatter_token_rows(pool, rows, tables, start, 3)
    view = gather_block_view(pool2, tables)
    got = extract_token_rows(view, start, 3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(rows))
    # null block (0) untouched regions stay zero for the OTHER slot's view
    np.testing.assert_array_equal(np.asarray(pool2[:, 4]), np.zeros((2, 4, 2, 3)))


def test_scatter_past_table_routes_to_null_block():
    """Positions beyond the block table (chunked-prefill padding) must land
    in the null block, NOT clamp into the last real block."""
    pool = jnp.zeros((1, 4, 4, 1, 1), jnp.float32)
    tables = jnp.asarray([[3, 2]], jnp.int32)  # M=2 -> positions >= 8 overflow
    rows = jnp.ones((1, 1, 4, 1, 1), jnp.float32)
    pool2 = scatter_token_rows(pool, rows, tables, jnp.asarray([6], jnp.int32), 4)
    # positions 6,7 -> block 2 offsets 2,3; positions 8,9 -> null block
    assert float(pool2[0, 2, 2, 0, 0]) == 1.0 and float(pool2[0, 2, 3, 0, 0]) == 1.0
    np.testing.assert_array_equal(np.asarray(pool2[0, 3]), np.zeros((4, 1, 1)))
    assert float(jnp.sum(pool2[0, 1])) == 0.0  # untouched block stays zero


def test_make_paged_pool_rejects_foreign_layout():
    def bad_init(config, batch, max_len):
        return {"k": jnp.zeros((4, max_len)), "index": jnp.zeros((), jnp.int32)}

    with pytest.raises(ValueError, match="make_kv_cache layout"):
        make_paged_pool(bad_init, None, 4, 8)


def test_make_paged_pool_int8_leaves_page_together():
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32, kv_cache_quant=True)
    pool = make_paged_pool(gpt2.init_cache, cfg, 5, 4)
    assert set(pool) == {"k", "k_scale", "v", "v_scale"}
    assert pool["k"].shape[1] == 5 and pool["k"].dtype == jnp.int8
    assert pool["k_scale"].shape == pool["k"].shape[:-1]


def test_paged_cache_write_matches_dense_view_math():
    """The in-dispatch paged context equals the dense per-slot view after a
    cache_write: gather through the tables, overlay the new rows at the
    write position — exactly what attention would have seen, without the
    updated view ever existing."""
    rng = np.random.default_rng(5)
    N, bs, K, hd = 7, 4, 2, 3
    B, M, T = 2, 3, 2
    pool = jnp.asarray(rng.standard_normal((N, bs, K, hd)), jnp.float32)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    starts = jnp.asarray([5, 2], jnp.int32)
    new = jnp.asarray(rng.standard_normal((B, T, K, hd)), jnp.float32)
    stored, full = paged_cache_write(pool, new, tables, starts, jnp.float32)
    np.testing.assert_array_equal(np.asarray(stored), np.asarray(new))
    for b in range(B):
        view = np.asarray(pool[tables[b]]).reshape(M * bs, K, hd).copy()
        s = int(starts[b])
        view[s:s + T] = np.asarray(new[b])
        np.testing.assert_array_equal(np.asarray(full[b]), view)


def test_paged_cache_write_int8_attends_quantized_rows():
    """int8 pools: the overlaid new rows must be the DEQUANTIZED quantized
    codes (the dense path writes codes then dequantizes the whole view) —
    attending raw fp rows would break int8 token identity."""
    rng = np.random.default_rng(6)
    N, bs, K, hd = 5, 4, 2, 3
    pool_f = rng.standard_normal((N, bs, K, hd)).astype(np.float32)
    codes, scale = quantize_kv(jnp.asarray(pool_f.reshape(N * bs, K, hd)))
    pk = (codes.reshape(N, bs, K, hd), scale.reshape(N, bs, K))
    tables = jnp.asarray([[1, 2]], jnp.int32)
    starts = jnp.asarray([3], jnp.int32)
    new = jnp.asarray(rng.standard_normal((1, 1, K, hd)), jnp.float32)
    (n_codes, n_scale), full = paged_cache_write(pk, new, tables, starts, jnp.float32)
    want_row = dequantize_kv(n_codes, n_scale, jnp.float32)[0, 0]
    np.testing.assert_array_equal(np.asarray(full[0, 3]), np.asarray(want_row))
    assert n_codes.dtype == jnp.int8 and n_scale.shape == (1, 1, K)


def _overlay_by_select(ctx, new_rows, starts):
    """The overlay as ``generation._insert_rows`` computed it until PR 29: a
    context-sized index gathered out of the new rows and a select over the
    whole context.  Kept here as the oracle of the row-sized write."""
    b, p = ctx.shape[:2]
    t = new_rows.shape[1]
    rel = jnp.arange(p, dtype=jnp.int32)[None, :] - starts[:, None].astype(jnp.int32)
    tail = (1,) * (ctx.ndim - 2)
    picked = jnp.take_along_axis(new_rows, jnp.clip(rel, 0, t - 1).reshape(b, p, *tail), axis=1)
    return jnp.where(((rel >= 0) & (rel < t)).reshape(b, p, *tail), picked, ctx)


_OVERLAY_STARTS = {  # the first slot's write start, from the extent P and the number of new rows T
    "zero": lambda p, t: 0,
    "mid_block": lambda p, t: 5,
    "last_row": lambda p, t: p - 1,
    "across_extent": lambda p, t: p - t + 3,  # the last three rows fall past the extent (T = 1: all of it)
    "past_extent": lambda p, t: p + 2,
}


@pytest.mark.parametrize("start", list(_OVERLAY_STARTS))
@pytest.mark.parametrize("t", [1, 5, 32])
@pytest.mark.parametrize(
    "leaf,dtype",
    [("kv", "float32"), ("kv", "bfloat16"), ("kv", "int8"), ("latent", "float32"), ("latent", "bfloat16")],
)
def test_new_rows_are_written_where_the_select_put_them(leaf, dtype, t, start):
    """The row-sized write of ``_insert_rows`` gives, bit for bit, the context
    the select over the whole context gave: for a K/V leaf and a latent leaf,
    through ``paged_cache_write`` (int8 too: the dequantised new rows) and
    through ``overlay_new_rows``; rows past the extent are dropped, never
    clamped onto the context's last row."""
    rng = np.random.default_rng(29)
    n, bs, m = 9, 16, 3
    rest = (2, 8) if leaf == "kv" else (24,)
    p = m * bs  # a context of 48 rows
    fp = jnp.float32 if dtype == "int8" else jnp.dtype(dtype)
    pool = jnp.asarray(rng.standard_normal((n, bs) + rest), fp)
    new = jnp.asarray(rng.standard_normal((2, t) + rest), fp)
    tables = jnp.asarray([[4, 1, 7], [2, 8, 0]], jnp.int32)
    starts = jnp.asarray([_OVERLAY_STARTS[start](p, t), 7], jnp.int32)
    if dtype == "int8":
        codes, scale = quantize_kv(pool)
        pool = (codes, scale)
        ctx = dequantize_kv(codes[tables].reshape(2, p, *rest), scale[tables].reshape(2, p, *rest[:-1]), fp)
        new_full = dequantize_kv(*quantize_kv(new), fp)
    else:
        ctx = pool[tables].reshape(2, p, *rest)
        new_full = new
        np.testing.assert_array_equal(np.asarray(gather_paged_context(pool, tables)), np.asarray(ctx))
    want = np.asarray(_overlay_by_select(ctx, new_full, starts))
    _, full = jax.jit(paged_cache_write, static_argnums=4)(pool, new, tables, starts, fp)
    assert full.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(full), want)
    if leaf == "latent":  # the rotated keys: a lane slice of the gathered context, then the overlay
        lanes = slice(8, 16)
        got = jax.jit(overlay_new_rows)(ctx[..., lanes], new[..., lanes], starts)
        np.testing.assert_array_equal(np.asarray(got), want[..., lanes])
    # where the first slot's rows went, said without the oracle
    s0, ctx0, new0, got0 = int(starts[0]), np.asarray(ctx[0]), np.asarray(new_full[0]), np.asarray(full[0])
    kept = min(max(p - s0, 0), t)  # 0: nothing written, the last real row is the gathered one and no clamped new row
    np.testing.assert_array_equal(got0[:s0], ctx0[:s0])
    np.testing.assert_array_equal(got0[s0:s0 + kept], new0[:kept])
    np.testing.assert_array_equal(got0[s0 + kept:], ctx0[s0 + kept:])


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------


def _sched(num_blocks=9, slots=3, bs=4, m=6, chunk=4):
    return Scheduler(
        BlockAllocator(num_blocks), num_slots=slots, block_size=bs,
        max_blocks_per_seq=m, prefill_chunk=chunk,
    )


def test_scheduler_rejects_oversized_requests():
    s = _sched(num_blocks=5, m=3)  # capacity 4, per-seq cap 3
    with pytest.raises(ValueError, match="max_blocks_per_seq"):
        s.submit(Request(list(range(20)), 8))
    with pytest.raises(ValueError, match="pool capacity"):
        _sched(num_blocks=4, m=6).submit(Request(list(range(12)), 4))


def test_scheduler_admits_fifo_and_preempts_lifo():
    s = _sched()
    a, b, c, d = (Request([1, 2, 3], 2) for _ in range(4))
    for r in (a, b, c, d):
        s.submit(r)
    s.admit(now=0.0)
    assert s.active == 3 and s.pending == 1  # FIFO head three admitted
    admitted = [s.slots[i].request for i in sorted(s.slots)]
    assert admitted == [a, b, c]
    idx = s.preempt_one()
    assert s.slots.get(idx) is None
    assert s.queue[0] is c and c.preemptions == 1  # LIFO victim, queue FRONT
    assert s.preempted_count == 1


def test_scheduler_grow_preempts_until_satisfied():
    s = _sched(num_blocks=5, bs=4, chunk=4)  # 4 usable blocks
    old, young = Request([1] * 4, 8), Request([1] * 4, 8)
    s.submit(old), s.submit(young)
    s.admit(now=0.0)
    oi = next(i for i in s.slots if s.slots[i].request is old)
    yi = next(i for i in s.slots if s.slots[i].request is young)
    assert s.grow_to(oi, 8) and s.grow_to(yi, 8)  # 2 blocks each: full pool
    assert s.allocator.free_blocks == 0
    # old grows again: the YOUNG slot must be evicted to find a block
    assert s.grow_to(oi, 12)
    assert yi not in s.slots and young.state == RequestState.QUEUED
    assert len(s.slots[oi].blocks) == 3


def test_scheduler_self_preemption_returns_false():
    s = _sched(num_blocks=3, bs=4, chunk=4, m=6)  # 2 usable blocks
    solo = Request([1] * 4, 4)
    s.submit(solo)
    s.admit(now=0.0)
    idx = next(iter(s.slots))
    assert s.grow_to(idx, 8)  # takes both blocks
    assert not s.grow_to(idx, 12)  # needs a 3rd: only victim is itself
    assert s.active == 0 and s.queue[0] is solo


def test_scheduler_retire_frees_the_lane_and_keeps_the_blocks():
    """A request finishes by count, so the slot whose last token is dispatched
    retires before that token is read: its lane is free for the queue's head
    at once, its blocks stay its own, the scheduler is not idle, and
    ``release`` completes it from wherever it stands."""
    s = _sched(num_blocks=9, slots=1)
    first, second = Request([1] * 4, 2), Request([2] * 4, 2)
    s.submit(first), s.submit(second)
    (idx,) = s.admit(now=0.0)
    assert s.grow_to(idx, 8) and s.allocator.free_blocks == 6
    slot = s.retire(idx)
    assert slot.idx == idx and slot.request is first and s.retiring == [slot] and idx not in s.slots
    assert s.allocator.free_blocks == 6 and first.state == RequestState.PREFILLING  # blocks held, nothing decided yet
    assert s.admit(now=1.0) == [idx] and s.slots[idx].request is second  # the lane is taken while the token is unread
    s.queue.clear()
    assert not s.idle()
    assert s.release(slot, now=2.0) is first and first.state == RequestState.DONE and first.finish_t == 2.0
    assert s.retiring == [] and s.allocator.free_blocks == 8 and s.slots[idx].request is second
    assert s.finish(idx, now=3.0) is second and s.idle()


def test_scheduler_asks_the_engine_to_settle_before_it_evicts():
    """Whatever re-queues a request needs its tokens as values, and a
    read-back returns the retiring lanes' blocks: ``grow_to`` settles on a
    shortage and asks again before it evicts anyone; ``preempt_one`` and
    ``preempt_slot`` settle first however they are called."""
    s = _sched(num_blocks=5, slots=2, bs=4, chunk=4)  # 4 usable blocks
    old, young = Request([1] * 4, 8), Request([1] * 4, 8)
    s.submit(old), s.submit(young)
    oi, yi = s.admit(now=0.0)
    assert s.grow_to(oi, 8) and s.grow_to(yi, 8) and s.allocator.free_blocks == 0
    retired, calls = s.retire(yi), []

    def settle(reason):
        calls.append(reason)
        if retired in s.retiring:  # the tick in flight held the young request's last token
            s.release(retired, now=1.0)
            return True
        return False

    s.settle = settle
    assert s.grow_to(oi, 12) and calls == ["preempt"]  # the read-back freed two blocks: nobody was evicted
    assert s.preempted_count == 0 and young.state == RequestState.DONE and len(s.slots[oi].blocks) == 3
    assert s.preempt_one() == oi and calls == ["preempt"] * 3 and old.state == RequestState.QUEUED  # by preempt_one, then by preempt_slot


# ---------------------------------------------------------------------------
# Engine equivalence (the acceptance oracle)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gpt2_setup():
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
    params = gpt2.init_params(cfg, jax.random.key(0))
    return cfg, params


def _oracle(cfg, params, prompt, max_new):
    out = gpt2.generate(params, jnp.asarray([prompt], jnp.int32), cfg, max_new_tokens=max_new)
    return [int(t) for t in np.asarray(out[0])]


def test_continuous_batching_token_identical_randomized_mix(gpt2_setup):
    """The acceptance criterion: a randomized arrival/length mix through the
    continuous-batching engine produces, for EVERY request, exactly the
    tokens the offline generate_loop produces for that prompt alone."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(42)
    lengths = [int(rng.integers(3, 20)) for _ in range(6)]
    max_new = [int(rng.integers(1, 10)) for _ in range(6)]
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in lengths]
    want = {i: _oracle(cfg, params, p, m) for i, (p, m) in enumerate(zip(prompts, max_new))}

    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=40, max_slots=3,
                              prefill_chunk=8, max_blocks_per_seq=8),
    )
    ids = {}
    arrivals = rng.permutation(6)
    for k, i in enumerate(arrivals):
        ids[eng.submit(prompts[i], max_new[i])] = i
        if k % 2 == 1:
            eng.step()  # staggered: requests join a batch already in flight
    outputs = eng.run(max_ticks=1000)
    assert len(outputs) == 6
    for rid, out in outputs.items():
        assert out == want[ids[rid]], f"request {rid} diverged"
    # the fused decode step stayed at one dispatch per tick
    assert eng.decode_dispatches <= eng.ticks


def test_preemption_keeps_outputs_token_identical(gpt2_setup):
    """A pool tight enough to force eviction mid-flight: preempted requests
    re-prefill prompt+emitted and still finish token-identical."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (5, 11, 9)]
    max_new = [8, 6, 7]
    want = {i: _oracle(cfg, params, p, m) for i, (p, m) in enumerate(zip(prompts, max_new))}
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=9, max_slots=3,
                              prefill_chunk=4, max_blocks_per_seq=6),
    )
    ids = {eng.submit(p, m): i for i, (p, m) in enumerate(zip(prompts, max_new))}
    outputs = eng.run(max_ticks=2000)
    assert eng.sched.preempted_count > 0, "pool was not tight enough to force preemption"
    for rid, out in outputs.items():
        assert out == want[ids[rid]]


@pytest.mark.slow
def test_int8_kv_cache_pages_and_stays_token_identical():
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32, kv_cache_quant=True)
    params = gpt2.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (6, 13)]
    want = {i: _oracle(cfg, params, p, 5) for i, p in enumerate(prompts)}
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=20, max_slots=2,
                              prefill_chunk=8, max_blocks_per_seq=8),
    )
    ids = {eng.submit(p, 5): i for i, p in enumerate(prompts)}
    outputs = eng.run(max_ticks=500)
    for rid, out in outputs.items():
        assert out == want[ids[rid]]


@pytest.mark.slow
def test_llama_family_token_identical():
    """The engine is family-generic: llama's rope/GQA cached decode pages
    and stays token-identical too (tier-2: llama tiny compiles are heavy)."""
    from accelerate_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (5, 9)]
    want = {}
    for i, p in enumerate(prompts):
        out = llama.generate(params, jnp.asarray([p], jnp.int32), cfg, max_new_tokens=4)
        want[i] = [int(t) for t in np.asarray(out[0])]
    eng = ServingEngine(
        llama.apply_cached, llama.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=20, max_slots=2,
                              prefill_chunk=8, max_blocks_per_seq=4),
    )
    ids = {eng.submit(p, 4): i for i, p in enumerate(prompts)}
    outputs = eng.run(max_ticks=200)
    for rid, out in outputs.items():
        assert out == want[ids[rid]]


def test_chunked_prefill_interleaves_with_decode(gpt2_setup):
    """A long prompt admitted while another request decodes: decode ticks
    keep landing between the prefill chunks instead of stalling."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(11)
    short = list(rng.integers(0, cfg.vocab_size, size=4))
    long = list(rng.integers(0, cfg.vocab_size, size=30))
    want_short = _oracle(cfg, params, short, 12)
    want_long = _oracle(cfg, params, long, 3)
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=40, max_slots=2,
                              prefill_chunk=4, max_blocks_per_seq=9),
    )
    sid = eng.submit(short, 12)
    eng.step(); eng.step()  # short is decoding now
    lid = eng.submit(long, 3)  # 30-token prompt = 8 chunks of 4
    decode_before = eng.decode_dispatches
    for _ in range(6):
        eng.step()
    # while the long prompt chewed through its chunks, decode kept running
    assert eng.decode_dispatches - decode_before >= 5
    outputs = eng.run(max_ticks=500)
    assert outputs[sid] == want_short and outputs[lid] == want_long


# ---------------------------------------------------------------------------
# Decode fast path: paged-vs-dense token-identity matrix + prefix caching
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "decode_path",
    ["paged", pytest.param("dense", marks=pytest.mark.slow)],
)
@pytest.mark.parametrize("quant", [False, True])
def test_decode_path_matrix_token_identical(decode_path, quant):
    """The acceptance matrix: paged decode x int8 KV x forced preemption x
    chunked-prefill interleaving stays token-identical to the offline
    generate_loop — and the dense back end (what a family without an
    apply_paged is served on) agrees."""
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32, kv_cache_quant=quant)
    params = gpt2.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(13)
    # A tight pool (8 usable blocks vs 3 slots) forces preemption, and the
    # 11-token prompt takes 3 prefill chunks interleaved with decode.
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (5, 11, 9)]
    max_new = [8, 6, 7]
    want = {i: _oracle(cfg, params, p, m) for i, (p, m) in enumerate(zip(prompts, max_new))}
    eng = ServingEngine(
        GPT2_APPLY_CACHED[decode_path], gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=9, max_slots=3,
                              prefill_chunk=4, max_blocks_per_seq=6),
    )
    assert eng.stats()["decode_path"] == decode_path
    ids = {eng.submit(p, m): i for i, (p, m) in enumerate(zip(prompts, max_new))}
    outputs = eng.run(max_ticks=2000)
    assert eng.sched.preempted_count > 0, "pool was not tight enough to force preemption"
    assert eng.decode_dispatches <= eng.ticks  # still exactly <= 1 dispatch/tick
    for rid, out in outputs.items():
        assert out == want[ids[rid]], f"{decode_path}/int8={quant}: request {rid} diverged"


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry
    (scan and while bodies, pjit and closed calls, branches, kernels)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)  # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("pool_kind", ["fp", "int8", "fp_hd16"])
@pytest.mark.parametrize("family_name", ["gpt2", "llama"])
def test_paged_pool_is_a_constant_of_the_layer_scan(family_name, pool_kind):
    """What the device moves, as far as a jaxpr can say it: ``apply_paged``
    hands the layer scan no pool leaf to slice (its ``xs`` are the layers'
    weights and the layer number); the pool reaches the scan's body as loop
    constants.  As scanned inputs (until PR 27) XLA cut every layer's ``[N,
    bs, ...]`` slice out of the pool and re-tiled it whole, in every layer of
    every dispatch: 47% of the chip's busy time in the chat cell (PERF.md
    section 6, PR 27).

    ``fp`` is a pool the TPU holds block by block (K 2, hd 128, the chat
    cell's): no ``dynamic_slice`` anywhere cuts a ``num_blocks``-sized piece,
    and every gather from the pool reads the flat ``[L*N, ...]`` view through
    tables offset by ``layer * N``.  ``int8`` and ``fp_hd16`` are pools no
    gather reads where they lie (PERF.md section 7.0a): from the flat view
    XLA:TPU would copy the whole pool, so the body cuts its layer out, once
    a leaf, and gathers from that; no operation but that slice takes the
    whole pool.  On the chip the witness is ``serve.layer_loop_share``;
    ``tests/test_tpu_compile.py`` asks the TPU compiler itself."""
    from accelerate_tpu.models import llama

    family, config_cls = {"gpt2": (gpt2, gpt2.GPT2Config), "llama": (llama, llama.LlamaConfig)}[family_name]
    wide = dict(hidden_size=256, num_heads=2) if pool_kind == "fp" else {}
    cfg = config_cls.tiny(dtype=jnp.float32, kv_cache_quant=pool_kind == "int8", **wide)
    num_blocks, block_size, width, slots, chunk = 37, 4, 5, 3, 8
    params = jax.eval_shape(lambda: family.init_params(cfg, jax.random.key(0)))
    pool = jax.eval_shape(lambda: make_paged_pool(family.init_cache, cfg, num_blocks, block_size))
    whole = cfg.num_layers * num_blocks
    pool_sized = {num_blocks, whole}
    assert len(pool) == (4 if pool_kind == "int8" else 2)
    assert pool["k"].shape[-1] == (128 if pool_kind == "fp" else 16)
    assert not any(pool_sized & set(leaf.shape) for leaf in jax.tree.leaves(params))  # the sizes name the pool alone

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    lanes, one_chunk = (i32(slots, 1), i32(slots, width), i32(slots)), (i32(1, chunk), i32(1, width), i32(1))
    shapes = {"decode": (lanes,), "prefill": (one_chunk,), "mixed": (lanes, one_chunk)}
    for what, groups in shapes.items():
        jaxpr = jax.make_jaxpr(lambda p, pl, g: family.apply_paged(p, g, cfg, pl))(params, pool, groups).jaxpr
        scans = [e for e in jaxpr.eqns if e.primitive.name == "scan" and e.params["length"] == cfg.num_layers]
        assert len(scans) == 1, f"{what}: one layer scan expected"
        scan = scans[0]
        first_x = scan.params["num_consts"] + scan.params["num_carry"]
        for var in scan.invars[first_x:]:
            assert not pool_sized & set(var.aval.shape), f"{what}: the scan slices a pool-sized input {var.aval}"
        consts = [v for v in scan.invars[:scan.params["num_consts"]] if v.aval.shape[:2] == (cfg.num_layers, num_blocks)]
        assert len(consts) == len(pool), f"{what}: {len(consts)} of {len(pool)} pool leaves are constants of the loop"
        cut = [v.aval.shape for e in _eqns(jaxpr) if e.primitive.name == "dynamic_slice" for v in e.outvars
               if pool_sized & set(v.aval.shape)]
        takes_whole_pool = [e.primitive.name for e in _eqns(jaxpr)
                            if any(hasattr(v, "aval") and v.aval.shape[:2] == (cfg.num_layers, num_blocks) for v in e.invars)]
        if pool_kind == "fp":
            assert not cut, f"{what}: dynamic_slice cuts {cut} out of the pool"
            read = [v.aval.shape[0] for e in _eqns(jaxpr) if e.primitive.name == "gather"
                    for v in e.invars if hasattr(v, "aval") and pool_sized & set(v.aval.shape[:1])]
            assert read and set(read) == {whole}, f"{what}: reads of the pool lead with {read}, not with L*N"
        else:
            # once a leaf and a group in the jaxpr; the groups' slices are the same operation (XLA keeps one)
            assert sorted(cut) == sorted([(1,) + leaf.shape[1:] for leaf in pool.values()] * len(groups)), f"{what}: {cut}"
            assert set(takes_whole_pool) <= {"scan", "dynamic_slice"}, f"{what}: {takes_whole_pool} take the whole pool"


def test_paged_decode_gather_bytes_scale_with_live_blocks(gpt2_setup):
    """A host count, not a device measurement: ``decode_gather_bytes`` books
    the blocks that the (bucketed) tables of a paged decode *name*, which
    scale with what live requests own, while the dense program books the
    worst-case table.  What the device moves for them is another matter
    (until PR 27 it copied every layer's whole slice of the pool besides):
    ``test_paged_pool_is_a_constant_of_the_layer_scan`` holds the program's
    structure to it, ``serve.layer_loop_share`` reads it on the chip."""
    cfg, params = gpt2_setup

    def gather_per_tick(path):
        eng = ServingEngine(
            GPT2_APPLY_CACHED[path], gpt2.init_cache, params, cfg,
            serving=ServingConfig(block_size=4, num_blocks=40, max_slots=4,
                                  prefill_chunk=8, max_blocks_per_seq=8,
                                  prefix_cache=False),
        )
        eng.submit([1, 2, 3], 6)  # one short request: 1-2 live blocks
        eng.run(max_ticks=200)
        assert eng.decode_dispatches > 0
        return eng.decode_gather_bytes / eng.decode_dispatches, eng

    paged_bytes, eng = gather_per_tick("paged")
    dense_bytes, _ = gather_per_tick("dense")
    block = eng.cache.block_bytes()
    # dense: every slot's full table, live or not (4 slots * 8 blocks)
    assert dense_bytes == 4 * 8 * block
    # paged: the one live slot's owned blocks (<= 2 for 3+6 rows)
    assert paged_bytes <= 2 * block
    snap_stats = eng.stats()
    assert snap_stats["decode_path"] == "paged"
    assert snap_stats["decode_gather_bytes"] == eng.decode_gather_bytes


# -- prefix caching -----------------------------------------------------------


def _prefix_engine(cfg, params, **overrides):
    kw = dict(block_size=4, num_blocks=40, max_slots=2, prefill_chunk=8,
              max_blocks_per_seq=8)
    kw.update(overrides)
    return ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(**kw),
    )


def test_prefix_cache_shares_blocks_and_skips_prefill(gpt2_setup):
    """Two requests sharing a prompt physically share refcounted blocks
    (asserted via allocator accounting), the second request's prefill skips
    the shared prefix entirely, and both outputs are token-identical."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(19)
    prompt = list(rng.integers(0, cfg.vocab_size, size=13))  # 3 full blocks + 1
    want = _oracle(cfg, params, prompt, 4)

    eng = _prefix_engine(cfg, params)
    a = eng.submit(prompt, 4)
    out = eng.run(max_ticks=300)
    assert out[a] == want
    first_prefills = eng.prefill_dispatches
    assert first_prefills == 2  # 13 tokens = 2 chunks of 8
    assert eng.stats()["prefix_cached_blocks"] == 3  # the full prompt blocks
    cached = list(eng._prefix._by_block)

    b = eng.submit(prompt, 4)
    eng.step()  # admit + attach the shared prefix (+ the tail chunk + 1 decode)
    slot = next(iter(eng.sched.slots.values()))
    assert set(slot.blocks[:3]) <= set(cached), "prefix blocks not shared from the cache"
    for blk in slot.blocks[:3]:
        assert eng.cache.allocator.refcount(blk) == 2, "block not physically shared"
    out = eng.run(max_ticks=300)
    assert out[b] == want, "prefix-cached request diverged"
    assert eng.prefill_dispatches == first_prefills + 1  # only the 1-token tail
    assert eng.prefix_hits == 1 and eng.prefix_blocks_reused == 3
    # completion released the slot references; the cache keeps its own
    for blk in cached:
        assert eng.cache.allocator.refcount(blk) == 1
    assert eng.cache.allocator.free_blocks == eng.cache.allocator.capacity


def test_prefix_cow_reuses_partial_tail_block(gpt2_setup):
    """A fully-cached feed still must keep >= 1 token to prefill (the final
    chunk's logits ARE the next token): the partial tail is claimed by
    copying the cached block (COW) and writing continues in the copy — the
    shared block itself is never written."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(23)
    prompt = list(rng.integers(0, cfg.vocab_size, size=12))  # exactly 3 blocks
    want = _oracle(cfg, params, prompt, 4)
    eng = _prefix_engine(cfg, params)
    a = eng.submit(prompt, 4)
    assert eng.run(max_ticks=300)[a] == want
    cached_before = {
        blk: np.asarray(eng.cache.pool["k"][:, blk]).copy()
        for blk in eng._prefix._by_block
    }
    prefills_before = eng.prefill_dispatches
    b = eng.submit(prompt, 4)
    eng.step()
    slot = next(iter(eng.sched.slots.values()))
    # 11 reusable rows: 2 full shared blocks + a COW copy of the third
    assert eng.cow_copies == 1 and eng.prefix_blocks_reused == 3
    assert slot.blocks[2] not in cached_before, "tail was shared, not copied"
    assert eng.run(max_ticks=300)[b] == want, "COW request diverged"
    assert eng.prefill_dispatches == prefills_before + 1  # only the tail token
    for blk, data in cached_before.items():
        np.testing.assert_array_equal(
            np.asarray(eng.cache.pool["k"][:, blk]), data,
        ), "a shared block was written"


def test_prefix_cache_refcounts_round_trip_to_capacity(gpt2_setup):
    """Share/COW/refcount churn round-trips: after N requests sharing one
    prompt complete, cache-held blocks are reclaimable capacity — a full-
    capacity alloc succeeds by evicting them, and conservation holds."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(29)
    prompt = list(rng.integers(0, cfg.vocab_size, size=13))
    eng = _prefix_engine(cfg, params, max_slots=2)
    for _ in range(5):
        eng.submit(prompt, 3)
    eng.run(max_ticks=1000)
    alloc = eng.cache.allocator
    assert eng.prefix_hits >= 3  # slots admitted after the first prefill hit
    assert alloc.free_blocks == alloc.capacity  # cached blocks ARE capacity
    assert alloc.used_blocks == 0
    whole = alloc.alloc(alloc.capacity)  # evicts the cache to serve the grant
    assert sorted(whole) == list(range(1, alloc.num_blocks))
    assert len(eng._prefix) == 0
    alloc.free(whole)
    assert alloc.free_blocks == alloc.capacity


def test_quarantine_never_scrubs_shared_block_under_live_reader(gpt2_setup):
    """Scrub-on-last-release: a quarantined request's shared prefix blocks
    are NOT zeroed while another request still reads them (refcount > 1) —
    the survivor finishes token-identically — and they ARE scrubbed once the
    last reference drops."""
    import os as _os

    from accelerate_tpu.resilience import faultinject

    cfg, params = gpt2_setup
    rng = np.random.default_rng(31)
    prompt = list(rng.integers(0, cfg.vocab_size, size=13))
    want = _oracle(cfg, params, prompt, 6)
    _os.environ["ACCELERATE_TPU_FAULT_SERVING_NAN_REQUEST"] = "3"
    faultinject.reload()
    try:
        eng = _prefix_engine(cfg, params, max_slots=2)
        a = eng.submit(prompt, 6)
        assert eng.run(max_ticks=300)[a] == want
        shared = list(eng._prefix._by_block)
        before = {blk: np.asarray(eng.cache.pool["k"][:, blk]).copy() for blk in shared}
        survivor = eng.submit(prompt, 6)   # submission 2: shares the prefix
        doomed = eng.submit(prompt, 6)     # submission 3: poisoned, shares too
        # Drive until the poisoned request quarantines; the shared blocks
        # must survive untouched while the survivor still reads them.
        statuses = {}
        for _ in range(200):
            for c in eng.step():
                statuses[c.id] = (c.status, c.tokens)
            if doomed in statuses:
                break
        assert statuses[doomed][0] == "quarantined"
        assert survivor not in statuses, "survivor finished before the quarantine"
        for blk in shared:
            if eng.cache.allocator.refcount(blk) > 0:
                np.testing.assert_array_equal(
                    np.asarray(eng.cache.pool["k"][:, blk]), before[blk],
                )
        eng.run(max_ticks=500)
        done = {c.id: c for c in eng.pop_finished()}
        assert done[survivor].status == "ok"
        assert done[survivor].tokens == want, "survivor diverged"
        # quarantine dropped the blocks from the cache (no new sharers) and
        # the last release scrubbed them to zero before reuse
        assert len(eng._prefix) == 0
        for blk in shared:
            assert eng.cache.allocator.refcount(blk) == 0
            assert float(jnp.sum(jnp.abs(eng.cache.pool["k"][:, blk]))) == 0.0
    finally:
        _os.environ.pop("ACCELERATE_TPU_FAULT_SERVING_NAN_REQUEST", None)
        faultinject.reload()


def test_journal_recovery_rehits_prefix_cache(gpt2_setup, tmp_path):
    """Recovered resubmissions flow through the same admission path, so a
    successor serving journaled requests with a shared prefix re-hits its
    prefix cache as soon as the first recovery populates it."""
    cfg, params = gpt2_setup
    jp = str(tmp_path / "journal.json")
    rng = np.random.default_rng(37)
    prompt = list(rng.integers(0, cfg.vocab_size, size=13))
    want = _oracle(cfg, params, prompt, 4)
    eng = _prefix_engine(cfg, params, journal_path=jp)
    for i in range(3):
        eng.submit(prompt, 4, tag=f"t{i}")
    # abandon before any tick (the SIGKILL stand-in); recover in a successor
    succ = _prefix_engine(cfg, params, journal_path=jp, max_slots=1)
    mapping = succ.recover_from_journal()
    assert len(mapping) == 3
    succ.run(max_ticks=1000)
    done = {c.tag: c.tokens for c in succ.pop_finished()}
    assert all(done[f"t{i}"] == want for i in range(3))
    assert succ.prefix_hits >= 2, "recovered siblings did not re-hit the prefix cache"


def test_prefix_cache_unit_lookup_cow_and_eviction():
    """PrefixCache mechanics without an engine: chain-key identity, the
    max_rows cap, the COW tail handoff, LRU eviction of cache-only blocks,
    and the stranded-chain rule (a lookup stops at the first miss)."""
    alloc = BlockAllocator(9)
    cache = PrefixCache(alloc, block_size=4)
    tokens = list(range(12))
    keys = PrefixCache.chain_keys(tokens, 4)
    assert len(keys) == 3 and len(set(keys)) == 3
    # chain identity: same third block tokens after a different prefix
    other = [99] + tokens[1:]
    assert PrefixCache.chain_keys(other, 4)[2] != keys[2]

    blocks = alloc.alloc(3)
    for k, b in zip(keys, blocks):
        assert cache.register(k, b)
    alloc.free(blocks)  # the requester is done; cache keeps them alive
    assert alloc.free_blocks == alloc.capacity and cache.reclaimable_count == 3

    got, rows, cow = cache.lookup(tokens, max_rows=11)
    assert got == blocks[:2] and rows == 8 and cow == blocks[2]
    for b in got + [cow]:
        assert alloc.refcount(b) == 2
    alloc.free(got + [cow])

    # eviction: alloc beyond the free list reclaims LRU cache-only blocks
    grant = alloc.alloc(8)
    assert len(grant) == 8 and len(cache) == 0
    assert cache.lookup(tokens, max_rows=11) == ([], 0, None)
    alloc.free(grant)


def test_allocator_fuzz_shared_block_churn():
    """Allocator fuzz with sharing: random alloc/retain/free interleavings
    keep block conservation (free + held == capacity, each block counted
    once) and the whole pool round-trips to one full grant."""
    alloc = BlockAllocator(17)
    rng = np.random.default_rng(41)
    held = []  # each entry is one reference: (block,)
    for _ in range(400):
        r = rng.random()
        if held and r < 0.35:
            idx = int(rng.integers(len(held)))
            alloc.free([held.pop(idx)])
        elif held and r < 0.55:
            blk = held[int(rng.integers(len(held)))]
            alloc.retain(blk)
            held.append(blk)  # a second reference to the same block
        else:
            n = int(rng.integers(1, 4))
            if n <= alloc.free_blocks:
                held.extend(alloc.alloc(n))
        distinct = len(set(held))
        assert alloc.used_blocks == distinct
        assert alloc.free_blocks + distinct == alloc.capacity, "conservation broke"
    for blk in held:
        alloc.free([blk])
    whole = alloc.alloc(alloc.capacity)
    assert sorted(whole) == list(range(1, 17))


def test_prefix_cache_reclaimable_counter_fuzz():
    """The O(1) incremental reclaimable counter must agree with the O(n)
    refcount scan under random retain/free/register/invalidate/evict
    interleavings — it feeds free_blocks, so drift would either strand
    capacity or let alloc over-promise."""
    from accelerate_tpu.serving.blocks import PrefixCache

    alloc = BlockAllocator(17)
    cache = PrefixCache(alloc, block_size=4)
    rng = np.random.default_rng(43)
    held = []
    key_n = 0
    for _ in range(600):
        r = rng.random()
        if held and r < 0.30:
            alloc.free([held.pop(int(rng.integers(len(held))))])
        elif held and r < 0.45:
            blk = held[int(rng.integers(len(held)))]
            alloc.retain(blk)
            held.append(blk)
        elif held and r < 0.60:
            key_n += 1
            cache.register(bytes([key_n % 256, key_n // 256]), held[int(rng.integers(len(held)))])
        elif cache._by_block and r < 0.70:
            cache.invalidate_blocks([int(rng.integers(1, 17))])
        elif r < 0.78:
            cache.evict(int(rng.integers(1, 3)))
        else:
            n = int(rng.integers(1, 4))
            if n <= alloc.free_blocks:
                held.extend(alloc.alloc(n))
        scan = sum(1 for b in cache._by_block if alloc.refcount(b) == 1)
        assert cache.reclaimable_count == scan, "incremental counter drifted"
        assert alloc.free_blocks + alloc.used_blocks == alloc.capacity
    for blk in held:
        alloc.free([blk])
    # every remaining cached block is reclaimable; one full grant evicts all
    assert cache.reclaimable_count == len(cache._by_block)
    whole = alloc.alloc(alloc.capacity)
    assert sorted(whole) == list(range(1, 17)) and len(cache) == 0


# ---------------------------------------------------------------------------
# Engine API / metrics
# ---------------------------------------------------------------------------


def test_submit_validation_and_zero_max_new(gpt2_setup):
    cfg, params = gpt2_setup
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=20, max_slots=2,
                              prefill_chunk=4, max_blocks_per_seq=8),
    )
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2], -1)
    with pytest.raises(ValueError, match="max_blocks_per_seq"):
        eng.submit(list(range(40)), 10)
    rid = eng.submit([1, 2, 3], 0)
    done = eng.pop_finished()
    assert [c.id for c in done] == [rid] and done[0].tokens == [1, 2, 3]


def test_engine_rejects_geometry_beyond_model_window(gpt2_setup):
    cfg, params = gpt2_setup  # tiny max_seq_len = 128
    with pytest.raises(ValueError, match="max_seq_len"):
        ServingEngine(
            gpt2.apply_cached, gpt2.init_cache, params, cfg,
            serving=ServingConfig(block_size=16, num_blocks=64, max_slots=2),
        )


def test_slo_metrics_publish_through_telemetry(gpt2_setup, tmp_path):
    cfg, params = gpt2_setup
    tel = telemetry.enable(dir=str(tmp_path))
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=40, max_slots=2,
                              prefill_chunk=8, max_blocks_per_seq=8),
    )
    rng = np.random.default_rng(5)
    for n, m in ((5, 4), (9, 3)):
        eng.submit(list(rng.integers(0, cfg.vocab_size, size=n)), m)
    eng.run(max_ticks=500)
    snap = tel.registry.snapshot()
    assert snap["serving.requests"] == 2
    assert snap["serving.completed"] == 2
    assert snap["serving.tokens"] == 7
    assert snap["serving.decode_dispatches"] == eng.decode_dispatches
    assert snap["serving.ttft_ms.count"] == 2 and snap["serving.ttft_ms.p50"] >= 0
    assert snap["serving.queue_wait_ms.count"] == 2
    assert snap["serving.inter_token_ms.count"] == 7 - 2  # non-first tokens
    assert snap["serving.block_occupancy"] == 0.0  # drained
    completions = [c for c in eng.pop_finished()]
    assert all(c.ttft_ms is not None and c.ttft_ms >= 0 for c in completions)
    assert all(c.queue_wait_ms >= 0 for c in completions)
    telemetry.disable()
    events = []
    with open(tel.jsonl_path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") == "event" and rec.get("name") == "serving.request_complete":
                events.append(rec)
    assert len(events) == 2 and all("ttft_ms" in e for e in events)


def test_prepare_serving_entry_point(gpt2_setup):
    from accelerate_tpu.accelerator import Accelerator

    cfg, params = gpt2_setup
    acc = Accelerator()
    eng = acc.prepare_serving(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        block_size=4, num_blocks=20, max_slots=2, prefill_chunk=8,
        max_blocks_per_seq=8,
    )
    assert isinstance(eng, ServingEngine)
    with pytest.raises(ValueError, match="not both"):
        acc.prepare_serving(
            gpt2.apply_cached, gpt2.init_cache, params, cfg,
            serving=ServingConfig(), block_size=4,
        )
    rid = eng.submit([1, 2, 3, 4], 2)
    out = eng.run(max_ticks=200)
    assert len(out[rid]) == 6


# -- graceful drain under a PreemptionGuard -----------------------------------


def _drain_engine(cfg, params, **overrides):
    kw = dict(block_size=4, num_blocks=40, max_slots=2, prefill_chunk=8,
              max_blocks_per_seq=8)
    kw.update(overrides)
    return ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(**kw),
    )


def test_drain_on_preemption_signal(gpt2_setup, tmp_path):
    """An installed PreemptionGuard whose signal arrived makes the next tick
    DRAIN: admission stops, in-flight slots are preempted back to the queue
    with their emitted tokens, blocks are all freed, and the requeue journal
    covers exactly the incomplete requests (serving.drained event)."""
    import os as _os
    import signal as _signal

    from accelerate_tpu.resilience import PreemptionGuard

    cfg, params = gpt2_setup
    tel = telemetry.enable(dir=str(tmp_path))
    eng = _drain_engine(cfg, params)
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (5, 7, 6)]
    ids = [eng.submit(p, 12) for p in prompts]
    for _ in range(6):  # some requests mid-flight, at least one decoding
        eng.step()
    assert eng.sched.active > 0

    guard = PreemptionGuard(signals=(_signal.SIGTERM,), coordinated=False)
    guard.install()
    try:
        eng.install_preemption_guard(guard)
        _os.kill(_os.getpid(), _signal.SIGTERM)
        out = eng.step()  # this tick drains instead of dispatching
        assert out == [] and eng.drained
        assert eng.sched.active == 0, "drain left slots occupied"
        assert eng.cache.allocator.used_blocks == 0, "drain leaked blocks"
        journal = eng.requeue_journal
        completed_ids = {c.id for c in eng._finished}
        assert {r["id"] for r in journal} == set(ids) - completed_ids
        for rec in journal:
            assert rec["remaining"] == 12 - len(rec["emitted"])
            assert rec["prompt"] == prompts[ids.index(rec["id"])]
        # admission is closed, further ticks are inert no-ops
        with pytest.raises(RuntimeError, match="drained"):
            eng.submit([1, 2, 3], 2)
        dispatches_after = eng.decode_dispatches
        assert eng.step() == [] and eng.decode_dispatches == dispatches_after
    finally:
        guard.uninstall()
        telemetry.disable()
    # the serving.drained event landed in the telemetry JSONL
    found = []
    for fname in _os.listdir(tmp_path):
        if not fname.endswith(".jsonl"):
            continue
        with open(tmp_path / fname) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("kind") == "event" and rec.get("name") == "serving.drained":
                    found.append(rec)
    assert len(found) == 1 and found[0]["incomplete"] == len(journal)


def test_drain_journal_resubmission_token_identical(gpt2_setup):
    """The requeue journal is sufficient to finish the work elsewhere: a
    successor engine resubmits prompt+emitted with max_new=remaining and the
    concatenated output is token-identical to the oracle."""
    import os as _os
    import signal as _signal

    from accelerate_tpu.resilience import PreemptionGuard

    cfg, params = gpt2_setup
    rng = np.random.default_rng(23)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (6, 9)]
    max_new = [10, 8]
    want = {i: _oracle(cfg, params, p, m) for i, (p, m) in enumerate(zip(prompts, max_new))}

    eng = _drain_engine(cfg, params)
    ids = {eng.submit(p, m): i for i, (p, m) in enumerate(zip(prompts, max_new))}
    for _ in range(8):
        eng.step()
    guard = PreemptionGuard(signals=(_signal.SIGTERM,), coordinated=False)
    guard.install()
    try:
        eng.install_preemption_guard(guard)
        _os.kill(_os.getpid(), _signal.SIGTERM)
        eng.step()
    finally:
        guard.uninstall()
    assert eng.drained
    done = {ids[c.id]: c.tokens for c in eng._finished}

    successor = _drain_engine(cfg, params)
    rebind = {}
    for rec in eng.requeue_journal:
        rid = successor.submit(rec["prompt"] + rec["emitted"], rec["remaining"])
        rebind[rid] = (ids[rec["id"]], rec)
    out = successor.run(max_ticks=1000)
    # every request finishes exactly once: either pre-drain or via the journal
    assert set(done) | {rebind[rid][0] for rid in out} == set(range(len(prompts)))
    for rid, tokens in out.items():
        i, _rec = rebind[rid]
        assert tokens == want[i], f"request {i} diverged after journal resubmission"
    for i, tokens in done.items():
        assert tokens == want[i]


def test_drain_without_guard_is_manual_and_idempotent(gpt2_setup):
    cfg, params = gpt2_setup
    eng = _drain_engine(cfg, params)
    rid = eng.submit([1, 2, 3, 4, 5], 6)
    eng.step()
    j1 = eng.drain()
    j2 = eng.drain()
    assert j1 is not None and j1 == j2 and eng.drained
    assert [r["id"] for r in j1] == [rid]
    # a drained engine cannot be re-armed: its journal is final
    with pytest.raises(RuntimeError, match="already drained"):
        eng.install_preemption_guard(object())


def test_coordinated_guard_uses_local_flag_not_collective(gpt2_setup):
    """With a multi-host COORDINATED guard the engine must consult the LOCAL
    flag (calling should_stop would gate a cross-host gather on a per-guard
    call counter that engine ticks — data-dependent per host — would
    desynchronize), must NOT drain while no signal arrived, and must drain
    once the local flag is set."""
    from accelerate_tpu.resilience import PreemptionGuard

    cfg, params = gpt2_setup
    eng = _drain_engine(cfg, params)
    guard = PreemptionGuard(coordinated=True)  # never installed: flag-only
    eng.install_preemption_guard(guard)
    rid = eng.submit([1, 2, 3, 4], 8)
    out = eng.step()  # coordinated branch, flag unset -> a normal tick
    assert not eng.drained and eng.sched.active == 1
    guard._flag = True  # the signal handler's only action is setting this
    eng.step()
    assert eng.drained and [r["id"] for r in eng.requeue_journal] == [rid]


# ---------------------------------------------------------------------------
# Overload protection / deadlines / quarantine / journal (serving under fire)
# ---------------------------------------------------------------------------


def _robust_engine(cfg, params, **overrides):
    kw = dict(block_size=4, num_blocks=40, max_slots=2, prefill_chunk=8,
              max_blocks_per_seq=8)
    kw.update(overrides)
    return ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(**kw),
    )


def test_overload_sheds_with_typed_rejection(gpt2_setup, tmp_path):
    """Past max_queue_depth, submit raises AdmissionRejected (serving.shed):
    a burst degrades to load shedding, never unbounded queue growth — and
    already-accepted requests still complete normally."""
    cfg, params = gpt2_setup
    tel = telemetry.enable(dir=str(tmp_path))
    eng = _robust_engine(cfg, params, max_queue_depth=2)
    rng = np.random.default_rng(31)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=5)) for _ in range(4)]
    accepted = [eng.submit(p, 3) for p in prompts[:2]]
    for p in prompts[2:]:
        with pytest.raises(AdmissionRejected, match="max_queue_depth"):
            eng.submit(p, 3)
    assert eng.shed_count == 2
    assert tel.registry.snapshot()["serving.shed"] == 2
    out = eng.run(max_ticks=300)
    assert set(out) == set(accepted)  # shed requests never entered the queue
    # the bound is on QUEUE depth: once the queue drains, admission reopens
    rid = eng.submit(prompts[2], 2)
    assert rid in eng.run(max_ticks=300)


def test_queued_deadline_sheds_before_prefill(gpt2_setup, tmp_path):
    """An already-expired queued request is shed at the next tick WITHOUT
    spending a prefill dispatch, a slot, or any blocks on it; the expiry
    feeds serving.deadline_expired and the TTFT histogram (so the SLO burn
    rate sees the violation, not just the survivors)."""
    cfg, params = gpt2_setup
    tel = telemetry.enable(dir=str(tmp_path))
    eng = _robust_engine(cfg, params)
    rid = eng.submit([1, 2, 3, 4, 5], 4, deadline_ms=0.0)
    prefill_before = eng.prefill_dispatches
    done = eng.step()
    assert [c.id for c in done] == [rid]
    assert done[0].status == "deadline_expired"
    assert eng.prefill_dispatches == prefill_before, "burned a chunk on a corpse"
    assert eng.cache.allocator.used_blocks == 0
    snap = tel.registry.snapshot()
    assert snap["serving.deadline_expired"] == 1
    assert snap["serving.ttft_ms.count"] == 1  # the violation was observed


def test_inflight_deadline_cancels_and_frees_blocks(gpt2_setup):
    """A decoding request whose total deadline passes mid-flight is
    cancelled: blocks freed, slot returned, partial tokens reported with
    status deadline_expired — while a deadline-less neighbor finishes
    normally."""
    import time as _time

    cfg, params = gpt2_setup
    eng = _robust_engine(cfg, params)
    rng = np.random.default_rng(33)
    doomed = eng.submit(list(rng.integers(0, cfg.vocab_size, size=5)), 20,
                        deadline_ms=60.0)
    healthy = eng.submit(list(rng.integers(0, cfg.vocab_size, size=5)), 3)
    eng.step(); eng.step()  # both prefilled, decoding underway
    _time.sleep(0.08)  # blow the doomed request's 60 ms total budget
    out = eng.run(max_ticks=300)
    by_id = {c.id: c for c in eng.pop_finished()}
    assert by_id[doomed].status == "deadline_expired"
    assert by_id[doomed].new_tokens < 20  # cancelled mid-flight
    assert by_id[healthy].status == "ok" and len(out[healthy]) == 5 + 3
    assert eng.cache.allocator.used_blocks == 0, "cancellation leaked blocks"
    assert eng.deadline_expired_count == 1


def test_config_default_deadlines_apply(gpt2_setup):
    cfg, params = gpt2_setup
    eng = _robust_engine(cfg, params, default_deadline_ms=0.0)
    rid = eng.submit([1, 2, 3], 4)  # inherits the config default
    eng.step()
    assert eng.pop_finished()[0].status == "deadline_expired"
    # per-request override beats the default
    eng2 = _robust_engine(cfg, params, default_deadline_ms=0.0)
    rid2 = eng2.submit([1, 2, 3], 2, deadline_ms=60_000.0)
    out = eng2.run(max_ticks=300)
    assert len(out[rid2]) == 5


def test_poisoned_request_quarantined_others_bit_identical(gpt2_setup, tmp_path):
    """The health-guard analog for decode: NaN logits are detected INSIDE
    the fused program, the poisoned request completes with an error status,
    its blocks are scrubbed (0 * NaN = NaN in probs @ v would poison the
    blocks' next owner), and every other request's output is bit-identical
    to the offline oracle."""
    import os as _os

    from accelerate_tpu.resilience import faultinject

    cfg, params = gpt2_setup
    rng = np.random.default_rng(37)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (6, 9, 5)]
    want = {i: _oracle(cfg, params, p, 6) for i, p in enumerate(prompts)}
    _os.environ["ACCELERATE_TPU_FAULT_SERVING_NAN_REQUEST"] = "2"
    faultinject.reload()
    try:
        tel = telemetry.enable(dir=str(tmp_path))
        eng = _robust_engine(cfg, params, max_slots=3)
        ids = {eng.submit(p, 6): i for i, p in enumerate(prompts)}
        eng.run(max_ticks=500)
    finally:
        _os.environ.pop("ACCELERATE_TPU_FAULT_SERVING_NAN_REQUEST", None)
        faultinject.reload()
    done = {ids[c.id]: c for c in eng.pop_finished()}
    assert done[1].status == "quarantined"  # the 2nd submission
    for i in (0, 2):
        assert done[i].status == "ok"
        assert done[i].tokens == want[i], f"survivor {i} diverged"
    assert eng.quarantined_count == 1
    assert eng.cache.allocator.used_blocks == 0
    snap = tel.registry.snapshot()
    assert snap["serving.quarantined"] == 1
    # scrub proof: no non-finite value anywhere in the pool afterwards
    for name, leaf in eng.cache.pool.items():
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert bool(jnp.all(jnp.isfinite(leaf))), name
    # a fresh request reusing the scrubbed blocks still decodes clean
    rid = eng.submit(prompts[0], 6)
    assert eng.run(max_ticks=300)[rid] == want[0]


def test_requeue_wait_histogram_under_forced_preemption(gpt2_setup, tmp_path):
    """Satellite: admit_t records the FIRST admission only, so time spent
    re-queued after a preemption is invisible to queue_wait_ms — the
    serving.requeue_wait_ms histogram records one sample per re-admission."""
    cfg, params = gpt2_setup
    tel = telemetry.enable(dir=str(tmp_path))
    eng = _robust_engine(cfg, params, num_blocks=9, max_slots=3,
                         prefill_chunk=4, max_blocks_per_seq=6)
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (5, 11, 9)]
    for p, m in zip(prompts, (8, 6, 7)):
        eng.submit(p, m)
    eng.run(max_ticks=2000)
    assert eng.sched.preempted_count > 0, "pool was not tight enough"
    snap = tel.registry.snapshot()
    assert snap.get("serving.requeue_wait_ms.count", 0) >= 1, (
        "no re-queue wait sample landed despite forced preemption"
    )
    assert snap["serving.requeue_wait_ms.mean"] >= 0.0


def test_journal_wal_and_recovery_token_identical(gpt2_setup, tmp_path):
    """Write-ahead journal: admissions land on disk before submit returns;
    an ABANDONED engine (the in-process SIGKILL stand-in) leaves a journal
    a successor rebuilds its queue from and finishes token-identically.
    Terminal requests (completed / quarantined / expired) are not replayed."""
    cfg, params = gpt2_setup
    jp = str(tmp_path / "journal.json")
    rng = np.random.default_rng(41)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (5, 8, 11)]
    want = {i: _oracle(cfg, params, p, 5) for i, p in enumerate(prompts)}

    eng = _robust_engine(cfg, params, journal_path=jp)
    ids = {eng.submit(p, 5, tag=f"t{i}"): i for i, p in enumerate(prompts)}
    state = ServingJournal.load(jp)  # WAL: on disk before any tick ran
    assert len(ServingJournal.pending(state)) == 3
    eng.step(); eng.step(); eng.step()  # partial progress, then abandon
    finished_tags = {c.tag for c in eng.pop_finished()}

    succ = _robust_engine(cfg, params, journal_path=jp)
    mapping = succ.recover_from_journal()
    assert set(mapping) == {rid for rid in ids if f"t{ids[rid]}" not in finished_tags}
    succ.run(max_ticks=500)
    done = {c.tag: c.tokens for c in succ.pop_finished()}
    for old_id, i in ids.items():
        if f"t{i}" in finished_tags:
            continue
        assert done[f"t{i}"] == want[i], f"recovered request {i} diverged"
    # completed requests are terminal in the successor's journal too
    state2 = ServingJournal.load(jp)
    assert not ServingJournal.pending(state2)
    # double-recovery guard: the successor already overwrote the journal
    with pytest.raises(JournalError, match="before the first submit"):
        succ.recover_from_journal()


def test_recovery_bypasses_queue_bound(gpt2_setup, tmp_path):
    """Review-found: recovery resubmits through submit(), so a successor
    sharing the predecessor's max_queue_depth would SHED journaled requests
    past the bound — silently losing acknowledged work (a drained engine's
    backlog legally exceeds the queue depth: its in-flight slots requeue).
    A dead engine's backlog is not a traffic burst; recovery must admit it
    all."""
    cfg, params = gpt2_setup
    jp = str(tmp_path / "journal.json")
    rng = np.random.default_rng(47)
    eng = _robust_engine(cfg, params, journal_path=jp, max_queue_depth=None)
    n = 5
    for i in range(n):
        eng.submit(list(rng.integers(0, cfg.vocab_size, size=4)), 2, tag=f"t{i}")
    # abandon with all 5 pending; successor has a bound SMALLER than that
    succ = _robust_engine(cfg, params, journal_path=jp, max_queue_depth=2)
    mapping = succ.recover_from_journal()
    assert len(mapping) == n, "recovery shed journaled requests at the queue bound"
    out = succ.run(max_ticks=500)
    assert len(out) == n
    # the bound still applies to NEW traffic after recovery
    for i in range(2):
        succ.submit([1, 2, 3], 2)
    with pytest.raises(AdmissionRejected):
        succ.submit([1, 2, 3], 2)


def test_journal_deferred_batches_into_one_atomic_flush(tmp_path):
    """Review-found: recovery must not overwrite the predecessor's journal
    until EVERY pending request is re-journaled — deferred() holds all
    mutations for one atomic os.replace, so a SIGKILL mid-recovery leaves
    the predecessor's complete file, never a partial successor one."""
    jp = str(tmp_path / "journal.json")
    old = ServingJournal(jp)
    old.record_admit(Request([1, 2, 3], 4, tag="a"))
    old.record_admit(Request([4, 5], 3, tag="b"))
    before = open(jp).read()
    new = ServingJournal(jp)
    with new.deferred():
        new.record_admit(Request([1, 2, 3], 4, tag="a2"))
        # mid-batch: the predecessor's file is untouched on disk
        assert open(jp).read() == before
        assert not new.flushed
        new.record_admit(Request([4, 5], 3, tag="b2"))
    state = ServingJournal.load(jp)
    assert {r["tag"] for r in ServingJournal.pending(state)} == {"a2", "b2"}
    assert new.flushed


def test_scrub_covers_null_block(gpt2_setup):
    """Review-found: a poisoned request's padded prefill rows scatter PAST
    its block table into the shared null block, so quarantine must scrub
    block 0 too — NaN there would reach every slot's gathered view (and
    0 * NaN = NaN in probs @ v ignores the mask's zero probability)."""
    cfg, params = gpt2_setup
    eng = _robust_engine(cfg, params)
    name = next(n for n, leaf in eng.cache.pool.items()
                if jnp.issubdtype(leaf.dtype, jnp.floating))
    leaf = eng.cache.pool[name]
    poisoned = jnp.full(leaf.shape[2:], jnp.nan, leaf.dtype)
    eng.cache.pool[name] = leaf.at[:, NULL_BLOCK].set(poisoned).at[:, 3].set(poisoned)
    eng._scrub_blocks([3])
    for n, leaf in eng.cache.pool.items():
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert bool(jnp.all(jnp.isfinite(leaf))), n


def test_journal_load_rejects_missing_torn_and_newer(tmp_path):
    with pytest.raises(JournalError, match="no journal"):
        ServingJournal.load(str(tmp_path / "absent.json"))
    torn = tmp_path / "torn.json"
    torn.write_text('{"version": 1, "requests": {"0": ')
    with pytest.raises(JournalError, match="unreadable"):
        ServingJournal.load(str(torn))
    newer = tmp_path / "newer.json"
    newer.write_text(json.dumps({"version": 99, "requests": {}, "done": {}}))
    with pytest.raises(JournalError, match="schema version"):
        ServingJournal.load(str(newer))


def test_sigkill_successor_finishes_from_journal_alone(gpt2_setup, tmp_path):
    """Acceptance criterion: a SIGKILLed engine's successor, rebuilt from
    the persisted journal ALONE (no drain ran, no handler, no atexit),
    completes every in-flight request token-identically (subprocess, the
    flightrec-smoke pattern)."""
    import os as _os
    import signal as _signal
    import subprocess
    import sys as _sys

    cfg, params = gpt2_setup
    jp = str(tmp_path / "journal.json")
    rng = np.random.default_rng(43)
    prompts = [
        [int(t) for t in rng.integers(0, cfg.vocab_size, size=n)]
        for n in (6, 10)
    ]
    want = {i: _oracle(cfg, params, p, 5) for i, p in enumerate(prompts)}

    script = f"""
import json, os, signal
import jax, jax.numpy as jnp
from accelerate_tpu.models import gpt2
from accelerate_tpu.serving import ServingConfig, ServingEngine

cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
params = gpt2.init_params(cfg, jax.random.key(0))
eng = ServingEngine(
    gpt2.apply_cached, gpt2.init_cache, params, cfg,
    serving=ServingConfig(block_size=4, num_blocks=40, max_slots=2,
                          prefill_chunk=8, max_blocks_per_seq=8,
                          journal_path={jp!r}),
)
for i, p in enumerate({prompts!r}):
    eng.submit(p, 5, tag=f"t{{i}}")
for _ in range(3):
    eng.step()
os.kill(os.getpid(), signal.SIGKILL)  # no handler, no drain, no atexit
"""
    env = dict(_os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "ACCELERATE_TPU_COMPILE_CACHE": "",
                "ACCELERATE_TPU_SENTINEL_PROFILE": "0"})
    env.pop("XLA_FLAGS", None)  # token identity needs the parent's device layout
    proc = subprocess.run([_sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == -_signal.SIGKILL, (proc.returncode, proc.stderr)

    succ = _robust_engine(cfg, params, journal_path=jp)
    mapping = succ.recover_from_journal()
    succ.run(max_ticks=500)
    done = {c.tag: c for c in succ.pop_finished()}
    assert set(done) == {"t0", "t1"} and len(mapping) == 2
    for i in range(2):
        assert done[f"t{i}"].status == "ok"
        assert done[f"t{i}"].tokens == want[i], (
            f"request {i} not token-identical after SIGKILL recovery"
        )


def test_fuzz_admission_deadline_preemption_shed_interleavings(gpt2_setup):
    """Satellite: randomized interleavings of admission x deadlines x forced
    preemption x shed.  Invariants: the allocator's free count round-trips
    to its initial value (block conservation) and every request reaches a
    terminal state within the tick bound (the LIFO victim policy cannot
    livelock the oldest request)."""
    cfg, params = gpt2_setup
    for seed in (0, 1, 2):
        rng = np.random.default_rng(seed)
        eng = _robust_engine(cfg, params, num_blocks=11, max_slots=3,
                             prefill_chunk=4, max_blocks_per_seq=6,
                             max_queue_depth=3)
        capacity = eng.cache.allocator.capacity
        submitted, shed = [], 0
        for k in range(10):
            prompt = list(rng.integers(0, cfg.vocab_size, size=int(rng.integers(3, 12))))
            max_new = int(rng.integers(1, 6))
            deadline = [None, None, 0.0, 40.0][int(rng.integers(4))]
            try:
                submitted.append(eng.submit(prompt, max_new, deadline_ms=deadline))
            except AdmissionRejected:
                shed += 1
            for _ in range(int(rng.integers(0, 3))):
                eng.step()
            if eng.sched.slots and rng.random() < 0.3:
                eng.sched.preempt_one()  # adversarial forced preemption
        eng.run(max_ticks=2000)  # raises on livelock (no drain in bound)
        done = eng.pop_finished()
        assert {c.id for c in done} == set(submitted), (
            f"seed {seed}: starved requests "
            f"{set(submitted) - {c.id for c in done}}"
        )
        assert eng.cache.allocator.free_blocks == capacity, (
            f"seed {seed}: leaked {capacity - eng.cache.allocator.free_blocks} blocks"
        )
        assert eng.shed_count == shed


def test_shed_and_deadline_counters_exposed_via_prometheus(gpt2_setup):
    """Satellite: the new robustness counters exist in the registry from
    engine construction (a dashboard can rate() them before the first
    incident) and render through the Prometheus exposition."""
    from accelerate_tpu.telemetry.export import render_prometheus

    cfg, params = gpt2_setup
    tel = telemetry.enable()
    _robust_engine(cfg, params)
    text = render_prometheus(tel.registry)
    for stem in (
        "serving_shed", "serving_deadline_expired", "serving_quarantined",
        "serving_prefix_hits", "serving_prefix_blocks_reused",
        "serving_prefix_cow_copies", "serving_decode_gather_bytes",
    ):
        assert f"accelerate_tpu_{stem}_total 0" in text, stem


def test_prepare_serving_wires_installed_guard(gpt2_setup, tmp_path):
    from accelerate_tpu.accelerator import Accelerator

    cfg, params = gpt2_setup
    acc = Accelerator()
    guard = acc.enable_preemption_handling(save_dir=str(tmp_path / "ckpt"))
    try:
        eng = acc.prepare_serving(
            gpt2.apply_cached, gpt2.init_cache, params, cfg,
            block_size=4, num_blocks=20, max_slots=2, prefill_chunk=8,
            max_blocks_per_seq=8,
        )
        assert eng._preemption_guard is guard
    finally:
        guard.uninstall()
        acc._preemption_guard = None
