"""Token rows of two kinds in one pool (PR 38): a family whose cache holds
**window** leaves (``generation.WINDOW``; ``models/afmoe.py``'s sliding layers)
keeps of them a ring of ``window_ring_blocks`` blocks a sequence, beside the
full leaves' block a ``block_size`` rows.  What is held here: the ring's bound
at any context and a short sequence's few blocks; admission, growth,
preemption and release by kind, both allocators back where they started;
served tokens identical to ``generate``; what a dispatch's window tables are
(``width_window`` on the ``serving.tick`` span); ``stats()`` by kind; the
refusals and their reason.  Tiny float32 preset: window 8, blocks of 4, chunks
of 4, so the ring is 4 blocks = 16 rows and a reply of 60 rows wraps it three
times."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import afmoe as af
from accelerate_tpu.models.generation import WINDOW, window_ring_blocks
from accelerate_tpu.serving import ServingConfig, ServingEngine
from accelerate_tpu.serving.blocks import BlockAllocator
from accelerate_tpu.serving.scheduler import Request, Scheduler

from conftest import recorded_spans, without_apply_paged

BLOCK, CHUNK, SLOTS, RING = 4, 4, 3, 4


@pytest.fixture(scope="module")
def model():
    c = af.AfmoeConfig.tiny(
        num_layers=5, layer_types=(af.SLIDING,) * 4 + (af.FULL,), experts_held=(2, 4), dtype=jnp.float32, param_dtype=jnp.float32)
    params = af.init_params(c, jax.random.key(38))
    params["moe"]["router_bias"] = 0.1 * jax.random.normal(jax.random.key(39), params["moe"]["router_bias"].shape)
    return c, params


def engine(model, **kw):
    c, params = model
    serving = dict(block_size=BLOCK, num_blocks=64, max_slots=SLOTS, max_blocks_per_seq=16, prefill_chunk=CHUNK)
    serving.update(kw)
    return ServingEngine(af.apply_cached, af.init_cache, params, c, ServingConfig(**serving))


def prompts(sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n, dtype=np.int32) for n in sizes]


def oracle(model, prompt, new):
    c, params = model
    return np.asarray(af.generate(params, jnp.asarray(prompt)[None], c, new))[0]


def test_the_ring_follows_from_window_chunk_and_block():
    assert window_ring_blocks(8, 4, 4) == RING and window_ring_blocks(4096, 32, 16) == 259 and window_ring_blocks(4096, 64, 16) == 261
    assert window_ring_blocks(1, 1, 16) == 2


def test_pool_of_two_kinds_and_its_accounting(model):
    eng = engine(model)
    full, window = eng.cache.allocator, eng.cache.window_allocator
    assert eng._ring_blocks == RING and window.num_blocks == SLOTS * RING + 1 and full.num_blocks == 64
    assert eng.cache.pool["k"].shape == (1, 64, BLOCK, 2, 16) and eng.cache.pool[WINDOW]["k"].shape == (4, 13, BLOCK, 2, 16)
    assert sorted(eng.cache.token_leaves()) == ["k", "v"] and sorted(eng.cache.window_leaves()) == ["k", "v"]
    stats = eng.stats()
    row = 2 * 2 * 16 * 4  # K and V of one row of one layer, float32
    assert stats["pool_bytes_by_kind"] == {"full": 64 * BLOCK * row, "window": 4 * 13 * BLOCK * row}
    assert stats["pool_bytes"] == stats["pool_bytes_by_kind"]["full"]
    assert stats["free_pool_bytes_by_kind"] == {"full": 63 * BLOCK * row, "window": 4 * 12 * BLOCK * row}
    assert stats["full_blocks_in_use"] == stats["window_blocks_in_use"] == 0 and stats["window_ring_blocks"] == RING
    assert "window leaves" in stats["prefix_cache_off"] and eng._prefix is None and stats["decode_path"] == "paged"
    from accelerate_tpu.telemetry.memledger import get_memory_ledger

    owners = {r.owner: r for r in get_memory_ledger().owners()}
    assert owners["serving.kv_window_pool"].device_bytes == stats["pool_bytes_by_kind"]["window"]
    assert owners["serving.kv_pool"].device_bytes == stats["pool_bytes_by_kind"]["full"]
    # a smaller pool bounds the window kind too: its size follows from num_blocks, max_slots and the ring, no option
    assert engine(model, num_blocks=10).cache.window_allocator.num_blocks == 10


def test_a_long_lane_holds_the_ring_and_a_short_one_what_it_has_rows_for(model):
    eng = engine(model)
    long_p, short_p = prompts([9, 5])
    rid_long, rid_short = eng.submit(long_p, 50), eng.submit(short_p, 6)
    seen = {rid_long: [], rid_short: []}
    while not eng.sched.idle():
        eng.step()
        for slot in eng.sched.slots.values():
            assert len(slot.window_blocks) == min(len(slot.blocks), RING)
            assert len(slot.blocks) * BLOCK >= slot.cache_len
            seen[slot.request.id].append((len(slot.blocks), len(slot.window_blocks)))
    assert max(seen[rid_long]) == (15, RING)  # 58 rows: 15 full blocks, the ring's four on the window leaves
    assert max(seen[rid_short]) == (3, 3)  # 10 rows: three blocks of either kind, not a ring reserved whole
    out = {c.id: c.tokens for c in eng.pop_finished()}
    assert (np.asarray(out[rid_long]) == oracle(model, long_p, 50)).all()
    assert (np.asarray(out[rid_short]) == oracle(model, short_p, 6)).all()
    stats = eng.stats()
    assert stats["full_blocks_in_use"] == stats["window_blocks_in_use"] == 0
    assert eng.cache.allocator.free_blocks == 63 and eng.cache.window_allocator.free_blocks == SLOTS * RING
    assert 0 < stats["window_rows_read"] < stats["context_rows"] and stats["moe_rows"] < stats["moe_pairs_routed"]


def test_tokens_identical_to_generate_under_load_with_chunks_riding(model):
    eng = engine(model)
    sizes, news = [5, 13, 30, 7, 21, 3], [40, 30, 20, 50, 9, 33]
    feeds = prompts(sizes, 1)
    rids = [eng.submit(p, n) for p, n in zip(feeds, news)]
    with recorded_spans() as spans:
        outs = eng.run()
    for rid, p, n in zip(rids, feeds, news):
        assert (np.asarray(outs[rid]) == oracle(model, p, n)).all(), rid
    stats = eng.stats()
    assert stats["mixed_dispatches"] > 0 and stats["pipelined_ticks"] > 0 and stats["preempted"] == 0
    ticks = [s.meta for s in spans if s.name == "serving.tick" and s.meta.get("width")]
    assert ticks and all(t["width_window"] == min(t["width"], RING) for t in ticks)
    assert {t["width"] for t in ticks} >= {1, 2, 4, 8, 16} and max(t["width_window"] for t in ticks) == RING
    assert stats["decode_bucket_widths"][-1] == 16


def test_preemption_under_block_pressure_resumes_to_the_same_tokens(model):
    eng = engine(model, num_blocks=22)  # 21 usable blocks of either kind: three lanes of 40 rows do not fit the full kind
    feeds, news = prompts([10, 12, 9], 2), [30, 28, 31]
    rids = [eng.submit(p, n) for p, n in zip(feeds, news)]
    outs = eng.run()
    stats = eng.stats()
    assert stats["preempted"] > 0
    for rid, p, n in zip(rids, feeds, news):
        assert (np.asarray(outs[rid]) == oracle(model, p, n)).all(), rid
    assert eng.cache.allocator.free_blocks == 21 and eng.cache.window_allocator.free_blocks == SLOTS * RING
    assert eng.cache.allocator.used_blocks == eng.cache.window_allocator.used_blocks == 0


def test_scheduler_admits_grows_and_preempts_by_either_kind():
    full, window = BlockAllocator(100), BlockAllocator(7)  # six window blocks: one ring and a half
    sched = Scheduler(full, num_slots=2, block_size=BLOCK, max_blocks_per_seq=16, prefill_chunk=CHUNK,
                      window_allocator=window, ring_blocks=RING)
    assert sched.blocks_by_kind(3) == (1, 1) and sched.blocks_by_kind(16) == (4, 4) and sched.blocks_by_kind(60) == (15, RING)
    first, second = Request([1] * 10, 40), Request([2] * 10, 40)
    assert sched.max_rows(first) == 52 and sched.max_blocks(first) == (13, RING)
    sched.submit(first)
    sched.submit(second)
    assert sched.admit(0.0) == [0, 1]
    assert sched.grow_to(0, 16) and sched.grow_to(1, 8)
    assert (full.used_blocks, window.used_blocks) == (6, 6)
    assert sched.grow_to(0, 40) and len(sched.slots[0].blocks) == 10 and len(sched.slots[0].window_blocks) == RING
    # the window kind is dry: the younger lane's growth preempts it (LIFO: itself), the full kind has 80 blocks free
    assert not sched.grow_to(1, 12)
    assert 1 not in sched.slots and sched.preempted_count == 1 and window.used_blocks == RING and full.used_blocks == 10
    assert sched.queue[0] is second
    # the queue's head waits for window blocks, though full blocks abound
    window.alloc(2)
    assert sched.admit(0.0) == []
    sched.finish(0, 1.0)
    assert (full.used_blocks, window.used_blocks) == (0, 2)
    assert sched.admit(0.0) == [0]
    # what no pool of this geometry can hold is refused at submit, by the kind that lacks
    small = Scheduler(BlockAllocator(100), num_slots=1, block_size=BLOCK, max_blocks_per_seq=16, prefill_chunk=CHUNK,
                      window_allocator=BlockAllocator(3), ring_blocks=RING)
    with pytest.raises(ValueError, match="window blocks"):
        small.submit(Request([1] * 10, 40))
    small.submit(Request([1] * 4, 4))
    # a family without window leaves: every count of the window kind is 0
    plain = Scheduler(BlockAllocator(100), num_slots=1, block_size=BLOCK, max_blocks_per_seq=16, prefill_chunk=CHUNK)
    assert plain.blocks_by_kind(60) == (15, 0)


def test_quarantine_scrubs_the_window_blocks_too(model):
    eng = engine(model)
    eng.submit(prompts([11], 3)[0], 20)
    for _ in range(6):
        eng.step()
    eng._settle("test")
    slot = next(iter(eng.sched.slots.values()))
    held, held_window = list(slot.blocks), list(slot.window_blocks)
    assert held_window and float(jnp.abs(eng.cache.pool[WINDOW]["k"][:, jnp.asarray(held_window)]).max()) > 0
    eng._quarantine(slot, time.monotonic())
    assert eng.cache.window_allocator.used_blocks == eng.cache.allocator.used_blocks == 0
    assert eng.cache.window_allocator.free_blocks == SLOTS * RING
    assert float(jnp.abs(eng.cache.pool[WINDOW]["k"][:, jnp.asarray(held_window + [0])]).max()) == 0
    assert float(jnp.abs(eng.cache.pool["v"][:, jnp.asarray(held)]).max()) == 0
    assert eng.pop_finished()[0].status == "quarantined"


def test_what_is_refused_and_why(model):
    c, params = model
    for bad in (dict(host_blocks=8), dict(spec_tokens=2)):
        with pytest.raises(ValueError, match="window leaves"):
            engine(model, **bad)
    with pytest.raises(ValueError, match="apply_paged"):
        ServingEngine(without_apply_paged(af), af.init_cache, params, c,
                      ServingConfig(block_size=BLOCK, num_blocks=64, max_slots=SLOTS, max_blocks_per_seq=16, prefill_chunk=CHUNK))
    # prefix_cache asked for and not built: the reason is in stats(); not asked for: no key
    assert "prefix_cache_off" not in engine(model, prefix_cache=False).stats()
    assert not hasattr(ServingConfig(), "window_blocks") and not hasattr(ServingConfig(), "sliding_window")
    # debug_blocks names a slot's blocks of either kind
    eng = engine(model)
    eng.submit(prompts([6], 4)[0], 4)
    eng.step()
    slots = eng.debug_blocks()["slots"]
    assert slots and all("window_blocks" in s for s in slots.values())
