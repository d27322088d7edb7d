"""KV survivability under memory pressure: the host-DRAM second tier.

Covers the tiered ``PagedKVCache`` (demote/promote round-trips for the fp
and int8 leaf layouts), two-tier block conservation under fuzzed migration
churn, preemption-as-migration through the engine (token-identity with ZERO
re-prefill dispatches on the migrated resume path), prefix-cache spillover
to host DRAM, the ``SERVING_HOST_FULL`` fault arm's fallback re-prefill,
journal tier-residency records across a simulated kill, the memory ledger's
``serving.kv_host`` owner, and the low-headroom hysteresis regression."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from conftest import without_apply_paged

from accelerate_tpu import telemetry
from accelerate_tpu.models import gpt2
from accelerate_tpu.serving import (
    BlockOutOfMemory,
    PrefixCache,
    ServingConfig,
    ServingEngine,
    ServingJournal,
)
from accelerate_tpu.serving.blocks import HostBlockPool, PagedKVCache


@pytest.fixture(autouse=True)
def _telemetry_clean():
    yield
    telemetry.disable()
    telemetry.get_telemetry().registry.reset()
    telemetry.get_telemetry().step_timer.reset()


def _fake_init_cache(config, batch, max_len):
    del config, batch
    return {
        "k": jnp.zeros((2, 1, max_len, 4), jnp.float32),
        "v": jnp.zeros((2, 1, max_len, 4), jnp.float32),
        "index": jnp.zeros((), jnp.int32),
    }


def _tiered_kv(num_blocks=9, host_blocks=6, bs=4):
    return PagedKVCache(_fake_init_cache, None, num_blocks, bs,
                        num_host_blocks=host_blocks)


# ---------------------------------------------------------------------------
# HostBlockPool unit behavior
# ---------------------------------------------------------------------------


def test_host_pool_mirrors_leaf_layout_and_counts():
    kv = _tiered_kv(num_blocks=9, host_blocks=5)
    host = kv.host
    assert sorted(host.leaves) == kv.leaf_names
    for name, leaf in host.leaves.items():
        dev = kv.pool[name]
        assert leaf.shape == (dev.shape[0], 5) + dev.shape[2:]
        assert leaf.dtype == np.dtype(dev.dtype)
    assert host.capacity == 5 and host.free_blocks == 5 and host.used_blocks == 0
    assert host.pool_bytes() == 5 * host.block_bytes()
    ids = host.alloc(3)
    assert len(set(ids)) == 3
    assert host.used_blocks == 3 and host.occupancy == pytest.approx(0.6)
    assert host.used_bytes() == 3 * host.block_bytes()
    host.free(ids)
    assert host.free_blocks == 5


def test_host_pool_alloc_is_all_or_nothing_and_double_free_raises():
    kv = _tiered_kv(host_blocks=3)
    host = kv.host
    got = host.alloc(2)
    with pytest.raises(BlockOutOfMemory):
        host.alloc(2)  # only 1 free: must not partially grant
    assert host.free_blocks == 1
    host.free(got)
    with pytest.raises(ValueError):
        host.free([got[0]])


def test_host_pool_scrubs_dirty_blocks_on_free():
    """Quarantine's scrub-on-release discipline applies in the host tier
    too: a block marked dirty is zeroed synchronously when freed."""
    kv = _tiered_kv(host_blocks=3)
    host = kv.host
    (hid,) = host.alloc(1)
    for leaf in host.leaves.values():
        leaf[:, hid] = 7.0
    host.mark_dirty([hid])
    host.free([hid])
    for leaf in host.leaves.values():
        np.testing.assert_array_equal(leaf[:, hid], np.zeros_like(leaf[:, hid]))


# ---------------------------------------------------------------------------
# demote / promote round-trips
# ---------------------------------------------------------------------------


def _fill_block(kv, block, value):
    for name in list(kv.pool):
        leaf = kv.pool[name]
        kv.pool[name] = leaf.at[:, block].set(
            jnp.full(leaf.shape[0:1] + leaf.shape[2:], value, leaf.dtype)
        )


def test_demote_promote_round_trip_bit_exact_fp():
    kv = _tiered_kv(num_blocks=9, host_blocks=6)
    blocks = kv.allocator.alloc(3)
    for i, b in enumerate(blocks):
        _fill_block(kv, b, float(i + 1))
    host_ids = kv.demote(blocks)
    assert kv.host.used_blocks == 3
    for name, leaf in kv.host.leaves.items():
        for i, hid in enumerate(host_ids):
            np.testing.assert_array_equal(
                leaf[:, hid], np.asarray(kv.pool[name][:, blocks[i]])
            )
    # demotion is a copy: device contents untouched, refs still the caller's
    kv.allocator.free(blocks)
    dst = kv.allocator.alloc(3)
    kv.promote(host_ids, dst)
    assert kv.host.used_blocks == 0  # promote frees the host ids
    for i, b in enumerate(dst):
        want = float(i + 1)
        for name in kv.pool:
            np.testing.assert_array_equal(
                np.asarray(kv.pool[name][:, b]),
                np.full_like(np.asarray(kv.pool[name][:, b]), want),
            )


def test_demote_promote_round_trip_bit_exact_int8():
    """The int8 codes+scale leaves page through the host tier exactly like
    the fp layout — integer codes must survive the round trip bit-exact."""
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32, kv_cache_quant=True)
    kv = PagedKVCache(gpt2.init_cache, cfg, 9, 4, num_host_blocks=4)
    dtypes = {np.dtype(leaf.dtype) for leaf in kv.pool.values()}
    assert np.dtype(np.int8) in dtypes, "quantized pool has no int8 leaf"
    (block,) = kv.allocator.alloc(1)
    rng = np.random.default_rng(0)
    for name in list(kv.pool):
        leaf = kv.pool[name]
        shape = leaf.shape[0:1] + leaf.shape[2:]
        if np.dtype(leaf.dtype) == np.dtype(np.int8):
            rows = rng.integers(-128, 128, size=shape, dtype=np.int8)
        else:
            rows = rng.standard_normal(shape).astype(leaf.dtype)
        kv.pool[name] = leaf.at[:, block].set(jnp.asarray(rows))
    before = {name: np.asarray(kv.pool[name][:, block]).copy() for name in kv.pool}
    (hid,) = kv.demote([block])
    kv.allocator.free([block])
    (dst,) = kv.allocator.alloc(1)
    kv.promote([hid], [dst])
    for name in kv.pool:
        np.testing.assert_array_equal(np.asarray(kv.pool[name][:, dst]), before[name])


def test_demote_raises_and_try_demote_degrades_when_host_full():
    kv = _tiered_kv(num_blocks=9, host_blocks=2)
    blocks = kv.allocator.alloc(3)
    with pytest.raises(BlockOutOfMemory):
        kv.demote(blocks)
    assert kv.try_demote(blocks) is None
    assert kv.host.used_blocks == 0  # the failed demote leaked nothing
    assert kv.try_demote(blocks[:2]) is not None


def test_host_full_fault_arm_forces_host_exhausted_paths(monkeypatch):
    from accelerate_tpu.resilience import faultinject

    kv = _tiered_kv(num_blocks=9, host_blocks=6)
    blocks = kv.allocator.alloc(2)
    monkeypatch.setenv("ACCELERATE_TPU_FAULT_SERVING_HOST_FULL", "1")
    faultinject.reload()
    try:
        assert not kv.host_can_fit(1)
        assert kv.try_demote(blocks) is None
    finally:
        monkeypatch.delenv("ACCELERATE_TPU_FAULT_SERVING_HOST_FULL")
        faultinject.reload()
    assert kv.host_can_fit(1)


# ---------------------------------------------------------------------------
# Two-tier conservation fuzz
# ---------------------------------------------------------------------------


def test_two_tier_conservation_fuzz():
    """Random alloc/free/demote/promote interleavings: block conservation
    holds in BOTH tiers at every step (used + free == capacity, no id ever
    granted twice while live), and every demoted block's content survives
    to its promotion."""
    rng = np.random.default_rng(1234)
    kv = _tiered_kv(num_blocks=13, host_blocks=7)
    alloc = kv.allocator
    live_dev = {}    # device block -> fill value
    on_host = []     # (host_ids, values) parcels awaiting promotion
    next_val = 1.0
    for _ in range(400):
        op = rng.integers(0, 4)
        if op == 0 and alloc.free_blocks:          # alloc + fill
            n = int(rng.integers(1, min(alloc.free_blocks, 3) + 1))
            for b in alloc.alloc(n):
                _fill_block(kv, b, next_val)
                live_dev[b] = next_val
                next_val += 1.0
        elif op == 1 and live_dev:                 # free
            b = list(live_dev)[rng.integers(len(live_dev))]
            alloc.free([b])
            del live_dev[b]
        elif op == 2 and live_dev:                 # demote a parcel, drop dev refs
            take = list(live_dev)[: int(rng.integers(1, 3))]
            host_ids = kv.try_demote(take)
            if host_ids is not None:
                on_host.append((host_ids, [live_dev[b] for b in take]))
                alloc.free(take)
                for b in take:
                    del live_dev[b]
        elif op == 3 and on_host:                  # promote a parcel back
            host_ids, values = on_host[rng.integers(len(on_host))]
            if alloc.free_blocks >= len(host_ids):
                on_host.remove((host_ids, values))
                dst = alloc.alloc(len(host_ids))
                kv.promote(host_ids, dst)
                for b, v in zip(dst, values):
                    got = np.asarray(kv.pool["k"][:, b])
                    np.testing.assert_array_equal(got, np.full_like(got, v))
                    live_dev[b] = v
        # conservation, both tiers, every step
        assert alloc.used_blocks + alloc.free_blocks == alloc.capacity
        assert kv.host.used_blocks + kv.host.free_blocks == kv.host.capacity
        assert alloc.used_blocks == len(live_dev)
        assert kv.host.used_blocks == sum(len(ids) for ids, _ in on_host)
    # drain everything: both tiers return to empty
    if live_dev:
        alloc.free(list(live_dev))
    for host_ids, _ in on_host:
        kv.host.free(host_ids)
    assert alloc.used_blocks == 0 and kv.host.used_blocks == 0


# ---------------------------------------------------------------------------
# Engine: preemption-as-migration token-identity matrix
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gpt2_setup():
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
    params = gpt2.init_params(cfg, jax.random.key(0))
    return cfg, params


def _oracle(cfg, params, prompt, max_new):
    out = gpt2.generate(params, jnp.asarray([prompt], jnp.int32), cfg,
                        max_new_tokens=max_new)
    return [int(t) for t in np.asarray(out[0])]


def _run_tiered_mix(cfg, params, *, seed=7, host_blocks=16, apply_cached=gpt2.apply_cached, **overrides):
    """A pool tight enough to force preemption, with the host tier on:
    returns (engine, completions, want-by-request-id)."""
    rng = np.random.default_rng(seed)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (5, 11, 9)]
    max_new = [8, 6, 7]
    want = {i: _oracle(cfg, params, p, m)
            for i, (p, m) in enumerate(zip(prompts, max_new))}
    kw = dict(block_size=4, num_blocks=9, max_slots=3, prefill_chunk=4,
              max_blocks_per_seq=6, host_blocks=host_blocks)
    kw.update(overrides)
    eng = ServingEngine(
        apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(**kw),
    )
    ids = {eng.submit(p, m): i for i, (p, m) in enumerate(zip(prompts, max_new))}
    outputs = eng.run(max_ticks=3000)
    assert eng.sched.preempted_count > 0, "pool was not tight enough to preempt"
    for rid, out in outputs.items():
        assert out == want[ids[rid]], f"request {rid} diverged after migration"
    return eng, {c.id: c for c in eng.pop_finished()}, ids, prompts


@pytest.mark.parametrize(
    "decode_path",
    ["paged", pytest.param("dense", marks=pytest.mark.slow)],
)
@pytest.mark.parametrize("quant", [False, True])
def test_tiered_preemption_token_identical_matrix(decode_path, quant):
    """The acceptance matrix with migration forced: paged/dense x fp/int8
    requests that round-trip HBM -> host -> HBM finish token-identical, and
    a migrated request that never fell back pays ZERO extra prefill
    dispatches on resume."""
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32, kv_cache_quant=quant)
    params = gpt2.init_params(cfg, jax.random.key(0))
    # the family decides the back end: the wrapper's module has no apply_paged
    apply_cached = {"paged": gpt2.apply_cached, "dense": without_apply_paged(gpt2)}[decode_path]
    eng, done, ids, prompts = _run_tiered_mix(cfg, params, apply_cached=apply_cached)
    assert eng.stats()["decode_path"] == decode_path
    st = eng.stats()["tiering"]
    assert st["demotions"] > 0 and st["promotions"] > 0, (
        f"no migration happened: {st}"
    )
    migrated = [c for c in done.values() if c.migrations > 0]
    assert migrated, "no request ever migrated through the host tier"
    for c in migrated:
        if c.fallback_reprefills == 0:
            base = -(-len(prompts[ids[c.id]]) // 4)  # ceil(prompt / chunk)
            assert c.prefill_dispatches == base, (
                f"request {c.id} re-prefilled on the migrated resume path: "
                f"{c.prefill_dispatches} dispatches vs {base} for the prompt"
            )
    # zero leaks: every surviving host block is a prefix-cache spill
    host_owned = eng._prefix.host_count if eng._prefix is not None else 0
    assert eng.cache.host.used_blocks == host_owned


@pytest.mark.slow
def test_tiered_preemption_with_speculative_decode(gpt2_setup):
    """Spec-decode requests migrate too: draft state is host-side, so a
    round-trip through the host tier stays token-identical with drafts on."""
    cfg, params = gpt2_setup
    eng, done, ids, prompts = _run_tiered_mix(cfg, params, spec_tokens=2)
    st = eng.stats()["tiering"]
    assert st["demotions"] > 0 and st["promotions"] > 0
    assert any(c.migrations > 0 for c in done.values())


@pytest.mark.slow
def test_tiered_migration_survives_without_prefix_cache(gpt2_setup):
    """Tiering is independent of prefix caching: with the cache off, the
    preempt -> demote -> promote -> resume path still round-trips."""
    cfg, params = gpt2_setup
    eng, done, ids, prompts = _run_tiered_mix(cfg, params, prefix_cache=False)
    assert eng.stats()["tiering"]["promotions"] > 0
    assert eng.cache.host.used_blocks == 0  # no prefix cache: nothing lingers


@pytest.mark.slow
def test_fallback_reprefill_when_host_tier_absent(gpt2_setup):
    """host_blocks=0 keeps PR 9 semantics exactly: preemption frees blocks
    and resumes via re-prefill; stats carry no tiering block."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (5, 11, 9)]
    want = {i: _oracle(cfg, params, p, m)
            for i, (p, m) in enumerate(zip(prompts, (8, 6, 7)))}
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=9, max_slots=3,
                              prefill_chunk=4, max_blocks_per_seq=6),
    )
    ids = {eng.submit(p, m): i for i, (p, m) in enumerate(zip(prompts, (8, 6, 7)))}
    outputs = eng.run(max_ticks=3000)
    assert eng.sched.preempted_count > 0
    assert eng.stats()["tiering"] is None
    assert eng.cache.host is None
    for rid, out in outputs.items():
        assert out == want[ids[rid]]


def test_host_full_fault_forces_engine_fallback_reprefill(gpt2_setup):
    """The SERVING_HOST_FULL arm: with the host tier nominally on but the
    fault forcing exhaustion, every preemption falls back to re-prefill —
    still token-identical, and the fallback counter records each one."""
    from accelerate_tpu.resilience import faultinject

    cfg, params = gpt2_setup
    os.environ["ACCELERATE_TPU_FAULT_SERVING_HOST_FULL"] = "1"
    faultinject.reload()
    try:
        eng, done, ids, prompts = _run_tiered_mix(cfg, params)
    finally:
        os.environ.pop("ACCELERATE_TPU_FAULT_SERVING_HOST_FULL", None)
        faultinject.reload()
    st = eng.stats()["tiering"]
    assert st["fallback_reprefills"] > 0, "fault never forced a fallback"
    assert st["promotions"] == 0, "a promotion happened with the host full"
    assert eng.cache.host.used_blocks == 0
    assert any(c.fallback_reprefills > 0 for c in done.values())


# ---------------------------------------------------------------------------
# Prefix-cache spillover
# ---------------------------------------------------------------------------


def test_prefix_cache_demotes_on_eviction_and_promotes_on_lookup():
    """Unit-level spillover: eviction pressure moves a cache-only chain to
    the host tier (device block freed, chain key preserved), and a later
    lookup promotes it back with the cached content intact."""
    kv = _tiered_kv(num_blocks=9, host_blocks=6, bs=4)
    cache = PrefixCache(kv.allocator, 4)
    cache.attach_tier(kv)
    tokens = list(range(12))  # 3 full blocks
    keys = cache.chain_keys(tokens, 4)
    blocks = kv.allocator.alloc(3)
    for i, b in enumerate(blocks):
        _fill_block(kv, b, float(10 + i))
    for key, b in zip(keys, blocks):
        assert cache.register(key, b)
    kv.allocator.free(blocks)  # cache holds the only refs now
    assert cache.reclaimable_count == 3

    assert cache.evict(3) == 3
    assert len(cache) == 0 and cache.host_count == 3
    assert cache.host_demotions == 3 and kv.host.used_blocks == 3
    assert kv.allocator.used_blocks == 0  # device side fully released

    got, rows, cow = cache.lookup(tokens, max_rows=12)
    assert rows == 12 and len(got) == 3 and cow is None
    assert cache.host_promotions == 3 and cache.host_count == 0
    assert kv.host.used_blocks == 0
    for i, b in enumerate(got):
        want = float(10 + i)
        arr = np.asarray(kv.pool["k"][:, b])
        np.testing.assert_array_equal(arr, np.full_like(arr, want))
    kv.allocator.free(got)  # lookup retained for the caller


def test_prefix_cache_eviction_drops_when_host_full():
    kv = _tiered_kv(num_blocks=9, host_blocks=1, bs=4)
    cache = PrefixCache(kv.allocator, 4)
    cache.attach_tier(kv)
    tokens = list(range(12))
    blocks = kv.allocator.alloc(3)
    for key, b in zip(cache.chain_keys(tokens, 4), blocks):
        cache.register(key, b)
    kv.allocator.free(blocks)
    assert cache.evict(3) == 3
    assert cache.host_count == 1 and cache.host_demotions == 1
    assert cache.host_drops == 2  # host had room for one chain block only


def test_prefix_cache_drop_host_entries_lru_first():
    kv = _tiered_kv(num_blocks=9, host_blocks=6, bs=4)
    cache = PrefixCache(kv.allocator, 4)
    cache.attach_tier(kv)
    tokens = list(range(16))  # 4 full blocks
    blocks = kv.allocator.alloc(4)
    for key, b in zip(cache.chain_keys(tokens, 4), blocks):
        cache.register(key, b)
    kv.allocator.free(blocks)
    cache.evict(4)
    assert cache.host_count == 4
    assert cache.drop_host_entries(3) == 3
    assert cache.host_count == 1 and kv.host.used_blocks == 1
    assert cache.drop_host_entries() == 1
    assert kv.host.used_blocks == 0


def test_quarantine_dirty_block_never_demotes():
    """A quarantine-dirty block must not spill its poisoned rows to host:
    eviction drops it outright (scrub-on-release handles the zeroing)."""
    kv = _tiered_kv(num_blocks=9, host_blocks=6, bs=4)
    cache = PrefixCache(kv.allocator, 4)
    cache.attach_tier(kv)
    tokens = list(range(4))
    (block,) = kv.allocator.alloc(1)
    cache.register(cache.chain_keys(tokens, 4)[0], block)
    kv.allocator.mark_dirty([block])
    kv.allocator.free([block])
    assert cache.evict(1) == 1
    assert cache.host_count == 0 and cache.host_drops == 1
    assert kv.host.used_blocks == 0


# ---------------------------------------------------------------------------
# Pressure-aware admission (the watermark demotes before admission sheds)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_pressure_relief_demotes_cold_chains_below_watermark(gpt2_setup, monkeypatch):
    """When the RAW free list (free minus cache-reclaimable) dips below the
    watermark, the tick demotes cold prefix chains to host — freeing real
    device blocks without dropping the cached prefixes."""
    monkeypatch.setenv("ACCELERATE_TPU_SERVING_HEADROOM_WATERMARK", "0.6")
    cfg, params = gpt2_setup
    rng = np.random.default_rng(23)
    prompt = list(rng.integers(0, cfg.vocab_size, size=16))  # 4 full blocks
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=9, max_slots=2,
                              prefill_chunk=8, max_blocks_per_seq=8,
                              host_blocks=8, tier_demote_batch=8),
    )
    a = eng.submit(prompt, 3)
    eng.run(max_ticks=300)
    assert len(eng._prefix) > 0  # chains cached, occupying the raw free list
    # raw free (4/8) is now below the 0.6 watermark; the next tick demotes
    before = eng._prefix.host_demotions
    eng.step()
    assert eng._prefix.host_demotions > before, "pressure relief never demoted"
    assert eng.cache.host.used_blocks == eng._prefix.host_count
    # the demoted chains remain hits: a same-prompt request promotes them back
    b = eng.submit(prompt, 3)
    out = eng.run(max_ticks=300)
    assert eng._prefix.host_promotions > 0
    want = _oracle(cfg, params, prompt, 3)
    assert out[b] == want


# ---------------------------------------------------------------------------
# Journal tier residency + kill recovery
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_journal_records_tier_residency_and_recovery_is_token_identical(
    gpt2_setup, tmp_path
):
    """A SIGKILL while blocks sit demoted: the journal's tier record carries
    residency plus emitted progress, and a successor (whose host DRAM is
    necessarily fresh) recovers every request token-identically."""
    cfg, params = gpt2_setup
    jp = str(tmp_path / "journal.json")
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (5, 11, 9)]
    max_new = [8, 6, 7]
    want = {i: _oracle(cfg, params, p, m)
            for i, (p, m) in enumerate(zip(prompts, max_new))}

    def build(path):
        return ServingEngine(
            gpt2.apply_cached, gpt2.init_cache, params, cfg,
            serving=ServingConfig(block_size=4, num_blocks=9, max_slots=3,
                                  prefill_chunk=4, max_blocks_per_seq=6,
                                  host_blocks=16, journal_path=path),
        )

    eng = build(jp)
    ids = {eng.submit(p, m): i for i, (p, m) in enumerate(zip(prompts, max_new))}
    # run until at least one request is host-resident, then "die" (abandon)
    for _ in range(500):
        eng.step()
        if any(req.demoted_blocks for req in eng.sched.queue):
            break
    else:
        pytest.fail("no request was ever host-resident")
    state = ServingJournal.load(jp)
    tiered = [r for r in state["requests"].values() if "tier" in r]
    assert tiered, "journal carries no tier residency record"
    assert any(r["tier"]["residency"] == "host" for r in tiered)
    for r in tiered:
        assert {"residency", "demoted_rows", "demoted_blocks", "migrations"} <= set(
            r["tier"]
        )

    partial = {c.id: c.tokens for c in eng.pop_finished()}
    succ = build(jp)
    mapping = succ.recover_from_journal()
    outputs = succ.run(max_ticks=3000)
    for old_id, i in ids.items():
        got = partial.get(old_id)
        if got is None:
            got = outputs[mapping[old_id]]
        assert got == want[i], f"request {old_id} diverged across the kill"


# ---------------------------------------------------------------------------
# Telemetry: tier metrics, memledger owner, hysteresis regression
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_tier_metrics_precreated_and_published(gpt2_setup, tmp_path):
    cfg, params = gpt2_setup
    tel = telemetry.enable(dir=str(tmp_path))
    eng, done, ids, prompts = _run_tiered_mix(cfg, params)
    snap = tel.registry.snapshot()
    st = eng.stats()["tiering"]
    assert snap["serving.tier.demotions"] == st["demotions"]
    assert snap["serving.tier.promotions"] == st["promotions"]
    assert snap["serving.tier.demoted_blocks"] == st["demoted_blocks"]
    assert snap["serving.tier.fallback_reprefills"] == st["fallback_reprefills"]
    assert snap["serving.tier.host_bytes"] == eng.cache.host.used_bytes()
    assert snap["serving.tier.host_occupancy"] == pytest.approx(
        eng.cache.host.occupancy, abs=1e-4
    )


def test_tier_counters_exist_at_zero_from_construction(gpt2_setup, tmp_path):
    """Pre-created at engine construction: a scrape before any migration
    already sees the tier series at 0."""
    cfg, params = gpt2_setup
    tel = telemetry.enable(dir=str(tmp_path))
    ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=20, max_slots=2,
                              host_blocks=4),
    )
    snap = tel.registry.snapshot()
    for name in ("serving.tier.demotions", "serving.tier.promotions",
                 "serving.tier.demoted_blocks",
                 "serving.tier.fallback_reprefills",
                 "serving.tier.host_bytes", "serving.tier.host_occupancy"):
        assert snap.get(name) == 0, f"{name} not pre-created at 0"


def test_memledger_registers_kv_host_owner_charging_host_bytes(gpt2_setup):
    from accelerate_tpu.telemetry.memledger import get_memory_ledger

    cfg, params = gpt2_setup
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=20, max_slots=2,
                              host_blocks=6),
    )
    snap = get_memory_ledger().snapshot()
    owners = {o["owner"]: o for o in snap["owners"]}
    assert "serving.kv_host" in owners
    rec = owners["serving.kv_host"]
    assert rec["host_bytes"] == eng.cache.host.pool_bytes()
    assert rec["device_bytes"] == 0, "host tier must not be charged to HBM"
    assert rec["detail"]["host_blocks"] == 6


def test_low_headroom_rearms_with_hysteresis(gpt2_setup, tmp_path):
    """The S-curve regression: one event per pressure episode.  Recovery TO
    the watermark does not re-arm (hysteresis band); recovery ABOVE the
    re-arm line does, so the next dip emits a second event."""
    cfg, params = gpt2_setup
    tel = telemetry.enable(dir=str(tmp_path))
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=41, max_slots=2,
                              max_blocks_per_seq=8, prefix_cache=False),
    )
    alloc = eng.cache.allocator
    assert eng._headroom_watermark_frac == pytest.approx(0.1)
    assert eng._headroom_rearm_frac == pytest.approx(0.15)

    held = alloc.alloc(38)          # free 2/40 = 0.05 < watermark
    eng._publish_gauges()           # -> event 1, armed
    alloc.free(held[:2]); held = held[2:]   # free 4/40 = 0.10: AT watermark
    eng._publish_gauges()           # inside the band: must NOT re-arm
    got = alloc.alloc(2); held += got       # dip again: 0.05
    eng._publish_gauges()           # still armed -> NO second event
    alloc.free(held[:5]); held = held[5:]   # free 7/40 = 0.175 >= re-arm
    eng._publish_gauges()           # re-arms
    got = alloc.alloc(5); held += got       # dip: 0.05
    eng._publish_gauges()           # -> event 2
    telemetry.disable()

    events = []
    with open(tel.jsonl_path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") == "event" and rec.get("name") == "memory.low_headroom":
                events.append(rec)
    assert len(events) == 2, (
        f"expected exactly 2 low-headroom events (one per episode), got "
        f"{len(events)}"
    )
