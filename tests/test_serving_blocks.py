"""Generation by diffusion over blocks on the serving engine
(``models/sdar_moe.py`` at the tiny float32 preset, blocks of 4): a decoding lane
carries a block of masked positions through denoising passes that write no
cache and one commit pass, on the one-dispatch, read-one-tick-late engine.

The oracle is ``generation.block_generate_loop`` (the offline twin; float32 and
greedy, so token lists are equal, and ``tests/test_sdar_moe.py`` holds the
logits of every pass to the plain reference).  Held here: tokens and pass
numbers equal to the oracle for every schedule and prompt remainder, pipelined
and settled after every step; the threshold path settles every tick by
observation; preemption, a prefix hit, quarantine, deadline and drain keep every
token and drop none; the counters by hand; every control of the benchmark
family fails the check and a sound engine reads 0; every other family keeps its
programs' arguments and outputs.
"""

import dataclasses
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from conftest import recorded_spans

from accelerate_tpu.models import gpt2, llama
from accelerate_tpu.models import sdar_moe as sd
from accelerate_tpu.models.generation import block_generate_loop
from accelerate_tpu.serving import ServingConfig, ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 4


def load_by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, *parts))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model():
    c = sd.SdarMoeConfig.tiny(dtype=jnp.float32)
    return c, sd.init_params(c, jax.random.key(34))


def engine_of(model, **kw):
    c, params = model
    geometry = dict(block_size=8, num_blocks=96, max_slots=4, max_blocks_per_seq=16, prefill_chunk=8)
    geometry.update(kw)
    return ServingEngine(sd.apply_cached, sd.init_cache, params, c, ServingConfig(**geometry))


def oracle(model, prompt, new, steps=None, threshold=None):
    c, params = model
    tokens, passes = block_generate_loop(
        sd.apply_cached, sd.init_cache, params, jnp.asarray(prompt)[None], c, new, denoise_steps=steps,
        confidence_threshold=threshold, return_passes=True)
    return np.asarray(tokens)[0].tolist(), np.asarray(passes)[0].tolist()


def run_all(engine, requests, settle_every_step=False):
    ids = [engine.submit(p, n, **kw) for p, n, kw in requests]
    ticks = 0
    while not engine.sched.idle():
        engine.step()
        if settle_every_step:
            engine.stats()
        ticks += 1
        assert ticks < 3000
    done = {r.id: r for r in engine.pop_finished()}
    return [done[i] for i in ids]


def requests_of(rng, sizes):
    return [(rng.integers(0, 255, p), n, {"denoise_steps": t}) for p, n, t in sizes]


# ---------------------------------------------------------------------------
# the engine against the offline loop
# ---------------------------------------------------------------------------

MIX = [(13, 10, 2), (8, 7, 4), (3, 9, 1), (19, 5, 3), (16, 12, 2), (5, 1, 2), (31, 6, None)]


@pytest.mark.parametrize("settled", [False, True], ids=["pipelined", "settled-every-step"])
def test_engine_tokens_and_pass_numbers_equal_the_offline_loop(model, settled):
    """T = 1..4 and the default, prompts with P mod 4 = 0, 1, 3, requests that end
    inside a block, more requests than lanes: the engine one tick ahead and the
    same engine settled after every step serve what the offline loop generates."""
    engine = engine_of(model)
    requests = requests_of(np.random.default_rng(0), MIX)
    replies = run_all(engine, requests, settle_every_step=settled)
    for reply, (prompt, new, kw) in zip(replies, requests):
        tokens, passes = oracle(model, prompt, new, kw["denoise_steps"])
        assert reply.status == "ok" and reply.tokens == tokens and reply.token_passes == passes
        assert reply.new_tokens == new == len(reply.token_passes)
    stats = engine.stats()
    assert stats["decode_path"] == "paged" and stats["block_length"] == 4
    dispatches = stats["prefill_dispatches"] + stats["decode_dispatches"] - stats["mixed_dispatches"]
    assert dispatches == stats["ticks"] and stats["mixed_dispatches"] > 0  # a chunk rides with the lanes: one dispatch a tick
    if settled:
        assert stats["pipelined_ticks"] == 0 and stats["settles"]["stats"] >= stats["ticks"] - 1
    else:
        assert stats["pipelined_ticks"] >= stats["ticks"] - 2 and set(stats["settles"]) <= {"idle", "stats"}
    assert stats["blocks_used"] == 0  # every block back


def test_tokens_of_a_block_arrive_together_and_ttft_is_the_first_blocks(model):
    engine = engine_of(model)
    (reply,) = run_all(engine, requests_of(np.random.default_rng(1), [(13, 10, 2)]))
    # block 0 holds one prompt token and three new ones, then 4, then 3 of 4 (the tail dropped)
    gaps = np.asarray(reply.inter_token_ms)
    assert len(gaps) == 9 and (gaps[[0, 1, 3, 4, 5, 7, 8]] == 0).all() and (gaps[[2, 6]] > 0).all()
    assert reply.ttft_ms > 0 and reply.prefill_dispatches == 2  # 12 prefilled rows in chunks of 8: no token from either


def test_counters_by_hand(model):
    """One request alone, P = 8, 8 new tokens, two passes a block: 1 chunk, then
    block 0 (2 denoising ticks), its commit, block 1 (2 denoising ticks) and no
    commit of the last block."""
    engine = engine_of(model)
    (reply,) = run_all(engine, requests_of(np.random.default_rng(2), [(8, 8, 2)]))
    s = engine.stats()
    assert (s["denoise_slot_ticks"], s["commit_slot_ticks"], s["blocks_committed"], s["block_tokens_emitted"]) == (4, 1, 1, 8)
    assert engine.decode_slot_ticks == 5 and s["decode_dispatches"] == 5 and s["prefill_dispatches"] == 1 and s["ticks"] == 6
    assert s["spec"]["tokens_per_dispatch"] == round(8 / 5, 4)
    assert sorted(reply.token_passes[:4]) == [0, 0, 1, 1] and reply.prefill_dispatches == 1
    # the expert counters ride out as for the other expert families: 3 layers x top-2 for every row of every dispatch
    rows = 1 * (4 * 4 + 8) + 5 * 4 * 4  # the chunk's dispatch (idle lanes and 8 rows), then 5 of the lanes alone
    assert s["moe_rows"] == 3 * 2 * rows
    # P = 7: three prompt tokens open block 0 and one position is left: one pass, whatever the schedule
    engine = engine_of(model)
    (reply,) = run_all(engine, requests_of(np.random.default_rng(3), [(7, 5, 4)]))
    assert reply.token_passes[0] == 0 and sorted(reply.token_passes[1:]) == [0, 1, 2, 3]
    assert engine.stats()["denoise_slot_ticks"] == 1 + 4


def test_denoising_passes_leave_the_pool_bit_for_bit(model):
    engine = engine_of(model)
    engine.submit(np.random.default_rng(4).integers(0, 255, 8), 8, denoise_steps=4)
    engine.step()  # the chunk
    engine.stats()
    pool = {k: np.asarray(v) for k, v in engine.cache.pool.items()}
    for _ in range(4):  # the four denoising passes of block 0
        engine.step()
    engine.stats()
    assert engine.stats()["denoise_slot_ticks"] == 4 and engine.stats()["commit_slot_ticks"] == 0
    assert all((np.asarray(v) == pool[k]).all() for k, v in engine.cache.pool.items())  # the null block too
    engine.step()  # the commit
    engine.stats()
    slot = next(iter(engine.sched.slots.values()))
    block = slot.blocks[1]  # rows 8 .. 11 lie in the second pool block
    assert slot.cache_len == 12 and (np.asarray(engine.cache.pool["k"])[:, block, :4] != pool["k"][:, block, :4]).any()
    assert (np.asarray(engine.cache.pool["k"])[:, block, 4:] == pool["k"][:, block, 4:]).all()


def test_block_length_one_is_served_as_any_autoregressive_family(model):
    c, params = model
    one = dataclasses.replace(c, block_length=1)
    engine = ServingEngine(sd.apply_cached, sd.init_cache, params, one, ServingConfig(block_size=8, num_blocks=32, max_slots=2, prefill_chunk=8))
    assert engine.programs.block == 1 and engine.programs.window == 1 and "block_length" not in engine.stats()
    prompt = np.random.default_rng(5).integers(0, 255, 11)
    rid = engine.submit(prompt, 9)
    assert engine.run()[rid] == np.asarray(sd.generate(params, jnp.asarray(prompt)[None], one, 9))[0].tolist()
    with pytest.raises(ValueError, match="decodes one token a step"):
        engine.submit(prompt, 4, denoise_steps=2)


# ---------------------------------------------------------------------------
# the threshold path
# ---------------------------------------------------------------------------


def test_a_confidence_threshold_settles_every_tick_and_unmasks_ahead_of_the_schedule(model):
    """A vocabulary of 256 with seeded weights gives confidences around 0.005:
    a threshold under them is passed by every masked position."""
    engine = engine_of(model)
    prompt = np.random.default_rng(6).integers(0, 255, 13)
    kw = dict(denoise_steps=4, confidence_threshold=0.0045)
    (reply,) = run_all(engine, [(prompt, 10, kw)])
    tokens, passes = oracle(model, prompt, 10, 4, 0.0045)
    assert reply.tokens == tokens and reply.token_passes == passes
    assert max(np.bincount(reply.token_passes)) > 3  # more than n_t = 1 a pass: whole blocks in their first pass
    s = engine.stats()
    decoding_ticks = s["decode_dispatches"]
    assert s["settles"]["blocks"] == decoding_ticks and s["pipelined_ticks"] <= s["prefill_dispatches"]
    # beside a request without one, the engine still settles every tick, and both are the oracle's
    engine = engine_of(model)
    other = np.random.default_rng(7).integers(0, 255, 9)
    a, b = run_all(engine, [(prompt, 10, kw), (other, 8, {"denoise_steps": 2})])
    assert a.tokens == tokens and b.tokens == oracle(model, other, 8, 2)[0]
    assert engine.stats()["settles"]["blocks"] >= 4
    # a threshold nothing passes is the static schedule, settled all the same (the count is a value to the engine)
    engine = engine_of(model)
    (reply,) = run_all(engine, [(prompt, 10, dict(denoise_steps=2, confidence_threshold=0.99))])
    assert reply.tokens == oracle(model, prompt, 10, 2)[0] and engine.stats()["settles"]["blocks"] > 0


# ---------------------------------------------------------------------------
# robustness: nothing is lost, nothing is served twice
# ---------------------------------------------------------------------------


def test_preemption_re_prefills_whole_blocks_and_resumes_to_the_same_tokens(model):
    engine = engine_of(model, num_blocks=8, max_slots=3, max_blocks_per_seq=6, block_size=4, prefill_chunk=4)
    requests = requests_of(np.random.default_rng(8), [(9, 12, 2), (10, 11, 2), (11, 10, 4)])
    replies = run_all(engine, requests)
    assert engine.stats()["preempted"] > 0, "pool was not tight enough to force preemption"
    assert engine.stats()["settles"].get("preempt", 0) > 0
    for reply, (prompt, new, kw) in zip(replies, requests):
        assert reply.tokens == oracle(model, prompt, new, kw["denoise_steps"])[0]
        assert len(reply.token_passes) == new
    assert sum(r.preemptions for r in replies) == engine.stats()["preempted"]


def test_a_prefix_hit_reuses_whole_pool_blocks(model):
    engine = engine_of(model)
    rng = np.random.default_rng(9)
    shared = rng.integers(0, 255, 19)
    first, second = np.concatenate([shared, rng.integers(0, 255, 3)]), np.concatenate([shared, rng.integers(0, 255, 6)])
    (a,) = run_all(engine, [(first, 6, {"denoise_steps": 2})])
    (b,) = run_all(engine, [(second, 7, {"denoise_steps": 2})])
    s = engine.stats()
    assert s["prefix_hits"] == 1 and s["prefix_blocks_reused"] == 2 and s["prefix_cow_copies"] == 0  # 16 of the 19 shared rows
    assert b.prefill_dispatches == 1 < a.prefill_dispatches  # rows 16 .. 23 in one chunk
    assert a.tokens == oracle(model, first, 6, 2)[0] and b.tokens == oracle(model, second, 7, 2)[0]
    # a prompt that is all hit (its whole blocks cached) decodes without a chunk
    (c,) = run_all(engine, [(first[:18], 5, {"denoise_steps": 2})])
    assert c.prefill_dispatches == 0 and c.tokens == oracle(model, first[:18], 5, 2)[0]


def test_a_poisoned_lane_is_quarantined_inside_a_block_and_the_others_keep_their_tokens(model, monkeypatch):
    from accelerate_tpu.resilience import faultinject

    monkeypatch.setenv("ACCELERATE_TPU_FAULT_SERVING_NAN_REQUEST", "2")
    faultinject.reload()
    try:
        engine = engine_of(model)
    finally:
        monkeypatch.delenv("ACCELERATE_TPU_FAULT_SERVING_NAN_REQUEST")
        faultinject.reload()
    requests = requests_of(np.random.default_rng(10), [(9, 9, 2), (13, 10, 4), (6, 8, 2)])
    replies = run_all(engine, requests)
    assert [r.status for r in replies] == ["ok", "quarantined", "ok"] and replies[1].new_tokens == 0
    for reply, (prompt, new, kw) in zip(replies, requests):
        if reply.status == "ok":
            assert reply.tokens == oracle(model, prompt, new, kw["denoise_steps"])[0]
    s = engine.stats()
    assert s["quarantined"] == 1 and s["settles"]["quarantine"] == 1 and s["blocks_used"] == 0


def test_deadline_and_drain_keep_every_token_read(model):
    engine = engine_of(model)
    prompt = np.random.default_rng(11).integers(0, 255, 10)
    rid = engine.submit(prompt, 40, denoise_steps=2, deadline_ms=1e9)
    while len(engine.sched.slots[0].request.emitted if engine.sched.slots else []) < 6:
        engine.step()
    req = next(iter(engine.sched.slots.values())).request
    req.deadline_ms = 0.0  # expired now
    engine.step()
    (reply,) = engine.pop_finished()
    full = oracle(model, prompt, 40, 2)[0]
    assert reply.status == "deadline_expired" and reply.new_tokens >= 6 and reply.tokens == full[: len(reply.tokens)]
    assert engine.stats()["settles"].get("deadline") == 1 and len(reply.token_passes) == reply.new_tokens
    # a drain: whole blocks are carried, and a successor finishes the request to the same tokens
    engine = engine_of(model)
    engine.submit(prompt, 14, denoise_steps=2, tag="x")
    for _ in range(7):
        engine.step()
    (entry,) = engine.drain()
    assert 0 < len(entry["emitted"]) < 14 and (len(prompt) + len(entry["emitted"])) % W == 0
    successor = engine_of(model)
    rid = successor.submit(entry["prompt"] + entry["emitted"], entry["remaining"], denoise_steps=2)
    assert successor.run()[rid] == oracle(model, prompt, 14, 2)[0]


def test_the_host_tier_round_trip_resumes_at_a_block_boundary(model):
    engine = engine_of(model, num_blocks=8, max_slots=3, max_blocks_per_seq=6, block_size=4, prefill_chunk=4, host_blocks=16)
    requests = requests_of(np.random.default_rng(12), [(9, 12, 2), (10, 11, 2), (11, 10, 4)])
    replies = run_all(engine, requests)
    s = engine.stats()
    assert s["preempted"] > 0 and s["tiering"]["demotions"] > 0 and s["tiering"]["promotions"] > 0
    for reply, (prompt, new, kw) in zip(replies, requests):
        assert reply.tokens == oracle(model, prompt, new, kw["denoise_steps"])[0]
    assert s["tiering"]["host_used"] == s["tiering"]["prefix_host_entries"]  # no request's blocks left in the tier: cold prefix chains alone


# ---------------------------------------------------------------------------
# what is refused, and what every other family keeps
# ---------------------------------------------------------------------------


def test_geometries_and_options_that_cannot_serve_blocks_are_refused(model):
    with pytest.raises(ValueError, match="verify window"):
        engine_of(model, spec_tokens=2)
    with pytest.raises(ValueError, match="multiples of the family's block_length"):
        engine_of(model, prefill_chunk=6)
    with pytest.raises(ValueError, match="multiples of the family's block_length"):
        engine_of(model, block_size=6, prefill_chunk=12)
    engine = engine_of(model)
    with pytest.raises(ValueError, match="denoise_steps"):
        engine.submit([1, 2, 3], 4, denoise_steps=5)
    assert len(dataclasses.fields(ServingConfig)) == 17  # no new field


@pytest.mark.parametrize("family", [llama, gpt2], ids=["llama", "gpt2"])
def test_every_other_family_keeps_its_programs(family):
    """The arguments they always took, the packed vector and the feed they always
    returned, ``stats()`` without a block key, ``CompletedRequest`` without pass numbers."""
    cfg_cls = llama.LlamaConfig if family is llama else gpt2.GPT2Config
    c = cfg_cls.tiny(dtype=jnp.float32)
    engine = ServingEngine(
        family.apply_cached, family.init_cache, family.init_params(c, jax.random.key(0)), c,
        ServingConfig(block_size=4, num_blocks=32, max_slots=2, max_blocks_per_seq=8, prefill_chunk=4))
    assert engine.programs.block == 1 and engine.programs.window == 1 and engine.block_length == 1
    tables, lengths = np.zeros((2, 2), np.int32), np.zeros((2,), np.int32)
    lanes = (tables, lengths, np.zeros((2, 1), np.int32), np.zeros((2,), np.int32), np.zeros((3,), np.int32), np.zeros((2,), np.int32))
    assert [a.shape for a in engine._idle_lanes(2)] == [a.shape for a in lanes]
    packed, feed, _ = jax.eval_shape(engine.programs.decode, engine.params, engine.cache.pool, *lanes)
    assert packed.shape == (2 + 2,) and feed.shape == (2 + 1,)
    chunk = (tables[0], np.int32(0), np.zeros((1, 4), np.int32), np.int32(1))
    leaves = len(jax.tree.leaves((engine.params, engine.cache.pool)))
    assert len(jax.make_jaxpr(engine.programs.decode)(engine.params, engine.cache.pool, *lanes).jaxpr.invars) == leaves + 6
    assert len(jax.make_jaxpr(engine.programs.decode_chunk)(engine.params, engine.cache.pool, *lanes, *chunk).jaxpr.invars) == leaves + 10
    prompt = np.arange(3, 10)
    rid = engine.submit(prompt, 5)
    out = engine.run()
    assert out[rid] == np.asarray(family.generate(engine.params, jnp.asarray(prompt)[None], c, 5))[0].tolist()
    (reply,) = engine.pop_finished()
    assert reply.token_passes == [] and not {"block_length", "denoise_slot_ticks", "commit_slot_ticks"} & set(engine.stats())
    assert "blocks" not in engine.stats()["settles"]


def test_the_block_programs_take_the_lanes_phase_as_inputs_of_one_program(model):
    engine = engine_of(model)
    s, leaves = 4, len(jax.tree.leaves((engine.params, engine.cache.pool)))
    lanes = engine._idle_lanes(2)
    assert [a.shape for a in lanes] == [(s, 2), (s,), (s, W), (s,), (s, W), (s,), (s,), (s,)]
    packed, feed, _ = jax.eval_shape(engine.programs.decode, engine.params, engine.cache.pool, *lanes)
    from accelerate_tpu.serving.programs import DISPATCH_COUNTERS

    counters = len(DISPATCH_COUNTERS)  # behind the states and the ok flags: one layout for every family that counts
    assert packed.shape == (s * W + s + counters,) and feed.shape == (s, W)
    chunk = (np.zeros((2,), np.int32), np.int32(0), np.zeros((1, 8), np.int32), np.int32(8))
    packed, feed, _ = jax.eval_shape(engine.programs.decode_chunk, engine.params, engine.cache.pool, *lanes, *chunk)
    assert packed.shape == (s * W + s + 2 + counters,) and feed.shape == (s, W)
    assert engine.programs.decode.__wrapped__.__name__ == "decode" and engine.programs.decode_chunk.__wrapped__.__name__ == "decode_chunk"
    assert len(jax.make_jaxpr(engine.programs.decode)(engine.params, engine.cache.pool, *lanes).jaxpr.invars) == leaves + 8
    text = jax.jit(engine.programs.decode).lower(engine.params, engine.cache.pool, *lanes).as_text(debug_info=True)
    assert "head.unmask" in text and "attn.block" in text and "moe.experts" in text


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


def test_the_tick_record_says_how_many_lanes_denoised_and_committed(model, tmp_path):
    engine = engine_of(model, trace=True, trace_dir=str(tmp_path))
    replies = run_all(engine, requests_of(np.random.default_rng(13), [(8, 8, 2), (9, 12, 4), (12, 9, 1)]))
    slow = engine.stats()["slow_ticks"]
    assert slow and all({"denoising", "committing"} <= set(t) for t in slow)
    assert all(t["denoising"] + t["committing"] == t["live"] for t in slow)
    assert any(t["committing"] for t in slow) and any(t["denoising"] for t in slow)
    traces = {t.rid: t for t in engine.tracer.traces()}
    decode = [iv for iv in traces[replies[0].id].intervals if iv.phase == "decode" or iv.meta.get("kind") == "decode"]
    s = engine.stats()
    assert decode and all("denoising" in iv.meta and "committing" in iv.meta for iv in decode)
    assert sum(iv.meta["ticks"] for iv in decode) == 5  # the first request's 4 denoising ticks and 1 commit (a first tick at a fresh width is compile_in_path's)
    assert s["denoise_slot_ticks"] + s["commit_slot_ticks"] == engine.decode_slot_ticks


def test_the_tick_account_of_a_block_family(model):
    """Two requests admitted in tick 1 (blocks of 4, 4 slots, chunks of 8).  A (13-token prompt, two passes a block)
    prefills 8 and 4 rows in ticks 1 and 2 and denoises its first block in ticks 3 and 4; B (8-token prompt, one pass a
    block) prefills in tick 3, riding with A's lane, and denoises its first block in tick 4.  Tick 5 reads tick 4 and
    yields both first blocks at once: each was held four ticks, A's rows rode in all four, B's in two.  A lane's rows
    of a dispatch are its block, whatever the pass unmasks; every read says which tick it read."""
    with recorded_spans() as spans:
        engine = engine_of(model)
        run_all(engine, requests_of(np.random.default_rng(1), [(13, 10, 2), (8, 7, 1)]))
    ticks = [s for s in spans if s.name == "serving.tick"]
    rows = [(t.meta["rows_live"], t.meta["rows_computed"], t.meta["mixed"]) for t in ticks]
    assert rows[:5] == [(8, 24, 0), (4, 24, 0), (4 + 8, 24, 1), (8, 16, 0), (8, 16, 0)]
    assert all(0 < t.meta["width_lanes"] <= t.meta["width"] for t in ticks[2:]) and ticks[0].meta["width_lanes"] == 0
    emits = [s for s in spans if s.name == "serving.tick.decode.emit" and "first_tokens" in s.meta]
    (first,) = emits
    assert first.meta == {"tick": 5, "tokens": 3 + 4, "first_tokens": 2, "held_ticks": 4 + 4, "own_ticks": 4 + 2}
    assert all("first_tokens" not in s.meta for s in spans if s.name == "serving.tick.prefill.emit")  # a chunk yields no token
    reads = [s for s in spans if s.name == "serving.tick.read"]
    assert all(set(s.meta) <= {"tick", "of", "settle"} for s in reads)  # the expert counters stand in stats(), not here
    assert [(s.meta["tick"], s.meta["of"]) for s in reads[:4]] == [(2, 1), (3, 2), (4, 3), (5, 4)]


def test_telemetry_counters_follow_the_engines(model, tmp_path):
    from accelerate_tpu.telemetry import get_telemetry

    tel = get_telemetry()
    tel.enable(dir=str(tmp_path))
    try:
        engine = engine_of(model)
        run_all(engine, requests_of(np.random.default_rng(14), [(8, 8, 2), (13, 6, 4)]))
        s = engine.stats()
        snap = tel.registry.snapshot()
        for name in ("denoise_slot_ticks", "commit_slot_ticks", "blocks_committed", "block_tokens_emitted"):
            assert snap["serving." + name] == s[name] > 0
    finally:
        tel.disable()


# ---------------------------------------------------------------------------
# the benchmark's check at the tiny preset
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def checked():
    """A sound engine's replies through the benchmark driver's comparison, with every control of the family."""
    fam = load_by_path("chipbench_families_sdar_moe", "chipbench", "families", "sdar_moe.py")
    sys.path.insert(0, os.path.join(ROOT, "chipbench"))
    driver = load_by_path("chipbench_drivers_serve_closed_blocks", "chipbench", "drivers", "serve_closed_blocks.py")
    cfg = {
        "hidden_size": 64, "moe_intermediate_size": 32, "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
        "vocab_size": 256, "max_position_embeddings": 256, "rms_norm_eps": 1e-6, "rope_theta": 1e6, "torch_dtype": "float32",
        "assumed": {"block_length": 4, "mask_token_id": 255, "norm_scale_std": 0.1},
    }
    params = fam.seeded_params(cfg, 2**31 + 7)
    engine = engine_of((fam.program_config(cfg, remat=False), params))
    replies = run_all(engine, requests_of(np.random.default_rng(15), [(13, 22, 2), (8, 17, 4), (21, 20, 3), (6, 24, 2)]))
    return fam, driver, driver.block_gap_rows(fam, cfg, params, replies, fam.CONTROLS)


def test_a_sound_engine_reads_zero_on_all_four_numbers(checked):
    _, driver, rows = checked
    stats = driver.gap_stats(rows["sound"])
    assert len(rows["sound"]["served"]) == 19 + 16 + 19 + 22 and len(rows["sound"]["position"]) >= 15  # the new tokens in whole blocks; passes with a choice
    assert max(rows["sound"]["served"]) < 1e-4 and max(rows["sound"]["position"]) < 1e-4
    assert stats == {"served_gap_mean": pytest.approx(0, abs=1e-5), "served_gap_share": 0.0,
                     "position_gap_mean": pytest.approx(0, abs=1e-5), "position_gap_share": 0.0}


@pytest.mark.parametrize("control", ["fp8", "causal_in_block", "stale_commit", "no_head_norms", "unnormalised", "skip_layer"])
def test_every_control_fails_the_check(checked, control):
    fam, driver, rows = checked
    assert set(fam.CONTROLS) == {"fp8", "causal_in_block", "stale_commit", "no_head_norms", "unnormalised", "skip_layer"}
    stats = driver.gap_stats(rows[control])
    assert stats["served_gap_mean"] > 0.01 and stats["served_gap_share"] > 0.05, stats  # a sound engine reads 0 and 0
