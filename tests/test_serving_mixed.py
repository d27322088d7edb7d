"""One dispatch a tick: a tick that holds a prefilling slot's chunk and live
decoding lanes issues ONE program (``programs.decode_chunk``), whose forward
runs everything that does not look at the cache once over all its rows.

The tokens served are the tokens of the two dispatches it replaces and of the
offline ``generate``, for every family the engine serves, with and without a
verify window, over an fp and an int8 pool; preemption, quarantine and the
per-width compiles behave as they did with two dispatches a tick; the three
dispatch counters add up."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from conftest import without_apply_paged

from accelerate_tpu import telemetry
from accelerate_tpu.models import deepseek_v3, gpt2, llama
from accelerate_tpu.resilience import faultinject
from accelerate_tpu.serving import ServingConfig, ServingEngine
from accelerate_tpu.serving.scheduler import RequestState
from accelerate_tpu.telemetry import CompileWatcher

FAMILIES = {  # name -> (the family, what it is served through)
    "gpt2": (gpt2, gpt2.apply_cached),
    "llama": (llama, llama.apply_cached),
    "deepseek_v3": (deepseek_v3, deepseek_v3.apply_cached),
    "dense": (gpt2, without_apply_paged(gpt2)),  # the family decides the back end: this one has no apply_paged
}
CONFIGS = {"gpt2": gpt2.GPT2Config, "llama": llama.LlamaConfig, "deepseek_v3": deepseek_v3.DeepseekV3Config, "dense": gpt2.GPT2Config}
PROMPT_LENGTHS, NEW_TOKENS = (5, 19, 9, 30, 12, 3), (6, 9, 4, 7, 12, 5)


@pytest.fixture(autouse=True)
def _telemetry_clean():
    yield
    telemetry.disable()
    telemetry.get_telemetry().registry.reset()


def _setup(name, quant=False):
    family, apply_cached = FAMILIES[name]
    kw = {"kv_cache_quant": True} if quant else {}
    cfg = CONFIGS[name].tiny(dtype=jnp.float32, **kw)
    return family, apply_cached, cfg, family.init_params(cfg, jax.random.key(0))


def _engine(family, apply_cached, cfg, params, **overrides):
    kw = dict(block_size=4, num_blocks=64, max_slots=3, max_blocks_per_seq=16, prefill_chunk=8, prefix_cache=False)
    kw.update(overrides)
    return ServingEngine(apply_cached, family.init_cache, params, cfg, serving=ServingConfig(**kw))


def _oracle(family, cfg, params, prompt, new):
    out = family.generate(params, jnp.asarray([prompt], jnp.int32), cfg, max_new_tokens=new)
    return [int(t) for t in np.asarray(out[0])]


def _prompts(cfg, lengths=PROMPT_LENGTHS, seed=3):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, cfg.vocab_size, n)] for n in lengths]


def _dispatches(stats):
    return stats["prefill_dispatches"] + stats["decode_dispatches"] - stats["mixed_dispatches"]


@pytest.mark.parametrize("name,spec,quant", [
    (name, spec, quant)
    for name in FAMILIES for spec in (0, 2) for quant in (False, True)
    if not (quant and name == "deepseek_v3")  # the latent cache has no int8 form
], ids=lambda v: {False: "fp", True: "int8", 0: "greedy", 2: "spec"}.get(v, v) if not isinstance(v, str) else v)
def test_mixed_ticks_serve_the_tokens_of_separate_dispatches_and_of_generate(name, spec, quant):
    """Each request run alone (every dispatch a chunk alone or the lanes alone: the two dispatches a tick used to
    make), then all together on the same engine (a chunk rides with the lanes in nearly every tick): token for
    token the same, and the offline loop's; and the second phase, meeting no new table width, compiles nothing."""
    family, apply_cached, cfg, params = _setup(name, quant)
    eng = _engine(family, apply_cached, cfg, params, spec_tokens=spec)
    assert eng.decode_path == ("dense" if name == "dense" else "paged")
    prompts = _prompts(cfg)
    apart = []
    for prompt, new in zip(prompts, NEW_TOKENS):
        rid = eng.submit(prompt, new)
        apart.append(eng.run(max_ticks=200)[rid])
    alone = eng.stats()
    assert alone["mixed_dispatches"] == 0 and _dispatches(alone) == alone["ticks"]
    executables = (eng.programs.decode._cache_size(), eng.programs.decode_chunk._cache_size())
    watcher = CompileWatcher()
    ids = [eng.submit(prompt, new) for prompt, new in zip(prompts, NEW_TOKENS)]
    together = eng.run(max_ticks=500)
    watcher.stop()
    stats = eng.stats()
    assert stats["mixed_dispatches"] > 0
    assert _dispatches(stats) == stats["ticks"]  # one dispatch a tick, whatever it held
    for i, rid in enumerate(ids):
        assert together[rid] == apart[i], f"request {i}: a mixed tick served other tokens than separate dispatches"
        assert together[rid] == _oracle(family, cfg, params, prompts[i], NEW_TOKENS[i]), f"request {i} diverged from generate"
    assert (eng.programs.decode._cache_size(), eng.programs.decode_chunk._cache_size()) == executables
    assert watcher.count == 0, "a tick under load compiled at a table width the warm-up had met"
    assert eng.cache.allocator.used_blocks == 0


def test_a_fresh_width_compiles_both_programs_at_once():
    """The first dispatch at a table width leaves both programs compiled at it, whichever met it: a chunk alone
    (as a warm-up of single requests meets it) pays for the decode at that width too, and the reverse."""
    family, apply_cached, cfg, params = _setup("gpt2")
    eng = _engine(family, apply_cached, cfg, params)
    eng.submit(_prompts(cfg, (5,))[0], 2)
    eng.step()  # one chunk alone, at width 2
    assert eng.stats()["prefill_dispatches"] == 1 and eng.stats()["decode_dispatches"] == 0
    assert eng.programs.decode._cache_size() == eng.programs.decode_chunk._cache_size() == 1
    assert eng._warm_widths == {2} and eng.stats()["decode_bucket_widths"] == []
    eng.run(max_ticks=50)
    assert eng.stats()["decode_bucket_widths"] == [2]
    assert eng.programs.decode._cache_size() == eng.programs.decode_chunk._cache_size() == 1


def test_the_prefilling_slot_preempted_by_a_decoders_growth_drops_its_chunk():
    """The chunk is built before the lanes: when growing an older decoder takes the prefilling slot's blocks, the
    tick dispatches the lanes alone, the chunk's request re-queues, and everybody's tokens are the oracle's."""
    family, apply_cached, cfg, params = _setup("gpt2")
    eng = _engine(family, apply_cached, cfg, params, num_blocks=11, max_slots=2, max_blocks_per_seq=10, prefill_chunk=4)
    old, young = _prompts(cfg, (6, 22), seed=5)
    built, build_chunk = [], eng._build_chunk
    eng._build_chunk = lambda: built.append(build_chunk()) or built[-1]
    ids = {eng.submit(old, 24): (old, 24)}
    for _ in range(3):
        eng.step()
    ids[eng.submit(young, 5)] = (young, 5)
    dropped = dispatching = 0
    while not eng.sched.idle():
        before = eng.stats()
        eng.step()
        after = eng.stats()
        dispatching += _dispatches(after) - _dispatches(before)
        assert _dispatches(after) - _dispatches(before) <= 1
        if built[-1] is not None and after["prefill_dispatches"] == before["prefill_dispatches"]:
            dropped += 1
            assert after["preempted"] > before["preempted"] and after["decode_dispatches"] == before["decode_dispatches"] + 1
            assert built[-1].slot.request.state == RequestState.QUEUED
    assert dropped >= 1, "the pool was not tight enough: no chunk was dropped"
    stats = eng.stats()
    assert _dispatches(stats) == dispatching + 3 == stats["ticks"] and stats["mixed_dispatches"] > 0  # 3 ticks before the loop
    for c in eng.pop_finished():
        prompt, new = ids[c.id]
        assert c.status == "ok" and c.tokens == _oracle(family, cfg, params, prompt, new)
    assert eng.cache.allocator.used_blocks == 0


def _pool_is_finite(eng):
    return all(bool(jnp.all(jnp.isfinite(leaf))) for leaf in eng.cache.pool.values() if jnp.issubdtype(leaf.dtype, jnp.floating))


def test_a_poisoned_decode_lane_does_not_take_the_chunk_with_it(monkeypatch):
    """A NaN in one lane of a mixed dispatch quarantines that lane's request alone: the chunk that rode in the
    same forward is kept, its request and the other lane finish on the oracle's tokens."""
    monkeypatch.setenv("ACCELERATE_TPU_FAULT_SERVING_NAN_REQUEST", "2")
    faultinject.reload()
    try:
        family, apply_cached, cfg, params = _setup("gpt2")
        eng = _engine(family, apply_cached, cfg, params)
        prompts = _prompts(cfg, (5, 6, 30), seed=9)  # a survivor, the poisoned one, a prompt of four chunks
        ids = [eng.submit(p, 6) for p in prompts]
        mixed_at_quarantine = None
        while not eng.sched.idle():
            before = eng.quarantined_count
            eng.step()
            if eng.quarantined_count > before:
                mixed_at_quarantine = eng._tick["mixed"]
                slot = next(s for s in eng.sched.slots.values() if s.request.id == ids[2])
                assert slot.request.state == RequestState.PREFILLING and slot.cache_len > 0  # its chunk of this tick was kept
    finally:
        monkeypatch.delenv("ACCELERATE_TPU_FAULT_SERVING_NAN_REQUEST")
        faultinject.reload()
    assert mixed_at_quarantine is True, "the poisoned lane's dispatch held no chunk"
    done = {c.id: c for c in eng.pop_finished()}
    assert done[ids[1]].status == "quarantined" and eng.quarantined_count == 1
    for i in (0, 2):
        assert done[ids[i]].status == "ok" and done[ids[i]].tokens == _oracle(family, cfg, params, prompts[i], 6)
    assert done[ids[2]].prefill_dispatches == 4  # no chunk was run twice
    assert _pool_is_finite(eng) and eng.cache.allocator.used_blocks == 0


def test_a_poisoned_chunk_does_not_take_the_decode_lanes_with_it():
    """The reverse: the chunk's request reads a NaN out of its own blocks in a mixed dispatch; it is quarantined
    alone, and the lanes that shared the forward emit the oracle's tokens in that tick and after."""
    family, apply_cached, cfg, params = _setup("gpt2")
    eng = _engine(family, apply_cached, cfg, params)
    prompts = _prompts(cfg, (5, 30), seed=9)
    ids = [eng.submit(prompts[0], 9), eng.submit(prompts[1], 6)]
    eng.step()  # the survivor's one chunk
    eng.step()  # the long prompt's first chunk rides with the survivor's first decode
    slot = next(s for s in eng.sched.slots.values() if s.request.id == ids[1])
    assert slot.request.state == RequestState.PREFILLING and slot.cache_len == 8 and eng.stats()["mixed_dispatches"] == 1
    eng.cache.pool = {n: leaf.at[:, slot.blocks[0]].set(jnp.nan) for n, leaf in eng.cache.pool.items()}
    emitted = len(next(s for s in eng.sched.slots.values() if s.request.id == ids[0]).request.emitted)  # stats() settled: every token read
    eng.step()
    assert eng._tick["mixed"] and eng.quarantined_count == 0  # the flag is read one dispatch late
    assert eng.stats()["settles"] == {"stats": 2} and eng.quarantined_count == 1
    survivor = next(s for s in eng.sched.slots.values() if s.request.id == ids[0])
    assert len(survivor.request.emitted) == emitted + 1  # the lane's token of the poisoned dispatch was served
    assert _pool_is_finite(eng)  # the quarantine scrubbed the chunk's blocks and the null block
    eng.run(max_ticks=100)
    done = {c.id: c for c in eng.pop_finished()}
    assert done[ids[1]].status == "quarantined"
    assert done[ids[0]].status == "ok" and done[ids[0]].tokens == _oracle(family, cfg, params, prompts[0], 9)
    assert eng.cache.allocator.used_blocks == 0


def test_the_dispatch_counters_add_up_everywhere_they_are_published(tmp_path):
    """``mixed_dispatches`` in ``stats()``, as the telemetry counter and in the tracer's tick record; with the two
    older counters it gives the dispatches of a run: one a tick."""
    tel = telemetry.enable(dir=str(tmp_path))
    family, apply_cached, cfg, params = _setup("llama")
    eng = _engine(family, apply_cached, cfg, params, trace=True)
    for prompt, new in zip(_prompts(cfg), NEW_TOKENS):
        eng.submit(prompt, new)
    mixed_ticks = 0
    while not eng.sched.idle():
        eng.step()
        mixed_ticks += eng._tick["mixed"]
    stats, snap = eng.stats(), tel.registry.snapshot()
    assert stats["mixed_dispatches"] == mixed_ticks == snap["serving.mixed_dispatches"] > 0
    assert snap["serving.prefill_dispatches"] == stats["prefill_dispatches"]
    assert snap["serving.decode_dispatches"] == stats["decode_dispatches"]
    assert _dispatches(stats) == stats["ticks"]
    assert stats["mixed_dispatches"] <= min(stats["prefill_dispatches"], stats["decode_dispatches"])
    slow = stats["slow_ticks"]
    assert slow and all(isinstance(t["mixed"], bool) for t in slow) and any(t["mixed"] for t in slow)
