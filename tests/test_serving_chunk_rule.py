"""The size of a tick's one prefill chunk is the engine's to choose
(``serving/engine.py:resolve_prefill_chunk``): as many rows as the decoders'
dispatch carries for free on the device it runs on, 32 where no row is free (a
family with routed experts) or nothing is known (off the TPU, an unknown device
kind), an integer kept as given.  The rule is a pure function, so a CPU asks it
about a v5e; what it resolves to is what the scheduler, the programs and
``stats()`` read; and the tokens served are the offline loop's at every size."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import llama
from accelerate_tpu.serving import ServingConfig, ServingEngine
from accelerate_tpu.serving import engine as engine_module
from accelerate_tpu.serving.blocks import BlockAllocator
from accelerate_tpu.serving.engine import DEFAULT_PREFILL_CHUNK, resolve_prefill_chunk
from accelerate_tpu.serving.scheduler import Request, Scheduler
from accelerate_tpu.telemetry import ridge_rows

V5E = "TPU v5 lite"
# What the chat cell's geometry (sixteen lanes of one row) resolves to on a v5e: CHUNK_RIDGE_FRACTION's comment has why.
CHAT_CHUNK = 64

# The four serving cells of BENCHMARK.json, by the `serve` section and the expert count of their configuration files
# (chipbench/configs/*.json), and what else the rule can meet.
GEOMETRIES = {
    "chat": (dict(device_kind=V5E, max_slots=16, window=1, block_size=16), CHAT_CHUNK),
    "kanana": (dict(device_kind=V5E, max_slots=16, window=1, block_size=16, routed_experts=128), 32),
    "lfm2": (dict(device_kind=V5E, max_slots=32, window=1, block_size=16, routed_experts=32), 32),
    "sdar": (dict(device_kind=V5E, max_slots=32, window=4, block_size=16, block_length=4, routed_experts=128), 32),
    "cpu": (dict(device_kind="cpu", max_slots=16, window=1, block_size=16), 32),
    "unknown-kind": (dict(device_kind="TPU v9 mega", max_slots=16, window=1, block_size=16), 32),
    "lanes-fill-the-share": (dict(device_kind=V5E, max_slots=64, window=1, block_size=16), 32),
    "verify-window": (dict(device_kind=V5E, max_slots=8, window=4, block_size=16), 32),
    "few-lanes": (dict(device_kind=V5E, max_slots=4, window=1, block_size=16), 64),
    "dense-blocks-of-8": (dict(device_kind=V5E, max_slots=2, window=8, block_size=16, block_length=8), 64),
    "wide-pool-blocks": (dict(device_kind=V5E, max_slots=8, window=1, block_size=64), 64),
    "v6e": (dict(device_kind="TPU v6 lite", max_slots=16, window=1, block_size=16), 160),
}


def test_the_ridge_is_peak_flops_over_hbm_bytes():
    assert ridge_rows(V5E) == pytest.approx(197e12 / 819e9) and 240 < ridge_rows(V5E) < 241
    assert ridge_rows("TPU v5p") == pytest.approx(459e12 / 2765e9)  # "v5 lite" is looked for before "v5"
    assert ridge_rows("cpu") is None and ridge_rows("TPU v9 mega") is None


@pytest.mark.parametrize("name", GEOMETRIES)
def test_the_rule_by_geometry(name):
    geometry, want = GEOMETRIES[name]
    got = resolve_prefill_chunk(None, **geometry)
    assert got == want
    assert got % DEFAULT_PREFILL_CHUNK == 0
    if got != DEFAULT_PREFILL_CHUNK:  # a chunk the rule chose holds whole pool blocks and whole blocks of a block family
        assert got % geometry["block_size"] == 0 and got % geometry.get("block_length", 1) == 0
        rows = geometry["max_slots"] * geometry["window"]
        share = engine_module.CHUNK_RIDGE_FRACTION * ridge_rows(geometry["device_kind"])
        step = math.lcm(DEFAULT_PREFILL_CHUNK, geometry["block_size"], geometry.get("block_length", 1))
        assert rows + got < share <= rows + got + step  # the largest such multiple under the share


@pytest.mark.parametrize("given", [1, 8, 32, 48, 4096])
@pytest.mark.parametrize("name", ["chat", "sdar", "cpu"])
def test_an_integer_is_kept_as_given(name, given):
    assert resolve_prefill_chunk(given, **GEOMETRIES[name][0]) == given


def _tiny(max_seq_len=256):
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, max_seq_len=max_seq_len)
    return cfg, llama.init_params(cfg, jax.random.key(0))


def _engine(cfg, params, **serving):
    kw = dict(block_size=16, num_blocks=64, max_slots=3, max_blocks_per_seq=16, prefix_cache=False)
    kw.update(serving)
    return ServingEngine(llama.apply_cached, llama.init_cache, params, cfg, serving=ServingConfig(**kw))


def test_the_engine_holds_the_resolved_integer(monkeypatch):
    cfg, params = _tiny()
    given = ServingConfig(block_size=16, num_blocks=64, max_slots=16, max_blocks_per_seq=16)
    assert given.prefill_chunk is None
    # on this CPU nothing is known to be free: the programs every default engine of the suite has
    eng = ServingEngine(llama.apply_cached, llama.init_cache, params, cfg, serving=given)
    assert eng.serving.prefill_chunk == eng.stats()["prefill_chunk"] == eng.sched.prefill_chunk == 32
    # the same configuration, asked as on a v5e (the rule takes the device's kind as an argument; the engine hands it over)
    monkeypatch.setattr(engine_module, "_device_kind", lambda: V5E)
    eng = ServingEngine(llama.apply_cached, llama.init_cache, params, cfg, serving=given)
    assert eng.serving.prefill_chunk == eng.stats()["prefill_chunk"] == eng.sched.prefill_chunk == CHAT_CHUNK
    assert given.prefill_chunk is None, "the caller's configuration is left as given: it may build another engine"
    rng = np.random.default_rng(5)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 150)]
    rid = eng.submit(prompt, 6)
    out = eng.run(max_ticks=100)[rid]
    assert eng.stats()["prefill_dispatches"] == math.ceil(150 / CHAT_CHUNK)  # the chunk buffer and the build read it too
    want = llama.generate(params, jnp.asarray([prompt], jnp.int32), cfg, max_new_tokens=6)
    assert out == [int(t) for t in np.asarray(want[0])]
    # an integer is a request, kept, on any device
    assert _engine(cfg, params, prefill_chunk=8).stats()["prefill_chunk"] == 8


def test_a_family_with_routed_experts_keeps_32_on_a_v5e(monkeypatch):
    """The engine's test is structural: the model config counts routed experts, under either name the families use."""
    from accelerate_tpu.models import deepseek_v3, lfm2_moe

    monkeypatch.setattr(engine_module, "_device_kind", lambda: V5E)
    for family in (deepseek_v3, lfm2_moe):
        config_cls = next(v for k, v in vars(family).items() if k.endswith("Config") and hasattr(v, "tiny"))
        cfg = config_cls.tiny(dtype=jnp.float32)
        assert engine_module._routed_experts(cfg) > 0
        eng = ServingEngine(
            family.apply_cached, family.init_cache, family.init_params(cfg, jax.random.key(0)), cfg,
            serving=ServingConfig(block_size=16, num_blocks=32, max_slots=4, max_blocks_per_seq=4),
        )
        assert eng.serving.prefill_chunk == eng.stats()["prefill_chunk"] == 32
    assert engine_module._routed_experts(_tiny()[0]) == 0


@pytest.mark.parametrize("chunk", [32, 64, 96])
def test_the_tokens_are_generates_at_every_chunk_size(chunk):
    """Chunked prefill is token-identical whatever the chunk: prompts under one chunk, of whole chunks and with a
    padded last chunk, prefilled while other lanes decode."""
    cfg, params = _tiny(max_seq_len=512)
    eng = _engine(cfg, params, prefill_chunk=chunk, max_blocks_per_seq=20)  # 192 + 3 rows reserve 288 at a chunk of 96
    rng = np.random.default_rng(chunk)
    lengths, new = (5, 70, 192, 33, 150, 96), (6, 9, 4, 7, 12, 5)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)] for n in lengths]
    ids = [eng.submit(p, m) for p, m in zip(prompts, new)]
    out = eng.run(max_ticks=500)
    stats = eng.stats()
    assert stats["prefill_chunk"] == chunk and stats["mixed_dispatches"] > 0
    assert stats["prefill_dispatches"] == sum(math.ceil(n / chunk) for n in lengths)
    for rid, prompt, m in zip(ids, prompts, new):
        want = llama.generate(params, jnp.asarray([prompt], jnp.int32), cfg, max_new_tokens=m)
        assert out[rid] == [int(t) for t in np.asarray(want[0])], f"chunk {chunk}: a prompt of {len(prompt)} diverged"
    assert eng.cache.allocator.used_blocks == 0


@pytest.mark.parametrize("chunk", [32, CHAT_CHUNK, 96])
def test_the_chat_geometry_admits_its_longest_request(chunk):
    """``max_rows`` rounds a request's budget up to a chunk boundary: at the chat cell's geometry (256 blocks of 16 a
    sequence) the longest request of its traffic, 2,048 + 384 rows, is admitted under the resolved chunk."""
    sched = Scheduler(BlockAllocator(8192), num_slots=16, block_size=16, max_blocks_per_seq=256, prefill_chunk=chunk)
    longest = Request(list(range(2048)), 384)
    rows = sched.max_rows(longest)
    assert rows == math.ceil((2048 + 383) / chunk) * chunk <= 256 * 16
    assert rows == {32: 2432, 64: 2432, 96: 2496}[chunk]  # a larger chunk reserves at most chunk - 1 rows more
    sched.validate(longest)
    with pytest.raises(ValueError, match="max_blocks_per_seq"):
        sched.validate(Request(list(range(4096 - 383 + 1)), 384))
