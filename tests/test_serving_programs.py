"""``serving/programs.py``: one forward per cache back end, three heads, and
the family as the only thing that decides the back end.

The engine's token-identity matrices (``test_serving.py``,
``test_spec_serving.py``, ``test_serving_tiering.py``) hold both back ends to
the offline oracle's tokens.  Here they are held to each other one level
down: the logits a forward returns and the pool its rows leave, over a pool
of arbitrary contents, for every kind of dispatch the tick makes."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from conftest import without_apply_paged

from accelerate_tpu.models import deepseek_v3, gpt2, llama, mixtral
from accelerate_tpu.models.generation import make_paged_pool
from accelerate_tpu.serving import ServingConfig, ServingEngine
from accelerate_tpu.serving import programs as P

BLOCK, BLOCKS, WIDTH, CHUNK, WINDOW = 4, 24, 4, 8, 3
SERVING = ServingConfig(block_size=BLOCK, num_blocks=BLOCKS, max_slots=4, max_blocks_per_seq=WIDTH, prefill_chunk=CHUNK)


def _family(name, quant):
    if name == "gpt2":
        family, cfg = gpt2, gpt2.GPT2Config.tiny(dtype=jnp.float32, kv_cache_quant=quant)
    else:
        family, cfg = llama, llama.LlamaConfig.tiny(dtype=jnp.float32, kv_cache_quant=quant)
        assert cfg.num_kv_heads < cfg.num_heads  # grouped queries: what gpt2 never exercises
    return family, cfg, family.init_params(cfg, jax.random.key(0))


def _random_pool(family, cfg, seed):
    """A pool whose every row holds something: what a forward reads through its tables is context, what
    it does not read must not matter."""
    pool = make_paged_pool(family.init_cache, cfg, BLOCKS, BLOCK)
    rng = np.random.default_rng(seed)
    out = {}
    for name, leaf in pool.items():
        if leaf.dtype == jnp.int8:
            out[name] = jnp.asarray(rng.integers(-127, 128, leaf.shape), jnp.int8)
        elif name.endswith("_scale"):
            out[name] = jnp.asarray(rng.uniform(0.002, 0.02, leaf.shape), leaf.dtype)
        else:
            out[name] = jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype)
    return out


def _dispatch(kind, vocab, seed):
    """(tables [B, M], starts [B], tokens [B, T]) of one dispatch of the tick."""
    rng = np.random.default_rng(seed)
    blocks = rng.permutation(BLOCKS - 1) + 1  # block 0 is the null block
    if kind == "prefill":  # one lane, a padded chunk at a start inside a block: 3 real tokens of 8
        tables = np.zeros((1, WIDTH), np.int32)
        tables[0, :4] = blocks[:4]
        tokens = np.zeros((1, CHUNK), np.int32)
        tokens[0, :3] = rng.integers(0, vocab, 3)
        return tables, np.asarray([5], np.int32), tokens
    # four lanes as a tick batches them: ragged lengths, one lane at a block's first row, one dead lane (no blocks)
    starts = np.asarray([9, 4, 0, 13], np.int32)
    t = 1 if kind == "decode" else WINDOW
    tables = np.zeros((4, WIDTH), np.int32)
    for lane, owned in enumerate((3, 2, 0, 4)):
        tables[lane, :owned] = blocks[4 * lane : 4 * lane + owned]
    starts[3] = 16 - t  # the widest lane writes its table's last rows
    return tables, starts, rng.integers(0, vocab, (4, t)).astype(np.int32)


def _assert_pools_match(got, want):
    for name in want:
        a, b = np.asarray(got[name]), np.asarray(want[name])
        if a.dtype == np.int8:  # a code may round the other way where the two forwards differ in the last ulp
            assert np.abs(a.astype(np.int32) - b.astype(np.int32)).max() <= 1, name
        else:
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("kind", ["decode", "verify", "prefill"])
@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("name", ["gpt2", "llama_gqa"])
def test_the_two_forwards_agree_on_logits_and_pool(name, quant, kind):
    family, cfg, params = _family(name, quant)
    pool = _random_pool(family, cfg, seed=3)
    tables, starts, tokens = _dispatch(kind, cfg.vocab_size, seed=5)
    paged = jax.jit(P._paged_forward(family.apply_paged, cfg))
    dense = jax.jit(P._dense_forward(family.apply_cached, cfg, list(pool)))
    logits_p, counters_p, rows_p = paged(params, pool, tables, starts, tokens)
    logits_d, counters_d, rows_d = dense(params, pool, tables, starts, tokens)
    assert not counters_p and not counters_d
    assert logits_p.shape == tokens.shape + (cfg.vocab_size,)
    live = tables[:, 0] != 0  # a dead lane reads the null block alone: its logits are nobody's
    np.testing.assert_allclose(np.asarray(logits_p)[live], np.asarray(logits_d)[live], rtol=2e-4, atol=2e-4)
    assert set(rows_p) == set(rows_d) == set(pool)
    count = tokens.shape[1]
    new_p = P._write_rows(pool, rows_p, tables, starts, count)
    new_d = P._write_rows(pool, rows_d, tables, starts, count)
    _assert_pools_match(new_p, new_d)
    # and the rows landed: every position a live lane wrote differs from what the pool held
    leaf = "k"
    for lane in np.flatnonzero(live):
        for pos in range(int(starts[lane]), int(starts[lane]) + count):
            blk, off = tables[lane, pos // BLOCK], pos % BLOCK
            assert not np.array_equal(np.asarray(new_p[leaf])[:, blk, off], np.asarray(pool[leaf])[:, blk, off])


@pytest.mark.parametrize("backend", ["paged", "dense"])
@pytest.mark.parametrize("name", ["gpt2", "llama_gqa"])
def test_greedy_is_the_draftless_case_of_the_verify_head(name, backend):
    """A verify window whose lanes carry no draft emits, in its first column, the token the decode head emits,
    accepts nothing, and writes the same first row."""
    family, cfg, params = _family(name, quant=False)
    apply_cached = family.apply_cached if backend == "paged" else without_apply_paged(family)
    built = P.build_programs(apply_cached, cfg, ["k", "v"], SERVING, spec_tokens=WINDOW - 1)
    assert built.backend == backend and built.decode_spec is not None
    tables, starts, tokens = _dispatch("verify", cfg.vocab_size, seed=7)
    tokens[:, 1:] = 0  # no drafts: the window is the last token and padding
    next_tok, ok, pool_1 = built.decode(params, _random_pool(family, cfg, seed=3), tables, starts, tokens[:, 0])
    t, m, ok_w, pool_w = built.decode_spec(
        params, _random_pool(family, cfg, seed=3), tables, starts, tokens, np.zeros((4,), np.int32))
    live = tables[:, 0] != 0
    assert np.asarray(m).tolist() == [0, 0, 0, 0]
    assert np.asarray(t)[live, 0].tolist() == np.asarray(next_tok)[live].tolist()
    assert np.asarray(ok)[live].all() and np.asarray(ok_w)[live].all()
    for lane in np.flatnonzero(live):
        blk, off = tables[lane, starts[lane] // BLOCK], starts[lane] % BLOCK
        for leaf in ("k", "v"):
            np.testing.assert_allclose(np.asarray(pool_w[leaf])[:, blk, off], np.asarray(pool_1[leaf])[:, blk, off],
                                       rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("family,config,backend", [
    (gpt2, gpt2.GPT2Config.tiny(), "paged"),
    (llama, llama.LlamaConfig.tiny(), "paged"),
    (deepseek_v3, deepseek_v3.DeepseekV3Config.tiny(), "paged"),
    (mixtral, mixtral.MixtralConfig.tiny(), "dense"),
    (None, gpt2.GPT2Config.tiny(), "dense"),
], ids=["gpt2", "llama", "deepseek_v3", "mixtral", "gpt2_wrapped"])
def test_the_family_decides_the_back_end(family, config, backend):
    apply_cached = without_apply_paged(gpt2) if family is None else family.apply_cached
    built = P.build_programs(apply_cached, config, ["k", "v"], SERVING, spec_tokens=0)
    assert built.backend == backend and built.decode_spec is None
    # what the tick asks of the back end: the table width of a dispatch, the blocks a decode gathers
    assert [built.table_width(n) for n in (1, 2, 3, 4, 9)] == ([1, 2, 4, 4, 4] if backend == "paged" else [WIDTH] * 5)
    assert built.gathered_blocks([2, 1]) == (3 if backend == "paged" else SERVING.max_slots * WIDTH)


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
def test_mixtral_through_the_engine_matches_its_offline_generate(quant):
    """The dense back end's one production use.  Capacity routing counts the tokens of a call, so the
    engine and the offline loop agree where they cut the prompt alike: a prompt of exactly one chunk."""
    cfg = mixtral.MixtralConfig.tiny(dtype=jnp.float32, kv_cache_quant=quant)
    params = mixtral.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, CHUNK)] for _ in range(3)]
    max_new = [5, 7, 4]
    eng = ServingEngine(mixtral.apply_cached, mixtral.init_cache, params, cfg, serving=dataclasses.replace(SERVING, max_slots=2))
    assert eng.decode_path == eng.stats()["decode_path"] == "dense"
    ids = {eng.submit(p, n): i for i, (p, n) in enumerate(zip(prompts, max_new))}
    outputs = eng.run(max_ticks=200)
    for rid, out in outputs.items():
        i = ids[rid]
        want = mixtral.generate(params, jnp.asarray([prompts[i]], jnp.int32), cfg, max_new_tokens=max_new[i])
        assert out == [int(t) for t in np.asarray(want[0])], f"request {i} diverged from mixtral.generate"
    assert eng.decode_dispatches <= eng.ticks and eng.cache.allocator.used_blocks == 0
    # the dense view is whole: every decode gathers every slot's full table
    assert eng.decode_gather_bytes == eng.decode_dispatches * 2 * WIDTH * eng.cache.block_bytes()
    assert eng.stats()["decode_bucket_widths"] == [WIDTH]


@pytest.mark.parametrize("option", [{"paged_kernel": True}, {"decode_path": "dense"}], ids=["paged_kernel", "decode_path"])
def test_no_option_selects_a_path(option):
    with pytest.raises(TypeError):
        ServingConfig(**option)
    assert len(dataclasses.fields(ServingConfig)) == 17
