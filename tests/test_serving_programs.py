"""``serving/programs.py``: one forward per cache back end over the groups of a
tick, two heads, and the family as the only thing that decides the back end.

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
    (logits_p,), counters_p, (rows_p,) = paged(params, pool, ((tokens, tables, starts),))
    (logits_d,), counters_d, (rows_d,) = dense(params, pool, ((tokens, tables, starts),))
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


def _disjoint_mixed_dispatch(kind, vocab):
    """The lanes of ``kind`` and a chunk as one tick holds them: the chunk's blocks are no lane's."""
    tables, starts, tokens = _dispatch(kind, vocab, seed=5)
    chunk_tables, chunk_starts, chunk = _dispatch("prefill", vocab, seed=6)
    free = [b for b in range(1, BLOCKS) if b not in set(tables.ravel().tolist())]
    chunk_tables[0, :4] = free[:4]
    return (tokens, tables, starts), (chunk, chunk_tables, chunk_starts)


@pytest.mark.parametrize("backend", ["paged", "dense"])
@pytest.mark.parametrize("kind", ["decode", "verify"])
@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("name", ["gpt2", "llama_gqa"])
def test_a_forward_over_two_groups_is_the_two_forwards_side_by_side(name, quant, kind, backend):
    """The mixed dispatch's forward: the decoding lanes and a chunk in one call give, a group each, the logits and
    the written rows that each gives in a call of its own, whatever shares the matmuls."""
    family, cfg, params = _family(name, quant)
    pool = _random_pool(family, cfg, seed=3)
    groups = _disjoint_mixed_dispatch(kind, cfg.vocab_size)
    make = {"paged": lambda: P._paged_forward(family.apply_paged, cfg), "dense": lambda: P._dense_forward(family.apply_cached, cfg, list(pool))}
    forward = jax.jit(make[backend]())
    logits, counters, rows = forward(params, pool, groups)
    assert len(logits) == len(rows) == 2 and not counters
    new_pool = pool
    for group, got_logits, got_rows in zip(groups, logits, rows):
        tokens, tables, starts = group
        (want_logits,), _, (want_rows,) = forward(params, pool, (group,))
        assert got_logits.shape == tokens.shape + (cfg.vocab_size,)
        live = tables[:, 0] != 0
        np.testing.assert_allclose(np.asarray(got_logits)[live], np.asarray(want_logits)[live], rtol=2e-4, atol=2e-4)
        _assert_pools_match(P._write_rows(pool, got_rows, tables, starts, tokens.shape[1]),
                            P._write_rows(pool, want_rows, tables, starts, tokens.shape[1]))
        new_pool = P._write_rows(new_pool, got_rows, tables, starts, tokens.shape[1])
    # both groups' rows landed in the one pool: the chunk's first real row and a lane's
    (_, tables, starts), (_, chunk_tables, chunk_starts) = groups
    for tab, pos in ((tables[0], int(starts[0])), (chunk_tables[0], int(chunk_starts[0]))):
        blk, off = tab[pos // BLOCK], pos % BLOCK
        assert not np.array_equal(np.asarray(new_pool["k"])[:, blk, off], np.asarray(pool["k"])[:, blk, off])


def _run_program(built, program, family, cfg, params, lanes, chunk=None, feed=None):
    """One dispatch of ``program`` over a fresh random pool -> (unpacked read-back with the program's ``feed``, new
    pool).  ``feed``: the previous dispatch's feed and the lanes' sources; every lane reads the host's token without."""
    tokens, tables, starts = lanes
    lanes = tables.shape[0]
    args = [tables, starts, tokens, np.zeros((lanes,), np.int32)]
    args += [np.zeros((lanes + 1,), np.int32), np.zeros((lanes,), np.int32)] if feed is None else list(feed)
    if chunk is not None:
        chunk_tokens, chunk_tables, chunk_starts = chunk
        args += [chunk_tables[0], chunk_starts[0], chunk_tokens, np.int32(3)]
    packed, new_feed, pool = program(params, _random_pool(family, cfg, seed=3), *args)
    return dict(built.unpack(packed, with_chunk=chunk is not None), feed=np.asarray(new_feed)), pool


@pytest.mark.parametrize("backend", ["paged", "dense"])
@pytest.mark.parametrize("spec", [0, WINDOW - 1], ids=["greedy", "spec"])
@pytest.mark.parametrize("name", ["gpt2", "llama_gqa"])
def test_decode_chunk_is_decode_and_the_chunk_in_one_dispatch(name, spec, backend):
    """The mixed program returns what the two dispatches it replaces return: the lanes' tokens (and accepts) of
    ``decode``, the chunk's token and flag of a chunk run alone on idle lanes, and a pool with both groups' rows."""
    family, cfg, params = _family(name, quant=False)
    apply_cached = family.apply_cached if backend == "paged" else without_apply_paged(family)
    built = P.build_programs(apply_cached, cfg, ["k", "v"], SERVING, spec_tokens=spec)
    assert built.backend == backend and built.window == spec + 1
    lanes, chunk = _disjoint_mixed_dispatch("verify" if spec else "decode", cfg.vocab_size)
    idle = tuple(np.zeros_like(a) for a in lanes)
    mixed, pool_m = _run_program(built, built.decode_chunk, family, cfg, params, lanes, chunk)
    alone, pool_d = _run_program(built, built.decode, family, cfg, params, lanes)
    chunk_alone, pool_c = _run_program(built, built.decode_chunk, family, cfg, params, idle, chunk)
    live = lanes[1][:, 0] != 0
    assert mixed["tokens"].shape == (4, spec + 1)
    assert mixed["tokens"][live].tolist() == alone["tokens"][live].tolist()
    assert mixed["accepts"][live].tolist() == alone["accepts"][live].tolist()
    assert mixed["ok"][live].all() and mixed["chunk_ok"][0] == 1
    assert mixed["chunk_token"].tolist() == chunk_alone["chunk_token"].tolist()
    assert not mixed["counters"].size  # no expert family here
    # the lanes' rows as decode wrote them, the chunk's as the chunk alone wrote them
    (_, tables, starts), (_, chunk_tables, chunk_starts) = lanes, chunk
    for lane in np.flatnonzero(live):
        blk, off = tables[lane, starts[lane] // BLOCK], starts[lane] % BLOCK
        np.testing.assert_allclose(np.asarray(pool_m["k"])[:, blk, off], np.asarray(pool_d["k"])[:, blk, off], rtol=2e-5, atol=2e-5)
    for pos in range(int(chunk_starts[0]), int(chunk_starts[0]) + 3):
        blk, off = chunk_tables[0, pos // BLOCK], pos % BLOCK
        np.testing.assert_allclose(np.asarray(pool_m["v"])[:, blk, off], np.asarray(pool_c["v"])[:, blk, off], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backend", ["paged", "dense"])
@pytest.mark.parametrize("spec", [0, WINDOW - 1], ids=["greedy", "spec"])
def test_both_programs_return_one_feed_and_read_it_where_the_source_says(spec, backend):
    """The feed that keeps the decoders' tokens on the device: int32 ``[max_slots + 1]`` from ``decode`` and from
    ``decode_chunk`` alike (every lane's next token, then the chunk's token, 0 without a chunk), so either's output is
    the other's input.  A lane whose ``source`` names its own entry, or the chunk's, computes what it computes from
    the same token passed by the host, and a lane on ``FEED_HOST`` ignores the feed; passing a program's own output
    back compiles nothing more at the width."""
    family, cfg, params = _family("llama_gqa", quant=False)
    apply_cached = family.apply_cached if backend == "paged" else without_apply_paged(family)
    built = P.build_programs(apply_cached, cfg, ["k", "v"], SERVING, spec_tokens=spec)
    lanes, chunk = _disjoint_mixed_dispatch("verify" if spec else "decode", cfg.vocab_size)
    tokens, tables, starts = lanes
    by_host, _ = _run_program(built, built.decode, family, cfg, params, lanes)
    mixed, _ = _run_program(built, built.decode_chunk, family, cfg, params, lanes, chunk)
    for out, chunk_entry in ((by_host, 0), (mixed, int(mixed["chunk_token"][0]))):
        assert out["feed"].shape == (5,) and out["feed"].dtype == np.int32
        last = out["tokens"][np.arange(4), out["accepts"]]  # a lane's next input: its argmax, or the last token its window accepted
        assert out["feed"].tolist() == last.tolist() + [chunk_entry]
    # lanes 0 and 3 take their first token from their own entries of a feed, lane 1 from the chunk's, lane 2 from the host
    feed = np.asarray([tokens[0, 0], 7777, 8888, tokens[3, 0], tokens[1, 0]], np.int32)
    source = np.asarray([P.FEED_LANE, P.FEED_CHUNK, P.FEED_HOST, P.FEED_LANE], np.int32)
    blanked = tokens.copy()
    blanked[[0, 1, 3], 0] = 0  # the host does not know these values
    for program, group in ((built.decode, None), (built.decode_chunk, chunk)):
        want, want_pool = _run_program(built, program, family, cfg, params, lanes, group)
        got, got_pool = _run_program(built, program, family, cfg, params, (blanked, tables, starts), group, feed=(feed, source))
        for key in ("tokens", "accepts", "ok", "feed"):
            assert got[key].tolist() == want[key].tolist(), key
        _assert_pools_match(got_pool, want_pool)
        ignored, _ = _run_program(built, program, family, cfg, params, lanes, group, feed=(feed, np.zeros((4,), np.int32)))
        assert ignored["tokens"].tolist() == want["tokens"].tolist()
    # one executable a program at the width, whichever program's feed comes back in (as device arrays, as the engine passes them)
    pool = _random_pool(family, cfg, seed=3)
    zeros, draft, host = jnp.zeros((5,), jnp.int32), np.zeros((4,), np.int32), np.zeros((4,), np.int32)
    chunk_args = (chunk[1][0], chunk[2][0], chunk[0], np.int32(3))
    _, feed_d, pool = built.decode(params, pool, tables, starts, tokens, draft, zeros, host)
    _, feed_c, pool = built.decode_chunk(params, pool, tables, starts, tokens, draft, feed_d, source, *chunk_args)
    sizes = (built.decode._cache_size(), built.decode_chunk._cache_size())
    _, feed_d, pool = built.decode(params, pool, tables, starts, tokens, draft, feed_c, source)
    _, feed_c, pool = built.decode_chunk(params, pool, tables, starts, tokens, draft, feed_c, host, *chunk_args)
    assert (built.decode._cache_size(), built.decode_chunk._cache_size()) == sizes
    assert feed_d.shape == feed_c.shape == (5,) and feed_d.dtype == feed_c.dtype == jnp.int32


@pytest.mark.parametrize("backend", ["paged", "dense"])
@pytest.mark.parametrize("name", ["gpt2", "llama_gqa"])
def test_greedy_is_the_draftless_case_of_the_verify_head(name, backend):
    """A verify window whose lanes carry no draft emits, in its first column, the token the one-row head emits,
    accepts nothing, and writes the same first row."""
    family, cfg, params = _family(name, quant=False)
    apply_cached = family.apply_cached if backend == "paged" else without_apply_paged(family)
    greedy = P.build_programs(apply_cached, cfg, ["k", "v"], SERVING, spec_tokens=0)
    spec = P.build_programs(apply_cached, cfg, ["k", "v"], SERVING, spec_tokens=WINDOW - 1)
    assert greedy.backend == spec.backend == backend and (greedy.window, spec.window) == (1, WINDOW)
    tables, starts, tokens = _dispatch("verify", cfg.vocab_size, seed=7)
    tokens[:, 1:] = 0  # no drafts: the window is the last token and padding
    one, pool_1 = _run_program(greedy, greedy.decode, family, cfg, params, (tokens[:, :1], tables, starts))
    win, pool_w = _run_program(spec, spec.decode, family, cfg, params, (tokens, tables, starts))
    live = tables[:, 0] != 0
    assert win["accepts"].tolist() == one["accepts"].tolist() == [0, 0, 0, 0]
    assert win["tokens"][live, 0].tolist() == one["tokens"][live, 0].tolist()
    assert one["ok"][live].all() and win["ok"][live].all()
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
    assert built.backend == backend and built.window == 1
    # what the tick asks of the back end: the table width of a dispatch, the blocks a decode gathers
    assert [built.table_width(n) for n in (1, 2, 3, 4, 9)] == ([1, 2, 4, 4, 4] if backend == "paged" else [WIDTH] * 5)
    assert built.gathered_blocks([2, 1]) == (3 if backend == "paged" else SERVING.max_slots * WIDTH)


@pytest.mark.parametrize("block_size,max_blocks,widths", [
    (16, 256, [16, 16, 16, 32, 64, 256, 256]),  # the serving cells' geometry: 256 rows are 16 blocks
    (64, 64, [4, 4, 16, 32, 64, 64, 64]),
    (4, 16, [16] * 7),  # a table cannot be wider than max_blocks_per_seq: one width
], ids=["bs16", "bs64", "bs4"])
def test_no_table_is_narrower_than_256_rows(monkeypatch, block_size, max_blocks, widths):
    """Each width compiles both programs, a second or two of set-up a width on a v5e's host, and under 256 rows the
    gather is noise: the narrowest table is the power of two of blocks that holds ``MIN_TABLE_ROWS`` (tests/conftest.py
    sets it to one row for every other test)."""
    monkeypatch.setattr(P, "MIN_TABLE_ROWS", 256)
    serving = ServingConfig(block_size=block_size, num_blocks=512, max_slots=4, max_blocks_per_seq=max_blocks)
    built = P.build_programs(gpt2.apply_cached, gpt2.GPT2Config.tiny(), ["k", "v"], serving, spec_tokens=0)
    assert [built.table_width(n) for n in (1, 2, 16, 17, 64, 200, 256)] == widths


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
