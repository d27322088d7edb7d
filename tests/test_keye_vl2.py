"""``models/keye_vl2.py`` (a Qwen3-MoE block whose attention reads the rows a
learned indexer selects) against the plain float32 reference of
``chipbench/families/keye_vl2.py``, at a tiny preset: d 64, 4 heads / 2 K/V
heads of 16, 8 experts top-2 of width 32, 2 index heads of 64 (two layers'
index keys to a row of the pool), and the top **8** of contexts up to 48 rows,
so that the selection bites at every position past the eighth.  Parameters and
compute are float32 here, so a tolerance is float32 round-off over the layers
(logits are of order 4); a query that attends over other rows than its
selection (all of them, a wrong top-k, a score without its ReLU) moves a logit
by tenths and cannot hide in it.

Through ``apply`` (the training-shape forward), ``apply_cached`` (prefill in
chunks, then a row at a time) and ``apply_paged`` with the engine's own
``_write_rows`` (a chunk over its gathered context, the decoding lanes over
the rows their selection names, read from the pool row by row); then the
engine itself, preempted and not."""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import keye_vl2 as kv
from accelerate_tpu.models import sdar_moe
from accelerate_tpu.models.generation import make_paged_pool, token_leaves
from accelerate_tpu.serving import ServingConfig, ServingEngine
from accelerate_tpu.serving import programs as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4  # float32 round-off of logits of order 4 through 3 layers; every fault below moves them by 0.05 and more
FAULT = 0.05
TOPK, BLOCK = 8, 4
INDEX_LEAVES = ("wqi", "wki", "wwi", "ln_ki", "ki_bias")


def load_by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, *parts))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def fam():
    return load_by_path("chipbench_families_keye_vl2", "chipbench", "families", "keye_vl2.py")


def tiny_cfg(topk=TOPK, layers=3, **kw):
    """The reference's configuration dict of the tiny preset (float32)."""
    cfg = {
        "hidden_size": 64, "moe_intermediate_size": 32, "num_hidden_layers": layers, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
        "vocab_size": 256, "max_position_embeddings": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000000,
        "torch_dtype": "float32",
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 2, "indexer_num_kv_heads": 1, "topk": topk},
        "assumed": {"index_rope_dim": 32, "index_norm_eps": 1e-6, "norm_scale_std": 0.1, "index_norm_bias_std": 0.1},
    }
    cfg.update(kw)
    return cfg


@pytest.fixture(scope="module")
def model(fam):
    cfg = tiny_cfg()
    return cfg, fam.program_config(cfg, remat=False), fam.seeded_params(cfg, 2**31 + 40)


_REFERENCES = {}  # one jitted reference a configuration and control


def reference_logits(fam, cfg, params, tokens, control="float32"):
    """The reference's one full forward of one sequence, ``[S, V]``, right-padded to a multiple of 48 (every mask is
    causal, a query selects among the positions before it, routing is by row: padding changes nothing before it)."""
    ref = _REFERENCES.setdefault((repr(sorted(cfg.items())), control), fam.Reference(cfg, control))
    n = len(tokens)
    ids = np.zeros((1, -(-n // 48) * 48), np.int32)
    ids[0, :n] = np.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        x, _ = ref.trunk(params, ids)
        return np.asarray(fam.ref_head(x, params["final_norm"], params["lm_head"], cfg, control))[:n]


def some_tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


def test_program_config_is_the_tiny_preset(fam, model):
    _, c, params = model
    assert c == kv.KeyeVl2Config.tiny(dtype=jnp.float32, param_dtype=jnp.float32, rope_theta=1e7)
    assert c.num_params() == fam.num_params(tiny_cfg()) and c.index_pack == 2
    shapes = jax.eval_shape(lambda k: kv.init_params(c, k), jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(lambda a: a.shape, params)
    assert kv.KeyeVl2Config().num_params() > 30e9  # the published 30B-A3B
    for bad in (dict(index_rope_dim=3), dict(index_rope_dim=80), dict(index_topk=0), dict(num_kv_heads=3)):
        with pytest.raises(ValueError):
            kv.KeyeVl2Config.tiny(**bad)


def test_apply_is_the_reference(fam, model):
    cfg, c, params = model
    tokens = some_tokens(48)
    want = reference_logits(fam, cfg, params, tokens)
    got = np.asarray(jax.jit(lambda p, t: kv.apply(p, t, c))(params, tokens[None]))[0]
    assert np.max(np.abs(got - want)) < TOL
    padded = np.concatenate([tokens[:40], np.zeros(8, np.int32)])[None]
    got = np.asarray(kv.apply(params, padded, c, attention_mask=(np.arange(48) < 40)[None]))[0, :40]
    assert np.max(np.abs(got - want[:40])) < TOL


def test_the_programs_selection_is_the_references(fam, model):
    """The first layer's selection at float32: the program's scores and top-k over a context of 48 rows, dense and
    as the decoding lanes take it (the last position), name the reference's rows."""
    cfg, c, params = model
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = np.asarray(params["embed"])[some_tokens(48, 1)]
    n = np.asarray(kv._llama._rms_norm(jnp.asarray(x)[None], lp["ln_attn"], c.rms_eps))[0]
    positions = jnp.arange(48)[None]
    qi, w, ki = kv._index_proj(jnp.asarray(n)[None], lp, c, positions)
    causal = jnp.tril(jnp.ones((48, 48), bool))[None]
    got = np.asarray(kv._selection(kv._index_scores(qi, w, ki), causal, TOPK))[0]
    with jax.default_matmul_precision("highest"):
        qr, wr, kr = fam.ref_index(jnp.asarray(n), lp, cfg)
        scores = jnp.einsum("qjd,td->qjt", qr, kr)
        scores = jnp.einsum("qjt,qj->qt", jax.nn.relu(scores), wr)
        want = np.asarray(fam.selection(scores, jnp.arange(48), TOPK))
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(np.arange(48) + 1, TOPK)).all()  # every position past the eighth chooses 8 rows
    # the scores themselves: float32 round-off
    assert np.max(np.abs(np.asarray(kv._index_scores(qi, w, ki))[0] - np.asarray(scores))) < 1e-5


def sorted_selection(scores, mask, topk):
    """``lax.top_k``'s set as a mask, lower positions first among equal scores (what the radix select is held
    to), and whether it left out an admitted key of the same bits as one it took: a key tied at its threshold."""
    _, idx = jax.lax.top_k(jnp.where(mask, scores, -jnp.inf), topk)
    chosen = np.zeros(mask.shape, bool)
    np.put_along_axis(chosen, np.asarray(idx), True, axis=-1)
    chosen &= mask
    bits = scores.view(np.uint32)
    tied = np.array([np.isin(b[m & ~c], b[c]).any() for b, m, c in zip(
        bits.reshape(-1, bits.shape[-1]), mask.reshape(-1, mask.shape[-1]), chosen.reshape(-1, chosen.shape[-1]))])
    return chosen, tied.reshape(mask.shape[:-1])


def selection_case(case, rng):
    """(scores, mask, topk) of one case: rows of [2, 3, P] unless named."""
    shape, topk = (2, 3, 64), 16
    ints = rng.integers(0, 4, shape).astype(np.float32)
    mask = rng.random(shape) < 0.7
    if case == "ties_straddle_the_cut":
        return ints, mask, topk
    if case == "signed_zeros":
        return rng.choice(np.float32([0.0, -0.0]), shape), mask, topk
    if case == "all_equal":
        return np.full(shape, 1.5, np.float32), mask, topk
    if case == "negative":
        return -np.abs(rng.normal(size=(2, 3, 257))).round(1).astype(np.float32), rng.random((2, 3, 257)) < 0.8, 37
    if case.startswith("admits_"):
        admitted = topk + {"admits_topk_minus_1": -1, "admits_topk": 0, "admits_topk_plus_1": 1}[case]
        order = np.argsort(rng.random(shape), axis=-1)
        return ints, order < admitted, topk
    if case == "random_admit":
        return rng.normal(size=(2, 3, 257)).astype(np.float32), rng.random((2, 3, 257)) < rng.random((2, 3, 1)), 29
    if case == "topk_1":
        return ints, mask, 1
    if case == "topk_p_minus_1":
        return ints, rng.random(shape) < 0.995, shape[-1] - 1
    # the benchmark cell's chunk: 32 rows at positions 16,000 .. 16,031 over a table of 32,768, the top 2,048, the
    # scores rounded to an eighth so that ties fall at the cut
    p = 32768
    scores = (np.round(rng.normal(size=(1, 32, p)) * 8) / 8).astype(np.float32)
    return scores, (16000 + np.arange(32))[None, :, None] >= np.arange(p)[None, None, :], 2048


SELECTION_CASES = ["ties_straddle_the_cut", "signed_zeros", "all_equal", "negative", "admits_topk_minus_1",
                   "admits_topk", "admits_topk_plus_1", "random_admit", "topk_1", "topk_p_minus_1", "chunk_shape"]


@pytest.mark.parametrize("case", SELECTION_CASES)
def test_the_selection_is_lax_top_k_bit_for_bit(case):
    """``_selection`` (a radix select over the scores' bits) names ``lax.top_k``'s set, ties and signed zeros and all,
    and takes ``min(admitted, topk)`` keys a query; ``_ties_left_out`` flags the queries whose selection left out a key
    tied at the threshold."""
    scores, mask, topk = selection_case(case, np.random.default_rng(SELECTION_CASES.index(case)))
    got = jax.jit(kv._selection, static_argnums=2)(scores, mask, topk)
    want, want_tied = sorted_selection(scores, mask, topk)
    assert (np.asarray(got) == want).all()
    assert (np.asarray(got).sum(-1) == np.minimum(mask.sum(-1), topk)).all()
    assert (np.asarray(jax.jit(kv._ties_left_out)(scores, mask, got)) == want_tied).all()
    if case in ("ties_straddle_the_cut", "signed_zeros", "all_equal", "chunk_shape"):
        assert want_tied.any()  # the case does cut ties


@pytest.mark.parametrize("control", ["dense", "no_relu", "topk_1024"])
def test_the_controls_are_told_from_the_program(fam, model, control):
    """The reference without the selection, without the score's ReLU, and at half the top-k (8 -> 4): every one moves
    a logit of the 48-row sequence by more than FAULT."""
    cfg, c, params = model
    tokens = some_tokens(48, 2)
    got = np.asarray(kv.apply(params, tokens[None], c))[0]
    assert np.max(np.abs(got - reference_logits(fam, cfg, params, tokens, control))) > FAULT


@pytest.mark.parametrize("chunk", [1, 5, 16])
def test_cached_prefill_and_decode_are_the_references_full_forward(fam, model, chunk):
    """Prefill in chunks of ``chunk`` (boundaries before, on and past the top-k), then decode a token at a time to
    48 rows: every logit is the full forward's."""
    cfg, c, params = model
    tokens = some_tokens(48, 3)
    want = reference_logits(fam, cfg, params, tokens)
    step = jax.jit(lambda p, t, cache: kv.apply_cached(p, t, c, cache))
    cache, got, prompt = kv.init_cache(c, 1, 48), [], 21
    for start in range(0, prompt, chunk):
        logits, cache = step(params, tokens[None, start : min(start + chunk, prompt)], cache)
        got.append(np.asarray(logits)[0])
    for at in range(prompt, 48):
        logits, cache = step(params, tokens[None, at : at + 1], cache)
        got.append(np.asarray(logits)[0])
    assert np.max(np.abs(np.concatenate(got) - want)) < TOL
    assert int(cache["index"]) == 48 and cache["ki"].shape == (2, 1, 48, 128)


def test_the_index_leaf_holds_two_layers_a_row():
    """``ki [ceil(L / 2), B, T, 128]``: layer ``2g + j`` at lanes ``[64 j, 64 (j + 1))`` of row group ``g``; an odd
    stack pads its last group.  Packing and unpacking are inverses, and the pool pages the leaf by block like K/V."""
    for layers in (3, 4):
        c = kv.KeyeVl2Config.tiny(num_layers=layers, dtype=jnp.float32)
        per_layer = jax.random.normal(jax.random.key(layers), (layers, 2, 5, 64))
        packed = kv.pack_index_keys(per_layer, c)
        assert packed.shape == (2, 2, 5, 128)
        assert (packed[1, :, :, :64] == per_layer[2]).all() and (packed[0, :, :, 64:] == per_layer[1]).all()
        assert (kv.unpack_index_keys(packed, c) == per_layer).all()
        pool = make_paged_pool(kv.init_cache, c, 10, BLOCK)
        assert sorted(token_leaves(pool)) == ["k", "ki", "v"] and pool["ki"].shape == (2, 10, BLOCK, 128)
    # an index head of 128 fills a row alone: one layer a row
    c = kv.KeyeVl2Config.tiny(index_head_dim=128, index_rope_dim=64)
    assert c.index_pack == 1 and kv.init_cache(c, 1, 8)["ki"].shape == (3, 1, 8, 128)


def paged_run(c, params, tokens, prompt, chunk, lanes=1, interpret=False, block=BLOCK):
    """The serving path by hand: one sequence in lane 0 of ``lanes``, its prompt in padded chunks of ``chunk`` and
    then a row a dispatch, through ``apply_paged`` over a pool of junk in blocks of ``block`` rows, written by the
    engine's own ``_write_rows`` (``interpret``: the decoding lanes score their index keys through the paged kernel
    in the Pallas interpreter).  Returns (logits of every real row, the counters of every dispatch)."""
    blocks = -(-(len(tokens) + chunk) // block)
    pool = make_paged_pool(kv.init_cache, c, blocks + 2, block)
    pool = jax.tree.map(lambda leaf: jnp.full_like(leaf, 7.0), pool)  # junk in every block: what is read was written
    tables = np.zeros((lanes, blocks), np.int32)
    tables[0] = 1 + np.arange(blocks)[::-1]  # the blocks of a sequence out of order in the pool

    @jax.jit
    def dispatch(pool, toks, starts):
        logits, rows, counters = kv.apply_paged(params, ((toks, tables, starts),), c, pool, interpret=interpret)
        return logits[0], P._write_rows(pool, rows[0], tables, starts, toks.shape[1]), counters

    got, counters = [], []
    for start in range(0, prompt, chunk):
        real = min(chunk, prompt - start)
        toks = np.zeros((lanes, chunk), np.int32)
        toks[0, :real] = tokens[start : start + real]
        logits, pool, count = dispatch(pool, toks, np.asarray([start] + [0] * (lanes - 1), np.int32))
        got.append(np.asarray(logits)[0, :real])
        counters.append({k: int(v) for k, v in count.items()})
    for at in range(prompt, len(tokens)):
        toks = np.zeros((lanes, 1), np.int32)
        toks[0, 0] = tokens[at]
        logits, pool, count = dispatch(pool, toks, np.asarray([at] + [0] * (lanes - 1), np.int32))
        got.append(np.asarray(logits)[0])
        counters.append({k: int(v) for k, v in count.items()})
    return np.concatenate(got), counters


@pytest.mark.parametrize("chunk", [4, 16])
def test_paged_is_the_references_full_forward(fam, model, chunk):
    """A chunk attends over its gathered context under each query's selection; the decoding lanes (tables of 13
    blocks = 52 rows, wider than the top-k) read the 8 rows their selection names and nothing else."""
    cfg, c, params = model
    tokens = some_tokens(48, 4)
    got, _ = paged_run(c, params, tokens, 23, chunk, lanes=2)
    assert np.max(np.abs(got - reference_logits(fam, cfg, params, tokens))) < TOL


@pytest.mark.parametrize("chunk,block", [(4, BLOCK), (16, 16)])
def test_the_lanes_index_scores_read_in_place_are_the_gathered_paths(fam, model, chunk, block):
    """The decoding lanes score their index keys through ``ops/pallas_paged_index.py`` (the Pallas interpreter), the
    chunk's group gathers them as ever, in blocks of 4 and of 16 rows: the logits are the gathered path's and the
    reference's, and ``attn_rows_read`` counts what the kernel copied, every block a lane touches whole, a layer."""
    cfg, c, params = model
    tokens = some_tokens(48, 4)
    gathered, counters_gathered = paged_run(c, params, tokens, 23, chunk, lanes=2, block=block)
    got, counters = paged_run(c, params, tokens, 23, chunk, lanes=2, interpret=True, block=block)
    assert np.max(np.abs(got - gathered)) < 1e-5
    assert np.max(np.abs(got - reference_logits(fam, cfg, params, tokens))) < TOL
    assert all(count["attn_rows_read"] == 0 for count in counters_gathered)
    prefill = -(-23 // chunk)
    assert all(count["attn_rows_read"] == 0 for count in counters[:prefill])  # a chunk gathers
    # lane 0 decodes at position 23 .. 47 and scores rows 0 .. p - 1 in place; the idle lane at 0 copies nothing
    assert [count["attn_rows_read"] for count in counters[prefill:]] == [
        c.num_layers * -(-p // block) * block for p in range(23, 48)]


def test_counters_of_a_dispatch_against_hand_worked_numbers(model):
    cfg, c, params = model
    tokens = some_tokens(30, 5)
    _, counters = paged_run(c, params, tokens, 20, 8, lanes=3)
    first, last = counters[0], counters[-1]
    assert set(last) == set(P.DISPATCH_COUNTERS) - {"window_rows_read"}
    assert all(count["attn_rows_read"] == 0 for count in counters)  # off the TPU the lanes gather their index keys
    # the last dispatch: three lanes of one row, the first at position 29 (the others hold no sequence); three layers
    # score its 30 index keys and attend over 8 of them where a full mask admits 30
    assert (last["index_rows_scored"], last["sparse_lane_rows"]) == (3 * 30, 3 * 8)
    assert (last["sparse_rows_read"], last["context_rows"]) == (3 * 8, 3 * 30)
    # the first group's lanes at position 0 hold no sequence yet: the first chunk (lane 0 at 0 .. 7) counts nothing
    assert set(first.values()) - {first["moe_rows"], first["moe_experts_hit"], first["moe_max_rows"],
                                  first["moe_pairs_routed"]} <= {0}
    # the second, lane 0 at positions 8 .. 15: 16 index keys scored, min(p + 1, 8) = 8 rows a query of p + 1 admitted
    second = counters[1]
    assert (second["index_rows_scored"], second["sparse_lane_rows"]) == (3 * 16, 3 * 8)
    assert (second["sparse_rows_read"], second["context_rows"]) == (3 * 8 * 8, 3 * sum(range(9, 17)))


def test_select_tied_rows_against_hand_worked_numbers(model):
    """With the indexer's head weights 0 every score is +0.0: all keys a query admits tie, and a query at position
    ``p >= 8`` keeps positions 0 .. 7 and leaves the rest out.  ``select_tied_rows`` counts those queries of a chunk
    (padded rows too) a layer; the decoding lanes (tables of 40 rows, wider than the top-k) take ``lax.top_k`` and
    count none.  Through ``apply_paged`` and through the engine's ``stats()``."""
    _, c, params = model
    flat = {**params, "layers": {**params["layers"], "wwi": jnp.zeros_like(params["layers"]["wwi"])}}
    _, counters = paged_run(c, flat, some_tokens(30, 5), 20, 8, lanes=3)
    # chunks at 0 .. 7 (no query past the top-k), 8 .. 15 and 16 .. 23 (4 of them padding): 8 queries a layer each
    assert [count["select_tied_rows"] for count in counters] == [0, 3 * 8, 3 * 8] + [0] * 10
    eng = engine((None, c, flat))
    eng.submit(some_tokens(13, 11), 6)
    eng.run()
    # chunks of 4 at 0 .. 3, 4 .. 7, 8 .. 11 and 12 .. 15 (3 of them padding): the last two cut 4 queries a layer each
    assert eng.stats()["select_tied_rows"] == 3 * (4 + 4)


def test_at_contexts_within_the_top_k_it_is_sdar_at_block_length_one(fam, model):
    """Where every row is selected the indexer changes nothing: on the same weights, less the indexer's, the family
    is ``sdar_moe``'s causal forward, through ``apply`` and through the serving path."""
    cfg, c, params = model
    cfg48 = tiny_cfg(topk=48)
    c48 = fam.program_config(cfg48, remat=False)
    sdar = sdar_moe.SdarMoeConfig.tiny(block_length=1, rope_theta=1e7, dtype=jnp.float32, param_dtype=jnp.float32)
    sdar_params = {**params, "layers": {k: v for k, v in params["layers"].items() if k not in INDEX_LEAVES}}
    tokens = some_tokens(40, 6)
    want = np.asarray(sdar_moe.apply(sdar_params, tokens[None], sdar))[0]
    assert np.max(np.abs(np.asarray(kv.apply(params, tokens[None], c48))[0] - want)) < TOL
    got, _ = paged_run(c48, params, tokens, 17, 4, lanes=2)
    assert np.max(np.abs(got - want)) < TOL
    assert np.max(np.abs(np.asarray(kv.apply(params, tokens[None], c))[0] - want)) > FAULT  # past 8 rows it is not


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


def engine(model, **kw):
    _, c, params = model
    serving = dict(block_size=BLOCK, num_blocks=64, max_slots=3, max_blocks_per_seq=16, prefill_chunk=4)
    serving.update(kw)
    return ServingEngine(kv.apply_cached, kv.init_cache, params, c, ServingConfig(**serving))


def greedy_over_the_reference(fam, model, out, prompt_len):
    """Whether every served token is the reference's argmax at the position before it (one full forward of the
    whole reply: the reference's jitted layers are shared by every reply of up to 48 rows)."""
    cfg, _, params = model
    want = reference_logits(fam, cfg, params, out)
    return (np.asarray(out)[prompt_len:] == want[prompt_len - 1 : -1].argmax(-1)).all()


def test_generate_is_greedy_over_the_reference(fam, model):
    _, c, params = model
    prompt = some_tokens(13, 9)
    out = np.asarray(kv.generate(params, prompt[None], c, 30, prefill_chunk=5))[0]
    assert greedy_over_the_reference(fam, model, out, 13)


def test_engine_serves_the_references_tokens_and_counts_what_it_read(fam, model):
    eng = engine(model)
    rng = np.random.default_rng(7)
    feeds, news = [rng.integers(0, 256, n, dtype=np.int32) for n in (5, 13, 21)], [30, 20, 25]
    rids = [eng.submit(p, n) for p, n in zip(feeds, news)]
    outs = eng.run()
    for rid, p in zip(rids, feeds):
        assert greedy_over_the_reference(fam, model, outs[rid], len(p)), rid
    stats = eng.stats()
    assert stats["decode_path"] == "paged" and stats["index_topk"] == TOPK and stats["mixed_dispatches"] > 0
    assert 0 < stats["sparse_rows_read"] < stats["context_rows"] and stats["sparse_lane_rows"] < stats["index_rows_scored"]
    assert stats["sparse_lane_rows"] <= stats["sparse_rows_read"]
    assert sorted(eng.cache.token_leaves()) == ["k", "ki", "v"]


def test_preemption_re_prefills_to_the_same_tokens(fam, model):
    eng = engine(model, num_blocks=22)  # 21 usable blocks: three lanes of 40 rows do not fit
    rng = np.random.default_rng(8)
    feeds, news = [rng.integers(0, 256, n, dtype=np.int32) for n in (10, 12, 9)], [30, 28, 31]
    rids = [eng.submit(p, n) for p, n in zip(feeds, news)]
    outs = eng.run()
    assert eng.stats()["preempted"] > 0
    for rid, p in zip(rids, feeds):
        assert greedy_over_the_reference(fam, model, outs[rid], len(p)), rid
    assert eng.cache.allocator.used_blocks == 0


def test_the_host_tier_and_a_prefix_hit_carry_the_index_keys(fam, model):
    """Blocks demoted to the host tier and promoted back, and blocks reused from the prefix cache, hold the index
    keys beside K/V: a request served from either reads the reference's tokens."""
    eng = engine(model, num_blocks=10, max_blocks_per_seq=8, host_blocks=16)
    rng = np.random.default_rng(12)
    feeds = [rng.integers(0, 256, n, dtype=np.int32) for n in (9, 10, 11)]
    rids = [eng.submit(p, n) for p, n in zip(feeds, (12, 11, 10))]
    outs = eng.run()
    tiering = eng.stats()["tiering"]
    assert eng.stats()["preempted"] > 0 and tiering["demotions"] > 0 and tiering["promotions"] > 0
    for rid, p in zip(rids, feeds):
        assert greedy_over_the_reference(fam, model, outs[rid], len(p)), rid
    again = np.concatenate([feeds[0], rng.integers(0, 256, 3, dtype=np.int32)])
    rid = eng.submit(again, 12)
    out = eng.run()[rid]
    assert eng.stats()["prefix_hits"] > 0 and greedy_over_the_reference(fam, model, out, len(again))


def test_a_verify_window_is_refused_by_name(model):
    with pytest.raises(ValueError, match="verify window over a selected set"):
        engine(model, spec_tokens=2)
