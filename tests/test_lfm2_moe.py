"""``models/lfm2_moe.py`` (short convolutions with a state a sequence, interleaved
with GQA attention; 32-class bias-chosen experts) against the plain float32
reference of ``chipbench/families/lfm2_moe.py``, at the tiny preset: d 64, 4
heads / 2 K/V heads of 16, dense 128, 8 experts top-2 of width 32, two dense
layers, ``layer_types`` once as the published 24 entries and once as the cut's
14.  Parameters and compute are float32 here, so a tolerance is float32
round-off over the layers (logits are of order 4); a lost state, a swapped
expert or a turned tap moves a logit by tenths and cannot hide in it.

The state's six rules (``serving/programs.py``), each held here by a test that
fails when the rule is broken: a lane at position 0 reads a zero state; a chunk
of ``n_real`` real rows leaves the state after row ``n_real - 1``; an idle lane,
and the slot whose chunk rides as the second group of a dispatch, keep their
state bit for bit; a chunk boundary anywhere gives the unchunked logits; a
slot's next request does not see its predecessor; preemption by
free-and-re-prefill resumes to the same tokens.
"""

import importlib.util
import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import lfm2_moe as lf
from accelerate_tpu.models.generation import STATE, make_paged_pool, read_state_rows, write_state_rows
from accelerate_tpu.ops.moe import routed_experts
from accelerate_tpu.serving import ServingConfig, ServingEngine
from accelerate_tpu.serving import programs as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 3e-4  # float32 round-off of logits of order 4 through up to 24 layers; every fault below moves them by 0.1 and more
CONTROL_LIMIT = 0.01  # tiny, float32: sound runs read 0 (no tie within round-off), every control many times the limit
PUBLISHED = list(lf.PUBLISHED_LAYER_TYPES)
STACKS = {"published-24": PUBLISHED, "cut-14": PUBLISHED[:14]}
CHUNKS = [1, 2, 3, 32]


def load_by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, *parts))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def fam():
    return load_by_path("chipbench_families_lfm2_moe", "chipbench", "families", "lfm2_moe.py")


def tiny_cfg(layer_types=PUBLISHED, **kw):
    """The reference's configuration dict of the tiny preset (float32)."""
    cfg = {
        "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32, "num_hidden_layers": len(layer_types),
        "layer_types": list(layer_types), "num_dense_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True, "routed_scaling_factor": 1,
        "use_expert_bias": True, "conv_L_cache": 3, "conv_bias": False, "vocab_size": 256, "max_position_embeddings": 256,
        "norm_eps": 1e-5, "rope_theta": 1e6, "torch_dtype": "float32",
        "assumed": {"head_dim": 16, "norm_scale_std": 0.1, "selection_bias_std": 0.1},
    }
    cfg.update(kw)
    return cfg


@pytest.fixture(scope="module", params=list(STACKS))
def model(fam, request):
    cfg = tiny_cfg(STACKS[request.param])
    return cfg, fam.program_config(cfg, remat=False), fam.seeded_params(cfg, 2**31 + 5)


@pytest.fixture(scope="module")
def cut(fam):
    cfg = tiny_cfg(STACKS["cut-14"])
    return cfg, fam.program_config(cfg, remat=False), fam.seeded_params(cfg, 2**31 + 6)


_REFERENCES = {}  # one jitted reference a configuration: its layers compile once a padded length, not once a call


def reference_logits(fam, cfg, params, tokens):
    """The reference's one full forward of one sequence, ``[S, V]``.  The sequence is right-padded to a multiple of 48
    (causal attention, a convolution that looks back only, routing by row: padding changes nothing before it)."""
    ref = _REFERENCES.setdefault(repr(sorted(cfg.items())), fam.Reference(cfg, "float32"))
    n = len(tokens)
    ids = np.zeros((1, -(-n // 48) * 48), np.int32)
    ids[0, :n] = np.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        x, _ = ref.trunk(params, ids)
        return np.asarray(fam.ref_head(x, params["final_norm"], params["embed"], cfg))[:n]


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


def test_program_config_is_the_tiny_preset(fam):
    c = fam.program_config(tiny_cfg(), remat=False)
    assert c == lf.Lfm2MoeConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    assert c.layer_types == lf.PUBLISHED_LAYER_TYPES and c.count(lf.ATTENTION) == 6 and c.count(lf.CONV) == 18
    assert not hasattr(c, "moe_impl") and not hasattr(c, "capacity_factor")
    assert c.num_params() == fam.num_params(tiny_cfg())
    assert jax.tree.map(lambda a: a.shape, lf.init_params(c, jax.random.key(0))) == jax.tree.map(
        lambda a: a.shape, fam.seeded_params(tiny_cfg(), 3))
    with pytest.raises(ValueError, match="layer_types"):
        lf.Lfm2MoeConfig.tiny(num_layers=3)


def test_the_published_widths_count_the_published_parameters():
    whole = lf.Lfm2MoeConfig()
    served = lf.Lfm2MoeConfig(num_layers=14, layer_types=lf.PUBLISHED_LAYER_TYPES[:14])
    assert whole.num_params() == 8_339_930_560 and served.num_params() == 4_667_077_376
    cache = jax.eval_shape(lambda: lf.init_cache(served, 1, 16))
    assert cache["k"].shape == cache["v"].shape == (3, 1, 16, 512)  # K/V rows in the attention layers only
    assert cache[STATE]["conv"].shape == (11, 1, 2, 2048)  # 2 x 2048 values a sequence a convolution layer


def test_apply_matches_the_reference(model, fam):
    cfg, c, params = model
    ids = np.random.default_rng(0).integers(0, 256, (2, 40))
    got = np.asarray(lf.apply(params, jnp.asarray(ids), c))
    for b in range(2):
        assert np.abs(got[b] - reference_logits(fam, cfg, params, ids[b])).max() < TOL


@pytest.mark.parametrize("chunk", CHUNKS)
def test_chunked_prefill_then_decode_through_apply_cached(model, fam, chunk):
    cfg, c, params = model
    ids = np.random.default_rng(1).integers(0, 256, (1, 23))
    prompt = 17
    cache = lf.init_cache(c, 1, 32)
    assert cache["k"].shape == (c.count(lf.ATTENTION), 1, 32, 32) and cache[STATE]["conv"].shape == (c.count(lf.CONV), 1, 2, 64)
    got = []
    step = jax.jit(lambda ids, cache: lf.apply_cached(params, ids, c, cache))
    for start in range(0, prompt, chunk):
        logits, cache = step(jnp.asarray(ids[:, start : min(start + chunk, prompt)]), cache)
        got.append(logits)
    for t in range(prompt, ids.shape[1]):
        logits, cache = step(jnp.asarray(ids[:, t : t + 1]), cache)
        got.append(logits)
    got = np.asarray(jnp.concatenate(got, axis=1))[0]
    assert np.abs(got - reference_logits(fam, cfg, params, ids[0])).max() < TOL


def test_loss_and_a_train_step_at_the_tiny_preset(cut):
    import optax

    from accelerate_tpu import Accelerator, JaxModel
    from accelerate_tpu.parallel.sharding import data_sharding

    _, c, params = cut
    params = jax.tree.map(jnp.copy, params)  # prepare donates what it is given: the fixture's leaves stay
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, 256, (8, 16), dtype=np.int32)
    first = float(lf.loss_fn(params, {"input_ids": jnp.asarray(tokens)}, c))
    assert 4.0 < first < 7.5  # about log(256) with seeded weights
    grads = jax.grad(lf.loss_fn)(params, {"input_ids": jnp.asarray(tokens)}, c)
    assert float(jnp.abs(grads["moe"]["router_bias"]).max()) == 0.0  # the bias only chooses: no gradient, its rule stays out
    assert float(jnp.abs(grads["conv"]["taps"]).max()) > 0 and float(jnp.abs(grads["attn"]["ln_q"]).max()) > 0
    acc = Accelerator()

    def apply_fn(params, input_ids, attention_mask=None):
        return {"loss": lf.loss_fn(params, {"input_ids": input_ids, "attention_mask": attention_mask}, c)}

    model, opt = acc.prepare(JaxModel(apply_fn, params, partition_rules=lf.PARTITION_RULES), optax.adam(1e-2))
    step = acc.make_train_step(model, opt)
    batch = {"input_ids": jax.device_put(tokens, data_sharding(acc.mesh))}
    losses = [float(step(batch)) for _ in range(8)]
    assert losses[-1] < 0.9 * losses[0], losses


# ---------------------------------------------------------------------------
# the paged path, as the engine drives it
# ---------------------------------------------------------------------------

BLOCK, BLOCKS, SLOTS, WIDTH = 4, 64, 4, 8


def junk_pool(c, seed=0):
    """A pool whose every state entry holds what a predecessor might have left (large values: a read shows at once)."""
    pool = make_paged_pool(lf.init_cache, c, BLOCKS, BLOCK, SLOTS)
    junk = 50.0 * jax.random.normal(jax.random.key(seed), pool[STATE]["conv"].shape, jnp.float32)
    return {**pool, STATE: {"conv": junk.astype(pool[STATE]["conv"].dtype)}}


def paged_step(c, params):
    @jax.jit
    def step(pool, ids, tab, starts, slots, counts):
        (logits,), (rows,), counters = lf.apply_paged(params, ((ids, tab, starts, slots, counts),), c, pool)
        return logits, P._write_rows(pool, rows, tab, starts, ids.shape[1], slots, counts), counters
    return step


def paged_logits(c, params, seqs, prompt, chunk):
    """Every sequence of ``seqs`` through ``apply_paged`` and the programs'
    write as the engine drives them: its prompt in chunks (padded to ``chunk``
    with a junk token) as a batch of one in its slot, then all sequences decoded
    together a token at a time; returns each one's logits at its real positions.
    Sequence ``i`` sits in slot ``i`` and owns blocks ``1 + 8 i ..``; every slot's
    state starts as junk."""
    pool, step = junk_pool(c), paged_step(c, params)
    tables = np.asarray([[1 + WIDTH * i + j for j in range(WIDTH)] for i in range(len(seqs))], np.int32)
    out = [[] for _ in seqs]
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    for i, seq in enumerate(seqs):
        for start in range(0, prompt, chunk):
            n_real = min(chunk, prompt - start)
            ids = np.full((1, chunk), 255, np.int32)
            ids[0, :n_real] = seq[start : start + n_real]
            logits, pool, _ = step(pool, i32(ids), i32(tables[i : i + 1]), i32([start]), i32([i]), i32([n_real]))
            out[i].append(np.asarray(logits)[0, :n_real])
    for t in range(prompt, len(seqs[0])):
        ids = np.asarray([[seq[t]] for seq in seqs], np.int32)
        n = len(seqs)
        logits, pool, _ = step(pool, i32(ids), i32(tables), jnp.full((n,), t, jnp.int32), jnp.arange(n, dtype=jnp.int32), jnp.ones((n,), jnp.int32))
        for i in range(n):
            out[i].append(np.asarray(logits)[i])
    return [np.concatenate(o, axis=0) for o in out]


@pytest.mark.parametrize("chunk", CHUNKS)
@pytest.mark.parametrize("mix", ["alone", "with-two-others"])
def test_paged_chunked_prefill_and_decode_match_the_reference(model, fam, chunk, mix):
    """Rules 1, 2 and 4 at once: every slot starts from junk (a lane at position
    0 must read zeros), the last chunk of 15 rows is padded (the state must be
    the one after its last real row), and the prompt is cut at every kind of
    boundary; the logits are the reference's one full forward, whoever shares the batch."""
    cfg, c, params = model
    rng = np.random.default_rng(2)
    seqs = [rng.integers(0, 256, 22) for _ in range(1 if mix == "alone" else 3)]
    got = paged_logits(c, params, seqs, prompt=15, chunk=chunk)
    for seq, logits in zip(seqs, got):
        assert np.abs(logits - reference_logits(fam, cfg, params, seq)).max() < TOL


def test_rule_1_a_lane_at_position_zero_reads_a_zero_state(cut):
    _, c, params = cut
    step = paged_step(c, params)
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    ids, tab = i32(np.arange(8)[None]), i32([[1, 2, 3, 4, 0, 0, 0, 0]])
    clean = make_paged_pool(lf.init_cache, c, BLOCKS, BLOCK, SLOTS)
    want, _, _ = step(clean, ids, tab, i32([0]), i32([2]), i32([8]))
    got, _, _ = step(junk_pool(c), ids, tab, i32([0]), i32([2]), i32([8]))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # a NaN the slot's last owner left is selected away, not multiplied by zero
    poisoned = {**clean, STATE: {"conv": jnp.full_like(clean[STATE]["conv"], jnp.nan)}}
    got, _, _ = step(poisoned, ids, tab, i32([0]), i32([2]), i32([8]))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # and past position 0 the slot's state is read: junk there changes the logits
    later, _, _ = step(junk_pool(c), ids, tab, i32([4]), i32([2]), i32([8]))
    base, _, _ = step(clean, ids, tab, i32([4]), i32([2]), i32([8]))
    assert np.abs(np.asarray(later) - np.asarray(base)).max() > 0.1
    leaf = jnp.arange(2 * 3 * 2 * 4, dtype=jnp.float32).reshape(2, 3, 2, 4)
    rows = np.asarray(read_state_rows(leaf, jnp.int32(1), i32([2, 0]), i32([5, 0])))
    assert np.array_equal(rows[0], np.asarray(leaf[1, 2])) and not rows[1].any()


def test_rule_2_a_padded_chunk_leaves_the_state_after_its_last_real_row(cut):
    _, c, params = cut
    step = paged_step(c, params)
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    tab = i32([[1, 2, 3, 4, 0, 0, 0, 0]])
    real = np.arange(10, 15)
    padded = np.concatenate([real, [200, 201, 202]])
    _, exact, _ = step(junk_pool(c), i32(real[None]), tab, i32([0]), i32([1]), i32([5]))
    _, got, _ = step(junk_pool(c), i32(padded[None]), tab, i32([0]), i32([1]), i32([5]))
    assert np.array_equal(np.asarray(got[STATE]["conv"]), np.asarray(exact[STATE]["conv"]))
    _, after_padding, _ = step(junk_pool(c), i32(padded[None]), tab, i32([0]), i32([1]), i32([8]))  # what a program that took the padding for real rows would keep
    assert np.abs(np.asarray(after_padding[STATE]["conv"][:, 1]) - np.asarray(exact[STATE]["conv"][:, 1])).max() > 1e-3
    # one real row of a chunk: the state is (what came in at its second place, that row's u)
    _, one, _ = step(got, i32(np.asarray([[77, 200, 201, 202, 203, 204, 205, 206]])), tab, i32([5]), i32([1]), i32([1]))
    assert np.array_equal(np.asarray(one[STATE]["conv"][:, 1, 0]), np.asarray(got[STATE]["conv"][:, 1, 1]))


def programs_of(c):
    serving = ServingConfig(block_size=BLOCK, num_blocks=BLOCKS, max_slots=SLOTS, max_blocks_per_seq=WIDTH, prefill_chunk=8)
    return P.build_programs(lf.apply_cached, c, ["k", "v"], serving, 0, stateful=True)


def test_rule_3_idle_lanes_and_the_prefilling_slots_own_lane_keep_their_state(cut):
    """The mixed dispatch: slot 0 decodes, slot 1 prefills a chunk that rides as
    the second group (its own decoding lane is idle), slots 2 and 3 are idle."""
    _, c, params = cut
    built = programs_of(c)
    assert built.stateful and built.backend == "paged"
    i32 = lambda x: np.asarray(x, np.int32)
    tables = np.zeros((SLOTS, WIDTH), np.int32)
    tables[0, :3] = [1, 2, 3]
    lengths, tokens = i32([9, 0, 0, 0]), i32([[5], [0], [0], [0]])
    draft = (np.zeros((SLOTS,), np.int32), np.zeros((SLOTS + 1,), np.int32), np.zeros((SLOTS,), np.int32))  # no draft; no feed, every lane the host's token
    chunk = (i32([9, 10, 11, 12, 0, 0, 0, 0]), np.int32(8), i32(np.arange(8)[None] + 30), np.int32(6))
    before = np.asarray(junk_pool(c)[STATE]["conv"])
    *_, mixed = built.decode_chunk(params, junk_pool(c), tables, lengths, tokens, *draft, *chunk, i32([1, 0, 0, 0]), np.int32(1))
    after = np.asarray(mixed[STATE]["conv"])
    assert np.array_equal(after[:, 2:], before[:, 2:])  # the idle lanes: bit for bit
    assert not np.array_equal(after[:, 0], before[:, 0])  # the decoding lane advanced
    # slot 1 holds what its chunk wrote and nothing of its idle decoding lane: the same chunk dispatched with every lane idle
    *_, alone = built.decode_chunk(params, junk_pool(c), tables * 0, lengths * 0, tokens * 0, *draft, *chunk, i32([0, 0, 0, 0]), np.int32(1))
    assert np.array_equal(after[:, 1], np.asarray(alone[STATE]["conv"])[:, 1])
    assert np.array_equal(np.asarray(alone[STATE]["conv"])[:, [0, 2, 3]], before[:, [0, 2, 3]])
    # decode alone: a lane that is not live keeps its state, whatever its table row and length say
    *_, decoded = built.decode(params, junk_pool(c), tables, lengths, tokens, *draft, i32([1, 0, 0, 0]))
    assert np.array_equal(np.asarray(decoded[STATE]["conv"])[:, 1:], before[:, 1:])
    assert np.array_equal(np.asarray(decoded[STATE]["conv"])[:, 0], after[:, 0])  # and the chunk beside it changed nothing of lane 0
    # the rule broken (every lane written) would show: the write itself, counts ignored
    state = {"conv": jnp.asarray(before)}
    rows = {"conv": jnp.ones((SLOTS, before.shape[0], 2, before.shape[-1]))}
    kept = write_state_rows(state, rows, jnp.arange(SLOTS), jnp.asarray([1, 0, 3, 0]))["conv"]
    assert np.array_equal(np.asarray(kept[:, [1, 3]]), before[:, [1, 3]]) and np.all(np.asarray(kept[:, [0, 2]]) == 1)


def test_a_family_without_a_state_keeps_its_programs_and_its_stats():
    """llama's programs take the arguments they always took (no ``live``, no
    slot), its pool holds token leaves alone and its ``stats()`` carry neither
    ``state_bytes`` nor ``state_resets``; ``ServingConfig`` has its 17 fields."""
    import dataclasses
    from accelerate_tpu.models import llama

    assert len(dataclasses.fields(ServingConfig)) == 17
    c = llama.LlamaConfig.tiny(dtype=jnp.float32)
    engine = ServingEngine(
        llama.apply_cached, llama.init_cache, llama.init_params(c, jax.random.key(0)), c,
        ServingConfig(block_size=4, num_blocks=32, max_slots=2, max_blocks_per_seq=8, prefill_chunk=4))
    assert not engine.programs.stateful and STATE not in engine.cache.pool and engine._state_args([0], 1) == []
    tables, lengths = np.zeros((2, 2), np.int32), np.zeros((2,), np.int32)
    lanes = (tables, lengths, np.zeros((2, 1), np.int32), np.zeros((2,), np.int32), np.zeros((3,), np.int32), np.zeros((2,), np.int32))
    packed, feed, _ = jax.eval_shape(engine.programs.decode, engine.params, engine.cache.pool, *lanes)
    assert packed.shape == (2 + 2,) and feed.shape == (2 + 1,)
    # the traced programs take the parameters, the pool and their own arguments: no input more (draft_len, the feed and the lanes' sources are among them)
    chunk = (tables[0], np.int32(0), np.zeros((1, 4), np.int32), np.int32(1))
    leaves = len(jax.tree.leaves((engine.params, engine.cache.pool)))
    assert len(jax.make_jaxpr(engine.programs.decode)(engine.params, engine.cache.pool, *lanes).jaxpr.invars) == leaves + 6
    assert len(jax.make_jaxpr(engine.programs.decode_chunk)(engine.params, engine.cache.pool, *lanes, *chunk).jaxpr.invars) == leaves + 10
    engine.submit(np.arange(6), 3)
    engine.run()
    assert not {"state_bytes", "state_resets", "prefix_cache_off"} & set(engine.stats())


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


def engine_of(model, **kw):
    from accelerate_tpu import Accelerator

    _, c, params = model
    geometry = dict(block_size=4, num_blocks=96, max_slots=4, max_blocks_per_seq=16, prefill_chunk=3)
    geometry.update(kw)
    return Accelerator().prepare_serving(lf.apply_cached, lf.init_cache, params, c, **geometry)


def assert_served_the_references_best(fam, model, reply, prompt, new):
    cfg, _, params = model
    assert reply.status == "ok" and len(reply.tokens) == len(prompt) + new
    want = reference_logits(fam, cfg, params, reply.tokens)
    for t in range(len(prompt), len(reply.tokens)):  # each served token is the reference's best, to round-off
        assert want[t - 1].max() - want[t - 1, reply.tokens[t]] < TOL


@pytest.mark.parametrize("chunk", CHUNKS)
@pytest.mark.parametrize("mix", [(5, 13, 21), (13, 7, 30, 9, 13)], ids=["three", "five"])
def test_engine_serves_on_the_paged_path_with_a_state_beside_the_rows(cut, fam, chunk, mix):
    engine = engine_of(cut, prefill_chunk=chunk)
    c = cut[1]
    stats = engine.stats()
    assert stats["decode_path"] == "paged" and engine.serving.spec_tokens == 0 and engine.serving.prefix_cache
    assert sorted(engine.cache.pool) == ["k", STATE, "v"] and engine.cache.leaf_names == ["k", "v"]
    assert engine.cache.pool["k"].shape == (3, 96, 4, 32) and engine.cache.pool[STATE]["conv"].shape == (11, 4, 2, 64)
    assert stats["state_bytes"] == 11 * 4 * 2 * 64 * 4 and stats["pool_bytes"] == 2 * 3 * 96 * 4 * 32 * 4
    assert engine.cache.block_bytes() == 2 * 3 * 4 * 32 * 4  # a state leaf is no block
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, n) for n in mix]
    ids = [engine.submit(p, 7) for p in prompts]
    engine.run()
    done = {r.id: r for r in engine.pop_finished()}
    for rid, prompt in zip(ids, prompts):
        assert_served_the_references_best(fam, cut, done[rid], prompt, 7)
    stats = engine.stats()
    dispatches = stats["prefill_dispatches"] + stats["decode_dispatches"] - stats["mixed_dispatches"]
    assert stats["mixed_dispatches"] > 0 and dispatches == stats["ticks"]  # a prefilling slot beside live decoders, one dispatch a tick
    assert stats["state_resets"] == len(mix)  # every request started from a zero state, once
    # hand count: 12 expert layers x top-2 for every row of every dispatch, the padded rows and idle slots among them
    assert stats["moe_rows"] == 12 * 2 * (stats["prefill_dispatches"] * chunk + dispatches * 4)
    assert 12 * dispatches <= stats["moe_max_rows"] <= stats["moe_rows"]
    assert 12 * 2 * dispatches <= stats["moe_experts_hit"] <= min(stats["moe_rows"], 12 * 8 * dispatches)


def test_expert_counters_against_hand_counted_values(cut):
    _, c, params = cut
    sizes = jnp.asarray([[4, 0, 0, 2, 0, 0, 0, 0], [1, 1, 1, 1, 1, 1, 0, 0]], jnp.int32)  # two layers, six pairs each
    assert {k: int(v) for k, v in lf.expert_counters(sizes).items()} == {"moe_rows": 12, "moe_experts_hit": 8, "moe_max_rows": 5, "moe_row_tiles": 0, "moe_pairs_routed": 12}
    # one dispatch of 5 rows through the 12 expert layers of the cut: 5 x top-2 pairs a layer
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    _, _, counters = paged_step(c, params)(
        junk_pool(c), i32(np.arange(5)[None]), i32([[1, 2, 0, 0, 0, 0, 0, 0]]), i32([0]), i32([0]), i32([5]))
    assert int(counters["moe_rows"]) == 12 * 5 * 2 and 12 * 2 <= int(counters["moe_experts_hit"]) <= 12 * 8
    assert 12 * 2 <= int(counters["moe_max_rows"]) <= 12 * 5
    # the weights are the chosen scores without the bias over (their sum + 1e-6)
    lp = {k: v[0] for k, v in params["moe"].items()}
    h = jax.random.normal(jax.random.key(6), (9, 64), jnp.float32)
    _, r = routed_experts(
        h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], top_k=2, scoring="sigmoid", select_bias=lp["router_bias"],
        normalize=True, normalize_eps=lf.NORM_TOPK_EPS, compute_dtype=jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(h @ lp["router"]))
    picked = np.take_along_axis(scores, np.asarray(r["experts"]), axis=-1)
    assert np.allclose(np.asarray(r["weights"]), picked / (picked.sum(-1, keepdims=True) + 1e-6), atol=1e-7)
    assert (np.asarray(r["experts"]) == np.argsort(-(scores + np.asarray(lp["router_bias"])), axis=-1)[:, :2]).all()


def test_rule_5_two_requests_through_one_slot(cut, fam):
    engine = engine_of(cut, max_slots=1)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, n) for n in (14, 6, 9)]
    ids = [engine.submit(p, 6) for p in prompts]
    engine.run()
    done = {r.id: r for r in engine.pop_finished()}
    assert engine.cache.pool[STATE]["conv"].shape[1] == 1 and engine.stats()["state_resets"] == 3
    for rid, prompt in zip(ids, prompts):
        assert_served_the_references_best(fam, cut, done[rid], prompt, 6)


def test_rule_6_preemption_by_free_and_re_prefill_resumes_to_the_same_tokens(cut, fam):
    engine = engine_of(cut, num_blocks=9, max_slots=3, max_blocks_per_seq=6, prefill_chunk=4)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, 9) for _ in range(3)]  # one shape: the offline loop compiles once
    new = [7, 7, 7]
    ids = [engine.submit(p, m) for p, m in zip(prompts, new)]
    engine.run(max_ticks=2000)
    stats = engine.stats()
    assert stats["preempted"] > 0, "pool was not tight enough to force preemption"
    assert stats["state_resets"] == 3 + stats["preempted"]  # a re-prefill starts its sequence over, from a zero state
    done = {r.id: r for r in engine.pop_finished()}
    for rid, prompt, m in zip(ids, prompts, new):
        alone = np.asarray(lf.generate(cut[2], jnp.asarray(prompt)[None], cut[1], m))[0]
        assert done[rid].tokens == alone.tolist()
        assert_served_the_references_best(fam, cut, done[rid], prompt, m)


def test_no_prefix_hit_for_a_family_with_a_state_and_the_usual_hits_for_llama(cut, fam):
    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import llama

    rng = np.random.default_rng(10)
    shared = rng.integers(0, 256, 16)
    prompts = [np.concatenate([shared, rng.integers(0, 256, n)]) for n in (3, 6, 9)]

    def serve(engine):
        first = engine.submit(prompts[0], 5)
        engine.run()
        ids = [first] + [engine.submit(p, 5) for p in prompts[1:]]
        engine.run()
        return ids, {r.id: r for r in engine.pop_finished()}, engine.stats()

    engine = engine_of(cut, prefill_chunk=8)
    assert engine.serving.prefix_cache and engine._prefix is None  # the default stays; the engine builds none
    ids, done, stats = serve(engine)
    assert stats["prefix_hits"] == 0 and stats["prefix_blocks_reused"] == 0 and stats["prefix_cached_blocks"] == 0
    assert "state" in stats["prefix_cache_off"] and "conv" in stats["prefix_cache_off"]
    for rid, prompt in zip(ids, prompts):
        assert_served_the_references_best(fam, cut, done[rid], prompt, 5)
    c = llama.LlamaConfig.tiny(dtype=jnp.float32)
    other = Accelerator().prepare_serving(
        llama.apply_cached, llama.init_cache, llama.init_params(c, jax.random.key(0)), c, block_size=4, num_blocks=96,
        max_slots=4, max_blocks_per_seq=16, prefill_chunk=8)
    _, _, stats = serve(other)
    assert stats["prefix_hits"] == 2 and stats["prefix_blocks_reused"] >= 8 and "prefix_cache_off" not in stats


@pytest.mark.parametrize("option", [{"host_blocks": 8}, {"spec_tokens": 2}], ids=["host_blocks", "spec_tokens"])
def test_the_host_tier_and_the_verify_window_are_refused_for_a_family_with_a_state(cut, option):
    with pytest.raises(ValueError, match=r"state a sequence \(conv\).*host_blocks.*spec_tokens"):
        engine_of(cut, **option)
    engine_of(cut, prefix_cache=False)  # and nothing else is


def test_the_memory_ledger_holds_the_state_beside_the_pool(cut):
    from accelerate_tpu.telemetry.memledger import get_memory_ledger

    engine = engine_of(cut)
    owners = {r.owner: r for r in get_memory_ledger().owners()}
    assert owners["serving.state_pool"].device_bytes == engine.stats()["state_bytes"] == engine.cache.state_bytes()
    assert owners["serving.kv_pool"].device_bytes == engine.stats()["pool_bytes"]


# ---------------------------------------------------------------------------
# the check and its controls
# ---------------------------------------------------------------------------


def served(params, c, prompt, new):
    """What a sound program serves for one request, as the check sees a reply."""
    tokens = np.asarray(lf.generate(params, jnp.asarray(prompt)[None], c, new, prefill_chunk=32))[0]
    return types.SimpleNamespace(tokens=tokens.tolist(), prompt_len=len(prompt))


@pytest.fixture(scope="module")
def replies(fam):
    cfg = tiny_cfg(STACKS["cut-14"])
    params = fam.seeded_params(cfg, 13)
    c = fam.program_config(cfg, remat=False)
    rng = np.random.default_rng(8)
    return cfg, params, [served(params, c, rng.integers(0, 256, n), 24) for n in (40, 70)]


def test_the_check_passes_a_sound_program(fam, replies):
    cfg, params, sample = replies
    serve_closed = load_by_path("chipbench_drivers_serve_closed", "chipbench", "drivers", "serve_closed.py")
    gaps = serve_closed.served_gaps(fam, cfg, params, sample, 24)
    assert len(gaps["served"]) == 48 and max(gaps["served"]) <= CONTROL_LIMIT


@pytest.mark.parametrize("control", [
    "fp8", "state_dropped", "taps_reversed", "bc_exchanged", "no_head_norms", "bias_in_weights", "unnormalised", "skip_layer"])
def test_each_control_fails_the_check(fam, replies, control):
    """The comparison that decides ``correct`` tells each fault from a sound
    run on its own: the token the faulty computation puts first lies, under the
    reference, further below the reference's best than the limit allows.  The
    dropped state among them: positions past 32 see what a program that loses
    the state between chunks of 32 computes."""
    cfg, params, sample = replies
    assert control in fam.CONTROLS and len(fam.CONTROLS) == 8
    serve_closed = load_by_path("chipbench_drivers_serve_closed", "chipbench", "drivers", "serve_closed.py")
    gaps = serve_closed.served_gaps(fam, cfg, params, sample, 24, control=control)
    assert max(gaps["control"]) > 4 * CONTROL_LIMIT, (control, max(gaps["control"]))


def test_the_dropped_state_control_is_what_a_program_without_the_state_computes(cut, fam):
    """Chunks of 32 through ``apply_cached`` with the state zeroed between them give the control's logits, not the reference's."""
    cfg, c, params = cut
    ids = np.random.default_rng(4).integers(0, 256, (1, 80))
    cache, got = lf.init_cache(c, 1, 96), []
    for start in range(0, 80, 32):
        logits, cache = lf.apply_cached(params, jnp.asarray(ids[:, start : start + 32]), c, cache)
        cache = {**cache, STATE: jax.tree.map(jnp.zeros_like, cache[STATE])}
        got.append(logits)
    got = np.asarray(jnp.concatenate(got, axis=1))[0]
    with jax.default_matmul_precision("highest"):
        control = np.asarray(fam.ref_logits(params, ids[0], cfg, "state_dropped"))
    assert np.abs(got - control).max() < TOL
    assert np.abs(got - reference_logits(fam, cfg, params, ids[0]))[32:].max() > 0.1


# ---------------------------------------------------------------------------
# the published names
# ---------------------------------------------------------------------------


def test_hf_state_dict_round_trip(fam):
    """A synthetic state dict under the published names (``model.layers.N.conv.in_proj.weight`` ..., written from the
    family's own leaves, the published 24-entry interleaving) comes back through ``hf_import`` leaf for leaf, nothing left over."""
    from accelerate_tpu.models.hf_import import config_from_hf, import_state_dict

    cfg = tiny_cfg()
    params = jax.tree.map(np.asarray, fam.seeded_params(cfg, 14))
    c = fam.program_config(cfg, remat=False)
    hf = types.SimpleNamespace(model_type="lfm2_moe", **{k: v for k, v in cfg.items() if k not in ("assumed", "torch_dtype")})
    assert config_from_hf(hf, dtype=jnp.float32, param_dtype=jnp.float32, remat=False) == c
    sd = {"model.embed_tokens.weight": params["embed"], "model.embedding_norm.weight": params["final_norm"],
          "lm_head.weight": params["embed"]}
    linear = {"conv": {"w_in": "conv.in_proj", "w_out": "conv.out_proj"},
              "attn": {"wq": "self_attn.q_proj", "wk": "self_attn.k_proj", "wv": "self_attn.v_proj", "wo": "self_attn.out_proj"}}
    seen = {"conv": 0, "attn": 0}
    for layer, kind in enumerate(PUBLISHED):
        pre, stack = f"model.layers.{layer}.", lf.OP_STACK[kind]
        n = seen[stack]
        seen[stack] += 1
        for ours, theirs in linear[stack].items():
            sd[pre + theirs + ".weight"] = params[stack][ours][n].T  # torch Linear holds [out, in]
        if stack == "conv":
            sd[pre + "conv.conv.weight"] = params["conv"]["taps"][n].T[:, None, :]  # Conv1d(groups=d): [d, 1, 3]
        else:
            sd[pre + "self_attn.q_layernorm.weight"] = params["attn"]["ln_q"][n]
            sd[pre + "self_attn.k_layernorm.weight"] = params["attn"]["ln_k"][n]
        ffn, i = ("dense", layer) if layer < 2 else ("moe", layer - 2)
        leaves = params[ffn]
        sd[pre + "operator_norm.weight"], sd[pre + "ffn_norm.weight"] = leaves["ln_op"][i], leaves["ln_ffn"][i]
        for ours, theirs in (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2")):
            if ffn == "dense":
                sd[pre + f"feed_forward.{theirs}.weight"] = leaves[ours][i].T
            else:
                for e in range(8):
                    sd[pre + f"feed_forward.experts.{e}.{theirs}.weight"] = leaves[ours][i, e].T
        if ffn == "moe":
            sd[pre + "feed_forward.gate.weight"] = leaves["router"][i].T
            sd[pre + "feed_forward.expert_bias"] = leaves["router_bias"][i]
    got = import_state_dict("lfm2_moe", sd, c)
    flat_want, tree_want = jax.tree_util.tree_flatten_with_path(params)
    flat_got, tree_got = jax.tree_util.tree_flatten_with_path(got)
    assert tree_want == tree_got
    for (path, want), (_, have) in zip(flat_want, flat_got):
        assert np.array_equal(np.asarray(have), want), path
    with pytest.raises(ValueError, match="unmapped"):
        import_state_dict("lfm2_moe", dict(sd, **{"model.layers.0.conv.conv.bias": np.zeros((64,))}), c)
    with pytest.raises(ValueError, match="head is the embedding"):
        import_state_dict("lfm2_moe", dict(sd, **{"lm_head.weight": params["embed"] + 1}), c)
    with pytest.raises(ValueError, match="convolution bias"):
        config_from_hf(types.SimpleNamespace(**dict(vars(hf), conv_bias=True)))
