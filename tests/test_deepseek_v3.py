"""``models/deepseek_v3.py`` (latent attention, dropless sigmoid-routed experts)
against the plain float32 reference of ``chipbench/families/deepseek_v3.py``, at
the tiny preset: d 64, 4 heads, ``kv_lora_rank`` 32, rope 8 (and rope 64, where
two layers' rotated keys share a row of the cache), 8 experts top-2 + 1 shared,
1 dense + 2 expert layers.  Parameters and compute are float32 here, so a
tolerance is float32 round-off over three layers (logits are of order 4) and a
swapped expert, which moves a logit by tenths, cannot hide in it.
"""

import importlib.util
import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import deepseek_v3 as ds
from accelerate_tpu.models.generation import make_paged_pool, scatter_token_rows
from accelerate_tpu.ops.moe import routed_experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4  # float32 round-off of logits of order 4 through three layers; a swapped expert moves them by 0.1 and more
CONTROL_LIMIT = 0.01  # tiny, float32: sound runs read 0 (no tie within round-off), every control at least six times the limit


def load_by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, *parts))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def fam():
    return load_by_path("chipbench_families_deepseek_v3", "chipbench", "families", "deepseek_v3.py")


def tiny_cfg(rope=8, **kw):
    """The reference's configuration dict of the tiny preset (float32)."""
    cfg = {
        "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": rope, "v_head_dim": 16, "n_routed_experts": 8,
        "num_experts_per_tok": 2, "n_shared_experts": 1, "n_group": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "rope_scaling": None, "vocab_size": 256,
        "max_position_embeddings": 128, "rms_norm_eps": 1e-6, "rope_theta": 1e6, "torch_dtype": "float32",
        "assumed": {"norm_scale_std": 0.1, "selection_bias_std": 0.1},
    }
    cfg.update(kw)
    return cfg


def build(fam, rope):
    cfg = tiny_cfg(rope=rope)
    return cfg, fam.program_config(cfg, remat=False), fam.seeded_params(cfg, 2**31 + 5)


@pytest.fixture(scope="module")
def model(fam):
    return build(fam, 8)


@pytest.fixture(scope="module")
def packed(fam):
    """Rope width 64, as published: two layers' rotated keys share a 128-lane row of the cache (3 layers: 2 rows)."""
    return build(fam, 64)


def reference_logits(fam, cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(fam.ref_logits(params, np.asarray(tokens), cfg))


def test_program_config_is_the_tiny_preset(fam):
    c = fam.program_config(tiny_cfg(), remat=False)
    assert c == ds.DeepseekV3Config.tiny(dtype=jnp.float32, param_dtype=jnp.float32, rope_theta=1e6)
    assert not hasattr(c, "moe_impl") and not hasattr(c, "capacity_factor")
    assert c.num_params() == fam.num_params(tiny_cfg())


def test_apply_matches_the_reference(model, fam):
    cfg, c, params = model
    ids = np.random.default_rng(0).integers(0, 256, (2, 40))
    got = np.asarray(ds.apply(params, jnp.asarray(ids), c))
    for b in range(2):
        assert np.abs(got[b] - reference_logits(fam, cfg, params, ids[b])).max() < TOL


@pytest.mark.parametrize("chunk", [3, 5, 8])
def test_chunked_prefill_then_decode_through_apply_cached(model, fam, chunk):
    cached_against_reference(model, fam, chunk)


def test_packed_rope_rows_through_apply_cached(packed, fam):
    assert ds.init_cache(packed[1], 1, 32)["kr"].shape == (2, 1, 32, 128)
    cached_against_reference(packed, fam, 5)


def cached_against_reference(model, fam, chunk):
    cfg, c, params = model
    ids = np.random.default_rng(1).integers(0, 256, (1, 23))
    prompt = 17
    cache = ds.init_cache(c, 1, 32)
    assert cache["ckv"].shape == (3, 1, 32, 32) and cache["kr"].shape[-1] * cache["kr"].shape[0] >= 3 * c.qk_rope_head_dim
    got = []
    step = jax.jit(lambda ids, cache: ds.apply_cached(params, ids, c, cache))
    for start in range(0, prompt, chunk):
        logits, cache = step(jnp.asarray(ids[:, start : min(start + chunk, prompt)]), cache)
        got.append(logits)
    for t in range(prompt, ids.shape[1]):
        logits, cache = step(jnp.asarray(ids[:, t : t + 1]), cache)
        got.append(logits)
    got = np.asarray(jnp.concatenate(got, axis=1))[0]
    assert np.abs(got - reference_logits(fam, cfg, params, ids[0])).max() < TOL


def paged_logits(c, params, seqs, prompt, chunk, block_size=4, num_blocks=64):
    """Every sequence of ``seqs`` through ``apply_paged`` as the engine drives
    it: its prompt in chunks (padded to ``chunk``) as a batch of one, then all
    sequences decoded together a token at a time; returns each one's logits
    at its real positions.  Sequence ``i`` owns blocks ``1 + 8 i ..``."""
    pool = make_paged_pool(ds.init_cache, c, num_blocks, block_size)
    width = 8
    tables = np.asarray([[1 + width * i + j for j in range(width)] for i in range(len(seqs))], np.int32)

    @jax.jit
    def step(pool, ids, tab, starts):
        (logits,), (rows,), _ = ds.apply_paged(params, ((ids, tab, starts),), c, pool)
        # the padded rows are written too, as the engine writes them: later rows overwrite them before any mask admits them
        return logits, {n: scatter_token_rows(pool[n], r, tab, starts, ids.shape[1]) for n, r in rows.items()}

    out = [[] for _ in seqs]
    for i, seq in enumerate(seqs):
        for start in range(0, prompt, chunk):
            n_real = min(chunk, prompt - start)
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :n_real] = seq[start : start + n_real]
            starts = jnp.asarray([start], jnp.int32)
            logits, pool = step(pool, jnp.asarray(ids), jnp.asarray(tables[i : i + 1]), starts)
            out[i].append(np.asarray(logits)[0, :n_real])
    for t in range(prompt, len(seqs[0])):
        ids = np.asarray([[seq[t]] for seq in seqs], np.int32)
        starts = jnp.full((len(seqs),), t, jnp.int32)
        logits, pool = step(pool, jnp.asarray(ids), jnp.asarray(tables), starts)
        for i in range(len(seqs)):
            out[i].append(np.asarray(logits)[i])
    return [np.concatenate(o, axis=0) for o in out]


@pytest.mark.parametrize("chunk", [3, 5, 8])
@pytest.mark.parametrize("mix", ["alone", "with-two-others"])
def test_paged_chunked_prefill_and_decode_match_the_reference(model, fam, chunk, mix):
    """The paged path's logits against the reference's one full forward,
    whoever shares the batch and however the prompt was cut."""
    paged_against_reference(model, fam, chunk, mix)


def test_packed_rope_rows_through_the_paged_pool(packed, fam):
    paged_against_reference(packed, fam, 5, "with-two-others")


def paged_against_reference(model, fam, chunk, mix):
    cfg, c, params = model
    rng = np.random.default_rng(2)
    seqs = [rng.integers(0, 256, 22) for _ in range(1 if mix == "alone" else 3)]
    got = paged_logits(c, params, seqs, prompt=15, chunk=chunk)
    for seq, logits in zip(seqs, got):
        assert np.abs(logits - reference_logits(fam, cfg, params, seq)).max() < TOL


def test_routing_is_the_same_whatever_the_chunking_and_the_batch(model):
    cfg, c, params = model
    rng = np.random.default_rng(2)
    seqs = [rng.integers(0, 256, 22) for _ in range(3)]
    base = paged_logits(c, params, seqs[:1], prompt=15, chunk=3)[0]
    for chunk, n in ((5, 1), (8, 3), (3, 3)):
        other = paged_logits(c, params, seqs[:n], prompt=15, chunk=chunk)[0]
        assert np.abs(other - base).max() < 1e-5  # a swapped expert would move a logit by 0.1 and more
    lp = {k: v[0] for k, v in params["moe"].items()}
    x = jax.random.normal(jax.random.key(3), (6, 64), jnp.float32)
    kw = dict(top_k=2, scoring="sigmoid", select_bias=lp["router_bias"], scale=2.5, compute_dtype=jnp.float32)
    y_all, r_all = routed_experts(x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], **kw)
    for i in range(6):
        y_one, r_one = routed_experts(x[i : i + 1], lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], **kw)
        assert (np.asarray(r_one["experts"]) == np.asarray(r_all["experts"][i : i + 1])).all()
        assert np.abs(np.asarray(y_one - y_all[i : i + 1])).max() < 1e-5


def test_absorbed_and_expanded_attention_agree(model):
    _, c, params = model
    lp = {k: v[0] for k, v in params["moe"].items()}
    keys = jax.random.split(jax.random.key(4), 4)
    b, s, p = 2, 1, 12
    q_nope = jax.random.normal(keys[0], (b, s, c.num_heads, c.qk_nope_head_dim))
    q_rope = jax.random.normal(keys[1], (b, s, c.num_heads, c.qk_rope_head_dim))
    ckv = jax.random.normal(keys[2], (b, p, c.kv_lora_rank))
    kr = jax.random.normal(keys[3], (b, p, c.qk_rope_head_dim))
    mask = jnp.arange(p)[None, None, :] <= jnp.asarray([7, 11])[:, None, None]
    expanded = ds._attend_expanded(q_nope, q_rope, ckv, kr, mask, lp, c)
    absorbed = ds._attend_absorbed(q_nope, q_rope, ckv, kr, mask, lp, c)
    assert np.abs(np.asarray(expanded - absorbed)).max() < 1e-4 * float(jnp.abs(expanded).max())


def test_rope_is_the_published_interleaved_pairing():
    """HF ``apply_rotary_pos_emb_interleave``: view ``[.., d/2, 2]``, transpose,
    then rotate-half with ``cos``/``sin`` of ``cat(freqs, freqs)``."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((2, 7, 3, 8)).astype(np.float32)  # [B, S, H, rope]
    positions = np.broadcast_to(np.arange(3, 10), (2, 7))
    theta, d = 1e6, 8
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    emb = np.concatenate([positions[..., None] * inv, positions[..., None] * inv], axis=-1)  # [B, S, d]
    cos, sin = np.cos(emb)[:, :, None, :], np.sin(emb)[:, :, None, :]
    deinterleaved = x.reshape(2, 7, 3, d // 2, 2).swapaxes(-1, -2).reshape(2, 7, 3, d)
    rotate_half = np.concatenate([-deinterleaved[..., d // 2 :], deinterleaved[..., : d // 2]], axis=-1)
    want = deinterleaved * cos + rotate_half * sin
    got = np.asarray(ds.rope_interleaved(jnp.asarray(x), jnp.asarray(positions), theta))
    assert np.abs(got - want).max() < 1e-5
    # and it is not the rotate-half pairing of llama.py (features j and j + d/2)
    other, _ = __import__("accelerate_tpu.models.llama", fromlist=["_rope"])._rope(
        jnp.asarray(x), jnp.asarray(x), jnp.asarray(positions), theta)
    assert np.abs(np.asarray(other) - want).max() > 0.1


@pytest.mark.parametrize("routing", ["seeded", "one-expert-idle-one-takes-every-row"])
def test_expert_layer_against_the_loop_over_experts(fam, routing):
    cfg = tiny_cfg()
    params = fam.seeded_params(cfg, 11)
    lp = {k: v[1] for k, v in params["moe"].items()}
    if routing != "seeded":
        lp["router_bias"] = lp["router_bias"].at[0].set(5.0).at[3].set(-5.0)  # expert 0 is everyone's first, expert 3 nobody's
    h = jax.random.normal(jax.random.key(6), (9, 64), jnp.float32)
    y, r = routed_experts(
        h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], top_k=2, scoring="sigmoid",
        select_bias=lp["router_bias"], normalize=True, scale=2.5, compute_dtype=jnp.float32)
    sizes = np.asarray(r["group_sizes"])
    assert sizes.sum() == 18
    if routing != "seeded":
        assert sizes[0] == 9 and sizes[3] == 0
    with jax.default_matmul_precision("highest"):
        weights, _ = fam.ref_routing(h, lp, cfg)
        want = sum(
            weights[:, e : e + 1] * fam._swiglu(h, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e], "float32")
            for e in range(8))
    assert np.abs(np.asarray(y - want)).max() < 1e-4
    # the weights are the scores without the bias, over their sum, times the factor
    scores = np.asarray(jax.nn.sigmoid(h @ lp["router"]))
    picked = np.take_along_axis(scores, np.asarray(r["experts"]), axis=-1)
    assert np.allclose(np.asarray(r["weights"]), 2.5 * picked / picked.sum(-1, keepdims=True), atol=1e-6)


def test_the_stacked_experts_are_read_by_layer_not_cut_out(fam):
    """``first_expert``: a layer's experts as rows of all the layers' merged give what its own slice gives."""
    params = fam.seeded_params(tiny_cfg(), 12)["moe"]
    h = jax.random.normal(jax.random.key(7), (5, 64), jnp.float32)
    kw = dict(top_k=2, scoring="sigmoid", scale=2.5, compute_dtype=jnp.float32)
    merged = [params[k].reshape((-1,) + params[k].shape[2:]) for k in ("w_gate", "w_up", "w_down")]
    for layer in range(2):
        own, _ = routed_experts(h, params["router"][layer], *[params[k][layer] for k in ("w_gate", "w_up", "w_down")], **kw)
        held, r = routed_experts(h, params["router"][layer], *merged, first_expert=jnp.int32(layer * 8), **kw)
        assert np.abs(np.asarray(own - held)).max() < 1e-6 and r["group_sizes"].shape == (8,)


def served(params, c, prompt, new):
    """What a sound program serves for one request, as the check sees a reply."""
    tokens = np.asarray(ds.generate(params, jnp.asarray(prompt)[None], c, new))[0]
    return types.SimpleNamespace(tokens=tokens.tolist(), prompt_len=len(prompt))


@pytest.fixture(scope="module")
def replies(fam):
    cfg = tiny_cfg()
    params = fam.seeded_params(cfg, 13)
    c = fam.program_config(cfg, remat=False)
    rng = np.random.default_rng(8)
    return cfg, params, [served(params, c, rng.integers(0, 256, n), 24) for n in (9, 20)]


def test_the_check_passes_a_sound_program(fam, replies):
    cfg, params, sample = replies
    serve_closed = load_by_path("chipbench_drivers_serve_closed", "chipbench", "drivers", "serve_closed.py")
    gaps = serve_closed.served_gaps(fam, cfg, params, sample, 24)
    assert len(gaps["served"]) == 48 and max(gaps["served"]) <= CONTROL_LIMIT


@pytest.mark.parametrize(
    "control", ["fp8", "no_shared", "bias_in_weights", "unnormalised", "unscaled", "k_rope_unrotated", "skip_layer"])
def test_each_control_fails_the_check(fam, replies, control):
    """The comparison that decides ``correct`` tells each fault from a sound
    run on its own: the token the faulty computation puts first lies, under the
    reference, further below the reference's best than the limit allows."""
    cfg, params, sample = replies
    serve_closed = load_by_path("chipbench_drivers_serve_closed", "chipbench", "drivers", "serve_closed.py")
    gaps = serve_closed.served_gaps(fam, cfg, params, sample, 24, control=control)
    assert max(gaps["control"]) > 4 * CONTROL_LIMIT, (control, max(gaps["control"]))


@pytest.mark.parametrize("chunk", [3, 5, 8])
@pytest.mark.parametrize("mix", [(5, 13, 21), (13, 7, 30, 9, 13)], ids=["three", "five"])
def test_engine_serves_on_the_paged_path_with_a_latent_pool(model, fam, chunk, mix):
    engine_against_reference(model, fam, chunk, mix)


def test_engine_serves_packed_rope_rows(packed, fam):
    engine_against_reference(packed, fam, 5, (5, 13, 21))


def engine_against_reference(model, fam, chunk, mix):
    from accelerate_tpu import Accelerator

    cfg, c, params = model
    engine = Accelerator().prepare_serving(
        ds.apply_cached, ds.init_cache, params, c, block_size=4, num_blocks=96, max_slots=4, max_blocks_per_seq=16,
        prefill_chunk=chunk)
    assert engine.stats()["decode_path"] == "paged" and engine.serving.spec_tokens == 0
    values = sum(int(np.prod(leaf.shape[3:])) * leaf.shape[0] for leaf in engine.cache.pool.values())
    packed = c.qk_rope_head_dim * (-(-3 // ds._rope_pack(c)) * ds._rope_pack(c))  # a padded half row where 3 layers fill 2 rows
    assert values == 3 * c.kv_lora_rank + packed
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, n) for n in mix]
    ids = [engine.submit(p, 7) for p in prompts]
    engine.run()
    done = {r.id: r for r in engine.pop_finished()}
    for rid, prompt in zip(ids, prompts):
        reply = done[rid]
        assert reply.status == "ok" and len(reply.tokens) == len(prompt) + 7
        want = reference_logits(fam, cfg, params, reply.tokens)
        for t in range(len(prompt), len(reply.tokens)):  # each served token is the reference's best, to round-off
            assert want[t - 1].max() - want[t - 1, reply.tokens[t]] < TOL
    stats = engine.stats()
    # hand count: 2 expert layers x top-2 for every row of every dispatch, the padded rows and idle slots among them;
    # a chunk rides with the 4 lanes whether they are live (a mixed dispatch) or idle (a chunk alone)
    dispatches = stats["prefill_dispatches"] + stats["decode_dispatches"] - stats["mixed_dispatches"]
    assert stats["mixed_dispatches"] > 0 and dispatches == stats["ticks"]
    assert stats["moe_rows"] == 2 * 2 * (stats["prefill_dispatches"] * chunk + dispatches * 4)
    assert 2 * dispatches <= stats["moe_max_rows"] <= stats["moe_rows"]
    assert 2 * 2 * dispatches <= stats["moe_experts_hit"] <= stats["moe_rows"]


def test_expert_counters_against_hand_counted_values():
    sizes = jnp.asarray([[4, 0, 0, 2, 0, 0, 0, 0], [1, 1, 1, 1, 1, 1, 0, 0]], jnp.int32)  # two layers, six pairs each
    assert {k: int(v) for k, v in ds.expert_counters(sizes).items()} == {"moe_rows": 12, "moe_experts_hit": 8, "moe_max_rows": 5, "moe_row_tiles": 0, "moe_pairs_routed": 12}


def test_a_family_without_experts_keeps_its_program():
    """llama's paged dispatch returns the flags it always returned: no counter rides in its outputs."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import llama

    c = llama.LlamaConfig.tiny(dtype=jnp.float32)
    engine = Accelerator().prepare_serving(
        llama.apply_cached, llama.init_cache, llama.init_params(c, jax.random.key(0)), c, block_size=4, num_blocks=32,
        max_slots=2, max_blocks_per_seq=8, prefill_chunk=4)
    tables, lengths = np.zeros((2, 2), np.int32), np.zeros((2,), np.int32)
    packed, feed, _ = jax.eval_shape(
        engine.programs.decode, engine.params, engine.cache.pool, tables, lengths, np.zeros((2, 1), np.int32), np.zeros((2,), np.int32),
        np.zeros((3,), np.int32), np.zeros((2,), np.int32))
    assert packed.shape == (2 + 2,) and feed.shape == (2 + 1,)  # a token and a flag a lane, nothing behind them
    engine.submit(np.arange(6), 3)
    engine.run()
    assert {engine.stats()[k] for k in ("moe_rows", "moe_experts_hit", "moe_max_rows")} == {0}


def test_prefix_cache_and_preemption_work_over_latent_blocks(model, fam):
    from accelerate_tpu import Accelerator

    cfg, c, params = model
    engine = Accelerator().prepare_serving(
        ds.apply_cached, ds.init_cache, params, c, block_size=4, num_blocks=15, max_slots=3, max_blocks_per_seq=12,
        prefill_chunk=8)
    rng = np.random.default_rng(10)
    shared = rng.integers(0, 256, 16)
    prompts = [np.concatenate([shared, rng.integers(0, 256, n)]) for n in (3, 6, 9, 5)]
    first = engine.submit(prompts[0], 10)
    engine.run()
    ids = [first] + [engine.submit(p, 10) for p in prompts[1:]]
    engine.run()
    stats = engine.stats()
    assert stats["prefix_hits"] >= 1 and stats["preempted"] >= 1  # 14 blocks for three slots of up to 9 each, 4 of them shared
    done = {r.id: r for r in engine.pop_finished()}
    for rid, prompt in zip(ids, prompts):
        reply = done[rid]
        assert reply.status == "ok"
        want = reference_logits(fam, cfg, params, reply.tokens)
        for t in range(len(prompt), len(reply.tokens)):
            assert want[t - 1].max() - want[t - 1, reply.tokens[t]] < TOL


def test_train_step_runs_at_the_tiny_preset():
    import optax

    from accelerate_tpu import Accelerator, JaxModel
    from accelerate_tpu.parallel.sharding import data_sharding

    c = ds.DeepseekV3Config.tiny()
    acc = Accelerator()

    def apply_fn(params, input_ids, attention_mask=None):
        return {"loss": ds.loss_fn(params, {"input_ids": input_ids, "attention_mask": attention_mask}, c)}

    model, opt = acc.prepare(
        JaxModel(apply_fn, ds.init_params(c, jax.random.key(0)), partition_rules=ds.PARTITION_RULES), optax.adam(1e-2))
    step = acc.make_train_step(model, opt)
    tokens = np.random.default_rng(1).integers(0, c.vocab_size, (8, 16), dtype=np.int32)
    batch = {"input_ids": jax.device_put(tokens, data_sharding(acc.mesh))}
    losses = [float(step(batch)) for _ in range(8)]
    assert losses[-1] < 0.9 * losses[0], losses


def test_hf_state_dict_round_trip(fam):
    """A synthetic state dict under the published names (``model.layers.N.self_attn.kv_b_proj.weight`` ...,
    written from the family's own leaves) comes back through ``hf_import`` leaf for leaf, and nothing is left over."""
    from accelerate_tpu.models.hf_import import config_from_hf, import_state_dict

    cfg = tiny_cfg()
    params = jax.tree.map(np.asarray, fam.seeded_params(cfg, 14))
    c = fam.program_config(cfg, remat=False)
    hf = types.SimpleNamespace(
        model_type="deepseek_v3", num_hidden_layers=3, num_attention_heads=4, max_position_embeddings=128, rms_norm_eps=1e-6,
        topk_group=1, **{k: cfg[k] for k in (
            "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size", "first_k_dense_replace", "kv_lora_rank",
            "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "num_experts_per_tok",
            "n_shared_experts", "routed_scaling_factor", "norm_topk_prob", "scoring_func", "rope_theta", "rope_scaling", "n_group")})
    assert config_from_hf(hf, dtype=jnp.float32, param_dtype=jnp.float32, remat=False) == c
    sd = {"model.embed_tokens.weight": params["embed"], "model.norm.weight": params["final_norm"], "lm_head.weight": params["lm_head"].T}
    names = {"wq": "self_attn.q_proj.weight", "w_kva": "self_attn.kv_a_proj_with_mqa.weight", "wo": "self_attn.o_proj.weight",
             "router": "mlp.gate.weight", "ws_gate": "mlp.shared_experts.gate_proj.weight", "ws_up": "mlp.shared_experts.up_proj.weight",
             "ws_down": "mlp.shared_experts.down_proj.weight"}
    vectors = {"ln_kv": "self_attn.kv_a_layernorm.weight", "ln_attn": "input_layernorm.weight", "ln_mlp": "post_attention_layernorm.weight",
               "router_bias": "mlp.gate.e_score_correction_bias"}
    layer = 0
    for stack in ("dense", "moe"):
        leaves = params[stack]
        for i in range(leaves["wq"].shape[0]):
            pre = f"model.layers.{layer}."
            for ours, theirs in names.items():
                if ours in leaves:
                    sd[pre + theirs] = leaves[ours][i].T  # torch Linear holds [out, in]
            for ours, theirs in vectors.items():
                if ours in leaves:
                    sd[pre + theirs] = leaves[ours][i]
            # kv_b_proj: per head, the k_nope rows and then the v rows
            uk = leaves["w_uk"][i].reshape(32, 4, 16)
            uv = leaves["w_uv"][i].reshape(32, 4, 16)
            sd[pre + "self_attn.kv_b_proj.weight"] = np.concatenate([uk, uv], axis=-1).reshape(32, 4 * 32).T
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")):
                if stack == "dense":
                    sd[pre + f"mlp.{theirs}.weight"] = leaves[ours][i].T
                else:
                    for e in range(8):
                        sd[pre + f"mlp.experts.{e}.{theirs}.weight"] = leaves[ours][i, e].T
            layer += 1
    got = import_state_dict("deepseek_v3", sd, c)
    flat_want, tree_want = jax.tree_util.tree_flatten_with_path(params)
    flat_got, tree_got = jax.tree_util.tree_flatten_with_path(got)
    assert tree_want == tree_got
    for (path, want), (_, have) in zip(flat_want, flat_got):
        assert np.array_equal(np.asarray(have), want), path
    with pytest.raises(ValueError, match="unmapped"):
        import_state_dict("deepseek_v3", dict(sd, **{"model.layers.0.self_attn.q_a_proj.weight": np.zeros((2, 2))}), c)
