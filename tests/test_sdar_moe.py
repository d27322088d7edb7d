"""``models/sdar_moe.py`` (a Qwen3-MoE block under a block-causal mask, generated
by diffusion over blocks of 4) against the plain float32 reference of
``chipbench/families/sdar_moe.py``, at the tiny preset: d 64, 4 heads / 2 K/V
heads of 16, 8 experts top-2 of width 32, 3 layers, vocabulary 256 with id 255
the mask.  Parameters and compute are float32 here, so a tolerance is float32
round-off over the layers (logits are of order 2); the same forward computed in
bf16 misses it by a factor of a hundred and more (held below), and a wrong mask,
a stale row or a dropped norm moves a logit by tenths.

What is compared is **logits, not tokens**: chunked prefill, every denoising
pass and every commit run through ``apply_paged`` and the programs' own write
(``serving/programs.py:_write_rows``, rows kept for the committing lanes alone),
as the engine drives them; the reference runs the finished sequence and, behind
it, the state every pass saw, in ONE forward under an explicit mask matrix
(``families/sdar_moe.py:layout``), and every pass's logits must be the
reference's at its rows.
"""

import importlib.util
import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import sdar_moe as sd
from accelerate_tpu.models.generation import MASKED, block_unmask, denoise_schedule, make_paged_pool
from accelerate_tpu.serving import programs as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4  # float32 round-off of logits of order 2 through 3 layers (read: 2e-6 ... 2e-5); bf16 reads 0.02 and more
W = 4  # the block
MASK_ID = 255


def load_by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, *parts))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def fam():
    return load_by_path("chipbench_families_sdar_moe", "chipbench", "families", "sdar_moe.py")


def tiny_cfg(**kw):
    """The reference's configuration dict of the tiny preset (float32)."""
    cfg = {
        "hidden_size": 64, "moe_intermediate_size": 32, "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
        "vocab_size": 256, "max_position_embeddings": 256, "rms_norm_eps": 1e-6, "rope_theta": 1e6, "rope_scaling": None,
        "attention_bias": False, "tie_word_embeddings": False, "decoder_sparse_step": 1, "mlp_only_layers": [],
        "torch_dtype": "float32", "assumed": {"block_length": W, "mask_token_id": MASK_ID, "norm_scale_std": 0.1},
    }
    cfg.update(kw)
    return cfg


@pytest.fixture(scope="module")
def model(fam):
    cfg = tiny_cfg()
    return cfg, fam.program_config(cfg, remat=False), fam.seeded_params(cfg, 2**31 + 34)


_REFERENCES = {}


def reference_of(fam, cfg, precision="float32"):
    return _REFERENCES.setdefault((repr(sorted(cfg.items(), key=str)), precision), fam.Reference(cfg, precision))


def reference_logits(fam, cfg, params, lay, precision="float32"):
    """The reference's one forward of a layout: logits at every row, ``[S, V]``."""
    ref = reference_of(fam, cfg, precision)
    with jax.default_matmul_precision("highest"):
        x = ref.trunk(params, lay["ids"], lay["positions"], lay["mask"])
        return np.asarray(fam.ref_head(x, params["final_norm"], params["lm_head"], cfg, precision))


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------


def test_program_config_is_the_tiny_preset(fam):
    c = fam.program_config(tiny_cfg(), remat=False)
    assert c == sd.SdarMoeConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    assert (c.block_length, c.mask_token_id) == (4, 255)
    with pytest.raises(ValueError, match="not a row"):
        sd.SdarMoeConfig.tiny(mask_token_id=256)
    with pytest.raises(ValueError, match="block_length"):
        sd.SdarMoeConfig.tiny(block_length=0)


def test_the_published_widths_count_the_published_parameters(fam):
    published = sd.SdarMoeConfig()  # the defaults are SDAR-30B-A3B-Chat's config.json
    layer = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 2048 + 2 * 128 + 2048 * 128 + 128 * 3 * 2048 * 768
    assert layer == 623_120_640
    assert published.num_params() == 48 * layer + 2 * 151_936 * 2048 + 2048 == 30_532_122_624  # the published 30 B
    cut = sd.SdarMoeConfig(num_layers=7)
    assert cut.num_params() == 7 * layer + 622_329_856 + 2048 == 4_984_176_384
    cache = jax.eval_shape(lambda: sd.init_cache(cut, 1, 16))
    assert cache["k"].shape == cache["v"].shape == (7, 1, 16, 4, 128)  # hd 128 x K 4: the pool is read in place


# ---------------------------------------------------------------------------
# the forward under the mask
# ---------------------------------------------------------------------------


def whole_sequence(fam, cfg, ids):
    n = len(ids)
    positions = np.arange(n, dtype=np.int32)
    return {"ids": np.asarray(ids, np.int32), "positions": positions, "mask": fam.block_causal(positions, W)}


def test_apply_matches_the_reference_under_the_block_mask(model, fam):
    cfg, c, params = model
    ids = np.random.default_rng(0).integers(0, 255, (2, 40))
    got = np.asarray(sd.apply(params, jnp.asarray(ids), c))
    for b in range(2):
        want = reference_logits(fam, cfg, params, whole_sequence(fam, cfg, ids[b]))
        assert np.abs(got[b] - want).max() < TOL
    # the mask is the block's: the first row of a block sees its last (a causal model's would not)
    moved = ids.copy()
    moved[:, 7] = (moved[:, 7] + 1) % 255
    again = np.asarray(sd.apply(params, jnp.asarray(moved), c))
    assert np.abs(again[:, 4] - got[:, 4]).max() > 1e-3 and np.abs(again[:, :4] - got[:, :4]).max() == 0.0
    # and the tolerance tells the precision: the same forward computed in bf16 misses it a hundred times over
    low = np.asarray(sd.apply(params, jnp.asarray(ids), fam.program_config(cfg, remat=False, dtype=jnp.bfloat16)))
    assert np.abs(low[0] - reference_logits(fam, cfg, params, whole_sequence(fam, cfg, ids[0]))).max() > 100 * TOL


def test_block_length_one_is_the_causal_model(model, fam):
    cfg, _, params = model
    one = dict(cfg, assumed=dict(cfg["assumed"], block_length=1))
    c = fam.program_config(one, remat=False)
    ids = np.random.default_rng(1).integers(0, 255, (1, 12))
    got = np.asarray(sd.apply(params, jnp.asarray(ids), c))[0]
    causal = {"ids": ids[0].astype(np.int32), "positions": np.arange(12, dtype=np.int32), "mask": np.tril(np.ones((12, 12), bool))}
    assert np.abs(got - reference_logits(fam, one, params, causal)).max() < TOL
    out = sd.generate(params, jnp.asarray(ids), c, 5)
    from accelerate_tpu.models.generation import generate_loop

    assert (np.asarray(out) == np.asarray(generate_loop(sd.apply_cached, sd.init_cache, params, jnp.asarray(ids), c, 5))).all()


def test_loss_has_a_gradient_in_every_leaf(model):
    _, c, params = model
    tokens = np.random.default_rng(2).integers(0, 255, (4, 16), dtype=np.int32)
    loss, grads = jax.value_and_grad(sd.loss_fn)(params, {"input_ids": jnp.asarray(tokens)}, c)
    assert 4.0 < float(loss) < 7.5  # about log(256) with seeded weights
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(grads))


# ---------------------------------------------------------------------------
# the unmask rule
# ---------------------------------------------------------------------------


def test_unmask_rule_by_hand():
    v = 6
    logits = np.full((3, 4, v), -5.0, np.float32)
    # lane 0: confidences rise with the position; lane 1: a tie between positions 1 and 3; lane 2: nothing masked
    for pos, (token, height) in enumerate([(1, 1.0), (2, 2.0), (3, 3.0), (4, 4.0)]):
        logits[0, pos, token] = height
    for pos, (token, height) in enumerate([(1, 1.0), (2, 3.0), (3, 2.0), (4, 3.0)]):
        logits[1, pos, token] = height
    state = np.asarray([[MASKED, MASKED, 5, MASKED], [MASKED] * 4, [1, 2, 3, 4]], np.int32)
    none = jnp.full((3,), 2.0, jnp.float32)
    out = np.asarray(block_unmask(jnp.asarray(state), jnp.asarray(logits), jnp.asarray([2, 1, 2], jnp.int32), none))
    assert out.tolist() == [[MASKED, 2, 5, 4], [MASKED, 2, MASKED, MASKED], [1, 2, 3, 4]]  # the tie goes to the lower position
    out = np.asarray(block_unmask(jnp.asarray(state), jnp.asarray(logits), jnp.asarray([9, 0, 0], jnp.int32), none))
    assert out.tolist() == [[1, 2, 5, 4], [MASKED] * 4, [1, 2, 3, 4]]  # never more than are masked; a count of 0 unmasks none
    # a threshold that three positions of lane 1 pass, against a count of 1: all three; that none passes: the count's one
    conf = np.exp(logits[1].max(-1)) / np.exp(logits[1]).sum(-1)
    between = float((np.sort(conf)[0] + np.sort(conf)[1]) / 2)
    out = np.asarray(block_unmask(jnp.asarray(state), jnp.asarray(logits), jnp.ones((3,), jnp.int32), jnp.asarray([2.0, between, 2.0])))
    assert out[1].tolist() == [MASKED, 2, 3, 4] and out[0].tolist() == [MASKED, MASKED, 5, 4]
    assert denoise_schedule(4, None) == [1, 1, 1, 1] and denoise_schedule(4, 3) == [2, 1, 1] and denoise_schedule(4, 1) == [4]
    with pytest.raises(ValueError, match="denoise_steps"):
        denoise_schedule(4, 5)


# ---------------------------------------------------------------------------
# the paged path, as the engine drives it: logits of every chunk, pass and commit
# ---------------------------------------------------------------------------

BLOCK, BLOCKS, WIDTH = 4, 64, 12


def dispatcher(c, params):
    """One dispatch over the lanes (and a chunk) as ``serving/programs.py`` makes it: ``apply_paged`` over the groups,
    then the programs' write, the lanes' rows kept where ``commit`` says so, the chunk's always."""
    @jax.jit
    def lanes_only(pool, ids, tables, starts, commit):
        (logits,), (rows,), _ = sd.apply_paged(params, ((ids, tables, starts),), c, pool)
        return logits, None, P._write_rows(pool, rows, tables, starts, W, keep=commit)

    @jax.jit
    def with_chunk(pool, ids, tables, starts, commit, chunk, table_row, start):
        groups = ((ids, tables, starts), (chunk, table_row[None], start[None]))
        (logits, chunk_logits), (rows, chunk_rows), _ = sd.apply_paged(params, groups, c, pool)
        pool = P._write_rows(pool, rows, tables, starts, W, keep=commit)
        return logits, chunk_logits[0], P._write_rows(pool, chunk_rows, table_row[None], start[None], chunk.shape[1])

    return lanes_only, with_chunk


def serve_by_hand(c, params, requests, chunk, stagger):
    """A hand-driven engine: request ``i`` = (prompt, new tokens, T) sits in lane ``i`` with blocks of its own, starts
    prefilling at tick ``i * stagger`` (one chunk a tick, the oldest first), then carries its blocks through the static
    schedule.  Returns of every request its final tokens (whole blocks), the pass number of every new token, the
    logits of every real prefilled row and ``[(logits [W, V])]`` of every denoising pass in order; and the set of
    (chunk riding, phases of the lanes) met in a dispatch.  A dispatch that only denoises must leave the pool bit
    for bit."""
    lanes_only, with_chunk = dispatcher(c, params)
    pool = make_paged_pool(sd.init_cache, c, BLOCKS, BLOCK)
    n = len(requests)
    tables = np.asarray([[1 + WIDTH * i + j for j in range(WIDTH)] for i in range(n)], np.int32)
    lanes = []
    for prompt, new, steps in requests:
        p0 = len(prompt) // W * W
        lanes.append(dict(prompt=list(prompt), new=new, schedule=denoise_schedule(W, steps), p0=p0, rows=0, state=None, t=0,
                          tokens=list(prompt[:p0]), passes=[], block_passes=None, prefill=[], denoise=[], done=False))
    met, tick = set(), 0
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    while not all(lane["done"] for lane in lanes):
        ids, starts, commit, phases = np.zeros((n, W), np.int32), np.zeros((n,), np.int32), np.zeros((n,), np.int32), {}
        tabs = np.zeros((n, WIDTH), np.int32)
        for i, lane in enumerate(lanes):
            if lane["state"] is None or lane["done"]:
                continue
            tabs[i], starts[i] = tables[i], lane["rows"]
            ids[i] = [MASK_ID if t == MASKED else t for t in lane["state"]]
            commit[i] = MASKED not in lane["state"]
            phases[i] = "commit" if commit[i] else ("first" if lane["t"] == 0 else "later")
        filling = [i for i, lane in enumerate(lanes) if lane["state"] is None and tick >= i * stagger]
        before = {k: np.asarray(v) for k, v in pool.items()}
        if filling:
            i = filling[0]
            lane = lanes[i]
            real = min(chunk, lane["p0"] - lane["rows"])
            piece = np.full((1, chunk), 254, np.int32)  # padded with a junk token
            piece[0, :real] = lane["prompt"][lane["rows"] : lane["rows"] + real]
            logits, chunk_logits, pool = with_chunk(pool, i32(ids), i32(tabs), i32(starts), i32(commit), i32(piece), i32(tables[i]), jnp.int32(lane["rows"]))
            lane["prefill"].append(np.asarray(chunk_logits)[:real])
            lane["rows"] += real
        else:
            logits, _, pool = lanes_only(pool, i32(ids), i32(tabs), i32(starts), i32(commit))
        met.add((bool(filling), frozenset(phases.values())))
        if not filling and "commit" not in phases.values():
            assert all((np.asarray(pool[k]) == before[k]).all() for k in pool), "a denoising pass wrote to the pool"
        logits = np.asarray(logits)
        for i, phase in phases.items():
            lane = lanes[i]
            if phase == "commit":
                lane["rows"] += W
                lane["tokens"] += lane["state"]
                lane["passes"] += lane["block_passes"]
                lane["done"] = len(lane["tokens"]) >= len(lane["prompt"]) + lane["new"]
                lane["state"], lane["block_passes"], lane["t"] = [MASKED] * W, [-1] * W, 0
                continue
            lane["denoise"].append(logits[i])
            count = min(lane["schedule"][lane["t"]], lane["state"].count(MASKED))
            new_state = np.asarray(block_unmask(i32([lane["state"]]), jnp.asarray(logits[i : i + 1]), i32([count]), jnp.full((1,), 2.0)))[0].tolist()
            for at, (old, tok) in enumerate(zip(lane["state"], new_state)):
                if old == MASKED and tok != MASKED:
                    lane["block_passes"][at] = lane["t"]
            lane["state"], lane["t"] = new_state, lane["t"] + 1
        for i, lane in enumerate(lanes):  # a prompt wholly in the pool opens its first block
            if lane["state"] is None and lane["rows"] == lane["p0"] and tick >= i * stagger:
                rest = lane["prompt"][lane["p0"] :]
                lane["state"], lane["block_passes"] = rest + [MASKED] * (W - len(rest)), [-1] * W
        tick += 1
        assert tick < 500
    for lane in lanes:
        lane["passes"] = lane["passes"][len(lane["prompt"]) - lane["p0"] :]  # of the new tokens alone
    return lanes, met


def assert_every_pass_is_the_references(fam, cfg, params, lane, cut=None):
    """The lane's prefill logits and every denoising pass's logits against the
    reference's one forward of (finished sequence, then the state each pass saw).
    ``cut`` drops the reply's tail as the engine does for a request that ends inside a block."""
    tokens, passes = lane["tokens"], lane["passes"]
    if cut is not None:
        tokens, passes = tokens[:cut], passes[: cut - len(lane["prompt"])]
    lay = fam.layout(tokens, len(lane["prompt"]), passes, cfg)
    want = reference_logits(fam, cfg, params, lay)
    prefilled = np.concatenate(lane["prefill"], axis=0) if lane["prefill"] else np.zeros((0, want.shape[1]))
    assert len(prefilled) == lane["p0"] and np.abs(prefilled - want[: lane["p0"]]).max(initial=0.0) < TOL
    groups = lay["group"].max(initial=-1) + 1
    assert groups <= len(lane["denoise"]) and (cut is not None or groups == len(lane["denoise"]))
    for g in range(groups):
        rows = lay["rows"][lay["group"] == g]
        assert np.abs(lane["denoise"][g] - want[rows]).max() < TOL, g
    return lay


@pytest.mark.parametrize("steps", [1, 2, 3, 4])
@pytest.mark.parametrize("prompt_len", [12, 13, 15], ids=["mod0", "mod1", "mod3"])
def test_prefill_passes_and_commits_through_the_pool_match_the_reference(model, fam, steps, prompt_len):
    cfg, c, params = model
    rng = np.random.default_rng(100 * steps + prompt_len)
    (lane,), met = serve_by_hand(c, params, [(rng.integers(0, 255, prompt_len).tolist(), 10, steps)], chunk=8, stagger=0)
    lay = assert_every_pass_is_the_references(fam, cfg, params, lane)
    assert lay["finished"] == len(lane["tokens"]) and len(lane["tokens"]) % W == 0
    first_block = W - prompt_len % W
    schedule = denoise_schedule(W, steps)
    assert max(lane["passes"]) < steps and sorted(lane["passes"][first_block : first_block + W]) == sorted(
        t for t, k in enumerate(schedule) for _ in range(k))
    # a request that ends inside a block: the engine drops the tail, and the layout the block whose passes it cannot rebuild
    cut = assert_every_pass_is_the_references(fam, cfg, params, lane, cut=prompt_len + 6)
    assert cut["finished"] == (prompt_len + 6) // W * W


@pytest.mark.parametrize("mix", ["three-staggered", "five-together"])
def test_lanes_in_every_phase_with_a_chunk_riding(model, fam, mix):
    """Whoever shares the dispatch: lanes at their first pass, at a later one and
    committing, and another request's chunk in the same forward."""
    cfg, c, params = model
    rng = np.random.default_rng(5)
    sizes = [(21, 9, 3), (9, 12, 2), (30, 7, 4)] if mix == "three-staggered" else [(8, 8, 2), (13, 6, 1), (5, 9, 4), (17, 8, 3), (3, 5, 2)]
    requests = [(rng.integers(0, 255, p).tolist(), n, t) for p, n, t in sizes]
    lanes, met = serve_by_hand(c, params, requests, chunk=8, stagger=3 if mix == "three-staggered" else 0)
    for lane in lanes:
        assert_every_pass_is_the_references(fam, cfg, params, lane)
    if mix == "three-staggered":
        assert any(riding and phases == {"first", "later", "commit"} for riding, phases in met) or (
            any(riding and "commit" in phases for riding, phases in met) and any(riding and {"first", "later"} <= phases for riding, phases in met))


def test_bf16_in_place_of_float32_fails_the_tolerance(model, fam):
    cfg, _, params = model
    low = fam.program_config(cfg, remat=False, dtype=jnp.bfloat16)
    rng = np.random.default_rng(8)
    (lane,), _ = serve_by_hand(low, params, [(rng.integers(0, 255, 13).tolist(), 8, 2)], chunk=8, stagger=0)
    lay = fam.layout(lane["tokens"], 13, lane["passes"], cfg)
    want = reference_logits(fam, cfg, params, lay)
    worst = max(np.abs(lane["denoise"][g] - want[lay["rows"][lay["group"] == g]]).max() for g in range(len(lane["denoise"])))
    assert worst > 50 * TOL


def test_expert_counters_of_one_dispatch(model):
    _, c, params = model
    lanes_only, _ = dispatcher(c, params)
    pool = make_paged_pool(sd.init_cache, c, BLOCKS, BLOCK)
    groups = ((jnp.zeros((2, W), jnp.int32), jnp.zeros((2, WIDTH), jnp.int32), jnp.zeros((2,), jnp.int32)),)
    _, _, counters = sd.apply_paged(params, groups, c, pool)
    assert int(counters["moe_rows"]) == 3 * 2 * W * 2  # layers x lanes x rows x top-2
    assert 3 * 2 <= int(counters["moe_experts_hit"]) <= 3 * 8


# ---------------------------------------------------------------------------
# hf_import
# ---------------------------------------------------------------------------


def test_hf_state_dict_round_trip(model, fam):
    """The Qwen3-MoE parameter names, from a synthetic state dict (no published checkpoint is in the repository)."""
    from accelerate_tpu.models import hf_import

    cfg, c, params = model
    sd_hf = {"model.embed_tokens.weight": np.asarray(params["embed"]), "model.norm.weight": np.asarray(params["final_norm"]),
             "lm_head.weight": np.asarray(params["lm_head"]).T}
    lay = params["layers"]
    for i in range(c.num_layers):
        pre = f"model.layers.{i}."
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
            sd_hf[pre + f"self_attn.{theirs}.weight"] = np.asarray(lay[ours][i]).T
        sd_hf[pre + "self_attn.q_norm.weight"] = np.asarray(lay["ln_q"][i])
        sd_hf[pre + "self_attn.k_norm.weight"] = np.asarray(lay["ln_k"][i])
        sd_hf[pre + "input_layernorm.weight"] = np.asarray(lay["ln_attn"][i])
        sd_hf[pre + "post_attention_layernorm.weight"] = np.asarray(lay["ln_mlp"][i])
        sd_hf[pre + "mlp.gate.weight"] = np.asarray(lay["router"][i]).T
        for e in range(c.num_experts):
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")):
                sd_hf[pre + f"mlp.experts.{e}.{theirs}.weight"] = np.asarray(lay[ours][i, e]).T
    hf_config = types.SimpleNamespace(
        model_type="sdar_moe", vocab_size=256, hidden_size=64, moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=2,
        norm_topk_prob=True, max_position_embeddings=256, rope_theta=1e6, rms_norm_eps=1e-6, tie_word_embeddings=False,
        decoder_sparse_step=1, mlp_only_layers=[], mask_token_id=255,  # block_length left out: the family's default, 4
    )
    got_cfg = hf_import.config_from_hf(hf_config, dtype=jnp.float32, param_dtype=jnp.float32, remat=False)
    assert got_cfg == c
    got = hf_import.import_state_dict("sdar_moe", sd_hf, got_cfg)
    assert jax.tree.structure(params) == jax.tree.structure(got)
    for (path, want), have in zip(jax.tree_util.tree_flatten_with_path(params)[0], jax.tree.leaves(got)):
        assert np.array_equal(np.asarray(have), np.asarray(want)), path
    with pytest.raises(ValueError, match="unmapped"):
        hf_import.import_state_dict("sdar_moe", dict(sd_hf, **{"model.layers.0.self_attn.q_proj.bias": np.zeros((64,))}), got_cfg)
    with pytest.raises(ValueError, match="dense layers between"):
        hf_import.config_from_hf(types.SimpleNamespace(**dict(vars(hf_config), mlp_only_layers=[1])))
