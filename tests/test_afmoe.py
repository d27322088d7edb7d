"""``models/afmoe.py`` (gated attention of two kinds, sliding window and full,
sandwich norms, sigmoid-routed experts of which the chip may hold a share, one
shared expert) against the plain float32 reference of
``chipbench/families/afmoe.py``, at a tiny preset: d 64, 4 heads / 2 K/V heads of
16, dense 128, 8 experts top-2 of width 32, a window of 8, one dense layer and
periods ``s s s f``.  Parameters and compute are float32 here, so a tolerance is
float32 round-off over the layers (logits are of order 4); a key seen past its
window, a rotated full layer, a dropped gate or a wrong share moves a logit by
tenths and cannot hide in it.

The contexts run to five windows, with chunk boundaries inside the first
window, on its edge and across it, through ``apply_cached`` (every row kept, the
window masked) and through ``apply_paged`` over a pool whose window leaves hold
a **ring** of ``window_ring_blocks`` blocks (the serving path: the ring wraps
several times)."""

import importlib.util
import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import afmoe as af
from accelerate_tpu.models.generation import WINDOW, make_paged_pool, window_ring_blocks
from accelerate_tpu.serving import programs as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4  # float32 round-off of logits of order 4 through up to 9 layers; every fault below moves them by 0.05 and more
FAULT = 0.05
WINDOW_ROWS, BLOCK = 8, 4
PERIODS = [af.SLIDING, af.SLIDING, af.SLIDING, af.FULL]
STACKS = {"two-periods": [af.SLIDING] + PERIODS * 2, "cut-5": [af.SLIDING] + PERIODS, "full-first": [af.FULL, af.SLIDING, af.FULL]}
CHUNKS = [1, 3, 8, 16]


def load_by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, *parts))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def fam():
    return load_by_path("chipbench_families_afmoe", "chipbench", "families", "afmoe.py")


def tiny_cfg(layer_types=STACKS["two-periods"], **kw):
    """The reference's configuration dict of the tiny preset (float32); ``num_experts`` is what is held."""
    cfg = {
        "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32, "num_hidden_layers": len(layer_types),
        "layer_types": list(layer_types), "num_dense_layers": 1, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "num_experts": 8, "num_experts_per_tok": 2, "num_shared_experts": 1, "route_norm": True,
        "route_scale": 2.448, "sliding_window": WINDOW_ROWS, "mup_enabled": True, "vocab_size": 256,
        "max_position_embeddings": 256, "rms_norm_eps": 1e-5, "rope_theta": 10000, "torch_dtype": "float32",
        "assumed": {"norm_scale_std": 0.1, "selection_bias_std": 0.1},
    }
    cfg.update(kw)
    return cfg


def share_cfg(**kw):
    """Four of the router's eight experts held, from the third on."""
    cfg = tiny_cfg(STACKS["cut-5"], num_experts=4, router_experts=8, **kw)
    cfg["assumed"] = dict(cfg["assumed"], experts_held_first=2)
    return cfg


@pytest.fixture(scope="module", params=list(STACKS))
def model(fam, request):
    cfg = tiny_cfg(STACKS[request.param])
    return cfg, fam.program_config(cfg, remat=False), fam.seeded_params(cfg, 2**31 + 38)


@pytest.fixture(scope="module")
def share(fam):
    cfg = share_cfg()
    return cfg, fam.program_config(cfg, remat=False), fam.seeded_params(cfg, 2**31 + 39)


_REFERENCES = {}  # one jitted reference a configuration and control


def reference_logits(fam, cfg, params, tokens, control="float32"):
    """The reference's one full forward of one sequence, ``[S, V]``, right-padded to a multiple of 48 (every mask is
    causal and routing is by row: padding changes nothing before it)."""
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


def test_program_config_is_the_tiny_preset(fam):
    c = fam.program_config(tiny_cfg(), remat=False)
    assert c == af.AfmoeConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    assert c.count(af.SLIDING) == 7 and c.count(af.FULL) == 2 and c.held == (0, 8) and c.share is None
    assert c.num_params() == fam.num_params(tiny_cfg())
    assert jax.tree.map(lambda a: a.shape, af.init_params(c, jax.random.key(0))) == jax.tree.map(
        lambda a: a.shape, fam.seeded_params(tiny_cfg(), 3))
    held = fam.program_config(share_cfg(), remat=False)
    assert held.num_experts == 8 and held.experts_held == (2, 4) and held.share == (2, 4)
    assert af.init_params(held, jax.random.key(0))["moe"]["w_gate"].shape == (4, 4, 64, 32)
    assert af.AfmoeConfig().layer_types == tuple(PERIODS * 15) and af.AfmoeConfig().num_params() > 390e9  # the published 400 B
    for bad in (dict(experts_held=(6, 4)), dict(experts_held=(0, 0)), dict(layer_types=("sliding",) * 9), dict(sliding_window=0)):
        with pytest.raises(ValueError):
            af.AfmoeConfig.tiny(**bad)


def test_apply_is_the_reference(fam, model):
    cfg, c, params = model
    tokens = some_tokens(40)
    got = np.asarray(jax.jit(lambda p, t: af.apply(p, t, c))(params, tokens[None]))[0]
    assert np.max(np.abs(got - reference_logits(fam, cfg, params, tokens))) < TOL
    padded = np.concatenate([tokens, np.zeros(8, np.int32)])[None]
    mask = (np.arange(48) < 40)[None]
    got = np.asarray(af.apply(params, padded, c, attention_mask=mask))[0, :40]
    assert np.max(np.abs(got - reference_logits(fam, cfg, params, tokens))) < TOL


def test_a_share_is_the_references_partial_sum(fam, share):
    cfg, c, params = share
    tokens = some_tokens(40, 1)
    got = np.asarray(af.apply(params, tokens[None], c))[0]
    assert np.max(np.abs(got - reference_logits(fam, cfg, params, tokens))) < TOL
    loss = jax.jit(lambda p: af.loss_fn(p, {"input_ids": tokens[None]}, dataclass_replace(c, remat=True)))
    grads = jax.grad(loss)(params)
    assert float(jnp.abs(grads["moe"]["w_gate"]).sum()) > 0 and float(jnp.abs(grads["moe"]["wg"]).sum()) > 0


def dataclass_replace(c, **kw):
    import dataclasses

    return dataclasses.replace(c, **kw)


@pytest.mark.parametrize("control", ["fp8", "all_full", "rope_on_full", "gate_dropped", "post_norms_dropped",
                                     "shared_dropped", "scale_dropped", "wrong_share", "bias_in_weights"])
def test_every_control_is_told_from_the_program(fam, share, control):
    assert control in fam.CONTROLS and len(fam.CONTROLS) == 9
    cfg, c, params = share
    tokens = some_tokens(40, 2)
    got = np.asarray(af.apply(params, tokens[None], c))[0]
    assert np.max(np.abs(got - reference_logits(fam, cfg, params, tokens, control))) > FAULT


@pytest.mark.parametrize("chunk", CHUNKS)
def test_cached_prefill_and_decode_are_the_references_full_forward(fam, model, chunk):
    """Prefill in chunks of ``chunk`` (boundaries inside the first window, on its edge, across it), then decode a
    token at a time to five windows: every logit is the full forward's."""
    cfg, c, params = model
    tokens = some_tokens(40, 3)
    want = reference_logits(fam, cfg, params, tokens)
    step = jax.jit(lambda p, t, cache: af.apply_cached(p, t, c, cache))
    cache, got, prompt = af.init_cache(c, 1, 48), [], 21
    for start in list(range(0, prompt, chunk)):
        logits, cache = step(params, tokens[None, start : min(start + chunk, prompt)], cache)
        got.append(np.asarray(logits)[0])
    for at in range(prompt, 40):
        logits, cache = step(params, tokens[None, at : at + 1], cache)
        got.append(np.asarray(logits)[0])
    assert np.max(np.abs(np.concatenate(got) - want)) < TOL
    assert int(cache["index"]) == 40 and (WINDOW in cache) == bool(c.count(af.SLIDING))


def paged_run(c, params, tokens, prompt, chunk, lanes=1, interpret=False):
    """The serving path by hand: one sequence in lane 0 of ``lanes``, its prompt in padded chunks of ``chunk`` and
    then a row a dispatch, through ``apply_paged`` over a pool whose window leaves are a ring, written by the
    engine's own ``_write_rows`` (``interpret``: a group of one row a lane reads both kinds of pool through the paged
    kernel in the Pallas interpreter).  Returns (logits of every real row, counters of the last dispatch, the ring's
    width)."""
    ring = window_ring_blocks(c.sliding_window, chunk, BLOCK)
    blocks = -(-(len(tokens) + chunk) // BLOCK)
    pool = make_paged_pool(af.init_cache, c, blocks + 2, BLOCK, window_blocks=ring + 2)
    pool = jax.tree.map(lambda leaf: jnp.full_like(leaf, 7.0), pool)  # junk in every block: what is read was written
    tables = np.zeros((lanes, blocks), np.int32)
    tables[0] = 1 + np.arange(blocks)
    wide = min(blocks, ring)
    wtables = np.zeros((lanes, wide), np.int32)
    wtables[0] = 1 + np.arange(wide)

    @jax.jit
    def dispatch(pool, toks, starts):
        logits, rows, counters = af.apply_paged(params, ((toks, tables, starts, wtables),), c, pool, interpret=interpret)
        return logits[0], P._write_rows(pool, rows[0], tables, starts, toks.shape[1], wtables=wtables), counters

    got, counters = [], None
    for start in range(0, prompt, chunk):
        real = min(chunk, prompt - start)
        toks = np.zeros((lanes, chunk), np.int32)
        toks[0, :real] = tokens[start : start + real]
        logits, pool, counters = dispatch(pool, toks, np.asarray([start] + [0] * (lanes - 1), np.int32))
        got.append(np.asarray(logits)[0, :real])
    for at in range(prompt, len(tokens)):
        toks = np.zeros((lanes, 1), np.int32)
        toks[0, 0] = tokens[at]
        logits, pool, counters = dispatch(pool, toks, np.asarray([at] + [0] * (lanes - 1), np.int32))
        got.append(np.asarray(logits)[0])
    return np.concatenate(got), counters, ring


@pytest.mark.parametrize("chunk", [3, 4, 16])
def test_paged_ring_is_the_references_full_forward(fam, model, chunk):
    cfg, c, params = model
    tokens = some_tokens(44, 4)
    got, _, ring = paged_run(c, params, tokens, 23, chunk)
    assert ring * BLOCK < 44 or chunk == 16  # the ring has wrapped: rows were overwritten in place
    assert np.max(np.abs(got - reference_logits(fam, cfg, params, tokens))) < TOL


@pytest.mark.parametrize("chunk", [1, 4])
def test_the_lanes_read_in_place_are_the_gathered_lanes(fam, model, chunk):
    """The decoding lanes through ``ops/pallas_paged_attention.py`` (the Pallas interpreter), the chunk's group
    gathered as ever; at a chunk of 1 the prompt's rows go through the kernel too, from position 0 on.  Both kinds of
    layer, the ring wrapped: every logit is the gathered path's and the reference's."""
    cfg, c, params = model
    tokens = some_tokens(44, 4)
    want, counters, _ = paged_run(c, params, tokens, 23, chunk, lanes=2)
    got, counters_in_place, _ = paged_run(c, params, tokens, 23, chunk, lanes=2, interpret=True)
    assert np.max(np.abs(got - want)) < TOL and np.max(np.abs(got - reference_logits(fam, cfg, params, tokens))) < TOL
    assert int(counters["attn_rows_read"]) == 0 < int(counters_in_place["attn_rows_read"])


def test_counters_of_one_dispatch_against_hand_worked_numbers(share):
    cfg, c, params = share
    tokens = some_tokens(30, 5)
    _, counters, _ = paged_run(c, params, tokens, 20, 4, lanes=3)
    counters = {k: int(v) for k, v in counters.items()}
    # the last dispatch: three lanes of one row, the first at position 29 (the others hold no sequence); four sliding
    # layers read the window's 8 rows where a full layer reads 30; four expert layers route 3 x top-2 pairs each
    assert counters["window_rows_read"] == 4 * 8 and counters["context_rows"] == 4 * 30
    assert counters["moe_pairs_routed"] == 4 * 3 * 2 and 0 <= counters["moe_rows"] <= counters["moe_pairs_routed"]
    assert counters["moe_experts_hit"] <= 4 * 4 and counters["moe_row_tiles"] == 0
    assert set(counters) == set(P.DISPATCH_COUNTERS)
    # inside the first window the sliding layers read what the full layer reads
    _, early, _ = paged_run(c, params, tokens[:6], 4, 4)
    assert int(early["window_rows_read"]) == int(early["context_rows"]) == 4 * 6
    # read in place: lane 0 at 29 copies blocks of 4 rows, the full layer positions 0 .. 28 (8 blocks), each sliding
    # layer 22 .. 28 (3 blocks, the edge blocks whole); the lanes at 0 copy nothing.  Gathered, nothing is counted
    assert counters["attn_rows_read"] == 0
    _, in_place, _ = paged_run(c, params, tokens, 20, 4, lanes=3, interpret=True)
    assert int(in_place["attn_rows_read"]) == 1 * 8 * 4 + 4 * 3 * 4


def test_generate_is_greedy_over_the_reference(fam, share):
    cfg, c, params = share
    prompt = some_tokens(13, 6)
    out = np.asarray(af.generate(params, prompt[None], c, 20, prefill_chunk=5))[0]
    want = reference_logits(fam, cfg, params, out)
    assert (out[13:] == want[12:-1].argmax(-1)).all()


# ---------------------------------------------------------------------------
# hf_import
# ---------------------------------------------------------------------------


def hf_state_dict(c, params, router_experts):
    """A synthetic state dict under ``modeling_afmoe.py``'s names (no published checkpoint is in the repository)."""
    sd = {"model.embed_tokens.weight": np.asarray(params["embed"]), "model.norm.weight": np.asarray(params["final_norm"]),
          "lm_head.weight": np.asarray(params["lm_head"]).T}
    names = (("wq", "self_attn.q_proj"), ("wk", "self_attn.k_proj"), ("wv", "self_attn.v_proj"), ("wg", "self_attn.gate_proj"),
             ("wo", "self_attn.o_proj"))
    norms = (("ln_in", "input_layernorm"), ("ln_post_attn", "post_attention_layernorm"), ("ln_pre_mlp", "pre_mlp_layernorm"),
             ("ln_post_mlp", "post_mlp_layernorm"), ("ln_q", "self_attn.q_norm"), ("ln_k", "self_attn.k_norm"))
    for i in range(c.num_layers):
        pre = f"model.layers.{i}."
        stack, at = (params["dense"], i) if i < c.num_dense_layers else (params["moe"], i - c.num_dense_layers)
        for ours, theirs in names:
            sd[pre + theirs + ".weight"] = np.asarray(stack[ours][at]).T
        for ours, theirs in norms:
            sd[pre + theirs + ".weight"] = np.asarray(stack[ours][at])
        mlp = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))
        if i < c.num_dense_layers:
            for ours, theirs in mlp:
                sd[pre + f"mlp.{theirs}.weight"] = np.asarray(stack[ours][at]).T
            continue
        sd[pre + "mlp.router.gate.weight"] = np.asarray(stack["router"][at]).T
        sd[pre + "mlp.expert_bias"] = np.asarray(stack["router_bias"][at])
        for ours, theirs in mlp:
            sd[pre + f"mlp.shared_experts.{theirs}.weight"] = np.asarray(stack["ws" + ours[1:]][at]).T
            for e in range(router_experts):
                held = e - c.held[0]
                w = stack[ours][at, held] if 0 <= held < c.held[1] else np.zeros(stack[ours].shape[2:], np.float32) + e
                sd[pre + f"mlp.experts.{e}.{theirs}.weight"] = np.asarray(w).T
    return sd


@pytest.mark.parametrize("held", [None, (2, 4)])
def test_hf_state_dict_round_trip(fam, share, held):
    from accelerate_tpu.models import hf_import

    cfg = share_cfg() if held else tiny_cfg(STACKS["cut-5"])
    c, params = fam.program_config(cfg, remat=False), fam.seeded_params(cfg, 11)
    hf_config = types.SimpleNamespace(
        model_type="afmoe", vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=5, layer_types=STACKS["cut-5"], num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_experts=8, num_experts_per_tok=2, num_shared_experts=1, route_norm=True, route_scale=2.448,
        sliding_window=8, mup_enabled=True, max_position_embeddings=256, rope_theta=10000, rms_norm_eps=1e-5,
        tie_word_embeddings=False, rope_scaling=None, score_func="sigmoid", n_group=1, topk_group=1,
    )
    overrides = dict(dtype=jnp.float32, param_dtype=jnp.float32, remat=False, **({"experts_held": held} if held else {}))
    got_cfg = hf_import.config_from_hf(hf_config, **overrides)
    assert got_cfg == c
    sd = hf_state_dict(c, params, 8)
    got = hf_import.import_state_dict("afmoe", sd, got_cfg)
    assert jax.tree.structure(params) == jax.tree.structure(got)
    for (path, want), have in zip(jax.tree_util.tree_flatten_with_path(params)[0], jax.tree.leaves(got)):
        assert np.array_equal(np.asarray(have), np.asarray(want)), path
    with pytest.raises(ValueError, match="unmapped"):
        hf_import.import_state_dict("afmoe", dict(sd, **{"model.layers.0.self_attn.q_proj.bias": np.zeros((64,))}), got_cfg)
    with pytest.raises(ValueError, match="tied head"):
        hf_import.config_from_hf(types.SimpleNamespace(**dict(vars(hf_config), tie_word_embeddings=True)))
