"""``ops/pallas_moe.py`` (the fused grouped SwiGLU kernel) through the Pallas
interpreter on the CPU, against the loop over experts that
``tests/test_deepseek_v3.py`` uses as oracle; the rule of ``ops/moe.py`` that
says which product ``routed_experts`` runs; the counter that says which one a
dispatch ran.  Nothing here is a time: the kernel's speed is a chip run's
(``PERF.md`` section 6, PR 35), Mosaic's verdict on it ``tests/test_tpu_compile.py``'s.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from accelerate_tpu.models import deepseek_v3 as ds
from accelerate_tpu.ops import moe, pallas_moe

E, D, F, LAYERS, FIRST = 8, 64, 256, 3, 8  # one layer's experts; the stack holds three layers', the middle one's are read
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 3e-2}  # float32: the same dots in another order; bf16: one rounding of the hidden and of the result
PATTERNS = {
    "an-idle-expert": [3, 0, 5, 1, 0, 0, 5, 7],
    "more-rows-than-a-tile": [3, 0, 37, 1, 0, 0, 5, 7],
    "every-row-on-one-expert": [0, 0, 0, 41, 0, 0, 0, 0],
    "one-row-each": [1] * 8,
    "the-last-expert-alone": [0, 0, 0, 0, 0, 0, 0, 16],
    "whole-tiles": [16, 32, 0, 0, 16, 0, 0, 0],
}


def stack(dtype, layers=LAYERS, seed=0, f=F):
    """(w_gate, w_up, w_down) of ``layers * E`` experts, fan-in scaled."""
    keys = jax.random.split(jax.random.key(seed), 3)
    normal = lambda k, shape, fan: (jax.random.normal(k, shape, jnp.float32) * fan ** -0.5).astype(dtype)
    g = layers * E
    return normal(keys[0], (g, D, f), D), normal(keys[1], (g, D, f), D), normal(keys[2], (g, f, D), f)


def rows_of(n, dtype, seed=1):
    return jax.random.normal(jax.random.key(seed), (n, D), jnp.float32).astype(dtype)


def loop_over_experts(rows, weights, sizes, first=0):
    """Each expert's SwiGLU over its own rows, in float32, one expert at a time."""
    w_gate, w_up, w_down = (w.astype(jnp.float32) for w in weights)
    out, start = [], 0
    with jax.default_matmul_precision("highest"):
        for e, size in enumerate(np.asarray(sizes)):
            x = rows[start : start + size].astype(jnp.float32)
            out.append((jax.nn.silu(x @ w_gate[first + e]) * (x @ w_up[first + e])) @ w_down[first + e])
            start += size
    return jnp.concatenate(out)


def poisoned(weights, first):
    """NaN in every expert of the stack but ``first .. first + E``."""
    return tuple(w.at[:first].set(jnp.nan).at[first + E :].set(jnp.nan) for w in weights)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_kernel_against_the_loop_over_experts(pattern, dtype):
    sizes = jnp.asarray(PATTERNS[pattern], jnp.int32)
    rows, weights = rows_of(int(sizes.sum()), dtype), stack(dtype, layers=1)
    got = pallas_moe.grouped_swiglu(rows, *weights, sizes, interpret=True)
    assert got.shape == rows.shape and got.dtype == rows.dtype
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - loop_over_experts(rows, weights, sizes)))) < TOL[dtype]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("traced_offset", [False, True], ids=["static-offset", "traced-offset"])
def test_the_stack_is_read_where_it_lies_and_no_other_layer_is_read(dtype, traced_offset):
    """``first_expert`` into a merged ``[L * E, d, f]`` stack whose other layers are NaN: none reaches the result."""
    sizes = jnp.asarray(PATTERNS["more-rows-than-a-tile"], jnp.int32)
    rows, weights = rows_of(int(sizes.sum()), dtype), stack(dtype)
    run = lambda first: pallas_moe.grouped_swiglu(rows, *poisoned(weights, FIRST), sizes, first, interpret=True)
    got = jax.jit(run)(jnp.int32(FIRST)) if traced_offset else run(FIRST)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - loop_over_experts(rows, weights, sizes, FIRST)))) < TOL[dtype]


@pytest.mark.parametrize("parts", [2, 4])
def test_f_in_several_tiles_accumulates_to_the_whole(monkeypatch, parts):
    f = 512
    sizes = jnp.asarray(PATTERNS["more-rows-than-a-tile"], jnp.int32)
    rows, weights = rows_of(int(sizes.sum()), jnp.float32), stack(jnp.float32, f=f)
    whole = pallas_moe.grouped_swiglu(rows, *weights, sizes, FIRST, interpret=True)
    monkeypatch.setattr(pallas_moe, "WEIGHT_VMEM_BYTES", 2 * 3 * D * (f // parts) * 4)
    assert pallas_moe.f_tile(D, f, 4) == f // parts
    split = pallas_moe.grouped_swiglu(rows, *poisoned(weights, FIRST), sizes, FIRST, interpret=True)
    assert float(jnp.max(jnp.abs(split - whole))) < 1e-5


@pytest.mark.parametrize("garbage", [jnp.nan, jnp.inf, 1e30], ids=["nan", "inf", "huge"])
def test_garbage_in_the_padded_rows_reaches_no_output(garbage):
    sizes = jnp.asarray(PATTERNS["an-idle-expert"], jnp.int32)
    n, tm = int(sizes.sum()), pallas_moe.ROW_TILE
    rows, weights = rows_of(n, jnp.float32), stack(jnp.float32, layers=1)
    tile_expert, live, _, dest = pallas_moe.tile_layout(sizes, n, tm)
    padded = jnp.full((tile_expert.shape[0] * tm, D), garbage, jnp.float32).at[dest].set(rows)
    got = pallas_moe.padded_swiglu(padded, *weights, tile_expert, live, tm=tm, interpret=True)[dest]
    assert float(jnp.max(jnp.abs(got - loop_over_experts(rows, weights, sizes)))) < 1e-5


@pytest.mark.parametrize("tm", [8, 16, 32])
@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_tile_layout_starts_every_expert_on_a_tile_and_places_every_row_once(pattern, tm):
    sizes = np.asarray(PATTERNS[pattern])
    n = int(sizes.sum())
    tile_expert, live, source, dest = (np.asarray(a) for a in pallas_moe.tile_layout(jnp.asarray(sizes, jnp.int32), n, tm))
    tiles = -(-sizes // tm)
    assert int(live[0]) == tiles.sum() <= len(tile_expert) == pallas_moe.max_row_tiles(n, E, tm)
    assert tile_expert[: live[0]].tolist() == np.repeat(np.arange(E), tiles).tolist()  # in expert order, idle experts skipped
    assert set(tile_expert[live[0] :].tolist()) <= {tile_expert[live[0] - 1]}  # dead tiles repeat the last live block
    assert len(set(dest.tolist())) == n and source[dest].tolist() == list(range(n))  # a row's place reads that row
    expert_of_row = np.repeat(np.arange(E), sizes)
    assert (tile_expert[dest // tm] == expert_of_row).all()  # and lies in a tile of its expert


def test_tile_bounds_and_f_tiles_at_the_cells_widths():
    assert pallas_moe.max_row_tiles(96, 128, 16) == 102 and pallas_moe.max_row_tiles(1280, 128, 16) == 208
    assert pallas_moe.max_row_tiles(256, 32, 16) == 48
    assert pallas_moe.f_tile(2048, 768, 2) == 768 and pallas_moe.f_tile(2048, 1792, 2) == 1792  # whole, bf16
    assert pallas_moe.f_tile(2048, 1792, 4) == 896 and pallas_moe.f_tile(7168, 2048, 2) == 512
    assert pallas_moe.f_tile(64, 200, 4) == 200  # a width that is no 128-multiple is taken whole or not at all
    assert pallas_moe.f_tile(2**16, 100, 4) is None


def on_a_tpu(monkeypatch):
    """The rule's backend test answered as on a TPU; the kernel itself then runs in Pallas' TPU interpreter."""
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    return pltpu.force_tpu_interpret_mode()


def test_the_rule_takes_the_kernel_only_on_one_tpu_device_at_few_rows_an_expert(monkeypatch):
    few, bf16 = (96, 128, 2048, 768), jnp.bfloat16
    assert moe.expert_row_tile(*few, bf16) == 0  # the CPU keeps lax.ragged_dot
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    assert moe.expert_row_tile(*few, bf16) == pallas_moe.ROW_TILE
    for pairs, experts in ((288, 128), (1024, 128), (1280, 128), (128, 32), (256, 32)):  # the six serving programs
        assert moe.expert_row_tile(pairs, experts, 2048, 768, bf16) == pallas_moe.ROW_TILE
    # the tile follows the mean rows an expert: 16 up to 16, 32 up to 32, 64 beyond
    assert [moe.expert_row_tile(rows * 128, 128, 2048, 768, bf16) for rows in (16, 17, 32, 33, 64, 128)] == [16, 32, 32, 64, 64, 64]
    bound = moe.FUSED_MAX_MEAN_ROWS
    assert moe.expert_row_tile(bound * 128 + 1, 128, 2048, 768, bf16) == 0  # a training batch, an offline prefill
    assert moe.expert_row_tile(2 * 4096 * 2, 8, 4096, 14336, bf16) == 0
    assert moe.expert_row_tile(96, 128, 2**16, 100, jnp.float32) == 0  # no weight tile fits the kernel's VMEM budget
    mesh = jax.make_mesh((2,), ("dp",))
    with jax.set_mesh(mesh):
        assert moe.expert_row_tile(*few, bf16) == 0  # more than one device: GSPMD cannot partition the kernel
    with jax.set_mesh(jax.make_mesh((1,), ("dp",))):
        assert moe.expert_row_tile(*few, bf16) == pallas_moe.ROW_TILE


def routed(x, router, weights, **kw):
    return moe.routed_experts(x, router, *weights, top_k=2, scoring="sigmoid", scale=2.5, compute_dtype=x.dtype, **kw)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
def test_routed_experts_gives_the_same_layer_through_either_product(monkeypatch, dtype):
    x = jax.random.normal(jax.random.key(3), (2, 9, D), jnp.float32).astype(dtype)
    router, weights = jax.random.normal(jax.random.key(4), (D, E), jnp.float32), stack(dtype)
    want, routing = routed(x, router, weights, first_expert=FIRST)
    text = lambda: str(jax.make_jaxpr(lambda x: routed(x, router, weights, first_expert=FIRST)[0])(x))
    assert "ragged_dot" in text() and "moe_grouped_swiglu" not in text()
    with on_a_tpu(monkeypatch):
        got, same = routed(x, router, poisoned(weights, FIRST), first_expert=FIRST)
        assert "ragged_dot" not in text() and "moe_grouped_swiglu" in text()
    assert np.array_equal(np.asarray(routing["group_sizes"]), np.asarray(same["group_sizes"]))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))) < 4 * TOL[dtype]


def test_gradients_through_the_kernel_are_the_ragged_paths(monkeypatch):
    x = jax.random.normal(jax.random.key(5), (11, D), jnp.float32)
    router, weights = jax.random.normal(jax.random.key(6), (D, E), jnp.float32), stack(jnp.float32)

    def loss(x, router, weights):
        y, _ = routed(x, router, weights, first_expert=FIRST)
        return jnp.sum(y * y)

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    want = grad(x, router, weights)
    with on_a_tpu(monkeypatch):
        got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(x, router, weights)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-5 * max(1.0, float(jnp.max(jnp.abs(w))))
    # the other layers' experts get no gradient, the read layer's do
    assert float(jnp.max(jnp.abs(got[1][2][0][:FIRST]))) == 0 and float(jnp.max(jnp.abs(got[1][2][0][FIRST : FIRST + E]))) > 0


def test_row_tiles_counted_by_hand():
    sizes = jnp.asarray([[4, 0, 0, 17, 0, 0, 0, 0], [1, 1, 16, 1, 1, 33, 0, 0]], jnp.int32)
    counters = {k: int(v) for k, v in ds.expert_counters(sizes, 16).items()}
    assert counters == {"moe_rows": 74, "moe_experts_hit": 8, "moe_max_rows": 50, "moe_row_tiles": 1 + 2 + 4 + 1 + 3, "moe_pairs_routed": 74}
    assert int(ds.expert_counters(sizes)["moe_row_tiles"]) == 0 and int(ds.expert_counters(sizes, 8)["moe_row_tiles"]) == 15


def tiny_expert_engine():
    """On one device, under no mesh: ``Accelerator().prepare_serving`` would install the suite's eight-device mesh,
    under which the rule keeps ``lax.ragged_dot``."""
    from accelerate_tpu.serving import ServingConfig, ServingEngine

    c = ds.DeepseekV3Config.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    return ServingEngine(
        ds.apply_cached, ds.init_cache, ds.init_params(c, jax.random.key(2)), c,
        ServingConfig(block_size=4, num_blocks=48, max_slots=2, max_blocks_per_seq=8, prefill_chunk=4))


def serve(engine, prompts, new=5):
    ids = [engine.submit(p, new) for p in prompts]
    out = engine.run()
    return [out[i] for i in ids], engine.stats()


def test_an_engine_serves_the_same_tokens_through_the_kernel_and_counts_its_tiles(monkeypatch):
    prompts = [np.arange(3, 12), np.arange(40, 45)]
    want, stats = serve(tiny_expert_engine(), prompts)
    assert stats["moe_rows"] > 0 and stats["moe_row_tiles"] == 0  # the CPU ran lax.ragged_dot
    with on_a_tpu(monkeypatch):
        got, stats = serve(tiny_expert_engine(), prompts)
    assert got == want
    # every hit expert has at least one tile; with at most 2 * (2 + 4) pairs a layer none needs a second
    assert stats["moe_row_tiles"] == stats["moe_experts_hit"] > 0
    assert stats["moe_rows"] <= stats["moe_row_tiles"] * pallas_moe.ROW_TILE


def test_a_llama_program_is_what_it_was(monkeypatch):
    """A family without experts compiles to the same text whatever the rule says, and holds no kernel of this file."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import llama

    c = llama.LlamaConfig.tiny(dtype=jnp.float32)

    def text():
        engine = Accelerator().prepare_serving(
            llama.apply_cached, llama.init_cache, llama.init_params(c, jax.random.key(0)), c, block_size=4, num_blocks=32,
            max_slots=2, max_blocks_per_seq=8, prefill_chunk=4)
        lanes = engine._idle_lanes(2)
        chunk = (np.zeros((2,), np.int32), np.int32(0), np.zeros((1, 4), np.int32), np.int32(1))
        return (engine.programs.decode.lower(engine.params, engine.cache.pool, *lanes).as_text(),
                engine.programs.decode_chunk.lower(engine.params, engine.cache.pool, *lanes, *chunk).as_text(),
                str(jax.make_jaxpr(engine.programs.decode)(engine.params, engine.cache.pool, *lanes)))

    before = text()
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    after = text()
    assert before == after and not any("pallas_call" in t or "ragged_dot" in t for t in after)
