"""``ops/moe.py:routed_experts`` told which experts it holds (PR 38): it routes
over all of the router's ``E``, computes the pairs that fall on a held expert
and adds nothing for the others.  **The shares add up**: over any split of the
``E`` experts into runs, the shares' partial sums are the uncut layer's routed
sum, and a shared expert, which every chip computes alike, is counted once.
All-held (no ``share``) is bit for bit the computation the three expert
families have always called.  Float32 throughout: the sums differ by the order
of a few additions, nothing else."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import deepseek_v3 as ds
from accelerate_tpu.ops import moe, pallas_moe
from accelerate_tpu.ops.moe import routed_experts, swiglu

D, F, E, K, ROWS = 32, 16, 16, 4, 24
ROUTING = dict(top_k=K, scoring="sigmoid", normalize=True, normalize_eps=1e-20, scale=2.448, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def layer():
    keys = jax.random.split(jax.random.key(38), 9)
    unit = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan)
    return {
        "x": jax.random.normal(keys[0], (2, ROWS // 2, D), jnp.float32),
        "router": unit(keys[1], (D, E), D), "bias": 0.1 * jax.random.normal(keys[2], (E,), jnp.float32),
        "w_gate": unit(keys[3], (E, D, F), D), "w_up": unit(keys[4], (E, D, F), D), "w_down": unit(keys[5], (E, F, D), F),
        "shared": (unit(keys[6], (D, F), D), unit(keys[7], (D, F), D), unit(keys[8], (F, D), F)),
    }


def routed(layer, share=None, **kw):
    first, count = (0, E) if share is None else share
    held = [layer[k][first : first + count] for k in ("w_gate", "w_up", "w_down")]
    return routed_experts(layer["x"], layer["router"], *held, select_bias=layer["bias"], share=share, **ROUTING, **kw)


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(layer, shares):
    whole, routing = routed(layer)
    shared = swiglu(layer["x"], *layer["shared"], jnp.float32)
    count = E // shares
    parts, computed = [], 0
    for s in range(shares):
        y, r = routed(layer, (s * count, count))
        assert r["group_sizes"].shape == (count,)
        np.testing.assert_array_equal(np.asarray(r["group_sizes"]), np.asarray(routing["group_sizes"])[s * count : (s + 1) * count])
        np.testing.assert_array_equal(np.asarray(r["experts"]), np.asarray(routing["experts"]))  # every share routes alike
        np.testing.assert_array_equal(np.asarray(r["weights"]), np.asarray(routing["weights"]))
        parts.append(y)
        computed += int(r["group_sizes"].sum())
    assert computed == ROWS * K  # every pair was computed once, somewhere
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(whole + shared), atol=2e-6, rtol=0)
    # the shared expert counted with every share would be counted `shares` times: the fault the rule guards against
    assert float(jnp.max(jnp.abs(sum(p + shared for p in parts) - (whole + shared)))) > 0.1


def test_a_share_with_no_pair_returns_zeros(layer):
    bias = jnp.where(jnp.arange(E) < 4, -10.0, layer["bias"])  # no row chooses experts 0..3
    y, r = routed(dict(layer, bias=bias), (0, 4))
    assert int(r["group_sizes"].sum()) == 0 and float(jnp.max(jnp.abs(y))) == 0.0
    y, r = routed(dict(layer, bias=bias), (4, 12))
    assert int(r["group_sizes"].sum()) == ROWS * K and float(jnp.max(jnp.abs(y))) > 0


def test_share_must_be_a_run_of_the_routers_experts(layer):
    for bad in [(0, 0), (-1, 4), (14, 4), (0, E + 1)]:
        with pytest.raises(ValueError, match="share"):
            routed_experts(layer["x"], layer["router"], layer["w_gate"], layer["w_up"], layer["w_down"], share=bad, **ROUTING)


def _jaxpr(fn, *args):
    return str(jax.make_jaxpr(fn)(*args))


def families_calls(layer):
    """The three expert families' calls of ``routed_experts`` (``deepseek_v3._ffn``, ``lfm2_moe._ffn``,
    ``sdar_moe._ffn``), by their keywords, on one layer's experts and on a merged stack read at an offset."""
    weights = (layer["w_gate"], layer["w_up"], layer["w_down"])
    stack = tuple(jnp.concatenate([jnp.zeros_like(w), w]) for w in weights)  # two layers merged, this one the second
    common = dict(top_k=K, compute_dtype=jnp.float32)
    calls = {
        "deepseek_v3": dict(scoring="sigmoid", select_bias=layer["bias"], normalize=True, scale=2.448),
        "lfm2_moe": dict(scoring="sigmoid", select_bias=layer["bias"], normalize=True, normalize_eps=1e-6, scale=1.0),
        "sdar_moe": dict(scoring="softmax", normalize=True),
    }
    for name, kw in calls.items():
        yield name, weights, dict(common, **kw, first_expert=0)
        yield name + "-held", stack, dict(common, **kw, first_expert=E)


def test_all_held_is_todays_computation_for_the_three_families(layer):
    """No ``share`` and a share of all ``E`` trace to the same program, operation for operation, and give the same
    bits; and the program holds nothing that only a share needs (no remainder by ``E``, no select over the rows)."""
    for name, weights, kw in families_calls(layer):
        plain = lambda x: routed_experts(x, layer["router"], *weights, **kw)[0]
        all_held = lambda x: routed_experts(x, layer["router"], *weights, share=(0, E), **kw)[0]
        assert _jaxpr(plain, layer["x"]) == _jaxpr(all_held, layer["x"]), name
        assert " rem " not in _jaxpr(plain, layer["x"]), name
        np.testing.assert_array_equal(np.asarray(jax.jit(plain)(layer["x"])), np.asarray(jax.jit(all_held)(layer["x"])))
        # against the sum written out expert by expert
        y, r = routed_experts(layer["x"], layer["router"], *weights, **kw)
        first = kw["first_expert"]
        want = jnp.zeros_like(layer["x"])
        for slot in range(K):
            e = r["experts"][..., slot] + first
            one = jax.vmap(jax.vmap(lambda row, i: swiglu(row, weights[0][i], weights[1][i], weights[2][i], jnp.float32)))(layer["x"], e)
            want = want + r["weights"][..., slot, None] * one
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-6, rtol=0, err_msg=name)


def test_expert_row_tile_is_asked_about_the_pairs_expected_here(layer, monkeypatch):
    asked = []
    real = moe.expert_row_tile
    monkeypatch.setattr(moe, "expert_row_tile", lambda *a: asked.append(a[:2]) or real(*a))
    routed(layer)
    routed(layer, (4, 4))
    routed(layer, (0, 3))
    assert asked == [(ROWS * K, E), (ROWS * K * 4 // E, 4), (-(-ROWS * K * 3 // E), 3)]
    # the same rows an expert on average, so the same product and the same tile as the uncut layer
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    bf16 = jnp.bfloat16
    for pairs, tile in ((64, 16), (256 * 17, 32), (256 * 33, 64), (256 * 129, 0)):
        assert real(pairs, 256, 3072, 3072, bf16) == real(pairs // 8, 32, 3072, 3072, bf16) == tile


def test_the_counters_under_a_share():
    sizes = jnp.asarray([[4, 0, 0, 2], [1, 1, 0, 1]], jnp.int32)  # two layers, four held experts: 9 of 48 pairs fell here
    counters = {k: int(v) for k, v in ds.expert_counters(sizes, 16, pairs_routed=48).items()}
    assert counters == {"moe_rows": 9, "moe_experts_hit": 5, "moe_max_rows": 5, "moe_row_tiles": 5, "moe_pairs_routed": 48}
    assert int(ds.expert_counters(sizes)["moe_pairs_routed"]) == 9  # all held: every pair routed is computed


@pytest.mark.parametrize("share", [(0, 8), (4, 4), (12, 4), (15, 1)])
def test_the_fused_kernel_computes_a_share(layer, share, monkeypatch):
    """The Pallas kernel (interpreted on the CPU) under a share: the same partial sum as ``lax.ragged_dot``'s, the
    rows behind the held run never written and never read, a stack read at an offset."""
    from jax.experimental.pallas import tpu as pltpu

    want, _ = routed(layer, share)
    real = pallas_moe.grouped_swiglu
    with pltpu.force_tpu_interpret_mode():
        monkeypatch.setattr(moe, "_on_tpu", lambda: True)
        monkeypatch.setattr(pallas_moe, "grouped_swiglu", lambda *a, **kw: real(*a, **kw, interpret=True))
        got, r = routed(layer, share)
        first, count = share
        stack = [jnp.concatenate([jnp.full_like(layer[k][:count], jnp.nan), layer[k][first : first + count]]) for k in ("w_gate", "w_up", "w_down")]
        offset, _ = routed_experts(layer["x"], layer["router"], *stack, select_bias=layer["bias"], share=share, first_expert=count, **ROUTING)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(offset), np.asarray(want), atol=2e-6, rtol=0)
