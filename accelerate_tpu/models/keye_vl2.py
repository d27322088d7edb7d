"""Keye-VL-2.0-class language model (``model_type: KeyeVL2``): a Qwen3-MoE
block whose attention reads the rows a **learned indexer** chooses, the
DeepSeek-V3.2 sparse attention at Keye's sizes.

Notation: ``n = RMSNorm(x)`` for a position's input ``x``; positions ``t``
(query) and ``s`` (key), ``s <= t``.

- Main attention, Qwen3's: ``q_t = RoPE(RMSNorm_hd(W_q n_t))`` (32 heads of
  128), ``k_s = RoPE(RMSNorm_hd(W_k n_s))``, ``v_s = W_v n_s`` (4 heads of 128);
  the half-split pairing at ``rope_theta``.
- Indexer (DeepSeek-V3.2-Exp's ``Indexer``): queries ``qI_{t,j} = RoPE_I((W_qI
  n_t)_j)``, ``j = 1..16``, each in R^64; one shared key ``kI_s =
  RoPE_I(LayerNorm(W_kI n_s))`` in R^64; head weights ``w_{t,j} = (W_w n_t)_j *
  16^-1/2 * 64^-1/2``; scores ``I_{t,s} = sum_j w_{t,j} ReLU(qI_{t,j} . kI_s)``.
  ``RoPE_I`` turns the leading ``index_rope_dim`` features of a head (the
  half-split pairing inside them, at ``rope_theta``) and leaves the rest.
- Selection: ``S_t`` = the ``index_topk`` positions ``s <= t`` with the largest
  ``I_{t,s}`` (``lax.top_k``, exact: lower positions first among equal
  scores); all of ``s <= t`` while ``t < index_topk``.  The current token is a
  candidate like any other; one ``S_t`` for every query head.
- ``o_t = W_o concat_h sum_{s in S_t} softmax_s(q_{t,h} . k_s / sqrt(128))
  v_s`` (GQA); ``a = x + o``.
- The expert layer, SDAR's (``models/sdar_moe.py:_ffn``): ``y = a + sum_{e in
  top8(p)} (p_e / sum_top8 p) SwiGLU_e(RMSNorm(a))``, ``p = softmax(W_r .)`` in
  float32.  The head is untied.

Everything but the indexer, the selection and the attention over it is
``models/sdar_moe.py``'s code (projections and head norms, experts, embedding,
head, the layer scan): at contexts of at most ``index_topk`` rows the family
*is* SDAR's at ``block_length`` 1, which ``tests/test_keye_vl2.py`` holds.

**The cache** holds K (after norm and RoPE) and V per K/V head, and the
indexer's keys ``kI`` (after norm and ``RoPE_I``) under the leaf ``ki``: a third
token leaf, paged by block beside K and V, written by the same scatter, carried
by admission, growth, preemption and the prefix cache like them.  At width 64 a
row of one layer's keys would put the block axis in a TPU's lanes (PERF.md
section 7.0a), so ``index_pack`` = 2 consecutive layers share a 128-wide row:
``ki [ceil(L / 2), B, max_len, 128]``, as ``models/deepseek_v3.py`` packs its
rotated keys.  A layer reads the packed rows whole and lays its index queries
into its own 64 lanes (:func:`_into_slot`), the other layer's lanes multiplied
by 0: on a v5e at the benchmark cell's widest table that read and score take
3.2 ms for four layers, against 4.5 for a leaf of one layer a row (copied
whole into another layout first) and 4.9 for the packed rows cut to the
layer's half (PERF.md section 6).

**Serving** (:func:`apply_paged`): a group gathers the index keys its tables
name under ``kv_pool.gather``, as any family gathers its context; the
decoding lanes (a group of one row a lane over tables wider than
``index_topk`` rows) score every row under the scope ``attn.index``, take the
top ``index_topk`` under ``attn.select``, and read **those rows alone** of K
and V, by position, under ``attn.sparse``: ``L * 128 B`` of index keys and
``min(L, 2048) * 2,048 B`` of K/V a lane a layer where a dense read is ``L *
2,048 B``.  Any other group (a prefill chunk; tables narrower than
``index_topk`` rows, where the selection is everything) gathers its K/V
context under ``kv_pool.gather`` and attends over it under the causal mask
and each query's selection.  The counters of a dispatch say what it read:
``index_rows_scored``, ``sparse_rows_read``, ``sparse_lane_rows`` and
``context_rows`` (:func:`apply_paged`).

Out of scope, and named so: the vision tower and M-RoPE's three position axes
(for text tokens the three are equal and M-RoPE is this 1-D RoPE), the
indexer's training loss (DeepSeek-V3.2 warms it up by a KL term against dense
attention; ``loss_fn`` here is next-token cross-entropy, through which the
indexer gets no gradient, because it only chooses), fp8 index keys, and a
checkpoint import.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.moe import expert_row_tile
from . import llama as _llama
from .deepseek_v3 import expert_counters
from .gpt2 import _layer_norm
from .llama import cross_entropy, labels_and_weights
from .sdar_moe import _embed, _ffn, _head, _mm, _out_proj, _qkv, _scan_layers

__all__ = [
    "KeyeVl2Config", "init_params", "apply", "loss_fn", "init_cache", "apply_cached", "apply_paged", "generate",
    "PARTITION_RULES", "param_specs",
]

INDEX_PACK = 2  # layers whose index keys share one 128-lane row of the "ki" leaf at index width 64


@dataclasses.dataclass(frozen=True)
class KeyeVl2Config:
    vocab_size: int = 151936
    hidden_size: int = 2048
    moe_intermediate_size: int = 768  # one routed expert
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    index_num_heads: int = 16
    index_head_dim: int = 64
    index_rope_dim: int = 32  # the leading features of an index head that RoPE_I turns
    index_topk: int = 2048
    index_norm_eps: float = 1e-6  # the index key's LayerNorm
    max_seq_len: int = 262144
    rope_theta: float = 10000000.0
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16  # compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError("num_heads must be a multiple of num_kv_heads, head_dim even")
        if self.index_rope_dim % 2 or not 0 <= self.index_rope_dim <= self.index_head_dim:
            raise ValueError(f"index_rope_dim must be even and at most index_head_dim, got {self.index_rope_dim}")
        if self.index_topk < 1:
            raise ValueError(f"index_topk must be >= 1, got {self.index_topk}")

    @property
    def index_pack(self) -> int:
        """Layers that share a row of the ``ki`` leaf: two where that row is 128 wide, else one."""
        return INDEX_PACK if INDEX_PACK * self.index_head_dim == 128 else 1

    @classmethod
    def tiny(cls, **kw) -> "KeyeVl2Config":
        """Test-sized config: 3 layers, 8 experts top-2, 2 index heads of 64 (two layers a row), the top 8 rows."""
        defaults = dict(
            vocab_size=256, hidden_size=64, moe_intermediate_size=32, num_layers=3, num_heads=4, num_kv_heads=2,
            head_dim=16, num_experts=8, num_experts_per_tok=2, index_num_heads=2, index_head_dim=64, index_rope_dim=32,
            index_topk=8, max_seq_len=256, remat=False,
        )
        defaults.update(kw)
        return cls(**defaults)

    def num_params(self) -> int:
        leaves = jax.tree_util.tree_leaves(_param_shapes(self), is_leaf=lambda x: isinstance(x, tuple))
        return int(sum(np.prod(s) for s in leaves))


# Experts are replicated: group sizes depend on the data, so the routed product runs per device.
PARTITION_RULES: list[tuple[str, P]] = [
    (r"embed", P("tp", "fsdp")),
    (r"lm_head", P("fsdp", "tp")),
    (r"layers/w[qkv]$", P(None, "fsdp", "tp")),
    (r"layers/wqi", P(None, "fsdp", "tp")),
    (r"layers/wo", P(None, "tp", "fsdp")),
    (r"final_norm", P(None)),
]


def _param_shapes(c: KeyeVl2Config) -> dict:
    d, e, f, hd, n = c.hidden_size, c.num_experts, c.moe_intermediate_size, c.head_dim, c.num_layers
    hi, di = c.index_num_heads, c.index_head_dim
    return {
        "embed": (c.vocab_size, d),
        "layers": {
            "ln_attn": (n, d), "wq": (n, d, c.num_heads * hd), "wk": (n, d, c.num_kv_heads * hd),
            "wv": (n, d, c.num_kv_heads * hd), "wo": (n, c.num_heads * hd, d), "ln_q": (n, hd), "ln_k": (n, hd),
            "wqi": (n, d, hi * di), "wki": (n, d, di), "wwi": (n, d, hi), "ln_ki": (n, di), "ki_bias": (n, di),
            "ln_mlp": (n, d), "router": (n, d, e),
            "w_gate": (n, e, d, f), "w_up": (n, e, d, f), "w_down": (n, e, f, d),
        },
        "final_norm": (d,),
        "lm_head": (d, c.vocab_size),
    }


def param_specs(config: KeyeVl2Config) -> dict:
    from ..parallel.sharding import spec_from_rules

    def one(kp, shape):
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        spec = spec_from_rules(path, len(shape), PARTITION_RULES)
        return spec if spec is not None else P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(one, _param_shapes(config), is_leaf=lambda x: isinstance(x, tuple))


def init_params(config: KeyeVl2Config, key: jax.Array) -> dict:
    """Truncated-normal fan-in matrices, unit norm scales, a zero LayerNorm bias."""
    shapes = _param_shapes(config)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.tree_util.tree_unflatten(treedef, list(jax.random.split(key, len(leaves))))

    def init_one(kp, shape, k):
        name = str(getattr(kp[-1], "key", kp[-1]))
        if name.startswith("ln_") or name == "final_norm":
            return jnp.ones(shape, config.param_dtype)
        if name == "ki_bias":
            return jnp.zeros(shape, config.param_dtype)
        fan_in = config.hidden_size if name == "embed" else shape[-2]
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) / np.sqrt(fan_in)).astype(
            config.param_dtype
        )

    return jax.tree_util.tree_map_with_path(init_one, shapes, keys, is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------------------
# the indexer, the selection, the attention over it
# ---------------------------------------------------------------------------


@jax.named_scope("attn.index")
def _index_proj(h, p, c: KeyeVl2Config, positions):
    """``h [B, S, d]`` -> (queries ``qI [B, S, Hi, di]``, head weights ``w [B,
    S, Hi]`` float32 with both scales, keys ``kI [B, S, di]``), ``RoPE_I`` on the
    leading ``index_rope_dim`` features of ``qI`` and ``kI``."""
    b, s, _ = h.shape
    hi, di, r = c.index_num_heads, c.index_head_dim, c.index_rope_dim
    qi = _mm(h, p["wqi"], c).reshape(b, s, hi, di)
    ki = _layer_norm(_mm(h, p["wki"], c), p["ln_ki"], p["ki_bias"], c.index_norm_eps)[:, :, None, :]
    if r:
        q_rot, k_rot = _llama._rope(qi[..., :r], ki[..., :r], positions, c.rope_theta)
        qi = jnp.concatenate([q_rot, qi[..., r:]], axis=-1)
        ki = jnp.concatenate([k_rot, ki[..., r:]], axis=-1)
    w = _mm(h, p["wwi"], c).astype(jnp.float32) * (hi ** -0.5 * di ** -0.5)
    return qi, w, ki[:, :, 0]


@jax.named_scope("attn.index")
def _index_scores(qi, w, keys) -> jax.Array:
    """``I [B, T, P]`` float32 of queries ``qI [B, T, Hi, w]`` with weights ``w
    [B, T, Hi]`` over the keys ``[B, P, w]``: ``sum_j w_j ReLU(qI_j . kI)``
    (``w`` = ``di``, or a packed row's width with the queries in their layer's
    lanes: :func:`_into_slot`)."""
    dots = jnp.einsum("bthd,bpd->bthp", qi, keys, preferred_element_type=jnp.float32)
    return jnp.einsum("bthp,bth->btp", jax.nn.relu(dots), w)


@jax.named_scope("attn.select")
def _selection(scores, mask, topk: int) -> jax.Array:
    """Each query's selection ``[B, T, P]``: the ``topk`` keys its ``mask``
    admits with the largest scores, or all of them where it admits no more."""
    if mask.shape[-1] <= topk:
        return mask
    _, idx = jax.lax.top_k(jnp.where(mask, scores, -jnp.inf), topk)
    b = jnp.arange(idx.shape[0], dtype=jnp.int32)[:, None, None]
    t = jnp.arange(idx.shape[1], dtype=jnp.int32)[None, :, None]
    return jnp.zeros(mask.shape, bool).at[b, t, idx].set(True) & mask


def _attend(q, k_ctx, v_ctx, mask, c: KeyeVl2Config) -> jax.Array:
    """``q [B, S, H, hd]`` over a context ``[B, P, K, hd]`` under ``mask [B, S,
    P]`` (causal and selected) -> ``[B, S, H * hd]``, under ``attn.sparse``."""
    with jax.named_scope("attn.sparse"):
        out = _llama._attention(q, k_ctx, v_ctx, mask, c.num_heads // c.num_kv_heads)
    return out.reshape(q.shape[:2] + (c.num_heads * c.head_dim,))


def _attention(q, qi, w, k_ctx, v_ctx, ki_ctx, mask, c: KeyeVl2Config):
    """The layer's attention over a dense context: keys ``[B, P, K, hd]`` and
    index keys ``[B, P, di]`` (the new rows in place), ``mask [B, S, P]`` the
    causal one: the indexer scores every key the mask admits, each query
    attends over its selection."""
    chosen = _selection(_index_scores(qi, w, ki_ctx), mask, c.index_topk)
    return _attend(q, k_ctx, v_ctx, chosen, c)


# ---------------------------------------------------------------------------
# training-shape forward
# ---------------------------------------------------------------------------


def _trunk(params, input_ids, config, positions=None, attention_mask=None) -> jax.Array:
    c = config
    b, s = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), bool)), (b, s, s))
    if attention_mask is not None:
        mask = mask & attention_mask.astype(bool)[:, None, :]
    act_spec = P(("dcn_dp", "dp", "fsdp"), "sp", None)
    x = _llama._maybe_constrain(_embed(params, input_ids, c), act_spec)

    def body(x, lp, _, held):
        with jax.named_scope("attn"):
            h = _llama._rms_norm(x, lp["ln_attn"], c.rms_eps)
            q, k, v = _qkv(h, lp, c, positions)
            qi, w, ki = _index_proj(h, lp, c, positions)
            x = x + _out_proj(_attention(q, qi, w, k, v, ki, mask, c), lp, c)
        x, _ = _ffn(x, lp, c, held)
        return _llama._maybe_constrain(x, act_spec), None

    if c.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    with jax.named_scope("layers"):
        x, _ = _scan_layers(params, body, x, ())
    return x


def apply(params, input_ids, config, positions=None, attention_mask=None) -> jax.Array:
    """Forward pass: token ids [B, S] -> logits [B, S, V] (fp32)."""
    x = _trunk(params, input_ids, config, positions, attention_mask)
    with jax.named_scope("head"):
        return _head(params, x, config)


def loss_fn(params: dict, batch: dict, config: KeyeVl2Config) -> jax.Array:
    """Next-token cross-entropy, fp32 (the indexer's own loss is out of scope: see the module docstring)."""
    labels, weights = labels_and_weights(batch)
    x = _trunk(params, batch["input_ids"], config, attention_mask=batch.get("attention_mask"))
    with jax.named_scope("head_loss"):
        return cross_entropy(_head(params, x, config), labels, weights)


# ---------------------------------------------------------------------------
# the cache: K/V per head and the index keys, two layers a row
# ---------------------------------------------------------------------------


def init_cache(config: KeyeVl2Config, batch_size: int, max_len: int) -> dict:
    """Zeroed cache: K/V ``[L, B, max_len, K, hd]``, index keys ``ki [ceil(L /
    pack), B, max_len, pack * di]`` (``pack`` consecutive layers side by side,
    :attr:`KeyeVl2Config.index_pack`), + write index."""
    from .generation import make_kv_cache

    c = config
    pack = c.index_pack
    cache = make_kv_cache(c.num_layers, batch_size, max_len, c.num_kv_heads, c.head_dim, c.dtype)
    cache["ki"] = jnp.zeros((-(-c.num_layers // pack), batch_size, max_len, pack * c.index_head_dim), c.dtype)
    return cache


def unpack_index_keys(ki: jax.Array, c: KeyeVl2Config) -> jax.Array:
    """``[L / pack, B, T, pack * di] -> [L, B, T, di]`` (a copy: the dense path and the tests only)."""
    g, b, t, _ = ki.shape
    pack = c.index_pack
    per_layer = jnp.moveaxis(ki.reshape(g, b, t, pack, c.index_head_dim), 3, 1)
    return per_layer.reshape(g * pack, b, t, c.index_head_dim)[: c.num_layers]


def pack_index_keys(ki: jax.Array, c: KeyeVl2Config) -> jax.Array:
    """Inverse of :func:`unpack_index_keys` for ``[L, ..., di]`` with the layers
    leading: pads to whole groups and lays a group's layers side by side."""
    pack = c.index_pack
    ki = jnp.pad(ki, ((0, (-ki.shape[0]) % pack),) + ((0, 0),) * (ki.ndim - 1))
    grouped = ki.reshape((ki.shape[0] // pack, pack) + ki.shape[1:])
    return jnp.moveaxis(grouped, 1, -2).reshape(grouped.shape[:1] + ki.shape[1:-1] + (pack * ki.shape[-1],))


def _into_slot(x: jax.Array, slot, c: KeyeVl2Config) -> jax.Array:
    """``x [..., di]`` laid into lanes ``[slot * di, (slot + 1) * di)`` of a row
    of the ``ki`` leaf, zeros in the other layers' lanes: an index query so laid
    scores a whole packed row as this layer's key alone (the other lanes
    multiply 0), so the gathered rows are never cut."""
    if c.index_pack == 1:
        return x
    lanes = jnp.arange(c.index_pack * c.index_head_dim) // c.index_head_dim
    return jnp.where(lanes == slot, jnp.concatenate([x] * c.index_pack, axis=-1), jnp.zeros((), x.dtype))


def apply_cached(params: dict, input_ids: jax.Array, config: KeyeVl2Config, cache: dict):
    """Forward over new tokens with cache read/write: ``input_ids [B, S]`` at
    positions ``cache['index'] .. index+S``; returns (logits ``[B, S, V]``,
    updated cache).  The indexer scores every key up to a query's own."""
    from .generation import check_cache_room

    c = config
    b, s = input_ids.shape
    index = cache["index"]
    max_len = cache["k"].shape[2]
    check_cache_room(index, s, max_len)
    new_positions = index + jnp.arange(s)
    positions = jnp.broadcast_to(new_positions, (b, s))
    mask = jnp.broadcast_to(new_positions[:, None] >= jnp.arange(max_len)[None, :], (b, s, max_len))
    x = _embed(params, input_ids, c)

    def body(x, lp, xs, held):
        k_l, v_l, ki_l = xs
        with jax.named_scope("attn"):
            h = _llama._rms_norm(x, lp["ln_attn"], c.rms_eps)
            q, k, v = _qkv(h, lp, c, positions)
            qi, w, ki = _index_proj(h, lp, c, positions)
            k_l = jax.lax.dynamic_update_slice(k_l, k.astype(k_l.dtype), (0, index, 0, 0))
            v_l = jax.lax.dynamic_update_slice(v_l, v.astype(v_l.dtype), (0, index, 0, 0))
            ki_l = jax.lax.dynamic_update_slice(ki_l, ki.astype(ki_l.dtype), (0, index, 0))
            x = x + _out_proj(_attention(q, qi, w, k_l, v_l, ki_l, mask, c), lp, c)
        x, _ = _ffn(x, lp, c, held)
        return x, (k_l, v_l, ki_l)

    with jax.named_scope("layers"):
        x, (new_k, new_v, new_ki) = _scan_layers(
            params, body, x, (cache["k"], cache["v"], unpack_index_keys(cache["ki"], c)), hold_experts=True)
    with jax.named_scope("head"):
        logits = _head(params, x, c)
    return logits, {"k": new_k, "v": new_v, "ki": pack_index_keys(new_ki, c), "index": index + s}


def _gather_rows(leaf, tables, layer, idx) -> jax.Array:
    """The rows at positions ``idx [B, k]`` of the sequences whose block tables
    ``[B, M]`` name them, of layer ``layer`` of a K/V pool leaf ``[L, N, bs, K,
    hd]``: ``[B, k, K, hd]``, a row at a time (where the TPU holds the leaf row
    by row, ``generation._blocks_lie_row_by_row``, ``[L * N * bs, K, hd]`` is a
    free view of it)."""
    layers, blocks, bs = leaf.shape[:3]
    block = jnp.take_along_axis(tables, idx // bs, axis=1)
    rows = (layer * blocks + block) * bs + idx % bs
    return jnp.take(leaf.reshape((layers * blocks * bs,) + leaf.shape[3:]), rows, axis=0, mode="clip")


def _attend_selected_rows(q, k_new, v_new, qi, w, ki_ctx, pool, tables, starts, layer, c: KeyeVl2Config):
    """The decoding lanes' attention, one row a lane at position ``starts``:
    the indexer over the index keys their tables name (``[B, P, pack * di]``, the new
    row in place), the top ``index_topk`` positions of those the lane sees, and
    attention over K/V read **at those positions alone**; the lane's own row,
    not in the pool yet, where it was chosen."""
    scores = _index_scores(qi, w, ki_ctx)[:, 0]  # [B, P]
    with jax.named_scope("attn.select"):
        seen = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :] <= starts[:, None]
        _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), c.index_topk)
        chosen = idx <= starts[:, None]  # a lane that sees fewer rows than the top-k takes all it sees, and no more
    with jax.named_scope("attn.sparse"):
        own = (idx == starts[:, None])[:, :, None, None]
        k_sel = jnp.where(own, k_new, _gather_rows(pool["k"], tables, layer, idx))
        v_sel = jnp.where(own, v_new, _gather_rows(pool["v"], tables, layer, idx))
    return _attend(q, k_sel, v_sel, chosen[:, None, :], c)


def sparse_counters(groups, config: KeyeVl2Config) -> dict:
    """What the attention of one ``apply_paged`` dispatch read, from positions
    alone (no score enters), each summed over its layers and over the lanes
    that hold a sequence (the first group's lanes at ``starts > 0``, every lane
    of a later group; a chunk's padded rows are computed and count):
    ``index_rows_scored`` = a lane's context ``L`` (its last position + 1, the
    index keys its queries score); ``sparse_lane_rows`` = ``min(L, topk)``, the
    K/V rows the least read of its selection takes; ``sparse_rows_read`` =
    ``min(p + 1, topk)`` a query at position ``p``, the rows it attends over;
    ``context_rows`` = ``p + 1``, the rows a full causal mask admits."""
    c = config
    out = dict.fromkeys(("index_rows_scored", "sparse_lane_rows", "sparse_rows_read", "context_rows"), 0)
    for g, (tokens, _, starts) in enumerate(groups):
        t = tokens.shape[1]
        holds = (starts > 0) if g == 0 else jnp.ones(starts.shape, bool)
        context = jnp.where(holds, starts + t, 0).astype(jnp.int32)  # [B]
        rows = jnp.where(holds[:, None], starts[:, None] + 1 + jnp.arange(t, dtype=jnp.int32)[None, :], 0)  # [B, T]: p + 1
        out["index_rows_scored"] += jnp.sum(context)
        out["sparse_lane_rows"] += jnp.sum(jnp.minimum(context, c.index_topk))
        out["sparse_rows_read"] += jnp.sum(jnp.minimum(rows, c.index_topk))
        out["context_rows"] += jnp.sum(rows)
    return {name: (c.num_layers * value).astype(jnp.int32) for name, value in out.items()}


def apply_paged(params: dict, groups, config: KeyeVl2Config, pool: dict):
    """Forward over new tokens straight against the paged pool (the contract of
    ``llama.apply_paged``): ``groups`` is a short tuple of ``(tokens [B, T],
    tables [B, M], starts [B])``, lane ``b`` of a group at positions ``starts[b]
    .. starts[b]+T-1``.  The projections, the indexer's, the experts and the head
    run once over the rows of all groups; the indexer's scan, the selection and
    attention a group at a time: the decoding lanes (the first group at one row
    a lane, tables wider than ``index_topk`` rows) by
    :func:`_attend_selected_rows`, every other group over its gathered context
    under the causal mask and each query's selection.  Returns (logits a group,
    the rows each group wrote ``{"k", "v": [B, L, T, K, hd], "ki": [B, L / pack,
    T, pack * di]}``, :func:`expert_counters` of the dispatch with
    :func:`sparse_counters` beside them)."""
    from .generation import (
        _insert_rows,
        address_paged_leaf_by_layer,
        address_paged_pool_by_layer,
        gather_paged_context,
        group_positions,
        join_groups,
        split_groups,
    )

    c = config
    pack = c.index_pack
    shapes = [tokens.shape for tokens, _, _ in groups]
    positions, masks = group_positions(groups, pool["k"].shape[2])
    joined = join_groups(positions)
    x = _embed(params, join_groups([tokens for tokens, _, _ in groups]), c)

    def body(x, lp, layer, held):
        with jax.named_scope("attn"):
            h = _llama._rms_norm(x, lp["ln_attn"], c.rms_eps)
            q, k, v = _qkv(h, lp, c, joined)
            qi, w, ki = _index_proj(h, lp, c, joined)
            attn, stored = [], []
            for i, (q_g, k_g, v_g, qi_g, w_g, ki_g, (_, tables, starts), mask) in enumerate(zip(
                    *(split_groups(a, shapes) for a in (q, k, v, qi, w, ki)), groups, masks)):
                k_g, v_g, ki_g = k_g.astype(pool["k"].dtype), v_g.astype(pool["v"].dtype), ki_g.astype(pool["ki"].dtype)
                # a row of "ki" holds the keys of `pack` layers, this layer's at lanes [slot * di, (slot + 1) * di):
                # the rows are read whole and the queries laid into the slot, never the row cut (PERF.md section 6)
                slot = layer % pack
                ki_leaf, ki_tables = address_paged_leaf_by_layer(pool["ki"], tables, layer // pack)
                with jax.named_scope("kv_pool.gather"):
                    ki_ctx = _insert_rows(gather_paged_context(ki_leaf, ki_tables), _into_slot(ki_g, slot, c), starts)
                qi_g = _into_slot(qi_g, slot, c)
                if i == 0 and q_g.shape[1] == 1 and mask.shape[-1] > c.index_topk:
                    attn.append(_attend_selected_rows(
                        q_g, k_g, v_g, qi_g, w_g, ki_ctx, pool, tables, starts, layer, c))
                else:
                    pk, pv, ltab = address_paged_pool_by_layer({"k": pool["k"], "v": pool["v"]}, tables, layer)
                    with jax.named_scope("kv_pool.gather"):
                        k_ctx = _insert_rows(gather_paged_context(pk, ltab), k_g, starts)
                        v_ctx = _insert_rows(gather_paged_context(pv, ltab), v_g, starts)
                    attn.append(_attention(q_g, qi_g, w_g, k_ctx, v_ctx, ki_ctx, mask, c))
                stored.append((k_g, v_g, ki_g))
            x = x + _out_proj(join_groups(attn), lp, c)
        x, sizes = _ffn(x, lp, c, held)
        return x, (tuple(stored), sizes)

    # the pool is a constant of the loop, addressed by layer in its body: never a scanned input
    with jax.named_scope("layers"):
        x, (stored, group_sizes) = _scan_layers(
            params, body, x, jnp.arange(c.num_layers, dtype=jnp.int32), hold_experts=True)
    with jax.named_scope("head"):
        logits = _head(params, x, c)
    rows = tuple(
        {"k": jnp.moveaxis(k_rows, 0, 1), "v": jnp.moveaxis(v_rows, 0, 1), "ki": jnp.moveaxis(pack_index_keys(ki_rows, c), 0, 1)}
        for k_rows, v_rows, ki_rows in stored)
    row_tile = expert_row_tile(  # which grouped product this dispatch's expert layers ran
        x.size // c.hidden_size * c.num_experts_per_tok, c.num_experts, c.hidden_size, c.moe_intermediate_size, c.dtype)
    return split_groups(logits, shapes), rows, {**expert_counters(group_sizes, row_tile), **sparse_counters(groups, c)}


def generate(
    params: dict,
    input_ids: jax.Array,
    config: KeyeVl2Config,
    max_new_tokens: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
    top_k: int = 0,
    top_p: float = 1.0,
    prefill_chunk: Optional[int] = None,
) -> jax.Array:
    """Greedy (temperature=0) or sampled generation through the cache:
    ``[B, S]`` dense prompt -> ``[B, S+max_new_tokens]``, one XLA program (the
    serving engine's oracle)."""
    from .generation import generate_loop

    return generate_loop(
        apply_cached, init_cache, params, input_ids, config,
        max_new_tokens, temperature=temperature, key=key, max_len=max_len,
        top_k=top_k, top_p=top_p, prefill_chunk=prefill_chunk,
    )
