"""SDAR-MoE-class decoder: a Qwen3-MoE block under a **block-causal** mask,
generated by diffusion over blocks.

The published block (``model_type: sdar_moe``; no bias anywhere, every layer
sparse, no shared expert, no selection bias, an untied head):

- ``a = x + W_o Attn(q, k, v)``, ``n = RMSNorm(x)``; ``q = RoPE(RMSNorm_hd(W_q
  n))``, ``k = RoPE(RMSNorm_hd(W_k n))`` (a norm over each head of ``q`` and of
  ``k`` before the half-split RoPE: the operator ``models/lfm2_moe.py`` has),
  ``v = W_v n``; GQA, scores ``q k / sqrt(head_dim)``, softmax over the keys the
  mask admits.
- ``y = a + sum_{e in top_k(p)} (p_e / sum_{top_k} p) SwiGLU_e(m)``, ``m =
  RMSNorm(a)``, ``p = softmax(W_r m)`` in float32 (``ops/moe.py:routed_experts``:
  dropless, row by row).
- ``logits = W_head RMSNorm(y)``.

**The mask** is what sets the family apart: position ``p`` sees key ``j`` iff
``j <= (p // B) * B + B - 1`` with ``B = block_length``: causal over blocks of
``B``, full inside a block, for the prompt too.  ``B = 1`` is the causal mask and
the model an autoregressive one.  A block of ``B`` masked positions
(``mask_token_id`` in their place) is denoised by forwards that write no cache
and committed by one that does (``generation.block_generate_loop`` offline,
``serving/engine.py`` under load); this file only computes a forward under the
mask.  The cache holds ``k`` after norm and RoPE, and ``v``, per K/V head
(``generation.make_kv_cache``'s layout: at 4 heads of 128 a TPU reads the paged
pool in place).

Out of scope, and named so: the training objective (a masked loss over noised
blocks beside their clean copies); ``loss_fn`` here is next-token
cross-entropy under the block-causal mask, enough for a gradient to exist.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.moe import expert_row_tile, routed_experts
from . import llama as _llama
from .deepseek_v3 import EXPERT_LEAVES, expert_counters
from .llama import cross_entropy, labels_and_weights

__all__ = [
    "SdarMoeConfig", "init_params", "apply", "loss_fn", "init_cache", "apply_cached", "apply_paged", "generate",
    "PARTITION_RULES", "param_specs",
]


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
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
    block_length: int = 4  # B: the mask's block and the generation's; 1 = causal, autoregressive
    mask_token_id: int = 151669  # what stands at a masked position of the block being denoised
    max_seq_len: int = 32768
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16  # compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError("num_heads must be a multiple of num_kv_heads, head_dim even")
        if self.block_length < 1:
            raise ValueError(f"block_length must be >= 1, got {self.block_length}")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} is not a row of the {self.vocab_size}-row vocabulary")

    @classmethod
    def tiny(cls, **kw) -> "SdarMoeConfig":
        """Test-sized config: 3 layers, 8 experts top-2, blocks of 4, the last id the mask."""
        defaults = dict(
            vocab_size=256, hidden_size=64, moe_intermediate_size=32, num_layers=3, num_heads=4, num_kv_heads=2,
            head_dim=16, num_experts=8, num_experts_per_tok=2, mask_token_id=255, max_seq_len=256, remat=False,
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
    (r"layers/w[qkv]", P(None, "fsdp", "tp")),
    (r"layers/wo", P(None, "tp", "fsdp")),
    (r"final_norm", P(None)),
]


def _param_shapes(c: SdarMoeConfig) -> dict:
    d, e, f, hd, n = c.hidden_size, c.num_experts, c.moe_intermediate_size, c.head_dim, c.num_layers
    return {
        "embed": (c.vocab_size, d),
        "layers": {
            "ln_attn": (n, d), "wq": (n, d, c.num_heads * hd), "wk": (n, d, c.num_kv_heads * hd),
            "wv": (n, d, c.num_kv_heads * hd), "wo": (n, c.num_heads * hd, d), "ln_q": (n, hd), "ln_k": (n, hd),
            "ln_mlp": (n, d), "router": (n, d, e),
            "w_gate": (n, e, d, f), "w_up": (n, e, d, f), "w_down": (n, e, f, d),
        },
        "final_norm": (d,),
        "lm_head": (d, c.vocab_size),
    }


def param_specs(config: SdarMoeConfig) -> dict:
    from ..parallel.sharding import spec_from_rules

    def one(kp, shape):
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        spec = spec_from_rules(path, len(shape), PARTITION_RULES)
        return spec if spec is not None else P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(one, _param_shapes(config), is_leaf=lambda x: isinstance(x, tuple))


def init_params(config: SdarMoeConfig, key: jax.Array) -> dict:
    """Truncated-normal fan-in matrices, unit norm scales."""
    shapes = _param_shapes(config)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.tree_util.tree_unflatten(treedef, list(jax.random.split(key, len(leaves))))

    def init_one(kp, shape, k):
        name = str(getattr(kp[-1], "key", kp[-1]))
        if name.startswith("ln_") or name == "final_norm":
            return jnp.ones(shape, config.param_dtype)
        fan_in = config.hidden_size if name == "embed" else shape[-2]
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) / np.sqrt(fan_in)).astype(
            config.param_dtype
        )

    return jax.tree_util.tree_map_with_path(init_one, shapes, keys, is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------


def _mm(h: jax.Array, w: jax.Array, c: SdarMoeConfig) -> jax.Array:
    return h @ w.astype(c.dtype)


def _qkv(h, p, c: SdarMoeConfig, positions):
    """``h [B, S, d]`` -> ``q [B, S, H, hd]``, ``k``, ``v [B, S, K, hd]``, ``q`` and ``k`` normed a head and rotated."""
    b, s, _ = h.shape
    with jax.named_scope("attn.qkv"):
        q = _mm(h, p["wq"], c).reshape(b, s, c.num_heads, c.head_dim)
        k = _mm(h, p["wk"], c).reshape(b, s, c.num_kv_heads, c.head_dim)
        v = _mm(h, p["wv"], c).reshape(b, s, c.num_kv_heads, c.head_dim)
        q, k = _llama._rms_norm(q, p["ln_q"], c.rms_eps), _llama._rms_norm(k, p["ln_k"], c.rms_eps)
        q, k = _llama._rope(q, k, positions, c.rope_theta)
    return q, k, v


def _attend(q, k_ctx, v_ctx, mask, c: SdarMoeConfig) -> jax.Array:
    """``q [B, S, H, hd]`` over a context ``[B, P, K, hd]`` under ``mask [B, S,
    P]`` -> ``[B, S, H * hd]``.  Under ``attn.block`` inside ``attn.core``: the
    in-block attention, whose mask admits a block's own later rows."""
    with jax.named_scope("attn.core"), jax.named_scope("attn.block"):
        out = _llama._attention(q, k_ctx, v_ctx, mask, c.num_heads // c.num_kv_heads)
    return out.reshape(q.shape[:2] + (c.num_heads * c.head_dim,))


@jax.named_scope("attn.out")
def _out_proj(attn, p, c: SdarMoeConfig) -> jax.Array:
    return _mm(attn, p["wo"], c)


def _ffn(x, p, c: SdarMoeConfig, held=None):
    """The layer's routed experts with their residual, under the scopes every
    expert family gives them (``mlp``, ``moe`` inside it).  ``held`` as in
    ``deepseek_v3._ffn``.  Returns (x, rows each expert computed ``[E]``)."""
    with jax.named_scope("mlp"):
        h = _llama._rms_norm(x, p["ln_mlp"], c.rms_eps)
        experts, first = (p, 0) if held is None else (held[0], held[1] * c.num_experts)
        with jax.named_scope("moe"):
            y, routing = routed_experts(
                h, p["router"], experts["w_gate"], experts["w_up"], experts["w_down"], top_k=c.num_experts_per_tok,
                scoring="softmax", normalize=c.norm_topk_prob, first_expert=first, compute_dtype=c.dtype,
            )
        return x + y, routing["group_sizes"]


def _scan_layers(params: dict, body, x, per_layer, hold_experts: bool = False):
    """``lax.scan`` of ``body(x, layer params, per-layer inputs, held) -> (x,
    ys)`` over the one stack.  With ``hold_experts`` (the serving paths) the
    routed experts are no scanned input: the body gets the stack's experts
    merged and the layer's number (``deepseek_v3._scan_stacks`` says why)."""
    stack = params["layers"]
    if not hold_experts:
        return jax.lax.scan(lambda x, a: body(x, a[0], a[1], None), x, (stack, per_layer))
    experts = {k: stack[k].reshape((-1,) + stack[k].shape[2:]) for k in EXPERT_LEAVES}
    scanned = {k: v for k, v in stack.items() if k not in EXPERT_LEAVES}
    n = stack["wq"].shape[0]
    return jax.lax.scan(
        lambda x, a: body(x, a[0], a[1], (experts, a[2])), x, (scanned, per_layer, jnp.arange(n, dtype=jnp.int32)))


def _embed(params, input_ids, c: SdarMoeConfig) -> jax.Array:
    with jax.named_scope("embed"):
        return _llama._embed_lookup(params["embed"], input_ids, c.dtype)


def _head(params, x, c: SdarMoeConfig) -> jax.Array:
    """Final norm and the untied head -> fp32 logits."""
    return (_llama._rms_norm(x, params["final_norm"], c.rms_eps) @ params["lm_head"].astype(c.dtype)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# training-shape forward
# ---------------------------------------------------------------------------


def _trunk(params, input_ids, config, positions=None, attention_mask=None) -> jax.Array:
    from .generation import block_end

    c = config
    b, s = input_ids.shape
    kv_valid = attention_mask.astype(bool) if attention_mask is not None else None
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    mask = block_end(positions, c.block_length)[:, :, None] >= positions[:, None, :]
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, :]
    act_spec = P(("dcn_dp", "dp", "fsdp"), "sp", None)
    x = _llama._maybe_constrain(_embed(params, input_ids, c), act_spec)

    def body(x, lp, _, held):
        with jax.named_scope("attn"):
            q, k, v = _qkv(_llama._rms_norm(x, lp["ln_attn"], c.rms_eps), lp, c, positions)
            x = x + _out_proj(_attend(q, k, v, mask, c), lp, c)
        x, _ = _ffn(x, lp, c, held)
        return _llama._maybe_constrain(x, act_spec), None

    if c.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    with jax.named_scope("layers"):
        x, _ = _scan_layers(params, body, x, ())
    return x


def apply(params, input_ids, config, positions=None, attention_mask=None) -> jax.Array:
    """Forward pass under the block-causal mask: token ids [B, S] -> logits
    [B, S, V] (fp32).  A row of the last, unfinished block sees the rows the
    caller put behind it: pad with care."""
    x = _trunk(params, input_ids, config, positions, attention_mask)
    with jax.named_scope("head"):
        return _head(params, x, config)


def loss_fn(params: dict, batch: dict, config: SdarMoeConfig) -> jax.Array:
    """Next-token cross-entropy, fp32, under the block-causal mask (not the
    family's masked-block objective: see the module docstring)."""
    labels, weights = labels_and_weights(batch)
    x = _trunk(params, batch["input_ids"], config, attention_mask=batch.get("attention_mask"))
    with jax.named_scope("head_loss"):
        return cross_entropy(_head(params, x, config), labels, weights)


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------


def init_cache(config: SdarMoeConfig, batch_size: int, max_len: int) -> dict:
    """Zeroed K/V cache ``[L, B, max_len, K, hd]`` + write index."""
    from .generation import make_kv_cache

    c = config
    return make_kv_cache(c.num_layers, batch_size, max_len, c.num_kv_heads, c.head_dim, c.dtype)


def apply_cached(params: dict, input_ids: jax.Array, config: SdarMoeConfig, cache: dict):
    """Forward over new tokens with cache read/write: ``input_ids [B, S]`` at
    positions ``cache['index'] .. index+S`` under the block-causal mask; returns
    (logits ``[B, S, V]``, updated cache).  The ``S`` rows end at a block's end
    (a chunk of whole blocks, or one block): a row sees its whole block, and
    what lies behind the new rows is not theirs.  A denoising pass is this call
    with the returned cache dropped; a commit keeps it."""
    from .generation import block_end, check_cache_room

    c = config
    b, s = input_ids.shape
    index = cache["index"]
    max_len = cache["k"].shape[2]
    check_cache_room(index, s, max_len)
    new_positions = index + jnp.arange(s)
    positions = jnp.broadcast_to(new_positions, (b, s))
    mask = jnp.broadcast_to(block_end(new_positions, c.block_length)[:, None] >= jnp.arange(max_len)[None, :], (b, s, max_len))
    x = _embed(params, input_ids, c)

    def body(x, lp, xs, held):
        k_l, v_l = xs
        with jax.named_scope("attn"):
            q, k, v = _qkv(_llama._rms_norm(x, lp["ln_attn"], c.rms_eps), lp, c, positions)
            k_l = jax.lax.dynamic_update_slice(k_l, k.astype(k_l.dtype), (0, index, 0, 0))
            v_l = jax.lax.dynamic_update_slice(v_l, v.astype(v_l.dtype), (0, index, 0, 0))
            x = x + _out_proj(_attend(q, k_l, v_l, mask, c), lp, c)
        x, _ = _ffn(x, lp, c, held)
        return x, (k_l, v_l)

    with jax.named_scope("layers"):
        x, (new_k, new_v) = _scan_layers(params, body, x, (cache["k"], cache["v"]), hold_experts=True)
    with jax.named_scope("head"):
        logits = _head(params, x, c)
    return logits, {"k": new_k, "v": new_v, "index": index + s}


def apply_paged(params: dict, groups, config: SdarMoeConfig, pool: dict):
    """Forward over new tokens straight against the paged pool (the contract of
    ``llama.apply_paged``): ``groups`` is a short tuple of ``(tokens [B, T],
    tables [B, M], starts [B])``, lane ``b`` of a group at positions ``starts[b]
    .. starts[b]+T-1`` of the sequence its table row names, under the
    block-causal mask (a lane's ``T`` rows end at a block's end: a chunk of whole
    blocks, or the one block a decoding lane carries).  The projections, the
    experts and the head run once over the rows of all groups; attention runs a
    group at a time.  Returns (logits a group, the K/V rows each group computed
    ``{"k", "v": [B, L, T, K, hd]}`` -- whether they are written is the caller's:
    a denoising pass writes none --, :func:`expert_counters` of the dispatch)."""
    from .generation import (
        address_paged_pool_by_layer,
        group_positions,
        join_groups,
        paged_cache_write,
        split_groups,
        unpack_paged_rows_from_scan,
    )

    c = config
    shapes = [tokens.shape for tokens, _, _ in groups]
    positions, masks = group_positions(groups, pool["k"].shape[2], c.block_length)
    positions = join_groups(positions)
    x = _embed(params, join_groups([tokens for tokens, _, _ in groups]), c)

    def body(x, lp, layer, held):
        with jax.named_scope("attn"):
            q, k, v = _qkv(_llama._rms_norm(x, lp["ln_attn"], c.rms_eps), lp, c, positions)
            attn, stored = [], []
            for q_g, k_g, v_g, (_, tables, starts), mask in zip(
                    *(split_groups(a, shapes) for a in (q, k, v)), groups, masks):
                pk, pv, ltab = address_paged_pool_by_layer(pool, tables, layer)
                with jax.named_scope("kv_pool"):
                    k_store, k_full = paged_cache_write(pk, k_g, ltab, starts, c.dtype)
                    v_store, v_full = paged_cache_write(pv, v_g, ltab, starts, c.dtype)
                attn.append(_attend(q_g, k_full, v_full, mask, c))
                stored.append((k_store, v_store))
            x = x + _out_proj(join_groups(attn), lp, c)
        x, sizes = _ffn(x, lp, c, held)
        return x, (tuple(stored), sizes)

    # the pool is a constant of the loop, addressed by layer in its body: never a scanned input
    with jax.named_scope("layers"):
        x, (stored, group_sizes) = _scan_layers(
            params, body, x, jnp.arange(c.num_layers, dtype=jnp.int32), hold_experts=True)
    with jax.named_scope("head"):
        logits = _head(params, x, c)
    rows = tuple(unpack_paged_rows_from_scan(k, v, False) for k, v in stored)
    row_tile = expert_row_tile(  # which grouped product this dispatch's expert layers ran
        x.size // c.hidden_size * c.num_experts_per_tok, c.num_experts, c.hidden_size, c.moe_intermediate_size, c.dtype)
    return split_groups(logits, shapes), rows, expert_counters(group_sizes, row_tile)


def generate(
    params: dict,
    input_ids: jax.Array,
    config: SdarMoeConfig,
    max_new_tokens: int,
    denoise_steps: Optional[int] = None,
    confidence_threshold: Optional[float] = None,
    max_len: Optional[int] = None,
    prefill_chunk: Optional[int] = None,
) -> jax.Array:
    """Greedy generation by diffusion over blocks of ``config.block_length``:
    ``[B, S]`` dense prompt -> ``[B, S+max_new_tokens]``
    (``generation.block_generate_loop``: the serving engine's oracle).  With
    ``block_length`` 1 it is ``generate_loop``'s greedy decoding."""
    from .generation import block_generate_loop

    return block_generate_loop(
        apply_cached, init_cache, params, input_ids, config, max_new_tokens,
        denoise_steps=denoise_steps, confidence_threshold=confidence_threshold, max_len=max_len,
        prefill_chunk=prefill_chunk,
    )
