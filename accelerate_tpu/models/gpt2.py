"""GPT-2-style decoder — second dense model family, TPU-first.

Parity rationale: the reference's Megatron bridge ships per-family train-step
handlers (``GPTTrainStep`` ``utils/megatron_lm.py:587``); our native analog is
a model family per architecture.  GPT-2 differs from llama everywhere it
matters for coverage: learned absolute positions (no RoPE), LayerNorm with
bias (not RMSNorm), MHA (no GQA), GELU MLP (not SwiGLU), tied embeddings.

Same TPU-first layout as ``models/llama.py``: stacked per-layer params scanned
with ``lax.scan``, bf16 compute / fp32 params, partition rules over the named
mesh axes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .llama import _dequant_layer, _sp_active, cross_entropy, labels_and_weights
from .llama import sp_attention as _sp_attention
from ..parallel.sharding import constrain as _constrain, embed_lookup as _embed_lookup

__all__ = ["GPT2Config", "init_params", "apply", "loss_fn", "PARTITION_RULES", "param_specs"]


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "chunked" streams the (tied) LM-head loss over vocab tiles
    # (ops/chunked_ce.py) — at GPT-2's 50257 vocab the dense fp32 logits are
    # the single largest activation; same knob as LlamaConfig.loss_impl.
    loss_impl: str = "dense"
    loss_chunk_size: int = 4096
    # Sequence parallelism: with an sp>1 mesh axis, attention runs the shared
    # ring/ulysses machinery (same knob as LlamaConfig.sp_impl) instead of
    # materializing the [B, S, S] mask — which is what makes long context
    # feasible on this family too.
    sp_impl: str = "ring"
    # int8 KV cache for generation (shared machinery; see LlamaConfig).
    kv_cache_quant: bool = False

    def __post_init__(self):
        if self.loss_impl not in ("dense", "chunked"):
            raise ValueError(f"loss_impl must be 'dense' or 'chunked', got {self.loss_impl!r}")
        if self.sp_impl not in ("ring", "ulysses"):
            raise ValueError(f"sp_impl must be 'ring' or 'ulysses', got {self.sp_impl!r}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def intermediate_size(self) -> int:
        return 4 * self.hidden_size

    @classmethod
    def tiny(cls, **kw) -> "GPT2Config":
        defaults = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                        max_seq_len=128, remat=False)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def gpt2_small(cls, **kw) -> "GPT2Config":
        return cls(**kw)

    def num_params(self) -> int:
        d, v, l = self.hidden_size, self.vocab_size, self.num_layers
        attn = 3 * d * d + 3 * d + d * d + d  # qkv + proj with biases
        mlp = d * 4 * d + 4 * d + 4 * d * d + d
        norms = 4 * d
        return l * (attn + mlp + norms) + v * d + self.max_seq_len * d + 2 * d


PARTITION_RULES: list[tuple[str, P]] = [
    (r"wte", P("tp", "fsdp")),
    (r"wpe", P(None, "fsdp")),
    (r"layers/w_qkv", P(None, "fsdp", "tp")),
    (r"layers/w_proj", P(None, "tp", "fsdp")),
    (r"layers/w_up", P(None, "fsdp", "tp")),
    (r"layers/w_down", P(None, "tp", "fsdp")),
    (r"layers/(b_|ln_)", P(None, None)),
    (r"final_ln", P(None)),
]


def _param_shapes(c: GPT2Config) -> dict:
    d, L = c.hidden_size, c.num_layers
    return {
        "wte": (c.vocab_size, d),
        "wpe": (c.max_seq_len, d),
        "layers": {
            "w_qkv": (L, d, 3 * d),
            "b_qkv": (L, 3 * d),
            "w_proj": (L, d, d),
            "b_proj": (L, d),
            "w_up": (L, d, 4 * d),
            "b_up": (L, 4 * d),
            "w_down": (L, 4 * d, d),
            "b_down": (L, d),
            "ln_attn_scale": (L, d),
            "ln_attn_bias": (L, d),
            "ln_mlp_scale": (L, d),
            "ln_mlp_bias": (L, d),
        },
        "final_ln_scale": (d,),
        "final_ln_bias": (d,),
    }


def param_specs(config: GPT2Config) -> dict:
    from ..parallel.sharding import spec_from_rules

    shapes = _param_shapes(config)

    def one(kp, shape):
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        spec = spec_from_rules(path, len(shape), PARTITION_RULES)
        return spec if spec is not None else P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(one, shapes, is_leaf=lambda x: isinstance(x, tuple))


def init_params(config: GPT2Config, key: jax.Array) -> dict:
    shapes = _param_shapes(config)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.tree_util.tree_unflatten(treedef, list(jax.random.split(key, len(leaves))))

    def init_one(kp, shape, k):
        # Name-based dispatch (see llama.init_params): a shape test would zero
        # the (max_seq_len, d) position table whenever max_seq_len == num_layers.
        # Scales to 1, biases to 0, weights normal(0.02) (GPT-2 init).
        name = str(getattr(kp[-1], "key", kp[-1]))
        if name.endswith("_scale"):
            return jnp.ones(shape, config.param_dtype)
        if name.startswith("b_") or name.endswith("_bias"):
            return jnp.zeros(shape, config.param_dtype)
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(config.param_dtype)

    return jax.tree_util.tree_map_with_path(
        init_one, shapes, keys, is_leaf=lambda x: isinstance(x, tuple)
    )


def _layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mean) ** 2, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * scale.astype(x.dtype) + bias.astype(x.dtype)


def _qkv(x, p, c: GPT2Config):
    """Pre-norm fused QKV projection -> q, k, v ``[B, S, H, hd]``."""
    b, s, _ = x.shape
    hn = _layer_norm(x, p["ln_attn_scale"], p["ln_attn_bias"], c.layer_norm_eps)
    qkv = hn @ p["w_qkv"].astype(c.dtype) + p["b_qkv"].astype(c.dtype)
    q, k, v = jnp.split(qkv.reshape(b, s, 3, c.num_heads, c.head_dim), 3, axis=2)
    return q[:, :, 0], k[:, :, 0], v[:, :, 0]


def _attend(q, k, v, mask, c: GPT2Config):
    """Masked softmax attention; mask broadcasts against ``[B, H, Sq, Sk]``."""
    b, s = q.shape[:2]
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) / np.sqrt(c.head_dim)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v).reshape(b, s, c.hidden_size)


def _mlp_block(x, p, c: GPT2Config):
    hn = _layer_norm(x, p["ln_mlp_scale"], p["ln_mlp_bias"], c.layer_norm_eps)
    u = jax.nn.gelu(hn @ p["w_up"].astype(c.dtype) + p["b_up"].astype(c.dtype))
    return x + u @ p["w_down"].astype(c.dtype) + p["b_down"].astype(c.dtype)


def _layer(carry, p, *, c: GPT2Config, mask, kv_valid=None, act_spec):
    x = carry
    b, s, _ = x.shape
    q, k, v = _qkv(x, p, c)
    if _sp_active():
        # Sequence-parallel path: the shared dispatch (ring / ulysses, with
        # the fused-Pallas fast paths) — causal at block granularity, the
        # [B, S] validity vector rides the ring; never a global [S, S] mask.
        attn = _sp_attention(q, k, v, c, causal=True, kv_valid=kv_valid)
        attn = attn.reshape(b, s, c.hidden_size)
    else:
        attn = _attend(q, k, v, mask[:, None], c)
    x = x + attn @ p["w_proj"].astype(c.dtype) + p["b_proj"].astype(c.dtype)
    x = _mlp_block(x, p, c)
    if act_spec is not None:
        x = _constrain(x, act_spec)
    return x, None


def lm_head(params: dict, config: GPT2Config) -> jax.Array:
    """The tied [d, V] head (wte transposed) in compute dtype — single source
    for apply() and the chunked loss (mirrors llama.lm_head)."""
    return params["wte"].astype(config.dtype).T


def apply(
    params: dict,
    input_ids: jax.Array,
    config: GPT2Config,
    attention_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Token ids [B, S] -> fp32 logits [B, S, V] (tied lm head)."""
    hidden = apply_hidden(params, input_ids, config, attention_mask)
    return (hidden @ lm_head(params, config)).astype(jnp.float32)


def apply_hidden(
    params: dict,
    input_ids: jax.Array,
    config: GPT2Config,
    attention_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Trunk forward -> final-LN hidden [B, S, d] (compute dtype)."""
    c = config
    b, s = input_ids.shape
    kv_valid = attention_mask.astype(bool) if attention_mask is not None else None
    if _sp_active():
        mask = None  # the sp path masks causally per block; no [S, S] tensor
    else:
        mask = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), bool)), (b, s, s))
        if kv_valid is not None:
            mask = mask & kv_valid[:, None, :]

    x = _embed_lookup(params["wte"], input_ids, c.dtype) + params["wpe"].astype(c.dtype)[:s][None]
    act_spec = P(("dcn_dp", "dp", "fsdp"), "sp", None)
    x = _constrain(x, act_spec)

    def body(carry, lp):
        return _layer(carry, _dequant_layer(lp), c=c, mask=mask, kv_valid=kv_valid,
                      act_spec=act_spec)

    if c.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return _layer_norm(x, params["final_ln_scale"], params["final_ln_bias"], c.layer_norm_eps)


def loss_fn(params: dict, batch: dict, config: GPT2Config) -> jax.Array:
    labels, weights = labels_and_weights(batch)
    if config.loss_impl == "chunked":
        from ..ops.chunked_ce import chunked_cross_entropy

        hidden = apply_hidden(
            params, batch["input_ids"], config, attention_mask=batch.get("attention_mask")
        )
        return chunked_cross_entropy(
            hidden, lm_head(params, config), labels, weights, config.loss_chunk_size
        )
    logits = apply(params, batch["input_ids"], config, attention_mask=batch.get("attention_mask"))
    return cross_entropy(logits, labels, weights)


# ---------------------------------------------------------------------------
# KV-cache inference (shared driver: models/generation.py)
# ---------------------------------------------------------------------------


def quantize_weights(params: dict, block_size: int = 64) -> dict:
    """int8-weight-resident storage for the stacked blocks (wte/wpe and
    per-layer norms/biases stay full precision); see
    ``llama.quantize_weights``."""
    from ..utils.quantization import quantize_layer_stack

    out = dict(params)
    out["layers"] = quantize_layer_stack(params["layers"], block_size)
    return out


def init_cache(config: GPT2Config, batch_size: int, max_len: int) -> dict:
    """Zeroed KV cache: k/v ``[L, B, max_len, H, hd]`` + write index."""
    from .generation import make_kv_cache

    c = config
    return make_kv_cache(
        c.num_layers, batch_size, max_len, c.num_heads, c.head_dim, c.dtype,
        quantized=c.kv_cache_quant,
    )


def apply_cached(
    params: dict,
    input_ids: jax.Array,
    config: GPT2Config,
    cache: dict,
) -> tuple[jax.Array, dict]:
    """Forward over new tokens at positions ``index..index+S`` with cache
    read/write; returns (logits [B, S, V], updated cache)."""
    c = config
    b, s = input_ids.shape
    from .generation import check_cache_room

    index = cache["index"]
    max_len = cache["k"].shape[2]
    check_cache_room(index, s, max_len)
    if max_len > c.max_seq_len:
        # wpe has max_seq_len rows; a longer cache would silently clamp the
        # position gather under jit and degrade output past the table edge.
        raise ValueError(
            f"cache length {max_len} exceeds max_seq_len {c.max_seq_len} "
            "(GPT-2's learned position table)"
        )

    positions = index + jnp.arange(s)
    x = _embed_lookup(params["wte"], input_ids, c.dtype) + params["wpe"].astype(c.dtype)[positions][None]

    k_pos = jnp.arange(max_len)
    mask = positions[:, None] >= k_pos[None, :]  # [S, max_len]

    from .generation import cache_write, pack_cache_for_scan, unpack_cache_from_scan

    def body(carry, xs):
        lp, ck, cv = xs
        lp = _dequant_layer(lp)
        x = carry
        q, k, v = _qkv(x, lp, c)
        ck, k_full = cache_write(ck, k, index, c.dtype)
        cv, v_full = cache_write(cv, v, index, c.dtype)
        attn = _attend(q, k_full, v_full, mask[None, None], c)
        x = x + attn @ lp["w_proj"].astype(c.dtype) + lp["b_proj"].astype(c.dtype)
        x = _mlp_block(x, lp, c)
        return x, (ck, cv)

    ck_in, cv_in, quant = pack_cache_for_scan(cache)
    x, (new_k, new_v) = jax.lax.scan(body, x, (params["layers"], ck_in, cv_in))
    x = _layer_norm(x, params["final_ln_scale"], params["final_ln_bias"], c.layer_norm_eps)
    logits = (x @ params["wte"].astype(c.dtype).T).astype(jnp.float32)
    return logits, unpack_cache_from_scan(new_k, new_v, index + s, quant)


def apply_paged(params: dict, groups, config: GPT2Config, pool: dict) -> tuple[tuple, tuple]:
    """Forward over new tokens straight against the paged block pool — the
    serving engine's model step (no per-slot dense cache view is ever built
    or returned).

    ``groups`` is a short tuple of ``(tokens [B, T], tables [B, M], starts
    [B])``, what one forward took before a tick's decode lanes and its
    prefill chunk shared a dispatch: row ``b`` of a group sits at positions
    ``starts[b] .. starts[b]+T-1`` of the sequence its table row names.
    Everything that does not look at the cache runs once over the rows of all
    groups; attention runs group by group, each consuming pool K/V through
    its own block tables (``paged_cache_write``).  Returns, a group each, the
    logits ``[B, T, V]`` and the freshly written rows ``{leaf: [B, L, T,
    ...]}`` for the caller to scatter into the pool."""
    from .generation import (
        address_paged_pool_by_layer,
        group_positions,
        join_groups,
        paged_cache_write,
        split_groups,
        unpack_paged_rows_from_scan,
    )

    c = config
    quant = "k_scale" in pool
    bs = pool["k"].shape[2]
    total = max(tables.shape[1] for _, tables, _ in groups) * bs
    if total > c.max_seq_len:
        raise ValueError(
            f"block table extent {total} exceeds max_seq_len {c.max_seq_len} "
            "(GPT-2's learned position table)"
        )
    shapes = [tokens.shape for tokens, _, _ in groups]
    positions, masks = group_positions(groups, bs)
    x = _embed_lookup(params["wte"], join_groups([tokens for tokens, _, _ in groups]), c.dtype)
    x = x + params["wpe"].astype(c.dtype)[join_groups(positions)]

    def body(carry, xs):
        lp, layer = xs
        lp = _dequant_layer(lp)
        x = carry
        attn, stored = [], []
        for q, k, v, (_, tables, starts), mask in zip(
                *(split_groups(a, shapes) for a in _qkv(x, lp, c)), groups, masks):
            pk, pv, ltab = address_paged_pool_by_layer(pool, tables, layer)
            k_store, k_full = paged_cache_write(pk, k, ltab, starts, c.dtype)
            v_store, v_full = paged_cache_write(pv, v, ltab, starts, c.dtype)
            attn.append(_attend(q, k_full, v_full, mask[:, None], c))
            stored.append((k_store, v_store))
        x = x + join_groups(attn) @ lp["w_proj"].astype(c.dtype) + lp["b_proj"].astype(c.dtype)
        x = _mlp_block(x, lp, c)
        return x, tuple(stored)

    # the pool is a constant of the loop, addressed by layer in its body: never a scanned input
    layers = jnp.arange(pool["k"].shape[0], dtype=jnp.int32)
    x, stored = jax.lax.scan(body, x, (params["layers"], layers))
    x = _layer_norm(x, params["final_ln_scale"], params["final_ln_bias"], c.layer_norm_eps)
    logits = (x @ params["wte"].astype(c.dtype).T).astype(jnp.float32)
    return split_groups(logits, shapes), tuple(unpack_paged_rows_from_scan(k, v, quant) for k, v in stored)


def generate(
    params: dict,
    input_ids: jax.Array,
    config: GPT2Config,
    max_new_tokens: int,
    temperature: float = 0.0,
    key=None,
    max_len=None,
    top_k: int = 0,
    top_p: float = 1.0,
    prefill_chunk=None,
) -> jax.Array:
    """Autoregressive generation (one compiled XLA program; see
    models/generation.py)."""
    from .generation import generate_loop

    return generate_loop(
        apply_cached, init_cache, params, input_ids, config,
        max_new_tokens, temperature=temperature, key=key, max_len=max_len,
        top_k=top_k, top_p=top_p, prefill_chunk=prefill_chunk,
    )


def speculative_generate(
    params: dict,
    draft_params: dict,
    input_ids: jax.Array,
    config: GPT2Config,
    draft_config: GPT2Config,
    max_new_tokens: int,
    num_draft_tokens: int = 4,
    max_len=None,
    return_stats: bool = False,
    temperature: float = 0.0,
    key=None,
) -> jax.Array:
    """Speculative decoding (see ``models/generation.py``): greedy by
    default (token-identical to ``generate(..., temperature=0)``), or the
    distribution-exact rejection-sampling mode with ``temperature>0`` +
    ``key``.  Batch 1 only.  The cache slack (prompt + new +
    num_draft_tokens) must fit the position table (``config.max_seq_len``)."""
    from .generation import speculative_generate_loop

    return speculative_generate_loop(
        apply_cached, init_cache, params, config,
        apply_cached, init_cache, draft_params, draft_config,
        input_ids, max_new_tokens,
        num_draft_tokens=num_draft_tokens, max_len=max_len,
        return_stats=return_stats, temperature=temperature, key=key,
    )


def generate_beam(
    params: dict,
    input_ids: jax.Array,
    config: GPT2Config,
    max_new_tokens: int,
    num_beams: int = 4,
    length_penalty: float = 1.0,
    eos_token_id=None,
    max_len=None,
) -> jax.Array:
    """Beam-search generation (see ``models/generation.py beam_search``)."""
    from .generation import beam_search

    return beam_search(
        apply_cached, init_cache, params, input_ids, config, max_new_tokens,
        num_beams=num_beams, length_penalty=length_penalty,
        eos_token_id=eos_token_id, max_len=max_len,
    )
