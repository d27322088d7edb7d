"""DeepSeek-V3-class decoder: multi-head latent attention (MLA) and a dropless
sigmoid-routed expert layer with shared experts.

The published block (HF ``DeepseekV3`` modeling; ``q_lora_rank: null``,
``rope_scaling: null``, one routing group):

- ``x += Attn(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``; final RMSNorm; untied head.
- Attention: ``q = h W_q`` -> ``[H, nope + rope]``; ``h W_kva`` -> ``c_raw[r] ||
  k_rope[rope]``; ``c = RMSNorm_kv(c_raw)``; RoPE with the published
  interleaved pairing on ``q_rope`` and on the one ``k_rope`` all heads share;
  ``k_nope = c W_uk``, ``v = c W_uv`` (the two halves of ``kv_b_proj``); scores
  ``(q_nope k_nope + q_rope k_rope) / sqrt(nope + rope)``, causal softmax,
  ``o = P v`` -> ``W_o``.  **The cache holds ``c`` and the rotated ``k_rope``**:
  ``r + rope`` values a token a layer, no head axis.  The *expanded* form
  builds ``k_nope`` and ``v`` for the whole context (prefill chunks, training);
  the *absorbed* form folds ``W_uk`` into the query and ``W_uv`` into the
  output, ``q_lat = q_nope W_uk^T``, scores ``q_lat c + q_rope k_rope``,
  ``o = (P c) W_uv``, and never expands the context (one-token decode).
- The first ``first_k_dense_replace`` layers carry a SwiGLU of
  ``intermediate_size``; every later layer routes: ``s = sigmoid(h W_r)``, the
  ``num_experts_per_tok`` experts with the largest ``s + e_score_correction_bias``,
  weights ``s`` of the chosen (without the bias) over their sum, times
  ``routed_scaling_factor``, plus the shared experts (one SwiGLU of width
  ``n_shared_experts * moe_intermediate_size``).  No capacity, no drops
  (``ops/moe.py:routed_experts``).

The stack is the dense layers, then one ``lax.scan`` over the identical expert
layers.  Out of scope, and named so: a query projection rank (``q_lora_rank``),
more than one routing group, RoPE scaling, and the update rule of
``e_score_correction_bias`` in training (``loss_fn`` treats the bias as a
constant: it receives no gradient, because it only chooses).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.moe import expert_row_tile, routed_experts, swiglu
from . import llama as _llama
from .llama import cross_entropy, labels_and_weights

__all__ = [
    "DeepseekV3Config", "init_params", "apply", "loss_fn", "init_cache", "apply_cached", "apply_paged",
    "generate", "PARTITION_RULES", "param_specs",
]

ROPE_PACK = 2  # layers whose rotated keys share one 128-lane row of the cache's "kr" leaf at rope width 64


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432  # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 2048  # one routed expert
    num_layers: int = 61
    first_k_dense_replace: int = 3
    num_heads: int = 128
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16  # compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True

    def __post_init__(self):
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring_func must be 'sigmoid' or 'softmax', got {self.scoring_func!r}")
        if not 0 <= self.first_k_dense_replace < self.num_layers:
            raise ValueError("first_k_dense_replace must leave at least one expert layer")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @classmethod
    def tiny(cls, **kw) -> "DeepseekV3Config":
        """Test-sized config: one dense layer, two expert layers of 8 experts."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_layers=3,
            first_k_dense_replace=1, num_heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1, routed_scaling_factor=2.5,
            max_seq_len=128, remat=False,
        )
        defaults.update(kw)
        return cls(**defaults)

    def num_params(self) -> int:
        shapes = _param_shapes(self)
        leaves = jax.tree_util.tree_leaves(shapes, is_leaf=lambda x: isinstance(x, tuple))
        return int(sum(np.prod(s) for s in leaves))


# Experts are replicated: group sizes depend on the data, so the routed product runs per device.
PARTITION_RULES: list[tuple[str, P]] = [
    (r"embed", P("tp", "fsdp")),
    (r"(dense|moe)/wq", P(None, "fsdp", "tp")),
    (r"(dense|moe)/w_kva", P(None, "fsdp", None)),
    (r"(dense|moe)/w_u[kv]", P(None, None, "tp")),
    (r"(dense|moe)/wo", P(None, "tp", "fsdp")),
    (r"dense/w_(gate|up)", P(None, "fsdp", "tp")),
    (r"dense/w_down", P(None, "tp", "fsdp")),
    (r"moe/ws_(gate|up)", P(None, "fsdp", "tp")),
    (r"moe/ws_down", P(None, "tp", "fsdp")),
    (r"final_norm", P(None)),
    (r"lm_head", P("fsdp", "tp")),
]


def _attn_shapes(c: DeepseekV3Config, n: int) -> dict:
    d, h, r = c.hidden_size, c.num_heads, c.kv_lora_rank
    return {
        "wq": (n, d, h * c.qk_head_dim),
        "w_kva": (n, d, r + c.qk_rope_head_dim),  # kv_a_proj_with_mqa: c_raw || k_rope
        "ln_kv": (n, r),  # kv_a_layernorm
        "w_uk": (n, r, h * c.qk_nope_head_dim),  # kv_b_proj, the k_nope columns of every head
        "w_uv": (n, r, h * c.v_head_dim),  # kv_b_proj, the v columns of every head
        "wo": (n, h * c.v_head_dim, d),
        "ln_attn": (n, d),
        "ln_mlp": (n, d),
    }


def _param_shapes(c: DeepseekV3Config) -> dict:
    d, e, f = c.hidden_size, c.n_routed_experts, c.moe_intermediate_size
    nd, nm, fs = c.first_k_dense_replace, c.num_moe_layers, c.n_shared_experts * c.moe_intermediate_size
    shapes = {
        "embed": (c.vocab_size, d),
        "moe": {
            **_attn_shapes(c, nm),
            "router": (nm, d, e),
            "router_bias": (nm, e),  # e_score_correction_bias: added to the scores to choose, never to weigh
            "w_gate": (nm, e, d, f), "w_up": (nm, e, d, f), "w_down": (nm, e, f, d),
            "ws_gate": (nm, d, fs), "ws_up": (nm, d, fs), "ws_down": (nm, fs, d),
        },
        "final_norm": (d,),
        "lm_head": (d, c.vocab_size),
    }
    if nd:
        fd = c.intermediate_size
        shapes["dense"] = {**_attn_shapes(c, nd), "w_gate": (nd, d, fd), "w_up": (nd, d, fd), "w_down": (nd, fd, d)}
    return shapes


def param_specs(config: DeepseekV3Config) -> dict:
    from ..parallel.sharding import spec_from_rules

    def one(kp, shape):
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        spec = spec_from_rules(path, len(shape), PARTITION_RULES)
        return spec if spec is not None else P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(one, _param_shapes(config), is_leaf=lambda x: isinstance(x, tuple))


def init_params(config: DeepseekV3Config, key: jax.Array) -> dict:
    """Truncated-normal fan-in matrices, unit norm scales, a zero selection bias."""
    shapes = _param_shapes(config)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.tree_util.tree_unflatten(treedef, list(jax.random.split(key, len(leaves))))

    def init_one(kp, shape, k):
        name = str(getattr(kp[-1], "key", kp[-1]))
        if name.startswith("ln_") or name == "final_norm":
            return jnp.ones(shape, config.param_dtype)
        if name == "router_bias":
            return jnp.zeros(shape, config.param_dtype)
        fan_in = config.hidden_size if name == "embed" else shape[-2]
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) / np.sqrt(fan_in)).astype(
            config.param_dtype
        )

    return jax.tree_util.tree_map_with_path(init_one, shapes, keys, is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------


def rope_interleaved(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """RoPE with the published interleaved pairing (``rope_interleave``) on
    ``[B, S, H, rope]``: feature ``2j`` turns with feature ``2j + 1`` by
    ``position * theta^(-2j / rope)``.  The result is laid out as the published
    code leaves it, the first of every pair in the front half and the second in
    the back half; queries and keys share the order, so scores do not see it."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, rope/2]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _mm(h: jax.Array, w: jax.Array, c: DeepseekV3Config) -> jax.Array:
    return h @ w.astype(c.dtype)


def _latent_proj(h, p, c: DeepseekV3Config, positions):
    """Queries and the cached pair of ``h`` ``[B, S, d]``: ``q_nope [B, S, H,
    nope]``, rotated ``q_rope [B, S, H, rope]``, the normed latent ``ckv [B, S,
    r]`` and the rotated shared key ``kr [B, S, rope]``."""
    b, s, _ = h.shape
    with jax.named_scope("attn.qkv"):
        q = _mm(h, p["wq"], c).reshape(b, s, c.num_heads, c.qk_head_dim)
        q_nope, q_rope = q[..., : c.qk_nope_head_dim], q[..., c.qk_nope_head_dim :]
        q_rope = rope_interleaved(q_rope, positions, c.rope_theta)
    with jax.named_scope("attn.latent"):
        kva = _mm(h, p["w_kva"], c)
        ckv = _llama._rms_norm(kva[..., : c.kv_lora_rank], p["ln_kv"], c.rms_eps)
        kr = rope_interleaved(kva[..., None, c.kv_lora_rank :], positions, c.rope_theta)[:, :, 0]
    return q_nope, q_rope, ckv, kr


def _softmax_over_context(scores, mask, c: DeepseekV3Config):
    """[B, H, S, P] scores in fp32, scaled by the published 1/sqrt(nope + rope)."""
    scores = scores.astype(jnp.float32) / np.sqrt(c.qk_head_dim)
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)
    return jax.nn.softmax(scores, axis=-1)


def _attend_expanded(q_nope, q_rope, ckv, kr, mask, p, c: DeepseekV3Config) -> jax.Array:
    """The published form: keys and values of every head built from the
    context's latents ``ckv [B, P, r]`` and shared keys ``kr [B, P, rope]``;
    ``mask [B, S, P]``.  Returns ``[B, S, H * v]``."""
    b, s, h, _ = q_nope.shape
    with jax.named_scope("attn.core"):
        k_nope = _mm(ckv, p["w_uk"], c).reshape(b, -1, h, c.qk_nope_head_dim)
        v = _mm(ckv, p["w_uv"], c).reshape(b, -1, h, c.v_head_dim)
        scores = jnp.einsum("bshn,bphn->bhsp", q_nope, k_nope) + jnp.einsum("bshr,bpr->bhsp", q_rope, kr)
        probs = _softmax_over_context(scores, mask, c).astype(v.dtype)
        return jnp.einsum("bhsp,bphv->bshv", probs, v).reshape(b, s, h * c.v_head_dim)


def _attend_absorbed(q_nope, q_rope, ckv, kr, mask, p, c: DeepseekV3Config) -> jax.Array:
    """The same attention with ``W_uk`` folded into the query and ``W_uv`` into
    the output: the context stays ``r + rope`` wide, whatever the heads."""
    b, s, h, _ = q_nope.shape
    r = c.kv_lora_rank
    with jax.named_scope("attn.absorb"):
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, p["w_uk"].astype(c.dtype).reshape(r, h, c.qk_nope_head_dim))
    with jax.named_scope("attn.core"):
        scores = jnp.einsum("bshr,bpr->bhsp", q_lat, ckv) + jnp.einsum("bshr,bpr->bhsp", q_rope, kr)
        probs = _softmax_over_context(scores, mask, c).astype(ckv.dtype)
        o_lat = jnp.einsum("bhsp,bpr->bshr", probs, ckv)
    with jax.named_scope("attn.absorb"):
        out = jnp.einsum("bshr,rhv->bshv", o_lat, p["w_uv"].astype(c.dtype).reshape(r, h, c.v_head_dim))
    return out.reshape(b, s, h * c.v_head_dim)


def _attend(q_nope, q_rope, ckv, kr, mask, p, c: DeepseekV3Config) -> jax.Array:
    """One new token a row decodes in the absorbed form, anything longer in the expanded one."""
    form = _attend_absorbed if q_nope.shape[1] == 1 else _attend_expanded
    return form(q_nope, q_rope, ckv, kr, mask, p, c)


@jax.named_scope("attn.out")
def _out_proj(attn, p, c: DeepseekV3Config) -> jax.Array:
    return _mm(attn, p["wo"], c)


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")  # of the "moe" stack: [layers, E, ., .]


def _ffn(x, p, c: DeepseekV3Config, held=None):
    """The layer's feed-forward sub-block with its residual, under the scope
    ``mlp`` every family gives it; an expert layer's parts lie under ``moe``
    inside it.  ``held`` = (all the stack's experts merged ``{leaf: [layers * E,
    ., .]}``, this layer's number in the stack) where the layer loop keeps the
    experts out of its scanned inputs.  Returns (x, rows each expert computed
    ``[E]`` or None)."""
    with jax.named_scope("mlp"):
        h = _llama._rms_norm(x, p["ln_mlp"], c.rms_eps)
        if "router" not in p:
            return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], c.dtype), None
        experts, first = (p, 0) if held is None else (held[0], held[1] * c.n_routed_experts)
        with jax.named_scope("moe"):
            y, routing = routed_experts(
                h, p["router"], experts["w_gate"], experts["w_up"], experts["w_down"], top_k=c.num_experts_per_tok,
                scoring=c.scoring_func, select_bias=p["router_bias"], normalize=c.norm_topk_prob,
                scale=c.routed_scaling_factor, first_expert=first, compute_dtype=c.dtype,
            )
            with jax.named_scope("moe.shared"):
                shared = swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"], c.dtype)
        return x + y + shared.astype(x.dtype), routing["group_sizes"]


def expert_counters(group_sizes: jax.Array, row_tile: int = 0, pairs_routed: Optional[int] = None) -> dict:
    """What a dispatch's expert layers did, from ``[layers, E]`` rows an expert
    computed: token-expert pairs, experts with at least one row and the hottest
    expert's rows, each summed over the layers; the row tiles of ``row_tile``
    rows that the fused kernel computed for them, 0 where the layers ran
    ``lax.ragged_dot`` (``row_tile`` 0: ``ops/moe.py:expert_row_tile`` of the
    dispatch says which); and ``moe_pairs_routed``, every pair the routers made:
    the pairs computed where a layer's experts are all held, ``pairs_routed``
    (layers x rows x top_k, a static count) where ``group_sizes`` are a share's
    ``[layers, held]``, so that ``moe_rows / moe_pairs_routed`` is the share's load."""
    rows = jnp.sum(group_sizes)
    return {
        "moe_rows": rows,
        "moe_experts_hit": jnp.sum(group_sizes > 0),
        "moe_max_rows": jnp.sum(jnp.max(group_sizes, axis=-1)),
        "moe_row_tiles": jnp.sum(-(-group_sizes // row_tile)) if row_tile else jnp.zeros((), group_sizes.dtype),
        "moe_pairs_routed": rows if pairs_routed is None else jnp.asarray(pairs_routed, group_sizes.dtype),
    }


def _scan_stacks(params: dict, body, x, per_layer, hold_experts: bool = False):
    """``lax.scan`` of ``body(x, layer params, per-layer inputs, held) -> (x,
    ys)`` over the dense stack and then the expert stack; ``per_layer`` leaves
    are ``[num_layers, ...]``.  With ``hold_experts`` (the serving paths) the
    expert stack's routed experts are no scanned input: the body gets them
    whole as ``held`` (see :func:`_ffn`), because a scan cuts a layer's experts
    out of the stack and XLA:TPU copies them for the grouped product, every
    layer of every dispatch.  Returns (x, ys with the two stacks' layers joined;
    a ``ys`` leaf only the expert layers yield stays ``[expert layers, ...]``)."""
    outs, at = [], 0
    for stack in (params[name] for name in ("dense", "moe") if name in params):
        n = stack["wq"].shape[0]
        xs = jax.tree.map(lambda leaf: leaf[at : at + n], per_layer)
        if hold_experts and "router" in stack:
            experts = {k: stack[k].reshape((-1,) + stack[k].shape[2:]) for k in EXPERT_LEAVES}
            scanned = {k: v for k, v in stack.items() if k not in EXPERT_LEAVES}
            x, ys = jax.lax.scan(
                lambda x, a: body(x, a[0], a[1], (experts, a[2])), x, (scanned, xs, jnp.arange(n, dtype=jnp.int32)))
        else:
            x, ys = jax.lax.scan(lambda x, a: body(x, a[0], a[1], None), x, (stack, xs))
        outs.append(ys)
        at += n
    if len(outs) == 1:
        return x, outs[0]
    return x, jax.tree.map(
        lambda d, m: m if d is None else jnp.concatenate([d, m], axis=0), *outs, is_leaf=lambda leaf: leaf is None)


# ---------------------------------------------------------------------------
# training-shape forward
# ---------------------------------------------------------------------------


def _trunk(params, input_ids, config, positions=None, attention_mask=None) -> jax.Array:
    c = config
    b, s = input_ids.shape
    kv_valid = attention_mask.astype(bool) if attention_mask is not None else None
    if positions is None:
        if kv_valid is not None:
            positions = jnp.maximum(jnp.cumsum(kv_valid.astype(jnp.int32), axis=-1) - 1, 0)
        else:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), bool)), (b, s, s))
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, :]
    act_spec = P(("dcn_dp", "dp", "fsdp"), "sp", None)
    x = _llama._maybe_constrain(_embed(params, input_ids, c), act_spec)

    def body(x, lp, _, held):
        with jax.named_scope("attn"):
            h = _llama._rms_norm(x, lp["ln_attn"], c.rms_eps)
            q_nope, q_rope, ckv, kr = _latent_proj(h, lp, c, positions)
            x = x + _out_proj(_attend_expanded(q_nope, q_rope, ckv, kr, mask, lp, c), lp, c)
        x, _ = _ffn(x, lp, c, held)
        return _llama._maybe_constrain(x, act_spec), None

    if c.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    with jax.named_scope("layers"):
        x, _ = _scan_stacks(params, body, x, ())
    return x


def _embed(params, input_ids, c: DeepseekV3Config) -> jax.Array:
    with jax.named_scope("embed"):
        return _llama._embed_lookup(params["embed"], input_ids, c.dtype)


def _head(params, x, c: DeepseekV3Config) -> jax.Array:
    """Final norm and the untied head -> fp32 logits."""
    return (_llama._rms_norm(x, params["final_norm"], c.rms_eps) @ params["lm_head"].astype(c.dtype)).astype(jnp.float32)


def apply(params, input_ids, config, positions=None, attention_mask=None) -> jax.Array:
    """Forward pass: token ids [B, S] -> logits [B, S, V] (fp32)."""
    x = _trunk(params, input_ids, config, positions, attention_mask)
    with jax.named_scope("head"):
        return _head(params, x, config)


def loss_fn(params: dict, batch: dict, config: DeepseekV3Config) -> jax.Array:
    """Next-token cross-entropy, fp32.  No auxiliary loss: the published
    recipe balances load by moving ``e_score_correction_bias`` outside the
    gradient, and that update rule is not implemented here (the bias stays
    as initialised)."""
    labels, weights = labels_and_weights(batch)
    x = _trunk(params, batch["input_ids"], config, attention_mask=batch.get("attention_mask"))
    with jax.named_scope("head_loss"):
        return cross_entropy(_head(params, x, config), labels, weights)


# ---------------------------------------------------------------------------
# the latent cache: dense view and paged pool
# ---------------------------------------------------------------------------


def _rope_pack(c: DeepseekV3Config) -> int:
    """Layers that share a row of the ``kr`` leaf.  A TPU holds a leaf whose
    rows are whole 128-lane tiles block by block, and any narrower one with the
    block axis in the lanes (PERF.md section 7.0a), which costs a copy of the
    whole leaf a dispatch: so rotated keys of width 64 lie two layers to a row."""
    return ROPE_PACK if ROPE_PACK * c.qk_rope_head_dim == 128 else 1


def init_cache(config: DeepseekV3Config, batch_size: int, max_len: int) -> dict:
    """Zeroed latent cache: ``ckv [L, B, max_len, r]``, the normed latents, and
    ``kr [ceil(L / pack), B, max_len, pack * rope]``, the rotated shared keys of
    ``pack`` consecutive layers side by side (:func:`_rope_pack`), + write index."""
    c = config
    pack = _rope_pack(c)
    return {
        "ckv": jnp.zeros((c.num_layers, batch_size, max_len, c.kv_lora_rank), c.dtype),
        "kr": jnp.zeros((-(-c.num_layers // pack), batch_size, max_len, pack * c.qk_rope_head_dim), c.dtype),
        "index": jnp.zeros((), jnp.int32),
    }


def _unpack_rope(kr: jax.Array, c: DeepseekV3Config) -> jax.Array:
    """``[L / pack, B, T, pack * rope] -> [L, B, T, rope]`` (a copy: the dense path only)."""
    g, b, t, _ = kr.shape
    pack = _rope_pack(c)
    per_layer = jnp.moveaxis(kr.reshape(g, b, t, pack, c.qk_rope_head_dim), 3, 1)
    return per_layer.reshape(g * pack, b, t, c.qk_rope_head_dim)[: c.num_layers]


def _pack_rope(kr: jax.Array, c: DeepseekV3Config, batch_axis: int = 1) -> jax.Array:
    """Inverse of :func:`_unpack_rope` for ``[L, ..., rope]`` with the layers
    leading: pads to whole groups and lays a group's layers side by side."""
    pack = _rope_pack(c)
    pad = (-kr.shape[0]) % pack
    kr = jnp.pad(kr, ((0, pad),) + ((0, 0),) * (kr.ndim - 1))
    grouped = kr.reshape((kr.shape[0] // pack, pack) + kr.shape[1:])
    return jnp.moveaxis(grouped, 1, -2).reshape(grouped.shape[:1] + kr.shape[1:-1] + (pack * kr.shape[-1],))


def apply_cached(params: dict, input_ids: jax.Array, config: DeepseekV3Config, cache: dict):
    """Forward over new tokens with cache read/write: ``input_ids [B, S]`` at
    positions ``cache['index'] .. index+S``; returns (logits ``[B, S, V]``,
    updated cache)."""
    from .generation import check_cache_room

    c = config
    b, s = input_ids.shape
    index = cache["index"]
    max_len = cache["ckv"].shape[2]
    check_cache_room(index, s, max_len)
    positions = jnp.broadcast_to(index + jnp.arange(s), (b, s))
    mask = jnp.broadcast_to((index + jnp.arange(s))[:, None] >= jnp.arange(max_len)[None, :], (b, s, max_len))
    x = _embed(params, input_ids, c)

    def body(x, lp, xs, held):
        ckv_l, kr_l = xs
        with jax.named_scope("attn"):
            h = _llama._rms_norm(x, lp["ln_attn"], c.rms_eps)
            q_nope, q_rope, ckv, kr = _latent_proj(h, lp, c, positions)
            ckv_l = jax.lax.dynamic_update_slice(ckv_l, ckv.astype(ckv_l.dtype), (0, index, 0))
            kr_l = jax.lax.dynamic_update_slice(kr_l, kr.astype(kr_l.dtype), (0, index, 0))
            x = x + _out_proj(_attend(q_nope, q_rope, ckv_l, kr_l, mask, lp, c), lp, c)
        x, _ = _ffn(x, lp, c, held)
        return x, (ckv_l, kr_l)

    with jax.named_scope("layers"):
        x, (new_ckv, new_kr) = _scan_stacks(
            params, body, x, (cache["ckv"], _unpack_rope(cache["kr"], c)), hold_experts=True)
    with jax.named_scope("head"):
        logits = _head(params, x, c)
    return logits, {"ckv": new_ckv, "kr": _pack_rope(new_kr, c), "index": index + s}


def apply_paged(params: dict, groups, config: DeepseekV3Config, pool: dict):
    """Forward over new tokens straight against the paged latent pool (the
    contract of ``llama.apply_paged``): ``groups`` is a short tuple of ``(tokens
    [B, T], tables [B, M], starts [B])``, row ``b`` of a group at positions
    ``starts[b] .. starts[b]+T-1``.  The projections, the routed and shared
    experts and the head run once over the rows of all groups, so a tick's
    chunk and its decode lanes stream the experts they hit once; every layer
    gathers each group's latents and rotated keys through its block tables from
    the pool where it lies, overlays the new rows, and attends, expanded for a
    chunk and absorbed for one token a row.  Returns (logits a group, the written
    rows a group ``{leaf: [B, layers or groups of layers, T, ...]}`` for the
    caller's scatter, :func:`expert_counters` of the dispatch)."""
    from .generation import (
        address_paged_leaf_by_layer,
        gather_paged_context,
        group_positions,
        join_groups,
        overlay_new_rows,
        paged_cache_write,
        split_groups,
    )

    c = config
    pack = _rope_pack(c)
    rope = c.qk_rope_head_dim
    shapes = [tokens.shape for tokens, _, _ in groups]
    positions, masks = group_positions(groups, pool["ckv"].shape[2])
    positions = join_groups(positions)
    x = _embed(params, join_groups([tokens for tokens, _, _ in groups]), c)

    def body(x, lp, layer, held):
        with jax.named_scope("attn"):
            h = _llama._rms_norm(x, lp["ln_attn"], c.rms_eps)
            attn, stored = [], []
            for q_nope, q_rope, ckv, kr, (_, tables, starts), mask in zip(
                    *(split_groups(a, shapes) for a in _latent_proj(h, lp, c, positions)), groups, masks):
                with jax.named_scope("kv_pool"):
                    ckv_leaf, ckv_tables = address_paged_leaf_by_layer(pool["ckv"], tables, layer)
                    ckv_rows, ckv_ctx = paged_cache_write(ckv_leaf, ckv, ckv_tables, starts, c.dtype)
                    # a row of "kr" holds the keys of `pack` layers: this layer's lie at lanes [slot * rope, (slot + 1) * rope)
                    kr_leaf, kr_tables = address_paged_leaf_by_layer(pool["kr"], tables, layer // pack)
                    with jax.named_scope("kv_pool.gather"):
                        kr_ctx = gather_paged_context(kr_leaf, kr_tables)
                        kr_ctx = jax.lax.dynamic_slice_in_dim(kr_ctx, (layer % pack) * rope, rope, axis=-1)
                    kr_rows = kr.astype(kr_leaf.dtype)
                    kr_ctx = overlay_new_rows(kr_ctx, kr_rows, starts)
                attn.append(_attend(q_nope, q_rope, ckv_ctx, kr_ctx, mask, lp, c))
                stored.append((ckv_rows, kr_rows))
            x = x + _out_proj(join_groups(attn), lp, c)
        x, group_sizes = _ffn(x, lp, c, held)
        return x, (tuple(stored), group_sizes)

    # the pool is a constant of the loops, addressed by layer in their bodies: never a scanned input
    with jax.named_scope("layers"):
        x, (stored, group_sizes) = _scan_stacks(
            params, body, x, jnp.arange(c.num_layers, dtype=jnp.int32), hold_experts=True)
    rows = tuple(
        {"ckv": jnp.moveaxis(ckv_rows, 0, 1), "kr": jnp.moveaxis(_pack_rope(kr_rows, c), 0, 1)}
        for ckv_rows, kr_rows in stored)
    with jax.named_scope("head"):
        logits = _head(params, x, c)
    row_tile = expert_row_tile(  # which grouped product this dispatch's expert layers ran
        x.size // c.hidden_size * c.num_experts_per_tok, c.n_routed_experts, c.hidden_size, c.moe_intermediate_size, c.dtype)
    return split_groups(logits, shapes), rows, expert_counters(group_sizes, row_tile)


def generate(
    params: dict,
    input_ids: jax.Array,
    config: DeepseekV3Config,
    max_new_tokens: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
    top_k: int = 0,
    top_p: float = 1.0,
    prefill_chunk: Optional[int] = None,
) -> jax.Array:
    """Greedy (temperature=0) or sampled generation through the latent cache:
    ``[B, S]`` dense prompt -> ``[B, S+max_new_tokens]``, one XLA program."""
    from .generation import generate_loop

    return generate_loop(
        apply_cached, init_cache, params, input_ids, config,
        max_new_tokens, temperature=temperature, key=key, max_len=max_len,
        top_k=top_k, top_p=top_p, prefill_chunk=prefill_chunk,
    )
