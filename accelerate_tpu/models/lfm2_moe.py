"""LFM2-MoE-class decoder: gated short convolutions interleaved with GQA
attention, and a dropless sigmoid-routed expert layer chosen by a bias.

The published block (HF ``Lfm2Moe`` modeling; no biases, ``conv_L_cache`` 3):

- ``x += Op_i(RMSNorm(x))``, ``x += FFN_i(RMSNorm(x))``; final RMSNorm; the head
  tied to the embedding.  ``Op_i`` is attention where ``layer_types[i] ==
  "full_attention"``, else the short convolution; ``layer_types`` is taken as
  published, any interleaving.
- Short convolution: ``[B || C || z] = h W_in``; ``u_t = B_t * z_t``; ``c_t = w_0 *
  u_{t-2} + w_1 * u_{t-1} + w_2 * u_t`` (depthwise, causal, ``u`` zero before the
  sequence; three shifted multiplies, no kernel); ``y_t = (C_t * c_t) W_out``.
  **What a sequence carries from one dispatch to the next is ``u_{t-2}, u_{t-1}``:
  2 x d values a convolution layer, whatever the length** -- the cache's
  ``state`` leaf, one entry a sequence, not a row a token.
- Attention: GQA; RMSNorm over each head of ``q`` and of ``k`` before RoPE
  (half-split pairing, ``llama._rope``); scores ``q k / sqrt(head_dim)``, causal
  softmax.  The cache holds ``k`` after norm and RoPE, and ``v``, **in the
  attention layers only**, as rows of ``K * head_dim`` values without a head axis
  (at 8 x 64 a row is four whole 128-lane tiles, which a TPU holds block by
  block and gathers in place; ``[.., 8, 64]`` it holds with the block axis in the
  lanes and slices a layer at a time: ``generation._latent_rows_lie_block_by_block``);
  the heads are split after the gather.
- The first ``num_dense_layers`` layers carry a SwiGLU of ``intermediate_size``;
  every later layer routes: ``s = sigmoid(h W_r)``, the ``num_experts_per_tok``
  experts with the largest ``s + expert_bias``, weights ``s`` of the chosen
  (without the bias) over (their sum + 1e-6), times ``routed_scaling_factor``; no
  shared expert, no capacity, no drops (``ops/moe.py:routed_experts``).

The stack: the feed-forward parts lie in two stacks (``dense``, ``moe``), each
one ``lax.scan`` (the expert stack with its experts held whole, as
``deepseek_v3._scan_stacks`` holds them); the operators lie in two stacks of
their own (``attn``, ``conv``), and a layer takes its operator by its number in
the stack of its kind, under a ``lax.cond`` on the layer's kind where a scanned
stack mixes kinds.  Out of scope, and named so: convolutions of another length
than 3 or with a bias, and the update rule of ``expert_bias`` in training
(``loss_fn`` treats the bias as a constant: it only chooses).
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
from .deepseek_v3 import EXPERT_LEAVES, expert_counters
from .llama import cross_entropy, labels_and_weights

__all__ = [
    "Lfm2MoeConfig", "init_params", "apply", "loss_fn", "init_cache", "apply_cached", "apply_paged", "generate",
    "PARTITION_RULES", "param_specs",
]

ATTENTION, CONV = "full_attention", "conv"
OP_STACK = {ATTENTION: "attn", CONV: "conv"}  # the parameter stack of each kind of operator
NORM_TOPK_EPS = 1e-6  # the published epsilon under the chosen scores' sum
PUBLISHED_LAYER_TYPES = tuple(ATTENTION if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168  # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 1792  # one routed expert
    num_layers: int = 24
    layer_types: tuple = PUBLISHED_LAYER_TYPES
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    conv_L_cache: int = 3
    max_seq_len: int = 128000
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16  # compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_layers or set(self.layer_types) - set(OP_STACK):
            raise ValueError(f"layer_types must name {self.num_layers} layers as {ATTENTION!r} or {CONV!r}")
        if self.conv_L_cache != 3:
            raise ValueError("only the published convolution length 3 is implemented")
        if not 0 <= self.num_dense_layers < self.num_layers:
            raise ValueError("num_dense_layers must leave at least one expert layer")
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError("num_heads must be a multiple of num_kv_heads, head_dim even")

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def kv_width(self) -> int:
        """One cache row of K (or of V) of one attention layer: all its heads side by side."""
        return self.num_kv_heads * self.head_dim

    def stacks(self) -> list:
        """The two feed-forward stacks in order: (name, first layer, its layers' kinds)."""
        nd = self.num_dense_layers
        out = [("dense", 0, self.layer_types[:nd]), ("moe", nd, self.layer_types[nd:])]
        return [s for s in out if s[2]]

    @classmethod
    def tiny(cls, **kw) -> "Lfm2MoeConfig":
        """Test-sized config: the published 24-entry interleaving, two dense layers, 8 experts top-2."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_heads=4,
            num_kv_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=2, max_seq_len=256, remat=False,
        )
        defaults.update(kw)
        return cls(**defaults)

    def num_params(self) -> int:
        leaves = jax.tree_util.tree_leaves(_param_shapes(self), is_leaf=lambda x: isinstance(x, tuple))
        return int(sum(np.prod(s) for s in leaves))


# Experts are replicated: group sizes depend on the data, so the routed product runs per device.
PARTITION_RULES: list[tuple[str, P]] = [
    (r"embed", P("tp", "fsdp")),
    (r"attn/w[qkv]", P(None, "fsdp", "tp")),
    (r"attn/wo", P(None, "tp", "fsdp")),
    (r"conv/w_in", P(None, "fsdp", "tp")),
    (r"conv/w_out", P(None, "tp", "fsdp")),
    (r"dense/w_(gate|up)", P(None, "fsdp", "tp")),
    (r"dense/w_down", P(None, "tp", "fsdp")),
    (r"final_norm", P(None)),
]


def _param_shapes(c: Lfm2MoeConfig) -> dict:
    d, e, f, hd = c.hidden_size, c.num_experts, c.moe_intermediate_size, c.head_dim
    la, lc, nd, nm = c.count(ATTENTION), c.count(CONV), c.num_dense_layers, c.num_layers - c.num_dense_layers
    shapes = {
        "embed": (c.vocab_size, d),
        "conv": {"w_in": (lc, d, 3 * d), "taps": (lc, 3, d), "w_out": (lc, d, d)},  # taps[j] multiplies u_{t-2+j}
        "attn": {
            "wq": (la, d, c.num_heads * hd), "wk": (la, d, c.kv_width), "wv": (la, d, c.kv_width),
            "wo": (la, c.num_heads * hd, d), "ln_q": (la, hd), "ln_k": (la, hd),
        },
        "dense": {
            "ln_op": (nd, d), "ln_ffn": (nd, d),
            "w_gate": (nd, d, c.intermediate_size), "w_up": (nd, d, c.intermediate_size), "w_down": (nd, c.intermediate_size, d),
        },
        "moe": {
            "ln_op": (nm, d), "ln_ffn": (nm, d), "router": (nm, d, e),
            "router_bias": (nm, e),  # expert_bias: added to the scores to choose, never to weigh
            "w_gate": (nm, e, d, f), "w_up": (nm, e, d, f), "w_down": (nm, e, f, d),
        },
        "final_norm": (d,),
    }
    # a stack of no layers is no stack (a model without attention, or without dense layers)
    return {k: v for k, v in shapes.items() if not isinstance(v, dict) or next(iter(v.values()))[0]}


def param_specs(config: Lfm2MoeConfig) -> dict:
    from ..parallel.sharding import spec_from_rules

    def one(kp, shape):
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        spec = spec_from_rules(path, len(shape), PARTITION_RULES)
        return spec if spec is not None else P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(one, _param_shapes(config), is_leaf=lambda x: isinstance(x, tuple))


def init_params(config: Lfm2MoeConfig, key: jax.Array) -> dict:
    """Truncated-normal fan-in matrices, unit norm scales, taps N(0, 1)/sqrt(3), a zero selection bias."""
    shapes = _param_shapes(config)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.tree_util.tree_unflatten(treedef, list(jax.random.split(key, len(leaves))))

    def init_one(kp, shape, k):
        name = str(getattr(kp[-1], "key", kp[-1]))
        if name.startswith("ln_") or name == "final_norm":
            return jnp.ones(shape, config.param_dtype)
        if name == "router_bias":
            return jnp.zeros(shape, config.param_dtype)
        if name == "taps":
            return (jax.random.normal(k, shape, jnp.float32) / np.sqrt(3.0)).astype(config.param_dtype)
        fan_in = config.hidden_size if name == "embed" else shape[-2]
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) / np.sqrt(fan_in)).astype(
            config.param_dtype
        )

    return jax.tree_util.tree_map_with_path(init_one, shapes, keys, is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------


def _mm(h: jax.Array, w: jax.Array, c: Lfm2MoeConfig) -> jax.Array:
    return h @ w.astype(c.dtype)


def _conv_in(h, p, c: Lfm2MoeConfig):
    """``h [B, S, d]`` -> (``u = B * z``, the output gate ``C``), each ``[B, S, d]``."""
    d = c.hidden_size
    with jax.named_scope("conv.in"):
        bcz = _mm(h, p["w_in"], c)
    with jax.named_scope("conv.mix"):
        return bcz[..., :d] * bcz[..., 2 * d :], bcz[..., d : 2 * d]


@jax.named_scope("conv.mix")
def _conv_taps(prev, u, p):
    """The three taps over ``u [B, T, d]`` with ``prev [B, 2, d]`` = ``u`` of the
    two positions before it: (``c [B, T, d]``, ``prev || u`` ``[B, T + 2, d]``, of
    which rows ``n .. n + 1`` are what a sequence carries after ``n`` of the ``T``)."""
    t = u.shape[1]
    ext = jnp.concatenate([prev.astype(u.dtype), u], axis=1)
    w = p["taps"].astype(jnp.float32)
    mixed = sum(w[j] * ext[:, j : j + t].astype(jnp.float32) for j in range(3))
    return mixed.astype(u.dtype), ext


@jax.named_scope("conv.out")
def _conv_out(gate, mixed, p, c: Lfm2MoeConfig) -> jax.Array:
    return _mm(gate * mixed, p["w_out"], c)


def _qkv(h, p, c: Lfm2MoeConfig, positions):
    """``h [B, S, d]`` -> ``q [B, S, H, hd]``, and the cached pair ``k``, ``v [B, S,
    K, hd]``, ``q`` and ``k`` normed a head and rotated."""
    b, s, _ = h.shape
    with jax.named_scope("attn.qkv"):
        q = _mm(h, p["wq"], c).reshape(b, s, c.num_heads, c.head_dim)
        k = _mm(h, p["wk"], c).reshape(b, s, c.num_kv_heads, c.head_dim)
        v = _mm(h, p["wv"], c).reshape(b, s, c.num_kv_heads, c.head_dim)
        q, k = _llama._rms_norm(q, p["ln_q"], c.norm_eps), _llama._rms_norm(k, p["ln_k"], c.norm_eps)
        q, k = _llama._rope(q, k, positions, c.rope_theta)
    return q, k, v


def _attend(q, k_ctx, v_ctx, mask, c: Lfm2MoeConfig) -> jax.Array:
    """``q [B, S, H, hd]`` over a context of cache rows ``[B, P, K * hd]``; ``mask [B, S, P]`` -> ``[B, S, H * hd]``."""
    b, s = q.shape[:2]
    with jax.named_scope("attn.core"):
        heads = lambda rows: rows.reshape(b, -1, c.num_kv_heads, c.head_dim)
        out = _llama._attention(q, heads(k_ctx), heads(v_ctx), mask, c.num_heads // c.num_kv_heads)
    return out.reshape(b, s, c.num_heads * c.head_dim)


def _rows(x: jax.Array) -> jax.Array:
    """K or V ``[B, S, K, hd]`` as cache rows ``[B, S, K * hd]``."""
    return x.reshape(x.shape[:2] + (-1,))


@jax.named_scope("attn.out")
def _out_proj(attn, p, c: Lfm2MoeConfig) -> jax.Array:
    return _mm(attn, p["wo"], c)


def _ffn(x, p, c: Lfm2MoeConfig, held=None):
    """The layer's feed-forward sub-block with its residual, under the scope
    ``mlp`` every family gives it (``deepseek_v3._ffn`` without shared experts).
    Returns (x, rows each expert computed ``[E]`` or None)."""
    with jax.named_scope("mlp"):
        h = _llama._rms_norm(x, p["ln_ffn"], c.norm_eps)
        if "router" not in p:
            return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], c.dtype), None
        experts, first = (p, 0) if held is None else (held[0], held[1] * c.num_experts)
        with jax.named_scope("moe"):
            y, routing = routed_experts(
                h, p["router"], experts["w_gate"], experts["w_up"], experts["w_down"], top_k=c.num_experts_per_tok,
                scoring="sigmoid", select_bias=p["router_bias"] if c.use_expert_bias else None,
                normalize=c.norm_topk_prob, normalize_eps=NORM_TOPK_EPS, scale=c.routed_scaling_factor,
                first_expert=first, compute_dtype=c.dtype,
            )
        return x + y, routing["group_sizes"]


def _operator(ops: dict, kinds: tuple, is_attention, h, number):
    """The layer's operator on the normed ``h``: ``ops[kind](h, number in the
    stack of its kind) -> (y, what the operator hands out)``.  Where every layer
    of the scanned stack is of one kind, that operator; else a ``lax.cond`` on
    the layer's kind, each branch handing out zeros in the other's place.
    Returns (y, {kind: out})."""
    present = sorted(set(kinds))
    if len(present) == 1:
        y, out = ops[present[0]](h, number)
        return y, {present[0]: out}
    shapes = {kind: jax.eval_shape(ops[kind], h, number)[1] for kind in present}

    def branch(kind):
        def run(h, number):
            y, out = ops[kind](h, number)
            zeros = lambda other: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes[other])
            return y, {k: out if k == kind else zeros(k) for k in present}
        return run

    return jax.lax.cond(is_attention, branch(ATTENTION), branch(CONV), h, number)


def _scan_stacks(params: dict, c: Lfm2MoeConfig, x, ops: dict, hold_experts: bool = False, remat: bool = False):
    """The layers: ``lax.scan`` over the dense stack, then over the expert
    stack, the layer's operator taken from ``ops`` (:func:`_operator`) by its
    kind and its number in the stack of that kind, which are scanned inputs
    beside the layer's norms and feed-forward weights.  With ``hold_experts``
    (the serving paths) the routed experts are no scanned input: the body gets
    them whole (``deepseek_v3._scan_stacks``: cut out a layer at a time they are
    copied for the grouped product, every layer of every dispatch).  Returns
    (x, {kind: what its operators handed out, ``[layers of the kind, ...]`` in
    layer order}, rows each expert computed ``[expert layers, E]``)."""
    outs, group_sizes = {kind: [] for kind in OP_STACK}, None
    seen = {kind: 0 for kind in OP_STACK}
    for name, _, kinds in c.stacks():
        stack, n = params[name], len(kinds)
        numbers = []
        for kind in kinds:
            numbers.append(seen[kind])
            seen[kind] += 1

        def body(x, a, kinds=kinds):
            lp, is_attention, number, held = a
            with jax.named_scope("attn"):  # the operator's place in the block, whichever kind fills it
                y, out = _operator(ops, kinds, is_attention, _llama._rms_norm(x, lp["ln_op"], c.norm_eps), number)
                x = x + y
            x, sizes = _ffn(x, lp, c, held)
            return x, (out, sizes)

        if remat:
            body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
        per_layer = (jnp.asarray([k == ATTENTION for k in kinds]), jnp.asarray(numbers, jnp.int32))
        if hold_experts and "router" in stack:
            experts = {k: stack[k].reshape((-1,) + stack[k].shape[2:]) for k in EXPERT_LEAVES}
            scanned = {k: v for k, v in stack.items() if k not in EXPERT_LEAVES}
            x, (out, sizes) = jax.lax.scan(
                lambda x, a: body(x, (a[0], a[1], a[2], (experts, a[3]))), x,
                (scanned, *per_layer, jnp.arange(n, dtype=jnp.int32)))
        else:
            x, (out, sizes) = jax.lax.scan(lambda x, a: body(x, (*a, None)), x, (stack, *per_layer))
        for kind, leaves in out.items():
            at = np.asarray([i for i, k in enumerate(kinds) if k == kind])
            outs[kind].append(jax.tree.map(lambda leaf: leaf[at], leaves))
        group_sizes = sizes if sizes is not None else group_sizes
    joined = {
        kind: jax.tree.map(lambda *parts: jnp.concatenate(parts, axis=0), *parts) for kind, parts in outs.items() if parts}
    return x, joined, group_sizes


def _layer_of(stack: dict, number) -> dict:
    """One operator's parameters out of the stack of its kind."""
    return {k: jax.lax.dynamic_index_in_dim(v, number, 0, keepdims=False) for k, v in stack.items()}


# ---------------------------------------------------------------------------
# training-shape forward
# ---------------------------------------------------------------------------


def _embed(params, input_ids, c: Lfm2MoeConfig) -> jax.Array:
    with jax.named_scope("embed"):
        return _llama._embed_lookup(params["embed"], input_ids, c.dtype)


def _head(params, x, c: Lfm2MoeConfig) -> jax.Array:
    """Final norm and the tied head -> fp32 logits."""
    return (_llama._rms_norm(x, params["final_norm"], c.norm_eps) @ params["embed"].T.astype(c.dtype)).astype(jnp.float32)


def _trunk(params, input_ids, config, positions=None, attention_mask=None) -> jax.Array:
    """Whole sequences from their first token: no state comes in.  A padding
    mask hides padded keys from attention; the convolution looks two positions
    back whatever they hold, so pad on the right."""
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

    def conv(h, number):
        p = _layer_of(params["conv"], number)
        with jax.named_scope("conv"):
            u, gate = _conv_in(h, p, c)
            mixed, _ = _conv_taps(jnp.zeros((b, 2, c.hidden_size), u.dtype), u, p)
            return _conv_out(gate, mixed, p, c), ()

    def attention(h, number):
        p = _layer_of(params["attn"], number)
        q, k, v = _qkv(h, p, c, positions)
        return _out_proj(_attend(q, _rows(k), _rows(v), mask, c), p, c), ()

    with jax.named_scope("layers"):
        x, _, _ = _scan_stacks(params, c, x, {CONV: conv, ATTENTION: attention}, remat=c.remat)
    return x


def apply(params, input_ids, config, positions=None, attention_mask=None) -> jax.Array:
    """Forward pass: token ids [B, S] -> logits [B, S, V] (fp32)."""
    x = _trunk(params, input_ids, config, positions, attention_mask)
    with jax.named_scope("head"):
        return _head(params, x, config)


def loss_fn(params: dict, batch: dict, config: Lfm2MoeConfig) -> jax.Array:
    """Next-token cross-entropy, fp32.  No auxiliary loss: the published
    recipe balances load by moving ``expert_bias`` outside the gradient, and
    that update rule is not implemented here (the bias stays as initialised)."""
    labels, weights = labels_and_weights(batch)
    x = _trunk(params, batch["input_ids"], config, attention_mask=batch.get("attention_mask"))
    with jax.named_scope("head_loss"):
        return cross_entropy(_head(params, x, config), labels, weights)


# ---------------------------------------------------------------------------
# the cache: token rows for the attention layers, a state for the convolution layers
# ---------------------------------------------------------------------------


def init_cache(config: Lfm2MoeConfig, batch_size: int, max_len: int) -> dict:
    """Zeroed cache.  Token rows, one a position, for the attention layers
    alone: ``k``, ``v`` ``[attention layers, B, max_len, K * hd]``.  And under
    ``generation.STATE`` what a sequence carries whatever its length:
    ``conv`` ``[convolution layers, B, 2, d]`` = ``u_{t-2}, u_{t-1}``.  + write index."""
    from .generation import STATE

    c = config
    cache = {"index": jnp.zeros((), jnp.int32)}
    if c.count(ATTENTION):
        rows = (c.count(ATTENTION), batch_size, max_len, c.kv_width)
        cache.update(k=jnp.zeros(rows, c.dtype), v=jnp.zeros(rows, c.dtype))
    if c.count(CONV):
        cache[STATE] = {"conv": jnp.zeros((c.count(CONV), batch_size, 2, c.hidden_size), c.dtype)}
    return cache


def apply_cached(params: dict, input_ids: jax.Array, config: Lfm2MoeConfig, cache: dict):
    """Forward over new tokens with cache read/write: ``input_ids [B, S]`` at
    positions ``cache['index'] .. index+S``; returns (logits ``[B, S, V]``,
    updated cache).  A zeroed cache at index 0 is a sequence's start."""
    from .generation import STATE, check_cache_room

    c = config
    b, s = input_ids.shape
    index = cache["index"]
    positions = jnp.broadcast_to(index + jnp.arange(s), (b, s))
    x = _embed(params, input_ids, c)
    ops = {}
    if c.count(CONV):
        def conv(h, number):
            p = _layer_of(params["conv"], number)
            with jax.named_scope("conv"):
                u, gate = _conv_in(h, p, c)
                mixed, ext = _conv_taps(jax.lax.dynamic_index_in_dim(cache[STATE]["conv"], number, 0, keepdims=False), u, p)
                return _conv_out(gate, mixed, p, c), ext[:, s:]

        ops[CONV] = conv
    if c.count(ATTENTION):
        max_len = cache["k"].shape[2]
        check_cache_room(index, s, max_len)
        mask = jnp.broadcast_to((index + jnp.arange(s))[:, None] >= jnp.arange(max_len)[None, :], (b, s, max_len))

        def attention(h, number):
            p = _layer_of(params["attn"], number)
            q, k, v = _qkv(h, p, c, positions)
            k, v = _rows(k).astype(cache["k"].dtype), _rows(v).astype(cache["v"].dtype)
            context = lambda leaf, new: jax.lax.dynamic_update_slice(
                jax.lax.dynamic_index_in_dim(leaf, number, 0, keepdims=False), new, (0, index, 0))
            return _out_proj(_attend(q, context(cache["k"], k), context(cache["v"], v), mask, c), p, c), (k, v)

        ops[ATTENTION] = attention
    with jax.named_scope("layers"):
        x, outs, _ = _scan_stacks(params, c, x, ops, hold_experts=True)
    with jax.named_scope("head"):
        logits = _head(params, x, c)
    new_cache = {"index": index + s}
    if ATTENTION in outs:
        k_rows, v_rows = outs[ATTENTION]  # [attention layers, B, S, w]: written behind the rows the cache holds
        new_cache["k"] = jax.lax.dynamic_update_slice(cache["k"], k_rows, (0, 0, index, 0))
        new_cache["v"] = jax.lax.dynamic_update_slice(cache["v"], v_rows, (0, 0, index, 0))
    if CONV in outs:
        new_cache[STATE] = {"conv": outs[CONV]}
    return logits, new_cache


def apply_paged(params: dict, groups, config: Lfm2MoeConfig, pool: dict):
    """Forward over new tokens straight against the paged pool, for a family
    with a state: ``groups`` is a short tuple of ``(tokens [B, T], tables [B, M],
    starts [B], slots [B], counts [B])``.  Lane ``b`` of a group has its tokens
    at positions ``starts[b] .. starts[b]+T-1`` of the sequence whose K/V rows
    its table row names and whose state lies at ``slots[b]`` of the pool's state
    leaves; ``counts[b]`` of its ``T`` rows are real (a padded last chunk has
    fewer; 0: the lane is idle).  A lane at ``starts == 0`` starts a sequence:
    it reads a zero state, whatever the slot holds.  The projections, the
    experts and the head run once over the rows of all groups; attention and
    the three taps run a group at a time.  Returns (logits a group, what each
    group wrote ``{"k", "v": [B, attention layers, T, w], STATE: {"conv": [B,
    convolution layers, 2, d]}}`` -- the state as it stands after row ``counts -
    1`` -- for the caller's write, :func:`expert_counters` of the dispatch)."""
    from .generation import (
        STATE,
        address_paged_leaf_by_layer,
        group_positions,
        join_groups,
        paged_cache_write,
        read_state_rows,
        split_groups,
    )

    c = config
    cached = [g[:3] for g in groups]
    shapes = [tokens.shape for tokens, _, _ in cached]
    x = _embed(params, join_groups([tokens for tokens, _, _ in cached]), c)
    ops = {}
    if c.count(CONV):
        def conv(h, number):
            p = _layer_of(params["conv"], number)
            with jax.named_scope("conv"):
                u, gate = _conv_in(h, p, c)
                mixed, carried = [], []
                for u_g, (_, _, starts, slots, counts) in zip(split_groups(u, shapes), groups):
                    prev = read_state_rows(pool[STATE]["conv"], number, slots, starts)
                    mixed_g, ext = _conv_taps(prev, u_g, p)
                    mixed.append(mixed_g)
                    after = counts[:, None] + jnp.arange(2, dtype=jnp.int32)[None, :]  # rows n, n + 1 of prev || u
                    carried.append(jnp.take_along_axis(ext, after[:, :, None], axis=1).astype(pool[STATE]["conv"].dtype))
                return _conv_out(gate, join_groups(mixed), p, c), tuple(carried)

        ops[CONV] = conv
    if c.count(ATTENTION):
        positions, masks = group_positions(cached, pool["k"].shape[2])
        positions = join_groups(positions)

        def attention(h, number):
            p = _layer_of(params["attn"], number)
            attn, stored = [], []
            for q, k, v, (_, tables, starts), mask in zip(
                    *(split_groups(a, shapes) for a in _qkv(h, p, c, positions)), cached, masks):
                with jax.named_scope("kv_pool"):
                    k_leaf, k_tables = address_paged_leaf_by_layer(pool["k"], tables, number)
                    v_leaf, v_tables = address_paged_leaf_by_layer(pool["v"], tables, number)
                    k_rows, k_ctx = paged_cache_write(k_leaf, _rows(k), k_tables, starts, c.dtype)
                    v_rows, v_ctx = paged_cache_write(v_leaf, _rows(v), v_tables, starts, c.dtype)
                attn.append(_attend(q, k_ctx, v_ctx, mask, c))
                stored.append((k_rows, v_rows))
            return _out_proj(join_groups(attn), p, c), tuple(stored)

        ops[ATTENTION] = attention
    # the pool is a constant of the loops, addressed by the operator's number in their bodies: never a scanned input
    with jax.named_scope("layers"):
        x, outs, group_sizes = _scan_stacks(params, c, x, ops, hold_experts=True)
    rows = [{} for _ in groups]
    for g, written in enumerate(rows):
        if ATTENTION in outs:
            written.update(k=jnp.moveaxis(outs[ATTENTION][g][0], 0, 1), v=jnp.moveaxis(outs[ATTENTION][g][1], 0, 1))
        if CONV in outs:
            written[STATE] = {"conv": jnp.moveaxis(outs[CONV][g], 0, 1)}
    with jax.named_scope("head"):
        logits = _head(params, x, c)
    row_tile = expert_row_tile(  # which grouped product this dispatch's expert layers ran
        x.size // c.hidden_size * c.num_experts_per_tok, c.num_experts, c.hidden_size, c.moe_intermediate_size, c.dtype)
    return split_groups(logits, shapes), tuple(rows), expert_counters(group_sizes, row_tile)


def generate(
    params: dict,
    input_ids: jax.Array,
    config: Lfm2MoeConfig,
    max_new_tokens: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
    top_k: int = 0,
    top_p: float = 1.0,
    prefill_chunk: Optional[int] = None,
) -> jax.Array:
    """Greedy (temperature=0) or sampled generation through the cache:
    ``[B, S]`` dense prompt -> ``[B, S+max_new_tokens]``, one XLA program."""
    from .generation import generate_loop

    return generate_loop(
        apply_cached, init_cache, params, input_ids, config,
        max_new_tokens, temperature=temperature, key=key, max_len=max_len,
        top_k=top_k, top_p=top_p, prefill_chunk=prefill_chunk,
    )
