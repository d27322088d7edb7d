"""AFMoE-class decoder (Arcee Trinity; ``model_type: afmoe``): gated GQA
attention whose layers are of two kinds, sliding-window and full, sandwich
norms, and a sigmoid-routed expert layer with a shared expert, of which a chip
may hold a **share**.

The published block (``transformers``' ``modeling_afmoe.py``; no bias anywhere,
an untied head):

- ``x0 = embed[ids] * sqrt(hidden_size)`` (``mup_enabled``).
- ``a = RMSNorm_in(x)``; ``q, k, v = a Wq, a Wk, a Wv``; ``g = a Wg`` (``Wg`` as
  wide as ``Wq``); RMSNorm over each head of ``q`` and of ``k``; **RoPE on ``q``
  and ``k`` in ``sliding_attention`` layers only, a ``full_attention`` layer
  uses no positional encoding**; causal GQA attention, in a sliding layer over
  the keys ``j`` with ``i - sliding_window < j <= i``; ``o = (attn * sigmoid(g))
  Wo``; ``x += RMSNorm_post_attn(o)``.
- ``m = RMSNorm_pre_mlp(x)``; ``f = SwiGLU(m)`` of ``intermediate_size`` in the
  first ``num_dense_layers`` layers, else ``f = SwiGLU_shared(m) + sum_{e in
  top_k} w_e SwiGLU_e(m)`` with ``s = sigmoid(m Wr)`` in float32, the experts
  chosen by ``s + expert_bias``, ``w = s_chosen / (sum s_chosen + 1e-20) *
  route_scale`` (``ops/moe.py:routed_experts``: dropless, row by row); ``x +=
  RMSNorm_post_mlp(f)``.
- Final RMSNorm, ``lm_head``.

Two things of it reach below this file.  **The experts held**:
``experts_held = (first, count)`` says which run of the router's
``num_experts`` this chip's weights are (``w_gate`` ``[layers, count, d, f]``);
the router stays ``num_experts`` wide, the pairs that fall on a held expert are
computed and the others add nothing, so ``f`` carries the *partial* sum (one
chip of an expert-parallel group before the exchange; there is no exchange
here).  ``None`` holds all.  **The cache**: K (after norm, and RoPE where the
layer has it) and V per K/V head; the full layers' rows under ``k`` / ``v`` and
the sliding layers' under ``generation.WINDOW``: in the paged pool a sequence
keeps a ring of blocks of the latter, ``sliding_window + prefill_chunk`` rows
whatever its length (``serving/engine.py``), and a sliding layer's attention is
masked by position over that ring.

The layers lie in two stacks (``dense``, ``moe``), each one ``lax.scan``; a
layer's kind and its number among the layers of its kind are scanned inputs,
and where a stack mixes kinds the paged attention is a ``lax.cond`` on the
kind (the two kinds read different leaves through different tables).
``layer_types`` is taken as published, any pattern.  Out of scope, and named
so: the update rule of ``expert_bias`` and the load-balancing loss in training
(``loss_fn`` is next-token cross-entropy, the bias a constant that only
chooses); ``mup``'s other multipliers, which the published forward does not have.
"""

from __future__ import annotations

import contextlib
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
    "AfmoeConfig", "init_params", "apply", "loss_fn", "init_cache", "apply_cached", "apply_paged", "generate",
    "PARTITION_RULES", "param_specs",
]

SLIDING, FULL = "sliding_attention", "full_attention"
ROUTE_NORM_EPS = 1e-20  # the published epsilon under the chosen scores' sum


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288  # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 3072  # one routed expert, and the shared expert a shared expert
    num_layers: int = 60
    layer_types: Optional[tuple] = None  # None: a full layer every fourth, the published pattern
    num_dense_layers: int = 6
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: int = 128
    num_experts: int = 256  # the router's width
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    experts_held: Optional[tuple] = None  # (first, count) of the router's experts this chip's weights are; None: all
    route_norm: bool = True
    route_scale: float = 2.448
    sliding_window: int = 4096
    mup_enabled: bool = True
    max_seq_len: int = 262144
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16  # compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True

    def __post_init__(self):
        types = self.layer_types
        if types is None:
            types = tuple(FULL if i % 4 == 3 else SLIDING for i in range(self.num_layers))
        object.__setattr__(self, "layer_types", tuple(types))
        if len(self.layer_types) != self.num_layers or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types must name {self.num_layers} layers as {SLIDING!r} or {FULL!r}")
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError("num_dense_layers must lie in 0..num_layers")
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError("num_heads must be a multiple of num_kv_heads, head_dim even")
        if self.sliding_window < 1:
            raise ValueError(f"sliding_window must be >= 1, got {self.sliding_window}")
        first, count = self.held
        if not (0 <= first and 1 <= count and first + count <= self.num_experts):
            raise ValueError(f"experts_held {self.experts_held!r} is not a run of the router's {self.num_experts} experts")
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", (int(first), int(count)))

    @property
    def held(self) -> tuple:
        """(first, count) of the experts this chip's weights are."""
        return (0, self.num_experts) if self.experts_held is None else tuple(self.experts_held)

    @property
    def share(self) -> Optional[tuple]:
        """What ``routed_experts`` is told: ``None`` where every expert is held."""
        return None if self.held[1] == self.num_experts else self.held

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def stacks(self) -> list:
        """The two stacks in order: (name, its layers' kinds)."""
        nd = self.num_dense_layers
        return [s for s in (("dense", self.layer_types[:nd]), ("moe", self.layer_types[nd:])) if s[1]]

    @classmethod
    def tiny(cls, **kw) -> "AfmoeConfig":
        """Test-sized config: one dense layer and two periods ``s s s f``, 8 experts top-2, a window of 8."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_layers=9,
            layer_types=(SLIDING,) + (SLIDING, SLIDING, SLIDING, FULL) * 2, num_dense_layers=1, num_heads=4,
            num_kv_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=2, sliding_window=8, max_seq_len=256,
            remat=False,
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
    (r"(dense|moe)/w[qkvg]$", P(None, "fsdp", "tp")),
    (r"(dense|moe)/wo", P(None, "tp", "fsdp")),
    (r"dense/w_(gate|up)", P(None, "fsdp", "tp")),
    (r"dense/w_down", P(None, "tp", "fsdp")),
    (r"moe/ws_(gate|up)", P(None, "fsdp", "tp")),
    (r"moe/ws_down", P(None, "tp", "fsdp")),
    (r"final_norm", P(None)),
]


def _layer_shapes(c: AfmoeConfig, n: int) -> dict:
    d, hd = c.hidden_size, c.head_dim
    return {
        "ln_in": (n, d), "ln_post_attn": (n, d), "ln_pre_mlp": (n, d), "ln_post_mlp": (n, d),
        "wq": (n, d, c.num_heads * hd), "wk": (n, d, c.num_kv_heads * hd), "wv": (n, d, c.num_kv_heads * hd),
        "wg": (n, d, c.num_heads * hd), "wo": (n, c.num_heads * hd, d), "ln_q": (n, hd), "ln_k": (n, hd),
    }


def _param_shapes(c: AfmoeConfig) -> dict:
    d, e, fe, held = c.hidden_size, c.num_experts, c.moe_intermediate_size, c.held[1]
    nd, nm, fs = c.num_dense_layers, c.num_layers - c.num_dense_layers, c.moe_intermediate_size * c.num_shared_experts
    shapes = {"embed": (c.vocab_size, d), "final_norm": (d,), "lm_head": (d, c.vocab_size)}
    if nd:
        f = c.intermediate_size
        shapes["dense"] = {**_layer_shapes(c, nd), "w_gate": (nd, d, f), "w_up": (nd, d, f), "w_down": (nd, f, d)}
    if nm:
        shapes["moe"] = {
            **_layer_shapes(c, nm), "router": (nm, d, e), "router_bias": (nm, e),
            "w_gate": (nm, held, d, fe), "w_up": (nm, held, d, fe), "w_down": (nm, held, fe, d),
            "ws_gate": (nm, d, fs), "ws_up": (nm, d, fs), "ws_down": (nm, fs, d),
        }
    return shapes


def param_specs(config: AfmoeConfig) -> dict:
    from ..parallel.sharding import spec_from_rules

    def one(kp, shape):
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        spec = spec_from_rules(path, len(shape), PARTITION_RULES)
        return spec if spec is not None else P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(one, _param_shapes(config), is_leaf=lambda x: isinstance(x, tuple))


def init_params(config: AfmoeConfig, key: jax.Array) -> dict:
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


def _mm(h: jax.Array, w: jax.Array, c: AfmoeConfig) -> jax.Array:
    return h @ w.astype(c.dtype)


def _qkv(h, p, c: AfmoeConfig):
    """``h [B, S, d]`` -> ``q [B, S, H, hd]``, ``k``, ``v [B, S, K, hd]``, ``q`` and ``k`` normed a head, not yet rotated."""
    b, s, _ = h.shape
    with jax.named_scope("attn.qkv"):
        q = _mm(h, p["wq"], c).reshape(b, s, c.num_heads, c.head_dim)
        k = _mm(h, p["wk"], c).reshape(b, s, c.num_kv_heads, c.head_dim)
        v = _mm(h, p["wv"], c).reshape(b, s, c.num_kv_heads, c.head_dim)
        return _llama._rms_norm(q, p["ln_q"], c.rms_eps), _llama._rms_norm(k, p["ln_k"], c.rms_eps), v


@jax.named_scope("attn.qkv")
def _rotated(q, k, positions, c: AfmoeConfig):
    return _llama._rope(q, k, positions, c.rope_theta)


@jax.named_scope("attn.gate")
def _gate(h, p, c: AfmoeConfig) -> jax.Array:
    """``sigmoid(h Wg)`` ``[B, S, H * hd]``: what the attention's output is multiplied by before ``Wo``."""
    return jax.nn.sigmoid(_mm(h, p["wg"], c))


def _attend(q, k_ctx, v_ctx, mask, c: AfmoeConfig, window: bool = False) -> jax.Array:
    """``q [B, S, H, hd]`` over a context ``[B, P, K, hd]`` under ``mask [B, S,
    P]`` -> ``[B, S, H * hd]``, under ``attn.core``; a sliding layer's also
    under ``attn.window`` where the caller says it is one."""
    with jax.named_scope("attn.core"), jax.named_scope("attn.window") if window else contextlib.nullcontext():
        out = _llama._attention(q, k_ctx, v_ctx, mask, c.num_heads // c.num_kv_heads)
    return out.reshape(q.shape[:2] + (c.num_heads * c.head_dim,))


def _attend_in_place(q, k_new, v_new, pk, pv, tables, starts, c: AfmoeConfig, window: bool, interpret: bool) -> jax.Array:
    """:func:`_attend` for the decoding lanes read in place (``generation.attend_in_place``), under the same scopes."""
    from .generation import attend_in_place

    with jax.named_scope("attn.core"), jax.named_scope("attn.window") if window else contextlib.nullcontext():
        out = attend_in_place(q, k_new, v_new, pk, pv, tables, starts, c.sliding_window if window else 0, interpret)
    return out.reshape(q.shape[:2] + (c.num_heads * c.head_dim,))


@jax.named_scope("attn.out")
def _out_proj(attn, gate, p, c: AfmoeConfig) -> jax.Array:
    return _mm(attn * gate, p["wo"], c)


def _ffn(x, p, c: AfmoeConfig, held=None):
    """The layer's feed-forward sub-block between its two norms, with the
    residual, under the scope ``mlp`` every family gives it; an expert layer's
    parts lie under ``moe`` inside it.  ``held`` = (all the stack's held experts
    merged ``{leaf: [layers * count, ., .]}``, this layer's number in the stack)
    where the layer loop keeps the experts out of its scanned inputs.  Returns
    (x, rows each held expert computed ``[count]`` or None)."""
    with jax.named_scope("mlp"):
        m = _llama._rms_norm(x, p["ln_pre_mlp"], c.rms_eps)
        if "router" not in p:
            f, sizes = swiglu(m, p["w_gate"], p["w_up"], p["w_down"], c.dtype), None
        else:
            experts, first = (p, 0) if held is None else (held[0], held[1] * c.held[1])
            with jax.named_scope("moe"):
                y, routing = routed_experts(
                    m, p["router"], experts["w_gate"], experts["w_up"], experts["w_down"], top_k=c.num_experts_per_tok,
                    scoring="sigmoid", select_bias=p["router_bias"], normalize=c.route_norm,
                    normalize_eps=ROUTE_NORM_EPS, scale=c.route_scale, first_expert=first, share=c.share,
                    compute_dtype=c.dtype,
                )
                with jax.named_scope("moe.shared"):
                    shared = swiglu(m, p["ws_gate"], p["ws_up"], p["ws_down"], c.dtype)
            f, sizes = y + shared.astype(y.dtype), routing["group_sizes"]
        return x + _llama._rms_norm(f.astype(x.dtype), p["ln_post_mlp"], c.rms_eps), sizes


def _scan_stacks(params: dict, c: AfmoeConfig, body, x, hold_experts: bool = False):
    """``lax.scan`` of ``body(x, layer params, is_sliding, number, held, mixed)
    -> (x, (attention's ys, sizes))`` over the dense stack and then the expert
    stack.  ``is_sliding`` and ``number`` (the layer's number among the layers
    of its kind) are scanned inputs; ``mixed`` says, statically, whether the
    stack holds both kinds (else ``is_sliding`` is a Python bool).  With
    ``hold_experts`` (the serving paths) the routed experts are no scanned
    input (``deepseek_v3._scan_stacks`` says why).  Returns (x, the attention's
    ys joined over the stacks ``[num_layers, ...]``, rows each held expert
    computed ``[expert layers, count]`` or None)."""
    outs, group_sizes = [], None
    seen = {SLIDING: 0, FULL: 0}
    for name, kinds in c.stacks():
        stack, n = params[name], len(kinds)
        numbers = []
        for kind in kinds:
            numbers.append(seen[kind])
            seen[kind] += 1
        mixed = len(set(kinds)) > 1
        sliding = jnp.asarray([k == SLIDING for k in kinds]) if mixed else None
        per_layer = (jnp.asarray(numbers, jnp.int32),) + ((sliding,) if mixed else ())

        def run(x, lp, rest, held, kinds=kinds, mixed=mixed):
            return body(x, lp, rest[1] if mixed else kinds[0] == SLIDING, rest[0], held, mixed)

        if hold_experts and "router" in stack:
            experts = {k: stack[k].reshape((-1,) + stack[k].shape[2:]) for k in EXPERT_LEAVES}
            scanned = {k: v for k, v in stack.items() if k not in EXPERT_LEAVES}
            x, (ys, sizes) = jax.lax.scan(
                lambda x, a: run(x, a[0], a[1], (experts, a[2])), x, (scanned, per_layer, jnp.arange(n, dtype=jnp.int32)))
        else:
            x, (ys, sizes) = jax.lax.scan(lambda x, a: run(x, a[0], a[1], None), x, (stack, per_layer))
        outs.append(ys)
        group_sizes = sizes if sizes is not None else group_sizes
    joined = outs[0] if len(outs) == 1 else jax.tree.map(lambda *parts: jnp.concatenate(parts, axis=0), *outs)
    return x, joined, group_sizes


def _by_kind(c: AfmoeConfig, leaf):
    """A scan's ``[num_layers, ...]`` output cut into (the full layers', the sliding layers'), each in layer order."""
    at = lambda kind: np.asarray([i for i, k in enumerate(c.layer_types) if k == kind], np.int32)
    return leaf[at(FULL)], leaf[at(SLIDING)]


def _select(pred, a, b):
    """``a`` where the layer is sliding, else ``b``; ``pred`` a Python bool where the stack is of one kind."""
    if isinstance(pred, bool):
        return a if pred else b
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _embed(params, input_ids, c: AfmoeConfig) -> jax.Array:
    with jax.named_scope("embed"):
        x = _llama._embed_lookup(params["embed"], input_ids, c.dtype)
        return x * jnp.asarray(np.sqrt(c.hidden_size), c.dtype) if c.mup_enabled else x


def _head(params, x, c: AfmoeConfig) -> jax.Array:
    """Final norm and the untied head -> fp32 logits."""
    return (_llama._rms_norm(x, params["final_norm"], c.rms_eps) @ params["lm_head"].astype(c.dtype)).astype(jnp.float32)


def _masks(q_pos, k_pos, c: AfmoeConfig):
    """(the sliding layers' mask, the full layers') of queries at ``q_pos [.., S]`` over keys at ``k_pos [P]``."""
    causal = q_pos[..., :, None] >= k_pos
    return causal & (k_pos > q_pos[..., :, None] - c.sliding_window), causal


# ---------------------------------------------------------------------------
# training-shape forward
# ---------------------------------------------------------------------------


def _trunk(params, input_ids, config, positions=None, attention_mask=None) -> jax.Array:
    c = config
    b, s = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    # keys by their place in the batch row: a right-padded row's real tokens sit at their positions
    mask_w, mask_f = (jnp.broadcast_to(m, (b, s, s)) for m in _masks(jnp.arange(s), jnp.arange(s), c))
    if attention_mask is not None:
        valid = attention_mask.astype(bool)[:, None, :]
        mask_w, mask_f = mask_w & valid, mask_f & valid
    act_spec = P(("dcn_dp", "dp", "fsdp"), "sp", None)
    x = _llama._maybe_constrain(_embed(params, input_ids, c), act_spec)

    def body(x, lp, is_sliding, number, held, mixed):
        with jax.named_scope("attn"):
            h = _llama._rms_norm(x, lp["ln_in"], c.rms_eps)
            q, k, v = _qkv(h, lp, c)
            q, k = _select(is_sliding, _rotated(q, k, positions, c), (q, k))
            attn = _attend(q, k, v, _select(is_sliding, mask_w, mask_f), c)
            x = x + _llama._rms_norm(_out_proj(attn, _gate(h, lp, c), lp, c), lp["ln_post_attn"], c.rms_eps)
        x, sizes = _ffn(x, lp, c, held)
        return _llama._maybe_constrain(x, act_spec), (None, sizes)

    def rematted(x, lp, is_sliding, number, held, mixed):
        # the layer's kind is a traced input only where the stack mixes kinds: else it stays a Python bool of the closure
        inner = lambda x, lp, sliding, number, held: body(x, lp, sliding if mixed else is_sliding, number, held, mixed)
        return jax.checkpoint(inner, policy=jax.checkpoint_policies.nothing_saveable)(
            x, lp, is_sliding if mixed else None, number, held)

    with jax.named_scope("layers"):
        x, _, _ = _scan_stacks(params, c, rematted if c.remat else body, x)
    return x


def apply(params, input_ids, config, positions=None, attention_mask=None) -> jax.Array:
    """Forward pass: token ids [B, S] -> logits [B, S, V] (fp32)."""
    x = _trunk(params, input_ids, config, positions, attention_mask)
    with jax.named_scope("head"):
        return _head(params, x, config)


def loss_fn(params: dict, batch: dict, config: AfmoeConfig) -> jax.Array:
    """Next-token cross-entropy, fp32 (no auxiliary loss: see the module docstring)."""
    labels, weights = labels_and_weights(batch)
    x = _trunk(params, batch["input_ids"], config, attention_mask=batch.get("attention_mask"))
    with jax.named_scope("head_loss"):
        return cross_entropy(_head(params, x, config), labels, weights)


# ---------------------------------------------------------------------------
# the cache: full leaves and window leaves
# ---------------------------------------------------------------------------


def init_cache(config: AfmoeConfig, batch_size: int, max_len: int) -> dict:
    """Zeroed cache: the full layers' rows ``k``, ``v`` ``[full layers, B,
    max_len, K, hd]`` at the top level, the sliding layers' under
    ``generation.WINDOW`` (``[sliding layers, B, max_len, K, hd]``: every row
    here, where a sequence is one dense array; a ring of blocks in the paged
    pool).  + write index.  Where they are put declares the kinds."""
    from .generation import WINDOW

    c = config
    rows = lambda n: jnp.zeros((n, batch_size, max_len, c.num_kv_heads, c.head_dim), c.dtype)
    cache = {"index": jnp.zeros((), jnp.int32)}
    if c.count(FULL):
        cache.update(k=rows(c.count(FULL)), v=rows(c.count(FULL)))
    if c.count(SLIDING):
        cache[WINDOW] = {"k": rows(c.count(SLIDING)), "v": rows(c.count(SLIDING))}
    return cache


def apply_cached(params: dict, input_ids: jax.Array, config: AfmoeConfig, cache: dict):
    """Forward over new tokens with cache read/write: ``input_ids [B, S]`` at
    positions ``cache['index'] .. index+S``; returns (logits ``[B, S, V]``,
    updated cache).  Every layer's rows are kept here; a sliding layer masks
    what lies behind its window."""
    from .generation import WINDOW, check_cache_room

    c = config
    b, s = input_ids.shape
    index = cache["index"]
    leaves = {FULL: cache if c.count(FULL) else None, SLIDING: cache.get(WINDOW)}
    max_len = next(v for v in leaves.values() if v is not None)["k"].shape[2]
    check_cache_room(index, s, max_len)
    new_positions = index + jnp.arange(s)
    positions = jnp.broadcast_to(new_positions, (b, s))
    mask_w, mask_f = (jnp.broadcast_to(m, (b, s, max_len)) for m in _masks(new_positions, jnp.arange(max_len), c))
    x = _embed(params, input_ids, c)

    def context(kind, number, k, v):
        group = leaves[kind]
        write = lambda leaf, new: jax.lax.dynamic_update_slice(
            jax.lax.dynamic_index_in_dim(leaf, number, 0, keepdims=False), new.astype(leaf.dtype), (0, index, 0, 0))
        return write(group["k"], k), write(group["v"], v)

    def body(x, lp, is_sliding, number, held, mixed):
        with jax.named_scope("attn"):
            h = _llama._rms_norm(x, lp["ln_in"], c.rms_eps)
            q, k, v = _qkv(h, lp, c)
            q, k = _select(is_sliding, _rotated(q, k, positions, c), (q, k))
            if mixed:
                k_ctx, v_ctx = _select(is_sliding, context(SLIDING, number, k, v), context(FULL, number, k, v))
            else:
                k_ctx, v_ctx = context(SLIDING if is_sliding else FULL, number, k, v)
            attn = _attend(q, k_ctx, v_ctx, _select(is_sliding, mask_w, mask_f), c)
            x = x + _llama._rms_norm(_out_proj(attn, _gate(h, lp, c), lp, c), lp["ln_post_attn"], c.rms_eps)
        x, sizes = _ffn(x, lp, c, held)
        return x, ((k, v), sizes)

    with jax.named_scope("layers"):
        x, (k_rows, v_rows), _ = _scan_stacks(params, c, body, x, hold_experts=True)
    with jax.named_scope("head"):
        logits = _head(params, x, c)
    new_cache = {"index": index + s}
    for kind, group, k_new, v_new in zip((FULL, SLIDING), (leaves[FULL], leaves[SLIDING]), _by_kind(c, k_rows), _by_kind(c, v_rows)):
        if group is None:
            continue
        written = {
            "k": jax.lax.dynamic_update_slice(group["k"], k_new.astype(group["k"].dtype), (0, 0, index, 0, 0)),
            "v": jax.lax.dynamic_update_slice(group["v"], v_new.astype(group["v"].dtype), (0, 0, index, 0, 0)),
        }
        if kind == FULL:
            new_cache.update(written)
        else:
            new_cache[WINDOW] = written
    return logits, new_cache


def apply_paged(params: dict, groups, config: AfmoeConfig, pool: dict, interpret: bool = False):
    """Forward over new tokens straight against the paged pool, for a family
    with window leaves: ``groups`` is a short tuple of ``(tokens [B, T], tables
    [B, M], starts [B], window tables [B, Mw])``, the decoding lanes first.
    Lane ``b`` of a group has its tokens at positions ``starts[b] ..
    starts[b]+T-1`` of the sequence whose full layers' rows its table row names
    and whose sliding layers' rows lie in the ring its window table names
    (``generation.window_group_masks``: position ``p`` in block ``(p // bs) mod
    Mw`` of it).  The projections, the gate, the experts and the head run once
    over the rows of all groups; attention runs a group at a time, a full layer
    over ``M`` blocks, a sliding layer over ``Mw``, whatever ``M``.  Returns
    (logits a group, what each group wrote ``{"k", "v": [B, full layers, T, K,
    hd], WINDOW: {"k", "v": [B, sliding layers, T, K, hd]}}`` for the caller's
    write, :func:`expert_counters` of the dispatch with the window layers' two
    counters beside them: over the first group's lanes that hold a sequence
    (``starts > 0``) and the sliding layers, ``window_rows_read`` the keys their
    masks admit and ``context_rows`` the keys a full layer's mask admits at the
    same rows), and ``attn_rows_read`` where the decoding lanes read a kind in
    place (``generation.reads_in_place``: the rows the kernel copied over their
    lanes and that kind's layers).  ``interpret`` runs the decoding lanes'
    attention through the kernel in the Pallas interpreter whatever the rule
    says (the CPU tests)."""
    from .generation import (
        WINDOW,
        address_paged_pool_by_layer,
        group_positions,
        join_groups,
        paged_cache_write,
        paged_window_write,
        reads_in_place,
        rows_read_in_place,
        split_groups,
        token_leaves,
        window_group_masks,
    )

    c = config
    cached = [g[:3] for g in groups]
    shapes = [tokens.shape for tokens, _, _ in cached]
    full_pool, window_pool = token_leaves(pool), pool.get(WINDOW)
    block_size = next(iter((window_pool or full_pool).values())).shape[2]
    positions, masks_f = group_positions(cached, block_size)
    masks_w = window_group_masks(groups, positions, block_size, c.sliding_window) if window_pool else masks_f
    positions = join_groups(positions)
    x = _embed(params, join_groups([tokens for tokens, _, _ in cached]), c)

    # the decoding lanes (the first group, one row a lane) read each kind's pool in place where the rule says so
    rows = cached[0][0].shape[1]
    in_place = lambda leaves, width: bool(leaves) and (interpret and rows == 1 or reads_in_place(leaves["k"], rows, width))
    kernel = {False: in_place(full_pool, groups[0][1].shape[1]), True: in_place(window_pool, groups[0][-1].shape[1])}

    def attention(sliding: bool):
        """One kind's attention over every group: (q, k, v, number) -> (attn joined, what each group stores)."""
        def run(q, k, v, number):
            if sliding:
                q, k = _rotated(q, k, positions, c)
            attn, stored = [], []
            for i, (q_g, k_g, v_g, group, mask) in enumerate(zip(
                    *(split_groups(a, shapes) for a in (q, k, v)), groups, masks_w if sliding else masks_f)):
                pk, pv, ltab = address_paged_pool_by_layer(window_pool if sliding else full_pool, group[3 if sliding else 1], number)
                if i == 0 and kernel[sliding]:
                    k_store, v_store = k_g.astype(pk.dtype), v_g.astype(pv.dtype)
                    attn.append(_attend_in_place(q_g, k_store, v_store, pk, pv, ltab, group[2], c, sliding, interpret))
                    stored.append((k_store, v_store))
                    continue
                with jax.named_scope("kv_pool"), jax.named_scope("kv_pool.window") if sliding else contextlib.nullcontext():
                    if sliding:
                        k_store, k_ctx = paged_window_write(pk, k_g, ltab, group[2])
                        v_store, v_ctx = paged_window_write(pv, v_g, ltab, group[2])
                    else:
                        k_store, k_ctx = paged_cache_write(pk, k_g, ltab, group[2], c.dtype)
                        v_store, v_ctx = paged_cache_write(pv, v_g, ltab, group[2], c.dtype)
                attn.append(_attend(q_g, k_ctx, v_ctx, mask, c, window=sliding))
                stored.append((k_store, v_store))
            return join_groups(attn), tuple(stored)
        return run

    def body(x, lp, is_sliding, number, held, mixed):
        with jax.named_scope("attn"):
            h = _llama._rms_norm(x, lp["ln_in"], c.rms_eps)
            q, k, v = _qkv(h, lp, c)
            if mixed:
                attn, stored = jax.lax.cond(is_sliding, attention(True), attention(False), q, k, v, number)
            else:
                attn, stored = attention(is_sliding)(q, k, v, number)
            x = x + _llama._rms_norm(_out_proj(attn, _gate(h, lp, c), lp, c), lp["ln_post_attn"], c.rms_eps)
        x, sizes = _ffn(x, lp, c, held)
        return x, (stored, sizes)

    # the pool is a constant of the loops, addressed by the layer's number among its kind in their bodies
    with jax.named_scope("layers"):
        x, stored, group_sizes = _scan_stacks(params, c, body, x, hold_experts=True)
    rows = []
    for k_rows, v_rows in stored:  # a group's [num_layers, B, T, K, hd], cut by kind and laid out as the scatter writes
        written = {}
        for kind, k_kind, v_kind in zip((FULL, SLIDING), _by_kind(c, k_rows), _by_kind(c, v_rows)):
            if not c.count(kind):
                continue
            leaves = {"k": jnp.moveaxis(k_kind, 0, 1), "v": jnp.moveaxis(v_kind, 0, 1)}
            if kind == FULL:
                written.update(leaves)
            else:
                written[WINDOW] = leaves
        rows.append(written)
    with jax.named_scope("head"):
        logits = _head(params, x, c)
    counters = {}
    if group_sizes is not None:
        pairs = x.size // c.hidden_size * c.num_experts_per_tok
        row_tile = expert_row_tile(  # which grouped product this dispatch's expert layers ran, over the held experts
            -(-pairs * c.held[1] // c.num_experts), c.held[1], c.hidden_size, c.moe_intermediate_size, c.dtype)
        counters = expert_counters(group_sizes, row_tile, pairs_routed=pairs * (c.num_layers - c.num_dense_layers))
    if window_pool:
        holds = (groups[0][2] > 0)[:, None, None]
        counters["window_rows_read"] = c.count(SLIDING) * jnp.sum(masks_w[0] & holds, dtype=jnp.int32)
        counters["context_rows"] = c.count(SLIDING) * jnp.sum(masks_f[0] & holds, dtype=jnp.int32)
    counters["attn_rows_read"] = sum(
        (c.count(kind) * rows_read_in_place(groups[0][2], block_size, c.sliding_window if kind == SLIDING else 0)
         for kind, sliding in ((FULL, False), (SLIDING, True)) if kernel[sliding]), jnp.zeros((), jnp.int32))
    return split_groups(logits, shapes), tuple(rows), counters


def generate(
    params: dict,
    input_ids: jax.Array,
    config: AfmoeConfig,
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
