"""Mixture-of-Experts expert parallelism over the ``ep`` mesh axis.

Parity target: the reference only *passes MoE through* to DeepSpeed
(``utils/dataclasses.py:1399`` marks MoE blocks as ZeRO-3 leaves; SURVEY §2.4 EP
row: "No routing/dispatch code in-repo"), so routing + dispatch here is net-new
capability designed TPU-first:

- **Dense dispatch** (Switch-Transformer style): routing is expressed as two
  einsums against a ``[B, S, E, C]`` dispatch/combine tensor instead of gather/
  scatter — ragged token movement becomes dense matmuls the MXU executes at full
  tilt, and static shapes keep XLA happy (no data-dependent shapes under jit).
- **Capacity factor**: each expert processes at most ``C = ceil(S/E * k * cf)``
  tokens per batch row; overflow tokens are dropped (contribute zero, residual
  carries them — standard Switch semantics).
- **GSPMD expert sharding**: expert weights are ``[E, d, f]`` arrays sharded
  ``P("ep", ...)``; dispatched activations are constrained to put their expert
  dim on ``ep``, so XLA compiles the token all-to-all onto ICI automatically —
  the hand-written NCCL all-to-all the reference's engines (DeepSpeed-MoE) do
  by hand.
- Router in fp32 (softmax stability), compute in the model's dtype.

Aux losses follow the Switch/Mixtral recipe: load-balance loss (router prob mass
x token fraction per expert) and router z-loss.
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import constrain

__all__ = ["router", "dispatch_combine", "moe_ffn", "moe_ffn_ragged", "routed_experts", "swiglu", "expert_capacity"]


def expert_capacity(seq_len: int, num_experts: int, top_k: int, capacity_factor: float) -> int:
    """Tokens-per-expert budget for one routing group (= one batch row)."""
    return max(1, int(np.ceil(seq_len * top_k * capacity_factor / num_experts)))


def router(x: jax.Array, w_router: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Routing probabilities.  x: [B, S, d], w_router: [d, E] -> (probs, logits)
    both [B, S, E] in fp32."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), w_router.astype(jnp.float32))
    return jax.nn.softmax(logits, axis=-1), logits


def dispatch_combine(
    probs: jax.Array,
    top_k: int,
    capacity: int,
) -> tuple[jax.Array, jax.Array, dict[str, jax.Array]]:
    """Build dispatch/combine tensors from routing probabilities.

    probs: [B, S, E].  Returns (dispatch [B,S,E,C] bool-as-float, combine
    [B,S,E,C] fp32, aux dict).  Top-k gates are renormalized to sum to 1 per
    token (Mixtral convention).  Position within an expert's capacity buffer is
    assigned greedily in sequence order, one top-k slot at a time (slot 0 of
    every token beats slot 1 of any token — earlier-priority routing).
    """
    b, s, e = probs.shape
    gates, idx = jax.lax.top_k(probs, top_k)  # [B, S, k]
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((b, s, e, capacity), jnp.float32)
    combine = jnp.zeros((b, s, e, capacity), jnp.float32)
    count = jnp.zeros((b, e), jnp.float32)  # tokens already admitted per expert
    kept_gate_mass = jnp.zeros((), jnp.float32)
    for slot in range(top_k):  # top_k is a small static int — unrolled at trace
        onehot = jax.nn.one_hot(idx[..., slot], e, dtype=jnp.float32)  # [B, S, E]
        pos = jnp.cumsum(onehot, axis=1) - 1.0 + count[:, None, :]  # [B, S, E]
        keep = (pos < capacity).astype(jnp.float32) * onehot
        count = count + jnp.sum(keep, axis=1)
        pos_idx = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
        slot_dispatch = keep[..., None] * jax.nn.one_hot(pos_idx, capacity, dtype=jnp.float32)
        dispatch = dispatch + slot_dispatch
        combine = combine + gates[..., slot, None, None] * slot_dispatch
        kept_gate_mass = kept_gate_mass + jnp.sum(gates[..., slot] * jnp.sum(keep, axis=-1))

    total_gate = jnp.asarray(b * s, jnp.float32)
    aux = {
        # Gate mass lost to capacity overflow, in [0, 1].
        "fraction_dropped": 1.0 - kept_gate_mass / total_gate,
    }
    return dispatch, combine, aux


def load_balancing_loss(probs: jax.Array, dispatch: jax.Array) -> jax.Array:
    """Switch-Transformer load-balance loss: E * sum_e f_e * p_e, where f_e is the
    fraction of tokens dispatched to expert e and p_e the mean router prob."""
    e = probs.shape[-1]
    tokens_per_expert = jnp.sum(dispatch, axis=(1, 3))  # [B, E]
    f = tokens_per_expert / jnp.maximum(jnp.sum(tokens_per_expert, axis=-1, keepdims=True), 1.0)
    p = jnp.mean(probs, axis=1)  # [B, E]
    return e * jnp.mean(jnp.sum(f * p, axis=-1))


def router_z_loss(logits: jax.Array) -> jax.Array:
    """Penalizes large router logits (numerics guard, ST-MoE recipe)."""
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)


def moe_ffn(
    x: jax.Array,
    w_router: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    capacity: Optional[int] = None,
    compute_dtype: Any = jnp.bfloat16,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """SwiGLU expert FFN with top-k routing.

    x: [B, S, d]; w_router: [d, E]; w_gate/w_up: [E, d, f]; w_down: [E, f, d].
    Returns (y [B, S, d] in x.dtype, aux losses dict).

    The expert dimension of the dispatched activations is sharding-constrained to
    the ``ep`` mesh axis: with tokens sharded on data axes and expert weights on
    ``ep``, XLA lowers the two dispatch einsums to the token all-to-all + grouped
    matmul pipeline.
    """
    b, s, d = x.shape
    e = w_gate.shape[0]
    if capacity is None:
        capacity = expert_capacity(s, e, top_k, capacity_factor)

    probs, logits = router(x, w_router)
    dispatch, combine, aux = dispatch_combine(probs, top_k, capacity)

    xe = jnp.einsum("bsec,bsd->becd", dispatch.astype(compute_dtype), x.astype(compute_dtype))
    xe = constrain(xe, P(("dcn_dp", "dp", "fsdp"), "ep", None, None))
    gate = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, w_gate.astype(compute_dtype)))
    up = jnp.einsum("becd,edf->becf", xe, w_up.astype(compute_dtype))
    ye = jnp.einsum("becf,efd->becd", gate * up, w_down.astype(compute_dtype))
    ye = constrain(ye, P(("dcn_dp", "dp", "fsdp"), "ep", None, None))
    y = jnp.einsum("bsec,becd->bsd", combine.astype(compute_dtype), ye)

    aux = dict(aux)
    aux["load_balancing_loss"] = load_balancing_loss(probs, dispatch)
    aux["router_z_loss"] = router_z_loss(logits)
    return y.astype(x.dtype), aux


def routed_experts(
    x: jax.Array,
    w_router: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    top_k: int,
    scoring: str = "softmax",
    select_bias: Optional[jax.Array] = None,
    normalize: bool = True,
    normalize_eps: float = 1e-20,
    scale: float = 1.0,
    first_expert: Any = 0,
    compute_dtype: Any = jnp.bfloat16,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Dropless routed SwiGLU experts: every row goes to its ``top_k`` experts,
    whatever else is in the batch.

    x: [..., d]; w_router: [d, E]; w_gate/w_up: [G, d, f]; w_down: [G, f, d],
    with ``G >= E``: the router's experts are rows ``first_expert ..
    first_expert + E`` of the weights (``G == E`` and 0 for one layer's own
    experts; a layer loop hands over all its layers' experts merged, ``[L*E, d,
    f]``, and ``layer * E``, so that the grouped product reads the layer's
    experts where the stack lies: cut out per layer they would be copied whole,
    1.2 GB a layer at 128 experts of 2048 x 768, before a row is multiplied).
    Scores are ``softmax`` or ``sigmoid`` of the fp32 router logits; the experts
    are the ``top_k`` of ``scores + select_bias`` (the bias only chooses), the
    weights are the chosen experts' scores, divided by (their sum +
    ``normalize_eps``) when ``normalize`` and multiplied by ``scale``.  Rows are sorted by expert and
    each expert's rows run as one group of ``lax.ragged_dot`` (on a TPU a
    grouped-matmul kernel that streams only the experts that have rows):
    compute is exactly ``rows * top_k`` pairs, no capacity, no drops, and a
    row's result does not depend on the other rows.  Group sizes depend on
    the data, so this runs per device (replicated experts); the ``ep``-sharded
    path is ``moe_ffn``.

    Returns (y [..., d] in x.dtype, routing dict: ``scores`` and ``logits``
    [..., E] fp32, ``experts`` [..., top_k], ``weights`` [..., top_k] fp32,
    ``group_sizes`` [E] int32 = rows each expert computed).
    """
    lead, d = x.shape[:-1], x.shape[-1]
    e = w_router.shape[-1]
    tokens = x.reshape(-1, d)
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("nd,de->ne", tokens.astype(jnp.float32), w_router.astype(jnp.float32))
        if scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        elif scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f"scoring must be 'softmax' or 'sigmoid', got {scoring!r}")
        choice = scores if select_bias is None else scores + select_bias.astype(jnp.float32)
        _, idx = jax.lax.top_k(choice, top_k)  # [N, k]
        weights = jnp.take_along_axis(scores, idx, axis=-1)
        if normalize:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + normalize_eps)
        weights = weights * scale

        n = tokens.shape[0] * top_k
        expert_of = idx.reshape(n)
        token_of = jnp.repeat(jnp.arange(tokens.shape[0]), top_k)
        order = jnp.argsort(expert_of, stable=True)
        group_sizes = jnp.bincount(expert_of, length=e).astype(jnp.int32)

    with jax.named_scope("moe.experts"):
        rows = tokens.astype(compute_dtype)[token_of[order]]  # [N*k, d] grouped by expert
        groups = group_sizes
        if w_gate.shape[0] != e:  # the other layers' experts are groups of no rows
            groups = jax.lax.dynamic_update_slice(jnp.zeros((w_gate.shape[0],), jnp.int32), group_sizes, (first_expert,))
        gate = jax.nn.silu(jax.lax.ragged_dot(rows, w_gate.astype(compute_dtype), groups))
        up = jax.lax.ragged_dot(rows, w_up.astype(compute_dtype), groups)
        y_rows = jax.lax.ragged_dot(gate * up, w_down.astype(compute_dtype), groups)
        weighted = y_rows.astype(jnp.float32) * weights.reshape(n)[order][:, None]
        y = jnp.zeros(tokens.shape, jnp.float32).at[token_of[order]].add(weighted)

    routing = {
        "scores": scores.reshape(*lead, e), "logits": logits.reshape(*lead, e),
        "experts": idx.reshape(*lead, top_k), "weights": weights.reshape(*lead, top_k),
        "group_sizes": group_sizes,
    }
    return y.reshape(x.shape).astype(x.dtype), routing


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array, compute_dtype: Any) -> jax.Array:
    """One dense SwiGLU expert, [..., d] -> [..., d]: the shared expert every
    row passes through beside its routed ones."""
    h = x.astype(compute_dtype)
    return (jax.nn.silu(h @ w_gate.astype(compute_dtype)) * (h @ w_up.astype(compute_dtype))) @ w_down.astype(compute_dtype)


def moe_ffn_ragged(
    x: jax.Array,
    w_router: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    top_k: int = 2,
    compute_dtype: Any = jnp.bfloat16,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Mixtral's routing (softmax scores, top-k renormalised to one) over
    :func:`routed_experts`, with the Switch aux losses ``moe_ffn`` returns:
    the exact computation the dense dispatch approximates, no capacity
    padding and no token dropped (``fraction_dropped`` is identically zero).
    Same signature/return contract as ``moe_ffn`` minus the capacity knobs."""
    e = w_gate.shape[0]
    y, routing = routed_experts(
        x, w_router, w_gate, w_up, w_down, top_k=top_k, scoring="softmax", compute_dtype=compute_dtype,
    )
    # Every routed token is kept, so the dispatch mass is the one-hot top-k
    # assignment itself (per batch row, like load_balancing_loss).
    probs = routing["scores"]
    onehot = jax.nn.one_hot(routing["experts"], e, dtype=jnp.float32).sum(axis=2)  # [B, S, E]
    tokens_per_expert = jnp.sum(onehot, axis=1)  # [B, E]
    f = tokens_per_expert / jnp.maximum(
        jnp.sum(tokens_per_expert, axis=-1, keepdims=True), 1.0
    )
    p = jnp.mean(probs, axis=1)
    aux = {
        "load_balancing_loss": e * jnp.mean(jnp.sum(f * p, axis=-1)),
        "router_z_loss": router_z_loss(routing["logits"]),
        "fraction_dropped": jnp.zeros((), jnp.float32),
    }
    return y, aux
