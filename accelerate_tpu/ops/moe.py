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

import functools
import importlib
import sys
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import _abstract_mesh, constrain

__all__ = [
    "router", "dispatch_combine", "moe_ffn", "moe_ffn_ragged", "routed_experts", "swiglu", "expert_capacity",
    "expert_row_tile",
]


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


# Up to how many rows an expert on average (token-expert pairs / experts) the fused kernel is taken, and its widest row
# tile.  From the probe of PR 35 (one TPU v5e, bf16, `routed_experts` alone over a merged stack, ms a layer, fused at a
# tile of 16 / at its best tile against `lax.ragged_dot`; PERF.md section 6): 128 experts of 2048 x 768 top-8 at 8, 16,
# 32, 64, 128, 256 rows an expert 1.61 / 3.66, 2.02 / 4.36, 2.64 (2.42 at 32) / 5.03, 3.94 (3.45 at 64) / 6.05, 6.14
# (5.25 at 32) / 7.98, 12.68 (10.90 at 64) / 12.59; 32 experts of 2048 x 1792 top-4 at 4, 16, 32, 64, 128 rows 1.07 /
# 1.67, 1.22 / 2.74, 1.46 (1.23 at 64) / 2.87, 1.85 (1.44 at 64) / 3.15, 2.84 (2.05 at 64) / 3.82.  The kernel wins by a
# fifth and more up to 128 rows an expert at both widths and ties at 256: 128 is the last point measured on its side.
FUSED_MAX_MEAN_ROWS = 128
FUSED_MAX_ROW_TILE = 64


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ``jax._src.pallas.pallas_call`` imports the Mosaic *GPU* interpreter if it is there (``try: ... except ImportError``):
# two thirds of what importing Pallas costs a process (0.9 of 1.4 s of a serving cell's set-up on a v5e host, where nothing
# is compiled to bytecode; PERF.md section 6, PR 35).
_GPU_INTERPRETER = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"


def pallas_module(name: str):
    """``ops/<name>.py``, a Pallas kernel's module, imported where the kernel is
    first asked for (as ``models/llama.py`` imports the flash kernel): only a
    TPU program that takes the kernel needs Pallas.  A process whose backend is
    a TPU has no use for the Mosaic GPU interpreter: if nobody has imported
    Pallas yet, that one import is told the module is absent, and the entry is
    taken back at once."""
    skip = _on_tpu() and "jax._src.pallas.pallas_call" not in sys.modules and _GPU_INTERPRETER not in sys.modules
    if skip:
        sys.modules[_GPU_INTERPRETER] = None
    try:
        return importlib.import_module(f"{__package__}.{name}")
    finally:
        if skip:
            del sys.modules[_GPU_INTERPRETER]


def expert_row_tile(pairs: int, experts: int, d: int, f: int, dtype: Any) -> int:
    """Which grouped product :func:`routed_experts` runs for ``pairs``
    token-expert pairs over ``experts`` experts of ``d x f``: the row tile of
    the fused Pallas kernel (``ops/pallas_moe.py``), or 0 for
    ``lax.ragged_dot``.  From static facts alone, no option anywhere: the
    kernel where a TPU runs the program on one device, the pairs average at
    most ``FUSED_MAX_MEAN_ROWS`` an expert and a weight tile fits its VMEM
    budget; its row tile is 16 (``pallas_moe.ROW_TILE``) up to 16 rows an
    expert on average, 32 up to 32, 64 beyond.  Off the TPU ``lax.ragged_dot``
    stays (the kernel would run in the Pallas interpreter); so it does under a
    mesh of more than one device (``pallas_call`` takes no part in GSPMD's
    partitioning and the group sizes depend on the data: a sharded caller would
    need a ``shard_map`` of its own) and at many rows an expert, where the
    kernel's small row tiles no longer win.  Both products compute every pair:
    no capacity, no drops."""
    mesh = _abstract_mesh()
    if not _on_tpu() or (not mesh.empty and mesh.size > 1) or pairs > FUSED_MAX_MEAN_ROWS * experts:
        return 0
    kernel = pallas_module("pallas_moe")
    if kernel.f_tile(d, f, jnp.dtype(dtype).itemsize) is None:
        return 0
    tm = kernel.ROW_TILE
    while tm < FUSED_MAX_ROW_TILE and tm * experts < pairs:
        tm *= 2
    return tm


def _ragged_swiglu(rows, w_gate, w_up, w_down, group_sizes, first_expert):
    """Every expert's SwiGLU over its rows as three ``lax.ragged_dot`` (on a
    TPU three Mosaic grouped matmuls that stream the experts with rows); the
    stack's other experts are groups of no rows."""
    groups = group_sizes
    if w_gate.shape[0] != group_sizes.shape[0]:
        groups = jax.lax.dynamic_update_slice(jnp.zeros((w_gate.shape[0],), jnp.int32), group_sizes, (first_expert,))
    gate = jax.nn.silu(jax.lax.ragged_dot(rows, w_gate, groups))
    up = jax.lax.ragged_dot(rows, w_up, groups)
    return jax.lax.ragged_dot(gate * up, w_down, groups)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _fused_swiglu(rows, w_gate, w_up, w_down, group_sizes, first_expert, tm):
    """The same product as one Pallas kernel; differentiated as
    :func:`_ragged_swiglu` is, so a gradient through it is that path's."""
    return pallas_module("pallas_moe").grouped_swiglu(rows, w_gate, w_up, w_down, group_sizes, first_expert, tm=tm)


def _fused_swiglu_fwd(rows, w_gate, w_up, w_down, group_sizes, first_expert, tm):
    out = _fused_swiglu(rows, w_gate, w_up, w_down, group_sizes, first_expert, tm)
    return out, (rows, w_gate, w_up, w_down, group_sizes, first_expert)


def _fused_swiglu_bwd(tm, residuals, g):
    *operands, group_sizes, first_expert = residuals
    _, vjp = jax.vjp(lambda *a: _ragged_swiglu(*a, group_sizes, first_expert), *operands)
    return (*vjp(g), None, None)


_fused_swiglu.defvjp(_fused_swiglu_fwd, _fused_swiglu_bwd)


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
    share: Optional[tuple[int, int]] = None,
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

    ``share`` = ``(first, count)``, two static integers: this device **holds a
    share** of the layer, experts ``first .. first + count`` of the router's
    ``E``, and the weights' rows ``first_expert .. first_expert + count`` are
    those experts (a merged stack is ``[L * count, d, f]`` and ``first_expert``
    ``layer * count``).  The router still scores all ``E`` and chooses the
    published ``top_k``; the pairs that fall on a held expert are computed,
    the others add nothing: the result is the **partial sum** over the held
    experts, what a chip of an expert-parallel group contributes before the
    exchange (no exchange here, and nothing that stands in for the absent
    chips).  Rows are sorted by expert, so the held experts' rows are one run
    of the sorted rows; they are brought to the front, the grouped product gets
    the held experts' ``group_sizes`` alone and computes no other row.  ``None``
    holds all ``E``: today's computation, operation for operation.
    Scores are ``softmax`` or ``sigmoid`` of the fp32 router logits; the experts
    are the ``top_k`` of ``scores + select_bias`` (the bias only chooses), the
    weights are the chosen experts' scores, divided by (their sum +
    ``normalize_eps``) when ``normalize`` and multiplied by ``scale``.  Rows are sorted by expert and
    each expert's rows run as one group of a grouped product that streams only
    the experts that have rows: the fused Pallas kernel of ``ops/pallas_moe.py``
    at a few rows an expert on one TPU device, ``lax.ragged_dot`` elsewhere
    (:func:`expert_row_tile` decides from shapes, backend and placement; no
    argument chooses).  Compute is exactly ``rows * top_k`` pairs, no capacity,
    no drops, and a row's result does not depend on the other rows.  Group
    sizes depend on the data, so this runs per device (replicated experts); the
    ``ep``-sharded path is ``moe_ffn``.

    Returns (y [..., d] in x.dtype, routing dict: ``scores`` and ``logits``
    [..., E] fp32, ``experts`` [..., top_k], ``weights`` [..., top_k] fp32,
    ``group_sizes`` int32 = rows each expert computed, ``[E]`` or under a share
    ``[count]``: the held experts', what was computed here).
    """
    lead, d = x.shape[:-1], x.shape[-1]
    e = w_router.shape[-1]
    first_held, held = (0, e) if share is None else (int(share[0]), int(share[1]))
    if not (0 <= first_held and 1 <= held and first_held + held <= e):
        raise ValueError(f"share {share!r} is not a run of the router's {e} experts")
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
        if held < e:  # the held experts first, in their order: the run of the sorted rows that is computed here
            expert_of = (expert_of - first_held) % e
        token_of = jnp.repeat(jnp.arange(tokens.shape[0]), top_k)
        order = jnp.argsort(expert_of, stable=True)
        group_sizes = jnp.bincount(expert_of, length=e).astype(jnp.int32)[:held]

    with jax.named_scope("moe.experts"):
        rows = tokens.astype(compute_dtype)[token_of[order]]  # [N*k, d] grouped by expert
        tm = expert_row_tile(-(-n * held // e), held, d, w_gate.shape[-1], compute_dtype)
        product = functools.partial(_fused_swiglu, tm=tm) if tm else _ragged_swiglu
        y_rows = product(
            rows, w_gate.astype(compute_dtype), w_up.astype(compute_dtype), w_down.astype(compute_dtype), group_sizes,
            jnp.asarray(first_expert, jnp.int32))
        weighted = y_rows.astype(jnp.float32) * weights.reshape(n)[order][:, None]
        if held < e:  # rows behind the held run belong to experts that lie elsewhere: neither product wrote them
            weighted = jnp.where((jnp.arange(n) < jnp.sum(group_sizes))[:, None], weighted, 0.0)
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
