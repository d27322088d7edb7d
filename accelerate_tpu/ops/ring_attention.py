"""Ring attention — sequence/context parallelism over the ``sp`` mesh axis.

Net-new capability vs the reference (SURVEY §2.4: context parallelism is ABSENT
upstream; only a Megatron passthrough flag exists).  Design follows the blockwise
ring-attention pattern (Liu et al.; see PAPERS.md): the sequence dimension is
sharded across devices; K/V blocks rotate around the ring via ``lax.ppermute``
(riding ICI neighbor links) while each device keeps a numerically-stable online
softmax accumulator (flash-attention style m/l/o state).  Compute for block r
overlaps with the transfer of block r+1 as scheduled by XLA.

Causal masking at block granularity: a device at ring position i only attends to
K/V chunks j <= i — chunks j > i contribute nothing but still ride the ring so
every hop is a pure neighbor exchange.

Round-1 implementation is pure-JAX inside ``shard_map`` (XLA already overlaps
ppermute with the block matmuls); the Pallas fused kernel drops into
``_block_attention`` later for VMEM-resident streaming.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

__all__ = [
    "ring_attention",
    "ring_self_attention",
    "full_sequence_attention",
    "resolve_sp_mesh",
    "tp_head_axis",
]


def resolve_sp_mesh(mesh: Optional[Mesh], axis_name: str) -> Optional[Mesh]:
    """Shared mesh resolution for the sp backends: fall back to the installed
    AcceleratorState mesh; None when the axis is absent/trivial (caller runs
    the dense path)."""
    if mesh is None:
        from ..state import AcceleratorState

        if AcceleratorState._shared_state:
            mesh = AcceleratorState().mesh
    if mesh is None or axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        return None
    return mesh


def tp_head_axis(mesh: Mesh, num_heads: int, num_kv_heads: int, extra_div: int = 1) -> Optional[str]:
    """Shared tp head-sharding policy: shard heads over tp when divisible (and,
    for ulysses, when the per-tp head count still divides by the sp axis)."""
    tp = mesh.shape.get("tp", 1)
    if (
        tp > 1
        and num_heads % tp == 0
        and num_kv_heads % tp == 0
        and (num_heads // tp) % extra_div == 0
    ):
        return "tp"
    return None

def shard_map(f, mesh, in_specs, out_specs):
    # Varying-axes checking is off: the bodies contain ops opaque to the
    # checker (pallas_call outputs carry no vma annotation).
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def _block_attention(q, k, v, mask, m_prev, l_prev, o_prev, scale):
    """One K/V block against local Q with online-softmax accumulation.

    q: [B, Sq, H, d]; k,v: [B, Sk, K, d] (GQA: H = K * groups); accumulators
    m,l: [B, H, Sq], o: [B, Sq, H, d].  All statistics in fp32.
    """
    b, sq, h, d = q.shape
    kheads = k.shape[2]
    groups = h // kheads
    qg = q.reshape(b, sq, kheads, groups, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    scores = scores.reshape(b, h, sq, -1)
    scores = jnp.where(mask, scores, -jnp.inf)

    m_cur = jnp.max(scores, axis=-1)  # [B, H, Sq]
    m_new = jnp.maximum(m_prev, m_cur)
    # Guard fully-masked rows (m_new = -inf) against NaN.
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_new = alpha * l_prev + p.sum(axis=-1)
    pk = p.reshape(b, kheads, groups, sq, -1)
    o_blk = jnp.einsum("bkgst,btkd->bskgd", pk.astype(v.dtype), v).reshape(b, sq, h, d)
    o_new = o_prev * alpha.transpose(0, 2, 1)[..., None] + o_blk.astype(jnp.float32)
    return m_new, l_new, o_new


def full_sequence_attention(q, k, v, causal: bool = True, kv_valid=None, impl=None) -> jax.Array:
    """Full-sequence attention on local data — the shared non-ring path: flash
    (blockwise) when an MXU-friendly block divides S, otherwise one dense block
    through the same online-softmax math.  Used as the sp=1 fallback here and
    as the per-device local attention inside ulysses_attention.

    ``kv_valid`` [B, S] (bool) marks valid keys for padded batches.
    ``impl="pallas"`` runs the fused Pallas kernel instead (legal here even
    under shard_map — the call is per-device), including padded batches
    (the kernel masks keys per tile, round 5); non-tileable sequence
    lengths fall back to the flash/dense path below."""
    b, s, h, d = q.shape
    from .flash_attention import flash_attention, pick_block

    if impl == "pallas":
        from .flash_attention import pick_block_pallas
        from .pallas_attention import pallas_attention

        blk = pick_block_pallas(s, head_dim=d)
        if blk is not None:
            return pallas_attention(
                q, k, v, causal=causal, block_size=blk, kv_valid=kv_valid
            )

    blk = pick_block(s)
    if blk is not None and s > blk:
        return flash_attention(q, k, v, causal=causal, block_size=blk, kv_valid=kv_valid)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))[None, None]
    else:
        mask = jnp.ones((1, 1, s, s), bool)
    if kv_valid is not None:
        mask = mask & kv_valid.astype(bool)[:, None, None, :]
    m0 = jnp.full((b, h, s), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    o0 = jnp.zeros((b, s, h, d), jnp.float32)
    _, l, o = _block_attention(q, k, v, mask, m0, l0, o0, 1.0 / np.sqrt(d))
    return (o / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]).astype(q.dtype)


def _ring_body(
    q, k, v, kv_valid, *, axis_name: str, causal: bool, has_valid: bool, vary_axes: tuple = ()
):
    """Per-device body under shard_map: local q stays, k/v (and their validity
    chunk, for padded batches) rotate ``n`` times."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    scale = 1.0 / np.sqrt(d)

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, sq, h, d), jnp.float32)
    # Mark accumulators device-varying over the ring axis so the fori_loop carry
    # type stays consistent (shard_map VMA rules).
    axes = tuple(vary_axes) or (axis_name,)
    m0, l0, o0 = (jax.lax.pcast(x, axes, to="varying") for x in (m0, l0, o0))

    local_pos = jnp.arange(sq)

    def step(r, carry):
        k_r, v_r, valid_r, m, l, o = carry
        src = (idx - r) % n  # ring position whose K/V we currently hold
        if causal:
            # Block-level causality + intra-block triangle when src == idx.
            q_pos = idx * sq + local_pos  # global positions of local queries
            k_pos = src * k_r.shape[1] + jnp.arange(k_r.shape[1])
            mask = (q_pos[:, None] >= k_pos[None, :])[None, None, :, :]
        else:
            mask = jnp.ones((1, 1, sq, k_r.shape[1]), bool)
        if has_valid:
            mask = mask & valid_r[:, None, None, :]
        m, l, o = _block_attention(q, k_r, v_r, mask, m, l, o, scale)
        # Rotate upward: device i sends to i+1 and receives i-1's block, so after
        # r hops we hold chunk (i - r) % n — matching `src` above.
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_next = jax.lax.ppermute(k_r, axis_name, perm)
        v_next = jax.lax.ppermute(v_r, axis_name, perm)
        valid_next = jax.lax.ppermute(valid_r, axis_name, perm) if has_valid else valid_r
        return k_next, v_next, valid_next, m, l, o

    _, _, _, m, l, o = jax.lax.fori_loop(0, n, step, (k, v, kv_valid, m0, l0, o0))
    l_safe = jnp.maximum(l, 1e-20)
    out = o / l_safe.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Optional[Mesh] = None,
    axis_name: str = "sp",
    causal: bool = True,
    kv_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """Sequence-parallel attention: [B, S, H, d] x [B, S, K, d] -> [B, S, H, d]
    with S sharded over ``axis_name``.

    ``kv_valid`` [B, S] (bool, sequence-sharded like K/V) marks valid keys for
    padded batches; the validity chunk rides the ring alongside its K/V block,
    so masking stays O(S/n) per device (never a global [S, S] mask).
    Falls back to a single dense block when the axis is size 1 / absent.
    """
    mesh = resolve_sp_mesh(mesh, axis_name)
    if mesh is None:
        return full_sequence_attention(q, k, v, causal=causal, kv_valid=kv_valid)

    # Keep the batch dim sharded over the data axes inside the ring (avoids a
    # batch all-gather at the shard_map boundary), and the head dim over tp when
    # divisible — heads are independent in the ring body, so tp devices each run
    # their own head shard instead of redundantly computing all heads.
    from ..parallel.mesh import data_axes

    batch_axes = tuple(a for a in data_axes(mesh) if a != axis_name)
    head_axis = tp_head_axis(mesh, q.shape[2], k.shape[2])
    vary = batch_axes + (axis_name,) + ((head_axis,) if head_axis else ())
    spec = P(batch_axes if batch_axes else None, axis_name, head_axis, None)
    has_valid = kv_valid is not None
    if has_valid:
        kv_valid = kv_valid.astype(bool)
    else:
        # Dummy operand keeping one shard_map signature for both modes (dead
        # code under has_valid=False; XLA drops it).
        kv_valid = jnp.ones(q.shape[:2], bool)
    valid_spec = P(batch_axes if batch_axes else None, axis_name)
    body = functools.partial(
        _ring_body, axis_name=axis_name, causal=causal, has_valid=has_valid, vary_axes=vary
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec, valid_spec),
        out_specs=spec,
    )(q, k, v, kv_valid)


def ring_self_attention(x_q, x_k, x_v, **kwargs):
    """Convenience wrapper matching a fused-QKV call pattern."""
    return ring_attention(x_q, x_k, x_v, **kwargs)
