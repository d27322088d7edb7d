"""Pallas TPU flash attention — hand-written MXU kernels (fwd + bwd).

The blockwise ``ops/flash_attention.py`` path expresses the online-softmax
recurrence through XLA (``lax.scan`` + remat); this module is the hardware
kernel behind the same math: one fused ``pallas_call`` per pass keeps the
query tile, running max/denominator and output accumulator in VMEM while K/V
tiles stream in, so the [S, S] score matrix never touches HBM in either
direction.  Backward uses the standard flash-attention decomposition
(saved logsumexp + delta = rowsum(dO*O)) with two kernels: dq accumulates over
K/V tiles, dk/dv accumulate over Q tiles.

The reference framework has no attention kernels at all (it delegates compute
to torch engines; SURVEY.md §2.4 — CP/ring/blockwise "ABSENT from the
reference"), so this is net-new capability, per-tile layout chosen for the
MXU (128-aligned tiles, fp32 accumulation via ``preferred_element_type``).

GQA is handled without materializing expanded K/V: the kernel grid runs over
Q heads and the K/V BlockSpec index maps divide by the group size; backward
produces per-Q-head dK/dV which are group-summed outside the kernel.

Partitioning note: ``pallas_call`` does not participate in GSPMD automatic
partitioning, so on a mesh the kernel always runs under ``shard_map``:

- non-sp meshes: :func:`pallas_attention_spmd` — batch over the data axes,
  heads over ``tp``, each device runs the fused kernel on its own shard;
- sp meshes: :func:`ring_attention_pallas` — the Pallas kernel is the
  per-block compute inside the ``ppermute`` ring (online-softmax combine of
  per-block (out, lse) pairs; backward ring rotates dK/dV accumulators home
  with their chunks), composing sequence parallelism with the fused kernel;
- ulysses: ``ulysses_attention(..., impl="pallas")`` runs this kernel as the
  per-device full-sequence attention between the two all-to-alls.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "pallas_attention",
    "pallas_attention_spmd",
    "ring_attention_pallas",
]

_NEG_INF = -1e30  # finite: avoids inf-inf NaNs inside the exp bookkeeping


def _vmem_spec(block_shape, index_map):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _compiler_params():
    """batch/head/outer-tile grid dims are parallel (lets Mosaic split them
    across the two TensorCores on megacore chips); only the innermost
    accumulation dim is sequential."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
    )


def _causal_mask(s, iq, ik, blk_q, blk_k, rows_are_k=False):
    """Mask score tile ``s`` ([blk_q, blk_k] or transposed) below the diagonal."""
    if rows_are_k:
        k_pos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        q_pos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    else:
        q_pos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _valid_row(kv_valid):
    """[B, S] key validity as [B, 1, S]: Mosaic tiles the last two block dims
    (8, 128), so a ``(1, blk_k)`` block over a 2-D ``[B, S]`` operand is
    refused at lowering (B > 1) or aborts the compiler (B == 1); with the unit
    dim second-to-last the block spans that whole dim and the lane dim stays
    128-aligned.  The dK/dV kernel, whose score rows are keys, takes the
    column form ``[B, S, 1]`` instead — the layouts lse/delta already use."""
    return kv_valid[:, None, :]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs,
                scale, blk_q, blk_k, causal, nk, has_valid=False):
    if has_valid:
        q_ref, k_ref, v_ref, valid_ref, o_ref, lse_ref, acc, m_scr, l_scr = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr), valid_ref = refs, None
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    def compute():
        q = q_ref[0, 0]  # [blk_q, d]
        k = k_ref[0, 0]  # [blk_k, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [blk_q, blk_k]
        if causal:
            s = _causal_mask(s, iq, ik, blk_q, blk_k)
        if valid_ref is not None:
            s = jnp.where(valid_ref[0] != 0, s, _NEG_INF)  # [1, blk_k] key validity

        m_prev = m_scr[:, :1]  # [blk_q, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [blk_q, blk_k] f32
        if valid_ref is not None:
            # A fully-masked row (every key invalid OR causally excluded —
            # left padding creates them) has m_new = -1e30, so every masked
            # entry sees exp(-1e30 - -1e30) = 1.  Gate on the masked score
            # itself: it covers validity AND causal exclusion jointly, so
            # empty rows keep l = 0 and output zeros like the einsum paths.
            p = jnp.where(s > _NEG_INF * 0.5, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)  # [blk_q, 1]
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc[:] = acc[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # Skip K/V tiles entirely above the causal diagonal.
        pl.when(ik * blk_k <= iq * blk_q + blk_q - 1)(compute)
    else:
        compute()

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(l)


def _flash_fwd(q, k, v, *, scale, causal, blk_q, blk_k, interpret, kv_valid=None):
    """q: [B, H, S, d]; k, v: [B, K, S, d]; optional kv_valid [B, S] (int32
    key validity).  Returns (out [B,H,S,d], lse [B,H,S])."""
    b, h, s, d = q.shape
    kh = k.shape[1]
    g = h // kh
    nq = s // blk_q
    nk = s // blk_k

    has_valid = kv_valid is not None
    kernel = functools.partial(
        _fwd_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k, causal=causal, nk=nk,
        has_valid=has_valid,
    )
    operands = [q, k, v] + ([_valid_row(kv_valid)] if has_valid else [])
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(b, h, nq, nk),
        in_specs=[
            _vmem_spec((1, 1, blk_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            _vmem_spec((1, 1, blk_k, d), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
            _vmem_spec((1, 1, blk_k, d), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
        ] + ([_vmem_spec((1, 1, blk_k), lambda ib, ih, iq, ik: (ib, 0, ik))] if has_valid else []),
        out_specs=[
            _vmem_spec((1, 1, blk_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            _vmem_spec((1, 1, blk_q, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, d), jnp.float32),
            pltpu.VMEM((blk_q, 128), jnp.float32),
            pltpu.VMEM((blk_q, 128), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*operands)
    return out, lse.reshape(b, h, s)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(*refs, scale, blk_q, blk_k, causal, nk, has_valid=False):
    if has_valid:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, valid_ref, dq_ref, dq_acc = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc), valid_ref = refs, None
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]    # [blk_q, 1]
        delta = delta_ref[0, 0]  # [blk_q, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = _causal_mask(s, iq, ik, blk_q, blk_k)
        if valid_ref is not None:
            s = jnp.where(valid_ref[0] != 0, s, _NEG_INF)  # [1, blk_k]
        p = jnp.exp(s - lse)  # [blk_q, blk_k]
        if valid_ref is not None:
            # Empty (fully-masked) rows carry lse ~ -1e30, so exp(s - lse)
            # explodes at their masked entries — gate on the masked score.
            p = jnp.where(s > _NEG_INF * 0.5, p, 0.0)
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(ik * blk_k <= iq * blk_q + blk_q - 1)(compute)
    else:
        compute()

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, blk_q, blk_k, causal, nq, has_valid=False):
    if has_valid:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, valid_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        valid_ref = None
    iq = pl.program_id(3)
    ik = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]    # [1, blk_q]
        delta = delta_ref[0, 0]  # [1, blk_q]

        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [blk_k, blk_q]
        if causal:
            st = _causal_mask(st, iq, ik, blk_q, blk_k, rows_are_k=True)
        if valid_ref is not None:
            # rows are K here: mask invalid KEY rows (their dk/dv stay 0).
            st = jnp.where(valid_ref[0] != 0, st, _NEG_INF)  # [blk_k, 1]
        pt = jnp.exp(st - lse)  # [blk_k, blk_q]
        if valid_ref is not None:
            # Same empty-row lse guard as the dq kernel, transposed.
            pt = jnp.where(st > _NEG_INF * 0.5, pt, 0.0)
        dv_acc[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            v.astype(jnp.float32), do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [blk_k, blk_q]
        dst = pt * (dpt - delta) * scale
        dk_acc[:] += jax.lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(ik * blk_k <= iq * blk_q + blk_q - 1)(compute)
    else:
        compute()

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, *, scale, causal, blk_q, blk_k, interpret,
               kv_valid=None):
    b, h, s, d = q.shape
    kh = k.shape[1]
    g = h // kh
    nq = s // blk_q
    nk = s // blk_k
    has_valid = kv_valid is not None

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse_col = lse.reshape(b, h, s, 1)
    delta_col = delta.reshape(b, h, s, 1)
    lse_row = lse.reshape(b, h, 1, s)
    delta_row = delta.reshape(b, h, 1, s)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k, causal=causal, nk=nk,
        has_valid=has_valid,
    )
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        grid=(b, h, nq, nk),
        in_specs=[
            _vmem_spec((1, 1, blk_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            _vmem_spec((1, 1, blk_k, d), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
            _vmem_spec((1, 1, blk_k, d), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
            _vmem_spec((1, 1, blk_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            _vmem_spec((1, 1, blk_q, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            _vmem_spec((1, 1, blk_q, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ] + ([_vmem_spec((1, 1, blk_k), lambda ib, ih, iq, ik: (ib, 0, ik))] if has_valid else []),
        out_specs=_vmem_spec((1, 1, blk_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*([q, k, v, do, lse_col, delta_col] + ([_valid_row(kv_valid)] if has_valid else [])))

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k, causal=causal, nq=nq,
        has_valid=has_valid,
    )
    # dK/dV computed per Q-head ([B, H, S, d]) then group-summed to K heads.
    dk_h, dv_h = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv",
        grid=(b, h, nk, nq),
        in_specs=[
            _vmem_spec((1, 1, blk_q, d), lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
            _vmem_spec((1, 1, blk_k, d), lambda ib, ih, ik, iq: (ib, ih // g, ik, 0)),
            _vmem_spec((1, 1, blk_k, d), lambda ib, ih, ik, iq: (ib, ih // g, ik, 0)),
            _vmem_spec((1, 1, blk_q, d), lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
            _vmem_spec((1, 1, 1, blk_q), lambda ib, ih, ik, iq: (ib, ih, 0, iq)),
            _vmem_spec((1, 1, 1, blk_q), lambda ib, ih, ik, iq: (ib, ih, 0, iq)),
        ] + ([_vmem_spec((1, blk_k, 1), lambda ib, ih, ik, iq: (ib, ik, 0))] if has_valid else []),
        out_specs=[
            _vmem_spec((1, 1, blk_k, d), lambda ib, ih, ik, iq: (ib, ih, ik, 0)),
            _vmem_spec((1, 1, blk_k, d), lambda ib, ih, ik, iq: (ib, ih, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, s, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, d), jnp.float32),
            pltpu.VMEM((blk_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*([q, k, v, do, lse_row, delta_row] + ([kv_valid[:, :, None]] if has_valid else [])))

    if g > 1:
        dk = dk_h.reshape(b, kh, g, s, d).sum(axis=2)
        dv = dv_h.reshape(b, kh, g, s, d).sum(axis=2)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# custom-vjp wrapper + public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _mha(q, k, v, kv_valid, scale, causal, blk_q, blk_k, interpret):
    out, _ = _flash_fwd(q, k, v, scale=scale, causal=causal, blk_q=blk_q,
                        blk_k=blk_k, interpret=interpret, kv_valid=kv_valid)
    return out


def _mha_fwd(q, k, v, kv_valid, scale, causal, blk_q, blk_k, interpret):
    out, lse = _flash_fwd(q, k, v, scale=scale, causal=causal, blk_q=blk_q,
                          blk_k=blk_k, interpret=interpret, kv_valid=kv_valid)
    return out, (q, k, v, kv_valid, out, lse)


def _mha_bwd(scale, causal, blk_q, blk_k, interpret, res, do):
    q, k, v, kv_valid, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, do, scale=scale, causal=causal,
                            blk_q=blk_q, blk_k=blk_k, interpret=interpret,
                            kv_valid=kv_valid)
    # kv_valid is integer-dtype: its cotangent is the symbolic float0 zero.
    d_valid = (
        None if kv_valid is None
        else np.zeros(kv_valid.shape, jax.dtypes.float0)
    )
    return dq, dk, dv, d_valid


_mha.defvjp(_mha_fwd, _mha_bwd)


def pallas_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_size: int = 512,
    interpret: Optional[bool] = None,
    kv_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """Fused flash attention on TPU via Pallas.

    Same contract as ``ops.flash_attention.flash_attention``: q ``[B, S, H, d]``,
    k/v ``[B, S, K, d]`` with ``H = K * groups``; causal GQA.  ``kv_valid``
    ``[B, S]`` (bool/int) masks padded KEYS per tile (round 5 — padded
    batches no longer need the scan fallback); fully-masked query rows
    output zeros, matching the einsum/ring paths.  ``interpret=None``
    auto-enables the Pallas interpreter off-TPU so the same tests run on the
    CPU mesh.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, h, d = q.shape
    kh = k.shape[2]
    if h % kh:
        raise ValueError(f"num q heads {h} not divisible by kv heads {kh}")
    blk = min(block_size, s)
    if s % blk:
        raise ValueError(f"seq len {s} must be divisible by block_size {blk}")

    qh = q.transpose(0, 2, 1, 3)  # [B, H, S, d]
    kk = k.transpose(0, 2, 1, 3)  # [B, K, S, d]
    vv = v.transpose(0, 2, 1, 3)
    scale = float(1.0 / np.sqrt(d))
    valid = None if kv_valid is None else kv_valid.astype(jnp.int32)
    out = _mha(qh, kk, vv, valid, scale, causal, blk, blk, interpret)
    return out.transpose(0, 2, 1, 3)


def pallas_attention_spmd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh=None,
    *,
    causal: bool = True,
    block_size: int = 512,
    interpret: Optional[bool] = None,
    kv_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """Pallas attention on a multi-device mesh.

    ``pallas_call`` is opaque to GSPMD, so the kernel is placed under
    ``shard_map``: batch stays sharded over the data axes and heads over
    ``tp`` (shared policy with ring/ulysses) — each device runs the fused
    kernel on its own shard with zero cross-device traffic (the sequence
    axis is NOT sharded here; use ring/ulysses for sp).  Falls back to the
    plain call when the mesh is trivial.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import data_axes
    from .ring_attention import shard_map, tp_head_axis

    if mesh is None:
        from ..state import AcceleratorState

        if AcceleratorState._shared_state:
            mesh = AcceleratorState().mesh
    if mesh is None:
        # Same mesh source the models' sharding constraints consult: a mesh
        # installed via jax.set_mesh without an AcceleratorState still routes
        # through shard_map instead of silently running GSPMD-opaque.
        from ..parallel.sharding import _abstract_mesh

        am = _abstract_mesh()
        if not am.empty:
            mesh = am
    if mesh is None or mesh.size == 1:
        return pallas_attention(
            q, k, v, causal=causal, block_size=block_size, interpret=interpret,
            kv_valid=kv_valid,
        )
    if "sp" in mesh.axis_names and mesh.shape["sp"] > 1:
        raise ValueError("pallas_attention_spmd does not shard the sequence axis; use ring/ulysses for sp>1")

    batch_axes = data_axes(mesh)
    head_axis = tp_head_axis(mesh, q.shape[2], k.shape[2])
    spec = P(batch_axes if batch_axes else None, None, head_axis, None)
    if kv_valid is None:  # hot path: no dummy operand threaded through

        def body(q, k, v):
            return pallas_attention(
                q, k, v, causal=causal, block_size=block_size, interpret=interpret
            )

        return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)

    valid_spec = P(batch_axes if batch_axes else None, None)

    def body(q, k, v, valid):
        return pallas_attention(
            q, k, v, causal=causal, block_size=block_size, interpret=interpret,
            kv_valid=valid,
        )

    return shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec, valid_spec), out_specs=spec
    )(q, k, v, kv_valid.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Pallas-in-ring: sequence parallelism with the fused kernel per block
# ---------------------------------------------------------------------------
#
# The ring loop is unrolled in Python (the axis size n is static), which keeps
# the Pallas kernels exactly as compiled for the single-device path:
#
# - step r == 0: the local K/V chunk sits at the same global offset as the
#   local queries, so the standard *causal* kernel applies;
# - step r >  0: after r upward rotations the held chunk is (idx - r) % n.
#   For equal chunks that is either entirely BEFORE the local queries
#   (idx >= r: full non-causal attention) or entirely after (idx < r: no
#   contribution) — so the *non-causal* kernel runs and a per-device gate
#   (idx >= r) decides whether its (out, lse) pair enters the combine.  The
#   gated-off devices still compute (same cost profile as the einsum ring,
#   and what keeps every hop a pure neighbor exchange).
#
# Forward combine is the associative flash merge of normalized outputs:
#   lse' = logaddexp(lse_a, lse_b);  out' = out_a·e^{lse_a-lse'} + out_b·e^{lse_b-lse'}.
#
# Backward is its own ring with the GLOBAL lse (saved from forward): per-block
# flash backward with the true softmax normalizer is exact, dQ accumulates
# locally, and the dK/dV accumulators ride the ring WITH their chunks so each
# chunk arrives home carrying its full gradient after n rotations.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_mha(q, k, v, axis_name, n, scale, causal, blk, interpret):
    out, _ = _ring_mha_fwd(q, k, v, axis_name, n, scale, causal, blk, interpret)
    return out


def _ring_perm(n):
    return [(i, (i + 1) % n) for i in range(n)]


def _ring_mha_fwd(q, k, v, axis_name, n, scale, causal, blk, interpret):
    """q: [B, H, Sq, d]; k, v: [B, K, Sq, d] — local chunks under shard_map."""
    idx = jax.lax.axis_index(axis_name)
    o_blk, lse_acc = _flash_fwd(
        q, k, v, scale=scale, causal=causal, blk_q=blk, blk_k=blk, interpret=interpret
    )
    out_acc = o_blk.astype(jnp.float32)
    k_r, v_r = k, v
    perm = _ring_perm(n)
    for r in range(1, n):
        k_r = jax.lax.ppermute(k_r, axis_name, perm)
        v_r = jax.lax.ppermute(v_r, axis_name, perm)
        o_blk, lse_blk = _flash_fwd(
            q, k_r, v_r, scale=scale, causal=False, blk_q=blk, blk_k=blk, interpret=interpret
        )
        if causal:
            # Contribution gate; lse starts finite (every row of the causal
            # step attends at least its own position), so the merge below
            # never sees a -inf minus -inf.
            lse_b = jnp.where(idx >= r, lse_blk, -jnp.inf)
        else:
            lse_b = lse_blk
        m = jnp.maximum(lse_acc, lse_b)
        lse_new = m + jnp.log(jnp.exp(lse_acc - m) + jnp.exp(lse_b - m))
        out_acc = (
            out_acc * jnp.exp(lse_acc - lse_new)[..., None]
            + o_blk.astype(jnp.float32) * jnp.exp(lse_b - lse_new)[..., None]
        )
        lse_acc = lse_new
    out = out_acc.astype(q.dtype)
    return out, (q, k, v, out, lse_acc)


def _ring_mha_bwd(axis_name, n, scale, causal, blk, interpret, res, do):
    q, k, v, out, lse = res
    idx = jax.lax.axis_index(axis_name)
    perm = _ring_perm(n)
    dq = jnp.zeros(q.shape, jnp.float32)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    k_r, v_r = k, v
    for r in range(n):
        if r:
            k_r = jax.lax.ppermute(k_r, axis_name, perm)
            v_r = jax.lax.ppermute(v_r, axis_name, perm)
            dk = jax.lax.ppermute(dk, axis_name, perm)
            dv = jax.lax.ppermute(dv, axis_name, perm)
        dq_b, dk_b, dv_b = _flash_bwd(
            q, k_r, v_r, out, lse, do,
            scale=scale, causal=(causal and r == 0), blk_q=blk, blk_k=blk,
            interpret=interpret,
        )
        if causal and r:
            gate = idx >= r
            dq_b = jnp.where(gate, dq_b.astype(jnp.float32), 0.0)
            dk_b = jnp.where(gate, dk_b.astype(jnp.float32), 0.0)
            dv_b = jnp.where(gate, dv_b.astype(jnp.float32), 0.0)
        dq = dq + dq_b.astype(jnp.float32)
        dk = dk + dk_b.astype(jnp.float32)
        dv = dv + dv_b.astype(jnp.float32)
    # n-1 rotations happened in the loop, so the accumulator at device idx
    # belongs to chunk (idx+1) % n — one final hop brings every chunk home.
    dk = jax.lax.ppermute(dk, axis_name, perm)
    dv = jax.lax.ppermute(dv, axis_name, perm)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_mha.defvjp(_ring_mha_fwd, _ring_mha_bwd)


def ring_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh=None,
    axis_name: str = "sp",
    *,
    causal: bool = True,
    block_size: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Sequence-parallel flash attention with the Pallas kernel per ring block.

    Same contract as ``ring_attention``: q ``[B, S, H, d]``, k/v
    ``[B, S, K, d]`` with S sharded over ``axis_name``; no padding-mask
    support (``kv_valid`` batches take the einsum ring).  Falls back to the
    plain fused kernel when the axis is absent/trivial.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import data_axes
    from .flash_attention import pick_block_pallas
    from .ring_attention import resolve_sp_mesh, shard_map, tp_head_axis

    mesh = resolve_sp_mesh(mesh, axis_name)
    if mesh is None:
        return pallas_attention(q, k, v, causal=causal, block_size=block_size, interpret=interpret)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    n = mesh.shape[axis_name]
    b, s, h, d = q.shape
    sq = s // n
    blk = pick_block_pallas(sq, head_dim=d)
    if blk is None:
        raise ValueError(
            f"ring_attention_pallas needs the per-device sequence chunk ({sq}) "
            "divisible by 64/128/256/512 (VMEM tiling)"
        )
    blk = min(blk, block_size)
    if sq % blk:
        # A caller-supplied block_size that does not divide the chunk would
        # silently truncate the kernel grid (nq = sq // blk) — refuse instead.
        raise ValueError(
            f"block_size {block_size} does not divide the per-device sequence "
            f"chunk {sq}"
        )
    scale = float(1.0 / np.sqrt(d))

    batch_axes = tuple(a for a in data_axes(mesh) if a != axis_name)
    head_axis = tp_head_axis(mesh, h, k.shape[2])
    spec = P(batch_axes if batch_axes else None, axis_name, head_axis, None)

    def body(q, k, v):
        qh = q.transpose(0, 2, 1, 3)
        kh = k.transpose(0, 2, 1, 3)
        vh = v.transpose(0, 2, 1, 3)
        out = _ring_mha(qh, kh, vh, axis_name, n, scale, causal, blk, interpret)
        return out.transpose(0, 2, 1, 3)

    return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)
