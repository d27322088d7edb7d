"""Pallas TPU kernel for the decoding lanes' attention over the paged pool.

A decoding lane's keys lie in the blocks its table names.  The gathered path
(``generation.paged_cache_write`` and ``_attention``) copies every block of
every lane's table into a dense context ``[B, W*bs, K, hd]`` and attention reads
the copy back: at the widest table of a dispatch, whatever each lane holds, and
for a sliding layer the whole ring, whatever its mask admits.  Here it is ONE
``pallas_call`` (``paged_decode_attention``) that reads **each lane's own
blocks where they lie in the pool**, those its mask admits and no others:

- the pool leaf stays in HBM (``pl.ANY``) as :func:`address_paged_pool_by_layer`
  hands it over, ``[rows, bs, K, hd]`` viewed as ``[rows, bs*K, hd]`` (a free
  view where ``generation._blocks_lie_row_by_row`` holds); the table ``[B, W]``
  and each lane's admitted positions ``lo .. hi`` are scalar-prefetched;
- position ``p`` lies in table entry ``(p // bs) mod W``: a full layer's table
  never wraps (``lo`` 0), a sliding layer's ring does (``lo`` the window's first
  position), one formula for both;
- the grid runs over the lanes; inside, a ``fori_loop`` walks the lane's blocks
  ``lo // bs .. hi // bs`` a step of :func:`blocks_per_step` at a time, one DMA
  a block into a double buffer: the next step's copies (or the next lane's first
  step's) are in flight while this step is computed, a whole step is waited for
  at once, and nothing past ``hi`` is copied;
- every query head of the lane is computed in one step against every row of
  the step (a row is one position's one kv head), the scores masked to each
  query's kv head and to ``lo .. hi`` by position; scores, the online softmax
  and the accumulation are float32, the probabilities rounded to the pool's
  dtype once for the product with V, as the gathered path rounds them.

It returns the unnormalised output with its running max and sum: the current
token's own row is not in the pool yet (the engine scatters it after the
forward), and :func:`merge_own_row` folds it in with a log-sum-exp merge in
XLA.  Nothing here decides *whether* the kernel runs: ``generation.reads_in_place``
does, from shapes, backend and placement.  ``interpret=True`` runs it through
the Pallas interpreter (the CPU tests).
"""

from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["STEP_BLOCKS", "STEP_BYTES", "blocks_per_step", "lane_blocks", "merge_own_row", "paged_decode_attention"]

# A step copies at least STEP_BLOCKS blocks and STEP_BYTES of a leaf: 16 blocks of K 8 x hd 128 (512 KB), 32 of K 2
# (256 KB), the best of the probe's steps at both (my chip runs, PR 39: PERF.md section 6)
STEP_BLOCKS, STEP_BYTES = 16, 256 * 1024
UNROLL = 8  # blocks whose copies one iteration of the issuing loop starts
MASKED = -1e30  # the score of a row the mask leaves out, and the running max of a lane with no rows


def blocks_per_step(bs: int, kv_heads: int, head_dim: int, itemsize: int) -> int:
    """Blocks a step copies: ``STEP_BLOCKS``, or more where they hold less than ``STEP_BYTES``."""
    return max(STEP_BLOCKS, -(-STEP_BYTES // (bs * kv_heads * head_dim * itemsize)))


def _kernel(ids, blocks, first, lo, hi, after, q_ref, row_head, row_pos, k_hbm, v_hbm, o_ref, m_ref, l_ref,
            k_buf, v_buf, sems, slot_ref, *, width, bs, kv_heads, scale, unroll):
    b, lanes = pl.program_id(0), pl.num_programs(0)
    g = k_buf.shape[1]
    h, hd = q_ref.shape[1:]

    def copy(leaf, slot, i, blk):
        src, buf = (k_hbm, k_buf) if leaf == 0 else (v_hbm, v_buf)
        return pltpu.make_async_copy(src.at[blk], buf.at[slot, i], sems.at[leaf, slot])

    def start(lane, step, slot):
        """Start the copies of the lane's step: a DMA a block and leaf, ``unroll`` blocks an iteration of a loop over
        the step's whole groups, then one block an iteration (the trace stays the same size whatever ``g``)."""
        at, n = lane * width + step * g, jnp.minimum(blocks[lane] - step * g, g)

        def one(i):
            blk = ids[at + i]
            copy(0, slot, i, blk).start()
            copy(1, slot, i, blk).start()

        def group(j, carry):
            for u in range(unroll):
                one(j * unroll + u)
            return carry

        jax.lax.fori_loop(0, n // unroll, group, 0)
        jax.lax.fori_loop(n // unroll * unroll, n, lambda i, carry: (one(i), carry)[1], 0)

    def wait(lane, step, slot):
        """Until the step's copies have landed: a whole step's at once (a wait counts the bytes of its destination,
        and the step's copies fill the slot), the blocks of a lane's last step one by one.  The V rows of that step
        past its blocks are zeroed: they hold what an earlier lane's copies left there, another request's rows, which
        the mask weighs 0 but which need not be finite (0 x NaN is NaN: one request's NaN would reach every lane
        after it, where the gathered path reads a lane's own blocks alone)."""
        n = blocks[lane] - step * g

        @pl.when(n >= g)
        def _():
            pltpu.make_async_copy(k_buf.at[slot], k_buf.at[slot], sems.at[0, slot]).wait()
            pltpu.make_async_copy(v_buf.at[slot], v_buf.at[slot], sems.at[1, slot]).wait()

        def one(i, carry):
            copy(0, slot, i, 0).wait()  # a wait reads the semaphore and the destination's size, not the source
            copy(1, slot, i, 0).wait()
            return carry

        def clear(i, carry):
            v_buf[slot, i] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)
            return carry

        @pl.when(n < g)
        def _():
            jax.lax.fori_loop(0, n, one, 0)
            jax.lax.fori_loop(n, g, clear, 0)

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0

        @pl.when(after[0] < lanes)
        def _():
            start(after[0], 0, 0)

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    m_ref[...] = jnp.full(m_ref.shape, MASKED, m_ref.dtype)
    l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)

    @pl.when(blocks[b] > 0)
    def _():
        steps = (blocks[b] + g - 1) // g
        slot0 = slot_ref[0]  # the slot this lane's first step was copied into, by the lane before it or at the start
        q = q_ref[0]
        head = jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0) // (h // kv_heads)  # each query head's kv head

        def step(s, carry):
            m_prev, l_prev, acc = carry
            cur = (slot0 + s) % 2

            # the next copies, into the other slot: this lane's next step, or the first step of the next lane with blocks
            last = s + 1 == steps
            lane, ahead = jnp.where(last, after[b + 1], b), jnp.where(last, 0, s + 1)

            @pl.when(lane < lanes)
            def _():
                start(lane, ahead, 1 - cur)

            wait(b, s, cur)
            k = k_buf[cur].reshape(-1, hd)
            v = v_buf[cur].reshape(-1, hd)
            scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
            ours = row_head[...] == head

            def by_position(scores):  # a lane's first and last steps hold positions outside lo .. hi
                pos = (first[b] + s * g) * bs + row_pos[...]
                return jnp.where(ours & (pos >= lo[b]) & (pos <= hi[b]), scores, MASKED)

            scores = jax.lax.cond((s == 0) | last, by_position, lambda x: jnp.where(ours, x, MASKED), scores)
            # every step holds an admitted position, so a row of every kv head: m_new is a real score, p is 0 where masked
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m_prev - m_new)
            pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return m_new, alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), alpha * acc + pv

        init = (jnp.full((h, 1), MASKED, jnp.float32), jnp.zeros((h, 1), jnp.float32), jnp.zeros((h, hd), jnp.float32))
        m, l, acc = jax.lax.fori_loop(0, steps, step, init)
        slot_ref[0] = (slot0 + steps) % 2  # where the next lane's first step went
        o_ref[0] = acc
        m_ref[0] = jnp.broadcast_to(m, (h, m_ref.shape[2]))
        l_ref[0] = jnp.broadcast_to(l, (h, l_ref.shape[2]))


def lane_blocks(lo, hi, bs: int):
    """Of each lane's admitted positions ``lo .. hi`` (none where ``hi < lo``):
    its first logical block and its number of blocks, int32 ``[B]`` each."""
    lo, hi = lo.astype(jnp.int32), hi.astype(jnp.int32)
    return lo // bs, jnp.where(hi >= lo, hi // bs - lo // bs + 1, 0)


@functools.partial(jax.jit, static_argnames=("step_blocks", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, tables, lo, hi, *, step_blocks: int = 0, interpret=False):
    """One query row a lane over the rows its table names, read where they lie.

    q: ``[B, H, hd]``; k_pool / v_pool: ``[rows, bs, K, hd]``, the leaf as
    ``address_paged_pool_by_layer`` hands it over (the table's ids index its
    rows); tables: ``[B, W]`` int32, position ``p`` in entry ``(p // bs) mod
    W``; lo / hi: ``[B]`` int32, the positions each lane's query sees (none
    where ``hi < lo``).  Returns float32 ``(acc [B, H, hd], m [B, H], l [B,
    H])``: the output before its division by ``l``, the scores' running max
    and the sum of ``exp(score - m)``; a lane with no rows gives 0, ``MASKED``
    and 0."""
    b, h, hd = q.shape
    n, bs, kv_heads, _ = k_pool.shape
    width = tables.shape[1]
    rows = bs * kv_heads
    g = min(step_blocks or blocks_per_step(bs, kv_heads, hd, k_pool.dtype.itemsize), width)  # no lane holds more than the table
    first, blocks = lane_blocks(lo, hi, bs)
    # ids[b, j]: the block of the lane's j-th block from its first, (first + j) mod W of its table: what step s copies
    # lies at j = s * g .. s * g + g - 1, no division left for the kernel's scalar unit
    ids = jnp.take_along_axis(tables, (first[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]) % width, axis=1)
    lane = jnp.arange(b, dtype=jnp.int32)
    # after[i]: the first lane >= i that has a block, b where none does: whose copies the kernel starts ahead of it
    after = jax.lax.cummin(jnp.where(blocks > 0, lane, b), axis=0, reverse=True)
    after = jnp.concatenate([after, jnp.full((1,), b, jnp.int32)])
    r = np.arange(g * rows, dtype=np.int32)[None]
    row_head, row_pos = jnp.asarray(r % kv_heads), jnp.asarray(r // kv_heads)  # a step's row: its kv head, its position
    flat = lambda pool: pool.reshape(n, rows, hd)  # a free view where the TPU holds the leaf row by row

    lane_block = lambda *shape: pl.BlockSpec((1, *shape), lambda i, *_: (i,) + (0,) * len(shape))
    constant = pl.BlockSpec((1, g * rows), lambda i, *_: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b,),
        in_specs=[lane_block(h, hd), constant, constant, pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[lane_block(h, hd), lane_block(h, 128), lane_block(h, 128)],
        scratch_shapes=[
            pltpu.VMEM((2, g, rows, hd), k_pool.dtype),
            pltpu.VMEM((2, g, rows, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    itemsize = k_pool.dtype.itemsize
    acc, m, l = pl.pallas_call(
        functools.partial(_kernel, width=width, bs=bs, kv_heads=kv_heads, scale=float(hd) ** -0.5, unroll=math.gcd(g, UNROLL)),
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 128), jnp.float32),
        ],
        # the lanes share the double buffer and the slot a lane's first step went to: one after the other
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(  # at the table's width: what the lanes hold lies at or under it
            flops=4 * b * h * width * rows * hd,
            transcendentals=b * h * width * rows,
            bytes_accessed=2 * b * width * rows * hd * itemsize + 2 * q.size * q.dtype.itemsize,
        ),
        interpret=interpret,
    )(ids.reshape(-1).astype(jnp.int32), blocks, first, lo.astype(jnp.int32), hi.astype(jnp.int32), after,
      q, row_head, row_pos, flat(k_pool), flat(v_pool))
    return acc, m[..., 0], l[..., 0]


def merge_own_row(acc, m, l, q, k_new, v_new):
    """The kernel's partial result with the lane's own new row folded in: the
    row sits at the query's own position, which every mask admits, and is not
    in the pool yet.  acc ``[B, H, hd]``, m / l ``[B, H]`` float32 (as
    :func:`paged_decode_attention` returns them); q ``[B, H, hd]``; k_new /
    v_new ``[B, K, hd]`` as stored.  Returns ``[B, H, hd]`` in q.dtype."""
    groups = q.shape[1] // k_new.shape[1]
    k = jnp.repeat(k_new, groups, axis=1).astype(jnp.float32)
    v = jnp.repeat(v_new, groups, axis=1).astype(jnp.float32)
    own = jnp.sum(q.astype(jnp.float32) * k, axis=-1) * q.shape[-1] ** -0.5
    top = jnp.maximum(m, own)
    a, c = jnp.exp(m - top), jnp.exp(own - top)
    out = (a[..., None] * acc + c[..., None] * v) / (a * l + c)[..., None]
    return out.astype(q.dtype)
