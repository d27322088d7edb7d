"""Pallas TPU kernel for the decoding lanes' index scores over the paged pool.

A learned sparse attention (``models/keye_vl2.py``) scores every cached row of
a decoding lane with its indexer before it chooses the rows it attends over:
``I_s = sum_j w_j ReLU(qI_j . kI_s)``.  The gathered path copies every block of
every lane's table of the ``ki`` leaf into a dense context ``[B, W*bs, pack *
di]`` and scores the copy: at the widest table of a dispatch, whatever each
lane holds.  Here it is ONE ``pallas_call`` (``paged_index_scores``) that reads
**each lane's own index blocks where they lie in the pool**:

- the leaf stays in HBM (``pl.ANY``) as ``generation.address_paged_leaf_by_layer``
  hands it over, ``[rows, bs, D]`` (the packed rows whole, ``D`` = pack * di);
  the table ``[B, W]`` and each lane's positions ``lo .. hi`` are
  scalar-prefetched;
- the grid runs over the lanes; inside, a ``fori_loop`` walks the lane's blocks
  from ``lo // bs`` (rounded down to a whole row of the output: ``128 // bs``
  blocks) to ``hi // bs``, a step of ``step_blocks`` at a time, one DMA a block
  into a double buffer: the next step's copies (or the next lane's first
  step's) are in flight while this step is computed, and nothing past ``hi`` is
  copied; a lane with ``hi < lo`` copies nothing;
- a step's scores are ``relu(qI . kᵀ)`` in float32 on the MXU for every head at
  once, weighted by ``w`` and summed over the heads; the lane's score row
  ``[W*bs]`` (laid out as rows of 128) is ``MASKED`` outside ``lo .. hi``.

The queries arrive laid into their layer's lanes of a packed row (zeros in the
other layers' lanes), so a row is scored whole as this layer's key alone.  The
lane's own new row is not in the pool yet: its caller scores it in XLA and sets
it at its position.  Nothing here decides *whether* the kernel runs:
``models/keye_vl2.py:index_reads_in_place`` does, from shapes, backend and
placement.  ``interpret=True`` runs it through the Pallas interpreter (the CPU
tests).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["MASKED", "STEP_BLOCKS", "lane_walk", "paged_index_scores"]

# Blocks a step copies: 128 of 4 KB (512 KB), the best of the probe's steps at three of four table widths (one TPU v5e,
# the keye cell's ki leaf, 16 lanes of which 8 live at 19-94% of the table's rows, four layers read and scored, ms
# gathered / in place at steps of 32, 64, 128 blocks: 256 blocks 0.294 / 0.250, 0.237, 0.218; 512 0.528 / 0.325, 0.307,
# 0.315; 1,024 0.973 / 0.586, 0.539, 0.518; 2,048 3.199 / 1.006, 0.904, 0.863; PERF.md section 6)
STEP_BLOCKS = 128
UNROLL = 8  # blocks whose copies one iteration of the issuing loop starts
LANES = 128  # scores a row of the output
MASKED = -1e30  # the score of a position outside lo .. hi


def lane_walk(lo, hi, bs: int):
    """Of each lane's positions ``lo .. hi`` (none where ``hi < lo``): the
    first block the kernel copies, ``lo // bs`` rounded down to a whole row of
    ``LANES`` scores, and the number of blocks from there to ``hi // bs``,
    int32 ``[B]`` each."""
    align = max(1, LANES // bs)
    lo, hi = lo.astype(jnp.int32), hi.astype(jnp.int32)
    first = lo // bs // align * align
    return first, jnp.where(hi >= lo, hi // bs - first + 1, 0)


def _kernel(tables, blocks, first, lo, hi, after, q_ref, w_ref, k_hbm, o_ref, k_buf, sems, slot_ref, *, width, bs,
            unroll):
    b, lanes = pl.program_id(0), pl.num_programs(0)
    g = k_buf.shape[1]
    align = LANES // bs  # blocks a row of the output holds

    def copy(slot, i, blk):
        return pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[slot, i], sems.at[slot])

    def start(lane, step, slot):
        """Start the copies of the lane's step, a DMA a block, ``unroll`` blocks an iteration of a loop over the
        step's whole groups, then one block an iteration."""
        at, n = lane * width + first[lane] + step * g, jnp.minimum(blocks[lane] - step * g, g)

        def group(j, carry):
            for u in range(unroll):
                copy(slot, j * unroll + u, tables[at + j * unroll + u]).start()
            return carry

        jax.lax.fori_loop(0, n // unroll, group, 0)
        jax.lax.fori_loop(n // unroll * unroll, n, lambda i, carry: (copy(slot, i, tables[at + i]).start(), carry)[1], 0)

    def wait(lane, step, slot):
        """Until the step's copies have landed: a whole step's at once (a wait counts the bytes of its destination),
        the blocks of a lane's last step one by one.  Rows of that step past its blocks hold what an earlier copy
        left there; they lie past ``hi`` and are masked, each position's score its own."""
        n = blocks[lane] - step * g

        @pl.when(n >= g)
        def _():
            pltpu.make_async_copy(k_buf.at[slot], k_buf.at[slot], sems.at[slot]).wait()

        @pl.when(n < g)
        def _():
            jax.lax.fori_loop(0, n, lambda i, carry: (copy(slot, i, 0).wait(), carry)[1], 0)

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0

        @pl.when(after[0] < lanes)
        def _():
            start(after[0], 0, 0)

    o_ref[...] = jnp.full(o_ref.shape, MASKED, o_ref.dtype)

    @pl.when(blocks[b] > 0)
    def _():
        steps = (blocks[b] + g - 1) // g
        slot0 = slot_ref[0]  # the slot this lane's first step was copied into, by the lane before it or at the start
        q = q_ref[0]
        w = w_ref[0]  # [Hi, 1]

        def step(s, carry):
            cur = (slot0 + s) % 2
            last = s + 1 == steps
            lane, ahead = jnp.where(last, after[b + 1], b), jnp.where(last, 0, s + 1)

            @pl.when(lane < lanes)
            def _():
                start(lane, ahead, 1 - cur)

            wait(b, s, cur)
            k = k_buf[cur].reshape(g * bs, k_buf.shape[-1])
            dots = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            scores = jnp.sum(jnp.maximum(dots, 0.0) * w, axis=0, keepdims=True)  # [1, g * bs]
            at = (first[b] + s * g) * bs
            pos = at + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            scores = jnp.where((pos >= lo[b]) & (pos <= hi[b]), scores, MASKED)
            n = blocks[b] - s * g
            row = at // LANES
            for j in range(g // align):  # a row of the output for every `align` blocks the step copied

                @pl.when(j * align < n)
                def _():
                    o_ref[0, pl.ds(row + j, 1), :] = scores[:, j * LANES:(j + 1) * LANES]

            return carry

        jax.lax.fori_loop(0, steps, step, 0)
        slot_ref[0] = (slot0 + steps) % 2  # where the next lane's first step went


@functools.partial(jax.jit, static_argnames=("step_blocks", "interpret"))
def paged_index_scores(qi, w, leaf, tables, lo, hi, *, step_blocks: int = 0, interpret=False):
    """Each lane's index scores over the rows its table names, read where they lie.

    qi: ``[B, Hi, D]``, the index queries laid into their layer's lanes of a
    packed row; w: ``[B, Hi]`` float32, the head weights; leaf: ``[rows, bs,
    D]`` as ``address_paged_leaf_by_layer`` hands it over (the table's ids
    index its rows; ``bs`` divides ``LANES``); tables: ``[B, W]`` int32,
    position ``p`` in entry ``p // bs``; lo / hi: ``[B]`` int32, the positions
    each lane's query sees (none where ``hi < lo``).  Returns float32 ``[B,
    W*bs]``: ``sum_j w_j relu(qi_j . k_p)`` at ``lo .. hi``, ``MASKED``
    elsewhere."""
    b, heads, d = qi.shape
    _, bs, _ = leaf.shape
    width = tables.shape[1]
    if LANES % bs:
        raise ValueError(f"a block of {bs} rows is not a whole part of a row of {LANES} scores")
    align = LANES // bs
    g = min(step_blocks or STEP_BLOCKS, width)
    g = -(-g // align) * align  # whole rows of the output a step
    rows = -(-width * bs // LANES)
    first, blocks = lane_walk(lo, hi, bs)
    lane = jnp.arange(b, dtype=jnp.int32)
    # after[i]: the first lane >= i that has a block, b where none does: whose copies the kernel starts ahead of it
    after = jax.lax.cummin(jnp.where(blocks > 0, lane, b), axis=0, reverse=True)
    after = jnp.concatenate([after, jnp.full((1,), b, jnp.int32)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, heads, d), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, heads, 1), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, rows, LANES), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, g, bs, d), leaf.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    scores = pl.pallas_call(
        functools.partial(_kernel, width=width, bs=bs, unroll=math.gcd(g, UNROLL)),
        name="paged_index_scores",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, LANES), jnp.float32),
        # the lanes share the double buffer and the slot a lane's first step went to: one after the other
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(  # at the table's width: what the lanes hold lies at or under it
            flops=2 * b * heads * width * bs * d + 3 * b * heads * width * bs,
            transcendentals=0,
            bytes_accessed=b * width * bs * d * leaf.dtype.itemsize + qi.size * qi.dtype.itemsize + 4 * b * rows * LANES,
        ),
        interpret=interpret,
    )(tables.reshape(-1).astype(jnp.int32), blocks, first, lo.astype(jnp.int32), hi.astype(jnp.int32), after,
      qi, w.astype(jnp.float32)[..., None], leaf)
    return scores.reshape(b, rows * LANES)[:, : width * bs]
