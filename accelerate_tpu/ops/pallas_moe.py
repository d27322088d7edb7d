"""Pallas TPU kernel for the routed experts at a few rows an expert.

``ops/moe.py:routed_experts`` sorts its token-expert pairs by expert and needs,
for every expert ``e`` with rows, ``silu(x_e @ Wg[e]) * (x_e @ Wu[e]) @ Wd[e]``.
With one to ten rows an expert that product is bound by the bytes of the hit
experts' three matrices, and ``lax.ragged_dot`` (three Mosaic grouped matmuls
whose tiles XLA:TPU picks from the total row count, the hidden ``[rows, f]``
written to HBM between them) ran it at 41-57% of that bound (PERF.md section 6,
PR 35).  Here it is ONE ``pallas_call`` (``moe_grouped_swiglu``):

- each expert's rows are laid out to start on a row-tile boundary (``tm``
  rows) in a padded buffer of static size (:func:`max_row_tiles`); a
  scalar-prefetched table names each tile's expert as a row of the weight
  stack ``[G, d, f]``, ``G >= E``, offset by ``first_expert``: **the stack is
  read where it lies**, a layer's experts are never cut out of it;
- the grid walks the tiles in expert order, the three weight blocks indexed by
  the tile's expert, ``f`` whole (or in as few parts as the VMEM budget
  allows, :func:`f_tile`): Pallas double-buffers them and skips the fetch when
  consecutive steps name the same block, so **every hit expert's matrices are
  streamed once** (one multi-megabyte DMA each while the tile before it is
  multiplied), none for an expert without rows; tiles past the last live one
  repeat its block and skip the body;
- gate and up stay float32 through the SiLU and the product, are rounded to the
  operands' dtype once, and the down product accumulates in float32: fewer
  roundings than the ``ragged_dot`` path, never more.

Nothing here decides *whether* the kernel runs: ``ops/moe.py`` does, from
shapes, backend and placement.  ``interpret=True`` runs the kernel through the
Pallas interpreter (the CPU tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ROW_TILE", "f_tile", "grouped_swiglu", "max_row_tiles"]

ROW_TILE = 16  # rows a tile: the bf16 sublane tile, two of float32's
# What the three double-buffered weight blocks may take of a v5e core's 128 MiB of VMEM: 2048 x 768 bf16 whole is
# 18.9 MB, 2048 x 1792 bf16 whole 44.0 MB; anything wider is split along f.
WEIGHT_VMEM_BYTES = 48 * 2**20
_VMEM_MARGIN_BYTES = 8 * 2**20  # the rows' and the result's blocks, the accumulator, the float32 gate / up / hidden


def f_tile(d: int, f: int, itemsize: int) -> int | None:
    """The widest tile of ``f`` (``f`` whole, else a 128-multiple that divides
    it) whose three double-buffered ``d x tile`` weight blocks fit
    ``WEIGHT_VMEM_BYTES``; None where none does."""
    for parts in range(1, max(f // 128, 1) + 1):
        tile = f // parts
        if f % parts or (parts > 1 and tile % 128):
            continue
        if 2 * 3 * d * tile * itemsize <= WEIGHT_VMEM_BYTES:
            return tile
    return None


def max_row_tiles(n: int, e: int, tm: int) -> int:
    """Static bound on the row tiles ``n`` pairs over ``e`` experts can need:
    ``n // tm`` full tiles and one partial tile a hit expert."""
    return n // tm + min(e, n)


def _kernel(tile_expert, live, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *, nf):
    del tile_expert  # read by the index maps
    t, j = pl.program_id(0), pl.program_id(1)

    @pl.when(t < live[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
        part = jnp.dot(hidden, wd_ref[0], preferred_element_type=jnp.float32)
        if nf == 1:
            o_ref[...] = part.astype(o_ref.dtype)
            return

        @pl.when(j == 0)
        def _():
            acc_ref[...] = part

        @pl.when(j > 0)
        def _():
            acc_ref[...] += part

        @pl.when(j == nf - 1)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def padded_swiglu(x, w_gate, w_up, w_down, tile_expert, live, *, tm: int, interpret: bool = False):
    """The kernel on rows already laid out by tile.  x: ``[T * tm, d]``;
    w_gate/w_up: ``[G, d, f]``; w_down: ``[G, f, d]``; tile_expert: ``[T]``
    int32, tile ``t``'s row of the stack (non-decreasing over the live tiles,
    the last live one's repeated behind them); live: ``[1]`` int32, the number
    of live tiles.  Returns ``[T * tm, d]`` in x.dtype; the rows of a tile that
    is not live are not written."""
    d, f = w_gate.shape[1:]
    tiles = x.shape[0] // tm
    itemsize = jnp.dtype(x.dtype).itemsize
    tf = f_tile(d, f, itemsize)
    if tf is None:
        raise ValueError(f"no tile of f={f} at d={d}, {itemsize} B an element, fits the kernel's VMEM budget")
    nf = f // tf

    def part(t, j, live):  # a dead tile keeps the last live step's block: nothing is fetched for it
        return j if nf == 1 else jnp.where(t < live[0], j, nf - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles, nf),
        in_specs=[
            pl.BlockSpec((tm, d), lambda t, j, te, live: (t, 0)),
            pl.BlockSpec((1, d, tf), lambda t, j, te, live: (te[t], 0, part(t, j, live))),
            pl.BlockSpec((1, d, tf), lambda t, j, te, live: (te[t], 0, part(t, j, live))),
            pl.BlockSpec((1, tf, d), lambda t, j, te, live: (te[t], part(t, j, live), 0)),
        ],
        out_specs=pl.BlockSpec((tm, d), lambda t, j, te, live: (t, 0)),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
    )
    hit = min(w_gate.shape[0], tiles)
    return pl.pallas_call(
        functools.partial(_kernel, nf=nf),
        name="moe_grouped_swiglu",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * 3 * d * tf * itemsize + _VMEM_MARGIN_BYTES,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * 3 * x.shape[0] * d * f,
            transcendentals=x.shape[0] * f,
            bytes_accessed=(3 * hit * d * f + 2 * x.size) * itemsize,
        ),
        interpret=interpret,
    )(tile_expert, live, x, w_gate, w_up, w_down)


def tile_layout(group_sizes, n: int, tm: int):
    """Where ``n`` rows sorted by expert go in the padded buffer.  Returns
    (tile_expert ``[T]``: each tile's expert, the last live tile's behind the
    live ones; live ``[1]``; source ``[T * tm]``: the sorted row a padded row
    reads, any real row where it is padding; dest ``[n]``: a sorted row's place
    in the padded buffer), all int32."""
    e = group_sizes.shape[0]
    tiles = max_row_tiles(n, e, tm)
    tiles_of = (group_sizes + tm - 1) // tm
    tile_end, row_end = jnp.cumsum(tiles_of), jnp.cumsum(group_sizes)
    tile_start, row_start = tile_end - tiles_of, row_end - group_sizes
    live = tile_end[-1:]
    t = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32), live - 1)  # a dead tile reads as the last live one
    tile_expert = jnp.sum(tile_end[None, :] <= t[:, None], axis=1, dtype=jnp.int32)
    first_row = row_start[tile_expert] + (t - tile_start[tile_expert]) * tm
    source = jnp.minimum(first_row[:, None] + jnp.arange(tm, dtype=jnp.int32)[None, :], n - 1).reshape(-1)
    i = jnp.arange(n, dtype=jnp.int32)
    expert = jnp.sum(row_end[None, :] <= i[:, None], axis=1, dtype=jnp.int32)
    dest = tile_start[expert] * tm + i - row_start[expert]
    return tile_expert, live.astype(jnp.int32), source.astype(jnp.int32), dest.astype(jnp.int32)


def grouped_swiglu(rows, w_gate, w_up, w_down, group_sizes, first_expert=0, *, tm: int = ROW_TILE,
                   interpret: bool = False):
    """``silu(rows_e @ w_gate[e]) * (rows_e @ w_up[e]) @ w_down[e]`` for every
    expert's rows.  rows: ``[n, d]`` sorted by expert; group_sizes: ``[E]``
    int32 summing to ``n``; the experts are rows ``first_expert .. first_expert
    + E`` of the ``[G, ., .]`` stacks.  Returns ``[n, d]`` in rows.dtype."""
    n = rows.shape[0]
    tile_expert, live, source, dest = tile_layout(group_sizes, n, tm)
    tile_expert = tile_expert + jnp.asarray(first_expert, jnp.int32)
    padded = padded_swiglu(rows[source], w_gate, w_up, w_down, tile_expert, live, tm=tm, interpret=interpret)
    return padded[dest]
