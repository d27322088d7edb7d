"""Shared autoregressive generation driver for the model families.

Each family supplies ``init_cache(config, batch, max_len)`` and
``apply_cached(params, ids, config, cache) -> (logits, cache)``; the driver
compiles prefill + a one-token ``lax.scan`` decode loop into a single XLA
program (no per-token Python dispatch — the TPU-native answer to the
reference's torch generation loop, BASELINE.md s/token tables)."""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "generate_loop", "select_token", "make_kv_cache", "check_cache_room",
    "quantize_kv", "dequantize_kv", "pack_cache_for_scan",
    "unpack_cache_from_scan", "cache_write", "speculative_generate_loop",
    "speculative_verify_greedy",
    "make_paged_pool", "gather_block_view", "extract_token_rows",
    "scatter_token_rows", "paged_cache_write", "gather_paged_context", "overlay_new_rows",
    "address_paged_pool_by_layer", "address_paged_leaf_by_layer",
    "unpack_paged_rows_from_scan", "demote_pool_blocks", "promote_pool_blocks",
    "STATE", "token_leaves", "state_leaves", "with_token_leaves", "read_state_rows", "write_state_rows",
    "WINDOW", "window_leaves", "with_window_leaves", "window_ring_blocks", "window_group_masks", "paged_window_write",
    "scatter_window_rows",
    "MASKED", "block_end", "denoise_schedule", "block_unmask", "block_generate_loop",
]


def make_kv_cache(num_layers: int, batch_size: int, max_len: int,
                  num_kv_heads: int, head_dim: int, dtype,
                  quantized: bool = False) -> dict:
    """Zeroed stacked KV cache shared by every family: k/v
    ``[L, B, max_len, K, hd]`` plus the int32 write index.

    ``quantized=True`` stores int8 codes with a per-(slot, head) absmax
    scale — halves cache HBM vs bf16 (2x the feasible context/batch at
    decode) at ~0.4% RMS quantization error per row.  Net-new vs the
    reference (no KV-cache machinery upstream at all)."""
    shape = (num_layers, batch_size, max_len, num_kv_heads, head_dim)
    if quantized:
        scale_shape = shape[:-1]
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(scale_shape, jnp.bfloat16),
            "v": jnp.zeros(shape, jnp.int8),
            "v_scale": jnp.zeros(scale_shape, jnp.bfloat16),
            "index": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "index": jnp.zeros((), jnp.int32),
    }


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(slot, head) absmax int8 quantization of new K/V rows:
    ``[..., hd]`` -> (codes int8 ``[..., hd]``, scale bf16 ``[...]``)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-6) / 127.0
    codes = jnp.clip(jnp.round(x / scale[..., None]), -127, 127).astype(jnp.int8)
    return codes, scale.astype(jnp.bfloat16)


def dequantize_kv(codes: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`quantize_kv`; the elementwise multiply fuses into
    the consuming attention matmul (no materialized fp cache)."""
    return codes.astype(dtype) * scale[..., None].astype(dtype)


def pack_cache_for_scan(cache: dict):
    """K/V leaves in the form a family's decode ``lax.scan`` threads: plain
    arrays, or (codes, scale) tuples for the int8 cache."""
    quant = "k_scale" in cache
    ck = (cache["k"], cache["k_scale"]) if quant else cache["k"]
    cv = (cache["v"], cache["v_scale"]) if quant else cache["v"]
    return ck, cv, quant


def unpack_cache_from_scan(new_k, new_v, index, quant: bool) -> dict:
    """Inverse of :func:`pack_cache_for_scan` for the scanned-out leaves."""
    if quant:
        return {
            "k": new_k[0], "k_scale": new_k[1],
            "v": new_v[0], "v_scale": new_v[1],
            "index": index,
        }
    return {"k": new_k, "v": new_v, "index": index}


def cache_write(cache_leaf, new_rows: jax.Array, index, dtype):
    """Write ``new_rows`` ``[B, S, K, hd]`` at ``index``; returns
    (updated leaf(s), full-precision view for attention).  Handles both the
    plain and int8 (codes, scale) layouts — shared by every family's cached
    attention."""
    if isinstance(cache_leaf, tuple):
        codes, scale = cache_leaf
        n_codes, n_scale = quantize_kv(new_rows)
        codes = jax.lax.dynamic_update_slice(codes, n_codes, (0, index, 0, 0))
        scale = jax.lax.dynamic_update_slice(scale, n_scale, (0, index, 0))
        return (codes, scale), dequantize_kv(codes, scale, dtype)
    updated = jax.lax.dynamic_update_slice(
        cache_leaf, new_rows.astype(cache_leaf.dtype), (0, index, 0, 0)
    )
    return updated, updated


# ---------------------------------------------------------------------------
# Paged (block) KV cache primitives — the storage layer under the serving
# engine (serving/engine.py).  The resident cache between decode steps is a
# POOL of fixed-size blocks shared by every request ([L, num_blocks,
# block_size, ...] per leaf) plus per-request block tables; these helpers
# translate between that pool and the dense per-request [L, B=1, T, ...]
# view the families' ``apply_cached`` already consumes, so paged serving
# needs no per-family changes.
# ---------------------------------------------------------------------------


# The two kinds of cache leaf, and the one place that tells them apart.  A family's ``init_cache`` yields **token
# rows**, ``[L, B, max_len, ...]`` at its top level (K and V per head, latent rows, int8 codes and scales): a row a
# token, paged by block.  And, where a layer carries something from position to position whatever the length (a
# short convolution's last inputs), **state** leaves ``[L, B, ...]`` under the key ``STATE``: one entry a sequence,
# held by decode slot.  The number of layers may differ from leaf to leaf.  A family declares a leaf's kind by where it
# puts it; nothing is guessed from a shape.
#
# Token rows are of two kinds, by layer.  The leaves at the top level hold a sequence's rows for its whole length (a
# **full** layer's).  Leaves under ``WINDOW`` are token rows too, ``[L, B, max_len, ...]`` in a family's own cache, but
# of layers that attend over the last ``config.sliding_window`` positions alone: in the pool they are a group of their
# own with its own number of blocks, and a sequence holds of them a **ring** of ``window_ring_blocks`` blocks at most,
# logical block ``j`` at entry ``j mod width`` of its window table, overwritten in place once the ring is full.
STATE = "state"
WINDOW = "window"


def token_leaves(pool: dict) -> dict:
    """The leaves of a cache or a pool that hold a row a token for the whole sequence."""
    return {name: leaf for name, leaf in pool.items() if name not in (STATE, WINDOW, "index")}


def state_leaves(pool: dict) -> dict:
    """The leaves that hold one entry a sequence (empty for most families)."""
    return pool.get(STATE, {})


def window_leaves(pool: dict) -> dict:
    """The token leaves of the layers that keep a window of rows (empty for most families)."""
    return pool.get(WINDOW, {})


def with_token_leaves(pool: dict, fn: Callable) -> dict:
    """The pool with ``fn`` applied to every full token leaf, the state and the
    window leaves as they are: what moves, copies or scrubs **blocks** of the
    full kind goes through here."""
    return {name: leaf if name in (STATE, WINDOW) else fn(leaf) for name, leaf in pool.items()}


def with_window_leaves(pool: dict, fn: Callable) -> dict:
    """The pool with ``fn`` applied to every window leaf: the window kind's blocks are numbered on their own."""
    return {**pool, WINDOW: {name: fn(leaf) for name, leaf in pool[WINDOW].items()}}


def window_ring_blocks(window: int, chunk: int, block_size: int) -> int:
    """The width of a sequence's window table: the blocks that hold ``window +
    chunk`` rows, and one.  A dispatch writes at most ``chunk`` rows of a
    sequence (the padded last chunk of a prompt too), each over the row ``width
    * block_size`` positions before it: with this width that row lies outside
    the window of every position the dispatch computes, and of every later one."""
    return -(-(window + chunk) // block_size) + 1


def make_paged_pool(
    init_cache: Callable, config, num_blocks: int, block_size: int, num_slots: int = 0, window_blocks: int = 0
) -> dict:
    """Zeroed pool derived from a family's own ``init_cache``.  Every token
    leaf ``[L, 1, block_size, *rest]`` of the batch-1 template becomes ``[L,
    num_blocks, block_size, *rest]`` (so the int8 codes+scale layout pages
    exactly like the fp one).  Block 0 is the engine's reserved NULL block:
    table padding and inactive-slot writes route there, and no allocated region
    ever reads it.  Every state leaf ``[L, 1, *rest]`` becomes ``[L, num_slots,
    *rest]`` under ``STATE``: entry ``s`` belongs to the sequence in decode slot
    ``s``.  Every window leaf becomes ``[L, window_blocks, block_size, *rest]``
    under ``WINDOW``, block 0 its own NULL block.  A family without a state or a
    window gets the pool it always got."""
    template = init_cache(config, 1, block_size)

    def paged(leaves: dict, blocks: int) -> dict:
        out = {}
        for name, leaf in leaves.items():
            if leaf.ndim < 3 or leaf.shape[1] != 1 or leaf.shape[2] != block_size:
                raise ValueError(
                    f"cache leaf {name!r} has shape {leaf.shape}; paged serving needs "
                    f"the make_kv_cache layout [L, B, max_len, ...] (batch axis 1, "
                    f"token axis 2)"
                )
            out[name] = jnp.zeros((leaf.shape[0], blocks) + leaf.shape[2:], leaf.dtype)
        return out

    pool = paged(token_leaves(template), num_blocks)
    if not pool:
        raise ValueError("init_cache produced no pageable KV leaves")
    if window_leaves(template):
        if window_blocks < 2:
            raise ValueError("the cache holds window leaves: the pool needs their number of blocks (a null block and one)")
        pool[WINDOW] = paged(window_leaves(template), window_blocks)
    state = state_leaves(template)
    if state:
        if num_slots < 1:
            raise ValueError(f"the cache holds a state a sequence ({sorted(state)}): the pool needs its number of slots")
        for name, leaf in state.items():
            if leaf.ndim < 2 or leaf.shape[1] != 1:
                raise ValueError(f"state leaf {name!r} has shape {leaf.shape}; a state leaf is [L, B, ...] (batch axis 1)")
        pool[STATE] = {name: jnp.zeros((leaf.shape[0], num_slots) + leaf.shape[2:], leaf.dtype) for name, leaf in state.items()}
    return pool


@jax.named_scope("kv_pool.gather")
def read_state_rows(leaf: jax.Array, layer: jax.Array, slots: jax.Array, starts: jax.Array) -> jax.Array:
    """What the sequences in ``slots [B]`` carry into layer ``layer`` of a state
    leaf ``[L, S, *r]`` -> ``[B, *r]``.  A lane at ``starts == 0`` starts its
    sequence and reads zeros, whatever its slot's last owner left there (a NaN
    too: selected, not multiplied), so admission costs no host work."""
    with jax.named_scope("state_pool"):
        rows = leaf.at[layer, slots].get(mode="clip")
        fresh = (starts == 0).reshape((-1,) + (1,) * (rows.ndim - 1))
        return jnp.where(fresh, jnp.zeros((), rows.dtype), rows)


@jax.named_scope("kv_pool.write")
def write_state_rows(state: dict, rows: dict, slots: jax.Array, counts: jax.Array) -> dict:
    """The state leaves ``{name: [L, S, *r]}`` with ``rows {name: [B, L, *r]}``
    written at ``slots [B]``, for the lanes that advanced (``counts > 0``: rows of
    theirs were real).  Any other lane's entry stays bit for bit: an idle lane
    computed on padding, and so did the decoding lane of the slot whose chunk
    rides in the same dispatch as another group."""
    with jax.named_scope("state_pool"):
        out = {}
        for name, leaf in state.items():
            dst = jnp.where(counts > 0, slots, leaf.shape[1])  # past the leaf: dropped
            out[name] = leaf.at[:, dst].set(jnp.moveaxis(rows[name], 0, 1).astype(leaf.dtype), mode="drop")
        return out


@jax.named_scope("kv_pool.gather")
def gather_block_view(pool_leaf: jax.Array, tables: jax.Array) -> jax.Array:
    """Dense per-slot view of a pool leaf: ``[L, N, bs, *r]`` gathered through
    block tables ``[S, M]`` -> ``[S, L, 1, M*bs, *r]`` (the families'
    batch-1 cache layout, slot axis leading for ``vmap``).  Table entries
    pointing at the null block contribute rows that the causal mask hides —
    the engine keeps every real token position inside the allocated block
    prefix."""
    g = jnp.take(pool_leaf, tables, axis=1)  # [L, S, M, bs, *r]
    g = jnp.moveaxis(g, 1, 0)  # [S, L, M, bs, *r]
    s, l, m, bs = g.shape[:4]
    return g.reshape(s, l, 1, m * bs, *g.shape[4:])


def _token_positions(start: jax.Array, count: int) -> jax.Array:
    return start[:, None].astype(jnp.int32) + jnp.arange(count, dtype=jnp.int32)[None, :]


def extract_token_rows(view_leaf: jax.Array, start: jax.Array, count: int) -> jax.Array:
    """Pull the rows a forward pass just wrote out of the dense view:
    ``[S, L, 1, T, *r]`` at token positions ``start[s] + arange(count)`` ->
    ``[S, L, count, *r]``."""
    pos = _token_positions(start, count)  # [S, count]
    idx = pos.reshape(pos.shape[0], 1, 1, count, *([1] * (view_leaf.ndim - 4)))
    rows = jnp.take_along_axis(view_leaf, idx, axis=3)  # [S, L, 1, count, *r]
    return rows.reshape(rows.shape[0], rows.shape[1], count, *rows.shape[4:])


@jax.named_scope("kv_pool.write")
def scatter_token_rows(
    pool_leaf: jax.Array,
    rows: jax.Array,
    tables: jax.Array,
    start: jax.Array,
    count: int,
    keep: Optional[jax.Array] = None,
) -> jax.Array:
    """Write token rows ``[S, L, count, *r]`` back into the pool at positions
    ``start[s] + arange(count)`` through block tables ``[S, M]``.  Positions
    past the table extent (chunked-prefill padding) are routed to the null
    block explicitly — ``take_along_axis`` would otherwise CLAMP the block
    index and corrupt a real block.  With ``keep [S]`` the rows of the lanes
    where it is 0 are written nowhere (a block past the pool: dropped), not
    even to the null block: a denoising pass leaves the pool as it was."""
    bs = pool_leaf.shape[2]
    m = tables.shape[1]
    pos = _token_positions(start, count)  # [S, count]
    blk_idx = pos // bs
    blk = jnp.take_along_axis(tables, jnp.clip(blk_idx, 0, m - 1), axis=1)
    blk = jnp.where(blk_idx < m, blk, 0)
    if keep is not None:
        blk = jnp.where(keep[:, None] > 0, blk, pool_leaf.shape[1])
    off = pos % bs
    rows = jnp.moveaxis(rows, 0, 1)  # [L, S, count, *r]
    if pool_leaf.ndim == 4:
        # A leaf with no head axis (latent rows, int8 scales): a token's row
        # is one packed sublane of a tile, and a scatter whose window spans
        # the layers makes XLA:TPU re-lay out the whole leaf to bring them
        # together, and back (two pool-sized copies a dispatch, compile-only,
        # PR 28).  Indexed by layer too, the window is the row and the leaf is
        # written where it lies.
        layer = jnp.arange(pool_leaf.shape[0], dtype=jnp.int32)[:, None, None]
        return pool_leaf.at[layer, blk[None], off[None]].set(rows)
    return pool_leaf.at[:, blk, off].set(rows)  # an index past the leaf (``keep``) is dropped: a scatter's default


def demote_pool_blocks(pool: dict, blocks) -> dict:
    """Gather whole blocks out of every pool leaf and land them in host
    memory: ``{name: [L, n, bs, *r] numpy}`` for ``n = len(blocks)``.  One
    device gather + one D2H transfer per leaf — the KV-tiering demotion
    primitive (serving/blocks.py), batched per call and never part of the
    fused decode dispatch.  On TPU the destination is the pinned-host
    mirror pool; ``device_get`` rather than a cross-memory-kind
    ``device_put`` keeps the copy a real transfer on CPU backends too,
    where host is already the default memory kind."""
    import numpy as np

    idx = jnp.asarray(blocks, jnp.int32)
    gathered = {name: jnp.take(leaf, idx, axis=1) for name, leaf in token_leaves(pool).items()}
    return {name: np.asarray(jax.device_get(g)) for name, g in gathered.items()}


def promote_pool_blocks(pool: dict, host_rows: dict, dst_blocks) -> dict:
    """Scatter host-resident block rows ``{name: [L, n, bs, *r]}`` back into
    the pool at block ids ``dst_blocks``; returns the updated pool.  One H2D
    transfer + one scatter per leaf — the promotion primitive paired with
    :func:`demote_pool_blocks`."""
    dst = jnp.asarray(dst_blocks, jnp.int32)
    return {
        **pool,
        **{name: leaf.at[:, dst].set(jnp.asarray(host_rows[name], leaf.dtype)) for name, leaf in token_leaves(pool).items()},
    }


def _insert_rows(ctx: jax.Array, new_rows: jax.Array, starts: jax.Array) -> jax.Array:
    """Overlay ``new_rows`` ``[B, T, *r]`` onto the gathered context ``[B, P,
    *r]`` at positions ``starts[b] .. starts[b]+T-1`` (``starts`` never
    negative) — the paged analog of the dense view after ``cache_write``:
    attention sees exactly the values a dense-view write would have produced,
    without an updated view ever being materialized as a program output.

    A **row-sized write**: the ``B*T`` rows are scattered into the gathered
    blocks, which XLA:TPU updates in place; nothing context-sized is gathered
    or selected (a ``take_along_axis`` + ``where`` over ``[B, P]`` was 5.95 of
    a chat decode's 17.0 ms at table width 64, the scatters are 0.08: PERF.md
    section 6, PR 29).  **Positions past the
    extent are dropped** (the padding of a last prefill chunk past the
    tables), never clamped: a clamped write lands on the context's last real
    row (the trap :func:`scatter_token_rows` documents for the pool)."""
    slot = jnp.arange(ctx.shape[0], dtype=jnp.int32)[:, None]
    pos = _token_positions(starts, new_rows.shape[1])  # [B, T]
    return ctx.at[slot, pos].set(new_rows, mode="drop", indices_are_sorted=True, unique_indices=True)


@jax.named_scope("kv_pool.gather")
def overlay_new_rows(ctx: jax.Array, new_rows: jax.Array, starts: jax.Array) -> jax.Array:
    """:func:`_insert_rows` for a family that gathers a context itself
    (:func:`gather_paged_context`) and cuts its part out before the overlay:
    a row-sized write, positions past the extent dropped (not clamped onto
    the context's last row)."""
    return _insert_rows(ctx, new_rows, starts)


def _blocks_lie_row_by_row(leaf) -> bool:
    """Whether a TPU holds a K/V pool leaf ``[..., bs, K, hd]`` block by block
    and, within a block, as ``bs*K`` whole rows of ``hd`` one after the other.
    It tiles the two minor axes by 8 sublanes x 128 lanes (K x 128 where K is
    1, 2 or 4 and hd 128), so it does exactly when K fills whole tiles and hd
    whole lanes; other geometries it pads or permutes (at hd 64 the block
    axis lies in the lanes; an int8 pool's ``bs`` and ``K`` change places).
    Where this holds, ``[L, N, bs, K, hd] -> [L*N, bs*K, hd]`` is a free view
    and XLA:TPU gathers blocks from the pool where it lies; where it does not,
    any gather from the whole pool is answered with a re-laid-out copy of the
    whole pool (PERF.md section 7.0a).  Deliberately narrow: a wrong ``False``
    costs what the program cost until PR 27, a wrong ``True`` a second pool,
    and ``tests/test_tpu_compile.py`` holds both sides of every edge to the
    compiler's own answer."""
    kv_heads, head_dim = leaf.shape[-2:]
    return leaf.dtype.itemsize > 1 and head_dim % 128 == 0 and (
        kv_heads % 8 == 0 or (head_dim == 128 and kv_heads in (1, 2, 4)))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# The narrowest block table, in bytes of a leaf a lane, whose decoding lanes read the pool in place.  From the probes of
# PR 39 (one TPU v5e, sixteen lanes at three quarters of the table's rows, ms a dispatch gathered / in place; PERF.md
# section 6): K 2 x hd 128 (8 KB a block), 36 layers: 64 blocks (512 KB) 1.85-2.01 / 1.92-2.15, 128 blocks (1 MB)
# 3.55-4.08 / 3.23-3.40, 256 (2 MB) 7.39-8.33 / 6.57-6.59; K 8 x hd 128 (32 KB a block), four window layers: 16 blocks
# (512 KB) 0.323 / 0.331, 32 (1 MB) 0.347 / 0.311, 64 0.731 / 0.475, 128 1.407 / 0.787, ring 259 4.88 / 1.85; one full
# layer ties at 16-64 blocks (0.31-0.33 both, a dispatch's floor) and wins from 128 (0.378 / 0.327; 1,024 blocks 4.49 /
# 0.89).  Under a table of 1 MB the kernel's own cost a lane (its first copies exposed, a step's fixed work) is not paid
# back by the rows it leaves unread.
MIN_IN_PLACE_TABLE_BYTES = 1 << 20


def reads_in_place(leaf, rows: int, width: int) -> bool:
    """Whether a group of ``rows`` rows a lane attends over its K/V leaf
    ``leaf`` (a pool leaf ``[..., bs, K, hd]``, or ``(codes, scale)``) through
    block tables ``width`` wide **in place**: the Pallas kernel of
    ``ops/pallas_paged_attention.py`` reads each lane's own blocks where they
    lie (:func:`attend_in_place`), in place of :func:`paged_cache_write`'s
    gathered context.  From static facts alone, no option anywhere: a TPU runs
    the program on one device (``pallas_call`` takes no part in GSPMD's
    partitioning), the group is the decoding lanes at one row a lane (a chunk,
    a verify window and a block of several rows keep the gathered path), the
    leaf is bf16 that the TPU holds row by row (:func:`_blocks_lie_row_by_row`:
    a block is whole ``[bs*K, hd]`` rows, one copy; int8, hd 64 and latent rows
    stay gathered) and its tables hold at least ``MIN_IN_PLACE_TABLE_BYTES`` of
    it a lane (narrower, the gather of a few blocks a lane is the cheaper
    read).  Off the TPU the gathered path stays (the kernel would run in the
    Pallas interpreter)."""
    from ..parallel.sharding import _abstract_mesh

    mesh = _abstract_mesh()
    if not (_on_tpu() and (mesh.empty or mesh.size == 1) and rows == 1 and not isinstance(leaf, tuple)
            and leaf.dtype == jnp.bfloat16 and _blocks_lie_row_by_row(leaf)):
        return False
    bs, kv_heads, head_dim = leaf.shape[-3:]
    return width * bs * kv_heads * head_dim * leaf.dtype.itemsize >= MIN_IN_PLACE_TABLE_BYTES


def _paged_kernel():
    from ..ops.moe import pallas_module

    return pallas_module("pallas_paged_attention")


def _admitted(starts: jax.Array, window: int):
    """``(lo, hi)`` ``[B]``: the positions in the pool a decoding lane's query
    at ``starts`` sees, every earlier one, or under a window of ``window`` the
    last ``window - 1`` (its own row, the ``window``-th, is not in the pool
    yet); none where ``hi < lo`` (a lane at 0)."""
    hi = starts.astype(jnp.int32) - 1
    return (jnp.maximum(hi - window + 2, 0) if window else jnp.zeros_like(hi)), hi


def attend_in_place(q, k_new, v_new, pk, pv, tables, starts, window: int = 0, interpret: bool = False) -> jax.Array:
    """The decoding lanes' attention read where the pool lies: ``q [B, 1, H,
    hd]`` over the rows its tables ``[B, W]`` name of the leaves ``pk``, ``pv``
    (as :func:`address_paged_pool_by_layer` hands them over; position ``p`` in
    entry ``(p // bs) mod W``, so a window layer's ring too), those at ``lo ..
    starts - 1`` (:func:`_admitted`), and its own new row ``k_new``, ``v_new``
    ``[B, 1, K, hd]`` as stored, merged in outside the kernel.  Returns ``[B,
    1, H, hd]`` in q.dtype: what :func:`paged_cache_write` and ``_attention``
    give under :func:`group_positions`' or :func:`window_group_masks`' mask,
    with float32 scores."""
    kernel = _paged_kernel()
    lo, hi = _admitted(starts, window)
    acc, m, l = kernel.paged_decode_attention(q[:, 0], pk, pv, tables, lo, hi, interpret=interpret)
    return kernel.merge_own_row(acc, m, l, q[:, 0], k_new[:, 0], v_new[:, 0])[:, None]


def rows_read_in_place(starts: jax.Array, block_size: int, window: int = 0) -> jax.Array:
    """The rows :func:`attend_in_place` copies a layer for lanes at ``starts``:
    every block it touches whole, the edge blocks too; int32."""
    _, blocks = _paged_kernel().lane_blocks(*_admitted(starts, window), block_size)
    return jnp.sum(blocks) * block_size


@jax.named_scope("kv_pool.gather")
def paged_cache_write(pool_layer, new_rows: jax.Array, tables: jax.Array, starts: jax.Array, dtype):
    """Per-layer paged analog of :func:`cache_write`: compute the stored
    representation of ``new_rows`` ``[B, T, K, hd]`` (cast for the fp pool,
    ``(codes, scale)`` for the int8 one) and the **dense attention context**
    ``[B, M*bs, K, hd]`` gathered straight through the block tables ``[B, M]``
    with the new rows overlaid at ``starts[b] + arange(T)``.  A latent leaf
    (``new_rows`` ``[B, T, w]``, no head axis) goes the same way.  The overlay
    is a row-sized write into the gathered blocks (:func:`_insert_rows`):
    after the gather nothing context-sized runs before attention.  Positions
    past the extent ``M*bs`` are dropped; a clamp would put a padded row of
    a last prefill chunk onto the context's last real row.

    ``pool_layer`` is any leaf whose leading axis the tables index, as
    :func:`address_paged_pool_by_layer` hands it over: the whole pool
    flattened to ``[L*N, bs, K, hd]`` with the tables offset to the layer's
    rows, or one layer ``[N, bs, K, hd]``.

    Unlike the dense path, nothing here flows back out as an updated cache:
    the pool leaf is consumed read-only, the stored rows ride out as tiny
    per-layer ``ys``, and the engine scatters them into the donated pool
    after the forward — HBM write traffic per token is the new rows, not the
    per-slot worst-case view."""
    b = tables.shape[0]
    m = tables.shape[1]
    if isinstance(pool_layer, tuple):  # int8: (codes [N, bs, K, hd], scale [N, bs, K])
        codes, scale = pool_layer
        bs = codes.shape[1]
        n_codes, n_scale = quantize_kv(new_rows)
        stored = (n_codes, n_scale)
        ctx = dequantize_kv(
            jnp.take(codes, tables, axis=0, mode="clip").reshape(b, m * bs, *codes.shape[2:]),
            jnp.take(scale, tables, axis=0, mode="clip").reshape(b, m * bs, *scale.shape[2:]),
            dtype,
        )
        # Attention must see the QUANTIZED new rows (the dense path writes
        # codes then dequantizes the whole view) or int8 serving would not be
        # token-identical to the offline int8 cache.
        new_full = dequantize_kv(n_codes, n_scale, dtype)
    else:
        stored = new_rows.astype(pool_layer.dtype)
        ctx = gather_paged_context(pool_layer, tables)
        new_full = stored
    return stored, _insert_rows(ctx, new_full, starts)


def gather_paged_context(pool_layer: jax.Array, tables: jax.Array) -> jax.Array:
    """The blocks the tables ``[B, M]`` name, as one context a row: ``[rows, bs,
    *r] -> [B, M*bs, *r]``, for a K/V leaf (``*r`` = ``K, hd``) and for a latent
    leaf (``*r`` = its width) alike.  Callers put it under the
    ``kv_pool.gather`` scope (``paged_cache_write`` does).  The block ids are
    the engine's own (null block 0, offset to the layer's rows) and lie inside
    the leaf: gathered with ``mode="clip"``, because ``jnp.take``'s default
    fills what is out of range, which XLA:TPU answers with a select over the
    whole context after the gather (3.4 of a chat decode's 20.8 ms at table
    width 256, PR 29)."""
    n, bs, *rest = pool_layer.shape
    b, m = tables.shape
    rows = pool_layer
    if len(rest) == 2 and _blocks_lie_row_by_row(pool_layer):
        # Whole blocks are gathered as [bs*K, hd] rows where that is a free
        # view: the TPU then reads a block as full (8, 128) tiles, 2.7 times
        # as fast as through the (K, 128) tiles of [bs, K, hd] at K = 2.
        rows = pool_layer.reshape(n, bs * rest[0], rest[1])
    return jnp.take(rows, tables, axis=0, mode="clip").reshape(b, m * bs, *rest)


def window_group_masks(groups, positions, block_size: int, window: int) -> tuple:
    """Of every group ``(tokens [B, T], tables, starts [B], window tables [B,
    W])`` of an ``apply_paged`` call: the mask of its new tokens over the rows
    its window table names, ``[B, T, W * block_size]``, by position.  Ring row
    ``r`` of a lane whose dispatch ends at position ``last = starts + T - 1``
    holds the newest position ``<= last`` that is ``r`` modulo the ring's rows
    (none yet where that is negative), and a query at ``i`` sees the keys ``j``
    with ``i - window < j <= i``: the ring's order does not matter, keys are
    cached after RoPE.  ``W`` is the ring's width, or a narrower table of a
    dispatch none of whose sequences has wrapped (every position lies under ``W
    * block_size``): the same formula."""
    masks = []
    for pos, (tokens, _, starts, wtables) in zip(positions, groups):
        rows = wtables.shape[1] * block_size
        last = (starts + tokens.shape[1] - 1)[:, None]
        held = last - (last - jnp.arange(rows, dtype=jnp.int32)[None, :]) % rows  # [B, rows]: the position a ring row holds
        held = held[:, None, :]
        masks.append((held >= 0) & (held <= pos[:, :, None]) & (held > pos[:, :, None] - window))
    return tuple(masks)


@jax.named_scope("kv_pool.gather")
def paged_window_write(pool_layer, new_rows: jax.Array, wtables: jax.Array, starts: jax.Array):
    """:func:`paged_cache_write` for a window leaf: the stored rows of
    ``new_rows [B, T, K, hd]`` and the context ``[B, W*bs, K, hd]`` gathered
    through the window tables ``[B, W]``, the new rows overlaid where the ring
    puts them, ``(starts[b] + t) mod (W*bs)``.  What a dispatch gathers for a
    window layer is the ring, whatever the sequences' lengths."""
    stored = new_rows.astype(pool_layer.dtype)
    ctx = gather_paged_context(pool_layer, wtables)
    slot = jnp.arange(ctx.shape[0], dtype=jnp.int32)[:, None]
    at = _token_positions(starts, new_rows.shape[1]) % ctx.shape[1]
    return stored, ctx.at[slot, at].set(stored, unique_indices=True)


@jax.named_scope("kv_pool.write")
def scatter_window_rows(pool_leaf: jax.Array, rows: jax.Array, wtables: jax.Array, start: jax.Array, count: int) -> jax.Array:
    """:func:`scatter_token_rows` for a window leaf: rows ``[S, L, count, *r]``
    at positions ``start[s] + arange(count)``, position ``p`` into block
    ``wtables[s, (p // bs) mod W]``.  An entry not yet allocated names the
    window kind's null block (the padding of a prompt's last chunk lands there
    or on rows that have left every window: :func:`window_ring_blocks`)."""
    bs = pool_leaf.shape[2]
    pos = _token_positions(start, count)
    blk = jnp.take_along_axis(wtables, (pos // bs) % wtables.shape[1], axis=1)
    return pool_leaf.at[:, blk, pos % bs].set(jnp.moveaxis(rows, 0, 1))


def _latent_rows_lie_block_by_block(leaf) -> bool:
    """Whether a TPU holds a latent pool leaf ``[..., bs, w]`` (no head axis)
    block by block: it does where a row is whole 128-lane tiles (``w`` 512, or
    two layers' rotated keys of 64 side by side); a row of 576 or of 64 it lays
    out with the block axis in the lanes, and any gather from the whole leaf is
    then answered with a copy of the whole leaf (compile-only, PR 28:
    ``tests/test_tpu_compile.py`` holds the cache geometries of
    ``models/deepseek_v3.py`` to the compiler's answer)."""
    return leaf.dtype.itemsize > 1 and leaf.shape[-1] % 128 == 0


@jax.named_scope("kv_pool.gather")
def address_paged_leaf_by_layer(leaf: jax.Array, tables: jax.Array, layer: jax.Array):
    """One layer of a latent pool leaf ``[L, N, bs, w]`` for a family's
    per-layer scan body: ``(rows, tables)`` as :func:`paged_cache_write` and
    :func:`gather_paged_context` take them.  Where the TPU holds the leaf block
    by block, the whole leaf with its two major axes merged and the tables
    offset to the layer's rows; else the layer's slice, cut here (see
    :func:`address_paged_pool_by_layer`, which does the same for K/V pairs)."""
    if _latent_rows_lie_block_by_block(leaf):
        return leaf.reshape((-1,) + leaf.shape[2:]), tables + layer * leaf.shape[1]
    return jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False), tables


@jax.named_scope("kv_pool.gather")
def address_paged_pool_by_layer(pool: dict, tables: jax.Array, layer: jax.Array):
    """One layer of the pool for a family's per-layer scan body: ``(pk, pv,
    tables)`` as ``paged_cache_write`` takes them
    (a leaf ``[rows, bs, K, hd]``, or ``(codes, scale)`` for int8, and block
    ids that index its rows).  The scan closes over the pool and scans the
    layer number; nothing of the pool is a scanned input.

    Where the TPU holds the pool block by block (:func:`_blocks_lie_row_by_row`)
    the leaves are the whole pool with its two major axes merged, ``[L, N, bs,
    ...] -> [L*N, bs, ...]`` — a free view — and the tables are offset to
    the layer's rows (``tables + layer * N``; the null block of layer ``l`` is
    row ``l*N``): the layer gathers the blocks its tables name from the pool
    where it lies, and nothing else of the pool is read, sliced or re-tiled.
    Handed to ``lax.scan`` as ``xs`` instead, every layer's whole ``[N, bs,
    ...]`` slice was cut out and re-tiled, a cost in ``num_blocks`` and not
    in the blocks named (PERF.md section 6, PR 27).

    Any other pool (int8, ``hd`` under 128, odd ``K``) no gather reads where
    it lies: from the merged view XLA:TPU would re-lay out the *whole* pool
    once a dispatch, a second pool in memory.  There the layer's slice is cut
    here, as the scan did, at the same cost as before."""
    if _blocks_lie_row_by_row(pool["k"]):
        num_blocks = pool["k"].shape[1]
        leaves = {name: leaf.reshape((-1,) + leaf.shape[2:]) for name, leaf in pool.items()}
        tables = tables + layer * num_blocks
    else:
        leaves = {name: jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False) for name, leaf in pool.items()}
    if "k_scale" in pool:
        return (leaves["k"], leaves["k_scale"]), (leaves["v"], leaves["v_scale"]), tables
    return leaves["k"], leaves["v"], tables


def block_end(positions: jax.Array, block_length: int) -> jax.Array:
    """The last position of the block of ``block_length`` a position lies in:
    the last key it sees under the block-causal mask (itself, at length 1)."""
    return positions if block_length == 1 else positions // block_length * block_length + (block_length - 1)


def group_positions(groups, block_size: int, block_length: int = 1):
    """Of every group ``(tokens [B, T], tables [B, M], starts [B])`` of an
    ``apply_paged`` call: the positions of its new tokens ``[B, T]`` (row
    ``b``'s sit at ``starts[b] .. starts[b] + T - 1``) and its attention mask
    over the context its own tables name, ``[B, T, M * block_size]``.  A
    position sees the keys up to its own; with ``block_length`` ``B > 1`` (a
    family generated by diffusion over blocks) up to the end of its block, ``(p
    // B) * B + B - 1``: causal over blocks, full inside one, for the chunk's
    group (the prompt is block-causal) and for the lanes' (a block sees all of
    itself) alike."""
    positions = tuple(_token_positions(starts, tokens.shape[1]) for tokens, _, starts in groups)
    with jax.named_scope("attn.block") if block_length > 1 else contextlib.nullcontext():
        masks = tuple(
            block_end(pos, block_length)[:, :, None] >= jnp.arange(tables.shape[1] * block_size, dtype=jnp.int32)[None, None, :]
            for pos, (_, tables, _) in zip(positions, groups))
    return positions, masks


def join_groups(parts) -> jax.Array:
    """The rows of every group ``[B_g, T_g, *r]`` side by side, ``[1, sum(B_g *
    T_g), *r]``: what an operator that does not look at the cache runs over
    once, whichever lanes the rows belong to.  One group stays as it is."""
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate([p.reshape((1, -1) + p.shape[2:]) for p in parts], axis=1)


def split_groups(joined: jax.Array, shapes) -> tuple:
    """Inverse of :func:`join_groups` for groups of ``shapes`` ``(B_g, T_g)``:
    each group's rows back as ``[B_g, T_g, *r]``, for the attention of its
    own lanes."""
    if len(shapes) == 1:
        return (joined,)
    parts, at = [], 0
    for b, t in shapes:
        parts.append(joined[:, at : at + b * t].reshape((b, t) + joined.shape[2:]))
        at += b * t
    return tuple(parts)


def unpack_paged_rows_from_scan(k_rows, v_rows, quant: bool) -> dict:
    """Stacked per-layer stored rows ``[L, B, T, ...]`` (scan ``ys``) ->
    ``{leaf: [B, L, T, ...]}``, the layout ``scatter_token_rows`` writes."""
    def out(rows):
        return jnp.moveaxis(rows, 0, 1)

    if quant:
        return {
            "k": out(k_rows[0]), "k_scale": out(k_rows[1]),
            "v": out(v_rows[0]), "v_scale": out(v_rows[1]),
        }
    return {"k": out(k_rows), "v": out(v_rows)}


def check_cache_room(index, new_tokens: int, max_len: int) -> None:
    """Eager-mode overflow guard: ``dynamic_update_slice`` CLAMPS an
    out-of-range write start under jit (silent cache corruption), so callers
    driving ``apply_cached`` directly get a real error when the index is
    concrete; traced callers rely on the documented ``index + S <= max_len``
    contract (generate_loop maintains it)."""
    try:
        concrete = int(index)
    except jax.errors.TracerIntegerConversionError:  # traced inside jit
        return
    except jax.errors.ConcretizationTypeError:  # abstract value (e.g. eval_shape)
        return
    if concrete + new_tokens > max_len:
        raise ValueError(
            f"KV cache overflow: index {concrete} + {new_tokens} new tokens > max_len {max_len}"
        )


def select_token(
    logits: jax.Array,
    temperature: float,
    key,
    i,
    top_k: int = 0,
    top_p: float = 1.0,
) -> jax.Array:
    """Greedy argmax (temperature<=0) or filtered categorical sample at step
    ``i``.  ``top_k > 0`` keeps only the k highest logits; ``top_p < 1`` keeps
    the smallest set of tokens whose cumulative probability reaches p (the
    top-1 token is always kept).  Both are static, jit-friendly filters
    (sort + mask — no dynamic shapes)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    sorted_desc = None  # shared by the two filters — at most ONE vocab sort
    if top_k > 0:
        k = min(int(top_k), logits.shape[-1])
        # Partial selection; the descending top-k values double as the sorted
        # prefix for the top_p pass (masked-out tokens carry zero probability,
        # so the softmax over the k survivors equals the full masked softmax).
        sorted_desc = jax.lax.top_k(logits, k)[0]
        logits = jnp.where(logits < sorted_desc[..., -1:], -jnp.inf, logits)
    if top_p < 1.0:
        if sorted_desc is None:
            sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # A token is cut when the mass BEFORE it already reaches p (so the
        # token that crosses the threshold is kept, and top-1 always is).
        cut = (cum - probs) >= top_p
        cutoff = jnp.min(jnp.where(cut, jnp.inf, sorted_desc), axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    step_key = jax.random.fold_in(key, i)
    return jax.random.categorical(step_key, logits, axis=-1).astype(jnp.int32)


def generate_loop(
    apply_cached: Callable,
    init_cache: Callable,
    params,
    input_ids: jax.Array,
    config,
    max_new_tokens: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
    top_k: int = 0,
    top_p: float = 1.0,
    prefill_chunk: Optional[int] = None,
) -> jax.Array:
    """Dense prompt ``[B, S]`` -> ``[B, S + max_new_tokens]``.

    ``prefill_chunk`` processes the prompt in slices of that many tokens:
    prefill attention scores are ``[B, chunk, max_len]`` instead of
    ``[B, S, max_len]``, which bounds prefill activation memory at long
    context (the decode loop is unaffected).  Identical outputs — the cache
    after chunked prefill equals the one-shot cache."""
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    if temperature <= 0.0 and (top_k > 0 or top_p < 1.0):
        raise ValueError(
            "top_k/top_p filter a SAMPLED distribution; greedy decoding "
            "(temperature<=0, the default) would silently ignore them — pass "
            "temperature>0 (with a PRNG key) to sample."
        )
    b, s = input_ids.shape
    total = s + max_new_tokens
    if max_len is None:
        max_len = total
    if total > max_len:
        raise ValueError(f"prompt ({s}) + max_new_tokens ({max_new_tokens}) > max_len ({max_len})")
    if temperature > 0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    if max_new_tokens == 0:
        return input_ids

    cache = init_cache(config, b, max_len)
    if prefill_chunk is not None and prefill_chunk < 1:
        raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
    if prefill_chunk is None or prefill_chunk >= s:
        logits, cache = apply_cached(params, input_ids, config, cache)
    else:
        # Static chunk count: equal slices of prefill_chunk plus one tail
        # slice — at most two program shapes, no per-chunk retrace churn.
        for start in range(0, s, prefill_chunk):
            logits, cache = apply_cached(
                params, input_ids[:, start : start + prefill_chunk], config, cache
            )
    next_tok = select_token(logits[:, -1], temperature, key, 0, top_k=top_k, top_p=top_p)

    def step(carry, i):
        tok, cache, key = carry
        logits, cache = apply_cached(params, tok[:, None], config, cache)
        nxt = select_token(logits[:, -1], temperature, key, i, top_k=top_k, top_p=top_p)
        return (nxt, cache, key), tok

    (last, _, _), toks = jax.lax.scan(
        step, (next_tok, cache, key), jnp.arange(1, max_new_tokens)
    )
    generated = (
        jnp.concatenate([toks.T, last[:, None]], axis=1) if max_new_tokens > 1 else last[:, None]
    )
    return jnp.concatenate([input_ids, generated], axis=1)


# ---------------------------------------------------------------------------
# Generation by diffusion over blocks (models/sdar_moe.py): a block of
# ``block_length`` positions starts masked, denoising passes unmask the most
# confident positions a few at a time against the cache of everything before
# the block, writing nothing, and one commit pass writes the finished block.
# ---------------------------------------------------------------------------

MASKED = -1  # a masked position of a block's state; the model reads ``mask_token_id`` there


def denoise_schedule(block_length: int, denoise_steps: Optional[int]) -> list:
    """Positions a denoising pass unmasks under the static schedule, pass by
    pass: ``block_length // T`` each, one more in the first ``block_length mod
    T`` (``T`` = ``denoise_steps``, default the block's length: one a pass).  A
    pass never unmasks more than are still masked, so a first block that opens
    on prompt tokens may finish in fewer passes."""
    steps = block_length if denoise_steps is None else int(denoise_steps)
    if not 1 <= steps <= block_length:
        raise ValueError(f"denoise_steps must lie in 1..block_length ({block_length}), got {denoise_steps}")
    return [block_length // steps + (t < block_length % steps) for t in range(steps)]


def block_unmask(state: jax.Array, logits: jax.Array, count: jax.Array, threshold: jax.Array) -> jax.Array:
    """One denoising pass's choice: ``state [B, W]`` (token ids, ``MASKED`` where
    masked), ``logits [B, W, V]`` of that state -> the new state.  At every masked
    position the candidate is the argmax and its confidence the softmax's value
    there, in float32.  Unmasked: the ``count [B]`` masked positions of highest
    confidence (ties to the lower position; never more than are masked), or every
    masked position whose confidence exceeds ``threshold [B]`` where those are
    more (a threshold no confidence reaches, 2.0, is the static schedule).
    Unmasked positions keep their token for good.  The one rule, shared by
    :func:`block_generate_loop` and the serving programs' head."""
    masked = state == MASKED
    logits = logits.astype(jnp.float32)
    best = jnp.max(logits, axis=-1)
    token = jnp.argmax(logits, axis=-1).astype(state.dtype)
    confidence = jnp.exp(best - jax.nn.logsumexp(logits, axis=-1))
    score = jnp.where(masked, confidence, -1.0)
    at = jnp.arange(state.shape[1])
    ahead = (score[:, None, :] > score[:, :, None]) | ((score[:, None, :] == score[:, :, None]) & (at[None, None, :] < at[None, :, None]))
    by_count = masked & (jnp.sum(ahead, axis=-1) < count[:, None])
    over = masked & (confidence > threshold[:, None])
    chosen = jnp.where((jnp.sum(over, axis=-1) > count)[:, None], over, by_count)
    return jnp.where(chosen, token, state)


@functools.lru_cache(maxsize=16)
def _jitted_cached_step(apply_cached: Callable, config):
    return jax.jit(lambda params, ids, cache: apply_cached(params, ids, config, cache))


def block_generate_loop(
    apply_cached: Callable,
    init_cache: Callable,
    params,
    input_ids: jax.Array,
    config,
    max_new_tokens: int,
    denoise_steps: Optional[int] = None,
    confidence_threshold: Optional[float] = None,
    max_len: Optional[int] = None,
    prefill_chunk: Optional[int] = None,
    return_passes: bool = False,
):
    """Greedy generation by diffusion over blocks of ``config.block_length``:
    dense prompt ``[B, S]`` -> ``[B, S + max_new_tokens]``, the serving engine's
    equivalence oracle for such a family (``generate_loop``'s place).

    The first ``(S // B) * B`` prompt tokens are prefilled (whole blocks, in
    chunks of ``prefill_chunk`` if given: a multiple of the block).  Block ``k``
    covers the next ``B`` positions; its state opens on the remaining prompt
    tokens (block 0 only) followed by masks.  A denoising pass runs the state
    (``config.mask_token_id`` at the masks) through ``apply_cached`` and drops
    the returned cache; :func:`block_unmask` unmasks by :func:`denoise_schedule`
    (and by ``confidence_threshold``, if given).  When no mask is left, a commit
    pass of the final tokens keeps its cache.  The last block's tail past
    ``max_new_tokens`` is dropped.  A plain Python loop over jitted forwards:
    an oracle, not a fast path.  ``return_passes`` also gives, for every new
    token, the pass of its block that unmasked it ``[B, max_new_tokens]``."""
    width = int(config.block_length)
    b, s = input_ids.shape
    if width == 1 and not return_passes:
        # A block of one is the causal mask and the engine serves it one row a tick: the autoregressive loop.
        return generate_loop(apply_cached, init_cache, params, input_ids, config, max_new_tokens, max_len=max_len,
                             prefill_chunk=prefill_chunk)
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    schedule = denoise_schedule(width, denoise_steps)
    if prefill_chunk is not None and (prefill_chunk < 1 or prefill_chunk % width):
        raise ValueError(f"prefill_chunk must be a multiple of the block length {width}, got {prefill_chunk}")
    if max_new_tokens == 0:
        return (input_ids, jnp.zeros((b, 0), jnp.int32)) if return_passes else input_ids
    prefilled = s // width * width
    blocks = -(-(s - prefilled + max_new_tokens) // width)
    total = prefilled + blocks * width
    if max_len is None:
        max_len = total
    if total > max_len:
        raise ValueError(f"prompt ({s}) + max_new_tokens ({max_new_tokens}) in whole blocks ({total}) > max_len ({max_len})")
    step = _jitted_cached_step(apply_cached, config)
    unmask = jax.jit(block_unmask)
    cache = init_cache(config, b, max_len)
    chunk = prefilled if prefill_chunk is None else prefill_chunk
    for start in range(0, prefilled, max(chunk, 1)):
        _, cache = step(params, input_ids[:, start : min(start + chunk, prefilled)], cache)
    threshold = jnp.full((b,), 2.0 if confidence_threshold is None else confidence_threshold, jnp.float32)
    out, passes = [input_ids[:, :prefilled]], []
    state = jnp.concatenate(
        [input_ids[:, prefilled:].astype(jnp.int32), jnp.full((b, width - (s - prefilled)), MASKED, jnp.int32)], axis=1)
    for _ in range(blocks):
        unmasked_at = jnp.where(state == MASKED, -1, 0)
        t = 0
        while bool(jnp.any(state == MASKED)):
            logits, _ = step(params, jnp.where(state == MASKED, config.mask_token_id, state), cache)  # writes nothing
            count = jnp.full((b,), schedule[t], jnp.int32)  # masks are left: fewer than T passes have run
            new_state = unmask(state, logits, count, threshold)
            unmasked_at = jnp.where((state == MASKED) & (new_state != MASKED), t, unmasked_at)
            state, t = new_state, t + 1
        _, cache = step(params, state, cache)  # the commit: the block's rows, from its final tokens
        out.append(state)
        passes.append(unmasked_at)
        state = jnp.full((b, width), MASKED, jnp.int32)
    tokens = jnp.concatenate(out, axis=1)[:, : s + max_new_tokens].astype(input_ids.dtype)
    if return_passes:
        return tokens, jnp.concatenate(passes, axis=1)[:, s - prefilled : s - prefilled + max_new_tokens]
    return tokens


def speculative_verify_greedy(
    t_logits: jax.Array,
    drafts: jax.Array,
    draft_len: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-row greedy verify/accept for draft-then-verify decoding — the
    accept kernel shared by the offline :func:`speculative_generate_loop`
    and the serving engine's in-dispatch verify (``serving/engine.py``).

    ``t_logits`` ``[B, γ+1, V]`` are the target's logits over the verify
    window (row ``j`` is the distribution AFTER consuming window token
    ``j``); ``drafts`` ``[B, γ]`` are the draft tokens fed at window
    positions ``1..γ``.  Returns ``(t, m)``: ``t`` ``[B, γ+1]`` the target
    argmax at every window position and ``m`` ``[B]`` the per-row accepted
    count — draft ``j`` is accepted iff it equals the target argmax at
    position ``j-1`` and every earlier draft was accepted.  The emitted
    chunk for row ``b`` is exactly ``t[b, :m[b]+1]``: accepted drafts equal
    the argmax rows they matched, and position ``m`` is the correction (on
    mismatch) or bonus (on full accept) token — which is what makes
    draft-then-verify token-identical to greedy decoding with the target
    alone.

    ``draft_len`` ``[B]`` (optional) masks per-row ragged proposals: draft
    positions at or beyond ``draft_len[b]`` can never be accepted.  This is
    the serving form — a static ``γ`` window carrying variable-length
    n-gram proposals per slot, mixed acceptance across rows in one dispatch.
    """
    gamma = drafts.shape[1]
    t = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)  # [B, γ+1]
    accept = t[:, :gamma] == drafts
    if draft_len is not None:
        accept = accept & (
            jnp.arange(gamma, dtype=jnp.int32)[None, :] < draft_len[:, None]
        )
    m = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)
    return t, m


def speculative_generate_loop(
    apply_cached: Callable,
    init_cache: Callable,
    params,
    config,
    draft_apply_cached: Callable,
    draft_init_cache: Callable,
    draft_params,
    draft_config,
    input_ids: jax.Array,
    max_new_tokens: int,
    num_draft_tokens: int = 4,
    max_len: Optional[int] = None,
    return_stats: bool = False,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Speculative decoding: a small draft model proposes ``γ =
    num_draft_tokens`` tokens autoregressively, the target verifies all of
    them (plus a bonus position) in ONE cached forward, and the longest
    accepted prefix lands — ``1..γ+1`` tokens per target forward instead
    of exactly 1.  Net-new vs the reference (no generation engine
    upstream); the TPU angle is that the whole propose→verify→accept round
    — including the variable-length accept — is one ``lax.while_loop``
    with static shapes, compiled once.

    Two modes, both distribution-exact w.r.t. the target alone:

    - ``temperature <= 0`` (default) — greedy: a draft token is accepted
      iff it equals the target's argmax; on mismatch the target's argmax
      is emitted.  Output **token-identical to greedy decoding with the
      target alone**.
    - ``temperature > 0`` (needs ``key``) — the Leviathan/Chen rejection
      scheme: draft token ``x`` (sampled from the draft's softmax ``q``)
      is accepted with probability ``min(1, p(x)/q(x))`` against the
      target's softmax ``p``; on rejection the replacement is sampled
      from the residual ``normalize(max(p - q, 0))``, and a full accept
      earns a bonus token sampled from ``p``.  Each emitted token is
      **exactly distributed as target-only sampling** at this
      temperature (the classic telescoping identity), so the speedup is
      again free of quality risk.

    Cache bookkeeping: both caches keep the invariant "``index`` counts the
    tokens strictly before ``last`` (the newest emitted, not-yet-fed
    token)".  Each round writes ``γ+1`` rows into both caches and then
    *rewinds* ``index`` to the accepted length; the next round's writes
    cover every stale row before any query can attend it (write extent
    ``[index', index'+γ]`` ⊇ stale ``[index', index+γ]`` since the accept
    count is ≥ 1), and the families' position-based causal mask hides
    anything beyond ``index``.

    This *offline loop* is batch-1 only: the dense bundled cache carries a
    single shared ``index``, so rows with different accept counts would
    need per-row cache indices.  That is a limitation of this loop's cache
    layout, **not** of speculative decoding — the serving engine runs the
    per-slot form (``ServingConfig.spec_tokens``) where paged block tables
    already carry per-slot lengths, so one fused dispatch verifies every
    slot's window with per-slot variable acceptance (the accept kernel,
    :func:`speculative_verify_greedy`, is shared with this loop).  ``top_k``
    / ``top_p`` are not supported here — filtering changes both
    distributions and the residual algebra; use ``generate_loop`` for
    filtered sampling.

    ``return_stats=True`` additionally returns ``{"rounds", "proposed",
    "accepted"}`` (int32 scalars): ``accepted / proposed`` is the draft
    acceptance rate — the quantity that decides the real-world speedup
    (``rounds`` target forwards produced ``accepted + rounds`` tokens).
    """
    b, s = input_ids.shape
    if b != 1:
        raise ValueError(
            f"speculative decoding is batch-1 only (got batch {b}): rows with "
            "different accept counts would need per-row cache indices"
        )
    sampled = temperature > 0.0
    if sampled and key is None:
        raise ValueError("sampled speculative decoding (temperature > 0) needs a PRNG key")
    gamma = int(num_draft_tokens)
    if gamma < 1:
        raise ValueError(f"num_draft_tokens must be >= 1, got {num_draft_tokens}")
    tv = getattr(config, "vocab_size", None)
    dv = getattr(draft_config, "vocab_size", None)
    if tv != dv:
        raise ValueError(f"target and draft vocab sizes differ: {tv} vs {dv}")
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    if max_new_tokens == 0:
        return input_ids
    # The last round can start at generated-count max_new-1 and still write
    # γ+1 rows — the caches need that much slack past the final token.
    need = s + max_new_tokens + gamma
    if max_len is None:
        max_len = need
    elif max_len < need:
        raise ValueError(
            f"max_len ({max_len}) < prompt + max_new_tokens + num_draft_tokens "
            f"({need}): the verify writes need overshoot room"
        )

    t_cache = init_cache(config, b, max_len)
    d_cache = draft_init_cache(draft_config, b, max_len)
    t_logits, t_cache = apply_cached(params, input_ids, config, t_cache)
    _, d_cache = draft_apply_cached(draft_params, input_ids, draft_config, d_cache)
    if sampled:
        # fp32 before the divide: the PROPOSAL distribution and the p/q used
        # in acceptance must be computed from identical logits, or the
        # rejection identity (and the exactness claim) silently breaks on
        # bf16 models.
        first = jax.random.categorical(
            jax.random.fold_in(key, 0),
            t_logits[:, -1].astype(jnp.float32) / temperature,
            axis=-1,
        ).astype(jnp.int32)
    else:
        first = jnp.argmax(t_logits[:, -1], axis=-1).astype(jnp.int32)  # [B]

    buf = jnp.zeros((b, max_new_tokens + gamma + 1), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, first[:, None], (0, 0))

    def cond(carry):
        return carry[0] < max_new_tokens

    def body(carry):
        n, last, t_cache, d_cache, buf, rounds, accepted = carry
        # Per-round key stream, derived from the static base key and the
        # round counter — deterministic, no key in the carry.
        rkey = jax.random.fold_in(key, 1 + rounds) if sampled else None

        # Draft proposes γ tokens — a one-token cached step under lax.scan
        # (cache in the carry), so the draft forward compiles ONCE however
        # large γ is.  One extra feed (logits discarded) keeps the draft
        # cache covering d_γ so a full accept stays aligned.
        def d_step(dcarry, j):
            dc, tok = dcarry
            dl, dc = draft_apply_cached(draft_params, tok[:, None], draft_config, dc)
            logits = dl[:, -1].astype(jnp.float32)  # [B, V]; fp32 so q == the
            # distribution actually sampled (see the `first` comment)
            if sampled:
                nxt = jax.random.categorical(
                    jax.random.fold_in(rkey, j), logits / temperature, axis=-1
                ).astype(jnp.int32)
            else:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (dc, nxt), (nxt, logits)

        (dc, tok), (d_steps, d_logits) = jax.lax.scan(
            d_step, (d_cache, last), jnp.arange(gamma)
        )
        _, dc = draft_apply_cached(draft_params, tok[:, None], draft_config, dc)
        d = jnp.moveaxis(d_steps, 0, 1)  # [γ, B] -> [B, γ]

        # Target verifies [last, d_1..d_γ] in one forward: row j carries the
        # target's distribution AFTER consuming seq[:, j].
        seq = jnp.concatenate([last[:, None], d], axis=1)  # [B, γ+1]
        t_logits, tc = apply_cached(params, seq, config, t_cache)

        if sampled:
            # Rejection acceptance: keep d_j with prob min(1, p(d_j)/q(d_j)).
            p = jax.nn.softmax(t_logits.astype(jnp.float32) / temperature, axis=-1)
            q = jax.nn.softmax(
                jnp.moveaxis(d_logits, 0, 1).astype(jnp.float32) / temperature, axis=-1
            )  # [B, γ, V]
            p_head = p[:, :gamma]
            p_at_d = jnp.take_along_axis(p_head, d[..., None], axis=-1)[..., 0]
            q_at_d = jnp.take_along_axis(q, d[..., None], axis=-1)[..., 0]
            u = jax.random.uniform(jax.random.fold_in(rkey, gamma), (b, gamma))
            accept = (u * jnp.maximum(q_at_d, 1e-30) < p_at_d).astype(jnp.int32)
            m = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)[0]  # scalar; b == 1
            # Replacement at the stop position: residual normalize(max(p-q, 0))
            # on a rejection, plain p on a full accept (bonus token).  A ~zero
            # residual (p == q numerically) falls back to p — acceptance was
            # then certain, so the branch is all but unreachable anyway.
            resid = jnp.maximum(p_head - q, 0.0)
            mass = jnp.sum(resid, axis=-1, keepdims=True)
            resid = jnp.where(mass > 1e-9, resid, p_head)
            dist = jnp.concatenate([resid, p[:, gamma:]], axis=1)  # [B, γ+1, V]
            dist_m = jax.lax.dynamic_index_in_dim(dist, m, axis=1, keepdims=False)
            fill = jax.random.categorical(
                jax.random.fold_in(rkey, gamma + 1), jnp.log(dist_m + 1e-38), axis=-1
            ).astype(jnp.int32)  # [B]
            fill_col = jnp.broadcast_to(fill[:, None], (b, gamma + 1))
        else:
            # Greedy acceptance: d_j must equal the target argmax; the fill
            # column is the target argmax itself (correction or bonus).
            # Shared per-row kernel with the serving engine's in-dispatch
            # verify — see speculative_verify_greedy.
            t, m_rows = speculative_verify_greedy(t_logits, d)
            m = m_rows[0]  # scalar; b == 1
            fill_col = t

        # The accepted chunk is [d_1..d_m, fill] — count = m+1, uniformly.
        count = m + 1
        d_pad = jnp.concatenate([d, jnp.zeros((b, 1), jnp.int32)], axis=1)
        chunk = jnp.where(jnp.arange(gamma + 1)[None, :] < m, d_pad, fill_col)
        buf = jax.lax.dynamic_update_slice(buf, chunk, (0, n))
        last = jax.lax.dynamic_index_in_dim(chunk, m, axis=1, keepdims=False)
        # Rewind both caches to the accepted length (both wrote γ+1 rows).
        tc = {**tc, "index": tc["index"] - (gamma + 1) + count}
        dc = {**dc, "index": dc["index"] - (gamma + 1) + count}
        return n + count, last, tc, dc, buf, rounds + 1, accepted + m

    zero = jnp.asarray(0, jnp.int32)
    carry = (jnp.asarray(1, jnp.int32), first, t_cache, d_cache, buf, zero, zero)
    _, _, _, _, buf, rounds, accepted = jax.lax.while_loop(cond, body, carry)
    out = jnp.concatenate([input_ids, buf[:, :max_new_tokens]], axis=1)
    if return_stats:
        return out, {"rounds": rounds, "proposed": rounds * gamma, "accepted": accepted}
    return out


def beam_search(
    apply_cached: Callable,
    init_cache: Callable,
    params,
    input_ids: jax.Array,
    config,
    max_new_tokens: int,
    num_beams: int = 4,
    length_penalty: float = 1.0,
    eos_token_id: Optional[int] = None,
    max_len: Optional[int] = None,
) -> jax.Array:
    """Beam search over the shared KV cache — one compiled XLA program.

    Dense prompt ``[B, S]`` -> best sequence ``[B, S + max_new_tokens]``.
    Each step scores ``num_beams * vocab`` continuations, keeps the top
    ``num_beams``, and reorders the cache rows to follow their beams (the
    same reorder torch generation does, here a ``jnp.take`` inside the scan).
    Beams that emit ``eos_token_id`` freeze: their score stops accumulating
    and they pad with EOS.  Final ranking divides by ``length**length_penalty``
    (>1 favors longer sequences, <1 shorter).

    Cache contract: every cache leaf with ``ndim >= 2`` MUST carry the batch
    on **axis 1** (the bundled families' ``[L, B, max_len, K, hd]`` layout from
    :func:`make_kv_cache` does).  Beam tiling/reordering identifies
    batch-bearing leaves by ``leaf.shape[1] == batch`` (then ``== batch*K``
    inside the scan); a custom ``init_cache`` whose batch lives on another
    axis — or a non-batch leaf whose axis-1 size coincides with the batch —
    is silently mis-tiled.  Scalar/1-D leaves (e.g. the write index) are
    left untouched.
    """
    if max_new_tokens < 1:
        raise ValueError("beam search needs max_new_tokens >= 1")
    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    b, s = input_ids.shape
    kbeams = num_beams
    total = s + max_new_tokens
    if max_len is None:
        max_len = total
    if total > max_len:
        raise ValueError(f"prompt ({s}) + max_new_tokens ({max_new_tokens}) > max_len ({max_len})")

    # Prefill ONCE at batch B (all beams share the prompt — tiling the prompt
    # would multiply prefill FLOPs/HBM by K), then tile the cache rows per beam.
    cache = init_cache(config, b, max_len)
    logits, cache = apply_cached(params, input_ids, config, cache)
    cache = jax.tree.map(
        lambda leaf: jnp.repeat(leaf, kbeams, axis=1)
        if leaf.ndim >= 2 and leaf.shape[1] == b
        else leaf,
        cache,
    )
    logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)  # [B, V]
    vocab = logp.shape[-1]
    if kbeams > vocab:
        raise ValueError(
            f"num_beams ({kbeams}) > vocab_size ({vocab}): top_k cannot select "
            "more beams than there are tokens"
        )

    # First expansion: the top-K tokens of the single (shared) beam.
    scores, tokens = jax.lax.top_k(logp, kbeams)  # [B, K]
    tokens = tokens.astype(jnp.int32)
    finished = (
        tokens == eos_token_id if eos_token_id is not None else jnp.zeros_like(tokens, bool)
    )
    lengths = jnp.ones((b, kbeams), jnp.int32)

    out = jnp.zeros((b, kbeams, max_new_tokens), jnp.int32)
    out = out.at[:, :, 0].set(tokens)

    batch_offsets = (jnp.arange(b) * kbeams)[:, None]  # [B, 1]

    def step(carry, i):
        tokens, scores, finished, lengths, out, cache = carry
        logits, new_cache = apply_cached(
            params, tokens.reshape(b * kbeams, 1), config, cache
        )
        logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
        logp = logp.reshape(b, kbeams, vocab)
        if eos_token_id is not None:
            # Frozen beams only continue with EOS at zero added score.
            frozen = jnp.full((vocab,), -jnp.inf).at[eos_token_id].set(0.0)
            logp = jnp.where(finished[:, :, None], frozen[None, None, :], logp)
        cand = (scores[:, :, None] + logp).reshape(b, kbeams * vocab)
        new_scores, flat_idx = jax.lax.top_k(cand, kbeams)
        beam_idx = (flat_idx // vocab).astype(jnp.int32)  # [B, K] source beam
        new_tokens = (flat_idx % vocab).astype(jnp.int32)

        gather_rows = (batch_offsets + beam_idx).reshape(-1)  # [B*K] cache rows

        def reorder(leaf):
            if leaf.ndim >= 2 and leaf.shape[1] == b * kbeams:
                return jnp.take(leaf, gather_rows, axis=1)
            return leaf

        cache = jax.tree.map(reorder, new_cache)
        out = jnp.take_along_axis(out, beam_idx[:, :, None], axis=1)
        out = out.at[:, :, i].set(new_tokens)
        prev_finished = jnp.take_along_axis(finished, beam_idx, axis=1)
        lengths = jnp.take_along_axis(lengths, beam_idx, axis=1) + (~prev_finished)
        if eos_token_id is not None:
            finished = prev_finished | (new_tokens == eos_token_id)
        else:
            finished = prev_finished
        return (new_tokens, new_scores, finished, lengths, out, cache), None

    if max_new_tokens > 1:
        (tokens, scores, finished, lengths, out, cache), _ = jax.lax.scan(
            step,
            (tokens, scores, finished, lengths, out, cache),
            jnp.arange(1, max_new_tokens),
        )

    ranked = scores / (lengths.astype(jnp.float32) ** length_penalty)
    best = jnp.argmax(ranked, axis=1)  # [B]
    best_out = jnp.take_along_axis(out, best[:, None, None], axis=1)[:, 0]  # [B, max_new]
    return jnp.concatenate([input_ids, best_out], axis=1)
