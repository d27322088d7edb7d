"""The engine's compiled programs: how a dispatch reads the pool.

A tick issues **one** dispatch: one **forward** over the new tokens of all its
lanes and one **head** that turns the logits into what the tick reads back.
The forward depends on the cache back end, the head on what the tick holds;
each is written once here.

``forward(params, pool, groups)`` takes a short tuple of groups ``(tokens [B,
T], tables [B, M], starts [B])`` and gives ``(logits, counters, rows)``, logits
``[B, T, V]`` and rows ``{leaf: [B, L, T, ...]}`` a group: lane ``b`` of a
group has its tokens at positions ``starts[b] .. starts[b] + T - 1`` of the
sequence its table row names, and ``rows`` are the cache rows those tokens
wrote.  A group is what shares one ``T``: the decoding lanes (``T`` 1, or ``k
+ 1`` under speculation) are one, a prefilling slot's chunk (``B`` 1, ``T``
``prefill_chunk``) another.  The head scatters the rows into the donated pool
(``_write_rows``) after it has read the logits, so the new pool is the last
thing a program computes.

The pool holds two kinds of leaf (``models/generation.py`` tells them apart):
**token rows** by block, which every family has, and, for a family whose cache
says so (``lfm2_moe``: its short convolutions), a **state** by decode slot: one
entry a sequence, whatever its length.  For such a family a group carries two
things more, ``(tokens, tables, starts, slots [B], counts [B])``: which slot's
state a lane reads and writes (the decoding lanes are the slots in order; the
chunk's lane is told its slot), and how many of its ``T`` rows are real (a
decoding lane 1, a chunk its ``n_real``, a lane that is not live 0).  What the
forward returns under ``generation.STATE`` is the state *after row ``counts -
1``*, and ``_write_rows`` writes it by slot for the lanes with ``counts > 0``
alone: an idle lane, and the decoding lane of the slot whose chunk rides in
the same dispatch, computed on padding and leave their entry bit for bit.  A
lane at ``starts == 0`` reads a zero state inside the program, so a slot's
next request never sees its predecessor and admission costs no host work.  A
family without a state compiles to the programs it always had: no argument,
no operation more.

Token rows are themselves of two kinds for a family whose cache says so
(``afmoe``: its sliding layers' leaves under ``generation.WINDOW``): rows a
**full** layer keeps for the whole sequence, behind the block tables above, and
rows a **window** layer keeps in a ring of blocks, behind a **window table** of
fixed width (logical block ``j`` at entry ``j mod width``).  For such a family
a group is ``(tokens, tables, starts, window tables [B, Mw])``, ``Mw`` =
:meth:`ServingPrograms.window_width` of the dispatch's table width (the
ring's, or the block tables' own while they are narrower: then nothing has
wrapped), the programs take the lanes' window tables ``[S, Mw]`` (and
``decode_chunk`` the chunk's ``[Mw]``) behind their other arguments, and
``_write_rows`` puts the rows returned under ``WINDOW`` each at its place in
the ring.  Never beside a state, never under block generation (refused by name).

- **paged** (a family with an ``apply_paged``: gpt2, llama, deepseek_v3, lfm2_moe, sdar_moe, afmoe, keye_vl2):
  the family runs everything that does not look at the cache (embedding,
  norms, projections, the MLP or the experts, the head) once over the rows of
  all groups, so the weights, and the experts the rows hit, stream once a
  tick; attention runs a group at a time, reading the pool in place through
  that group's block tables.  No per-slot view of the cache exists on either
  side of the dispatch.  An expert family returns its per-dispatch counters
  as a third value; they ride out behind the ``ok`` flags.
- **dense** (a family without one: mixtral, whose capacity routing depends
  on who shares the batch, so lanes must not be batched together): gather
  each lane's whole view through its table row, run the family's
  ``apply_cached`` lane by lane under ``vmap``, cut the written rows out of
  the updated views; a group at a time inside the one program.

The family decides the back end, and the window of a decoding lane: a family
generated by diffusion over blocks (a model config with ``block_length`` ``B >
1`` and ``mask_token_id``: ``models/sdar_moe.py``) gets the **block heads**
(:func:`_block_heads`): a lane's ``W = B`` rows are the block it carries, the
feed is every lane's block state ``[max_slots, B]``, the head is "confidence and
argmax at every masked position, unmask the ``count`` most confident", and the
lanes' rows are written to the pool for the lanes that **commit** in this
dispatch alone (``count``, ``commit``, ``threshold`` are per-lane inputs: still
two programs a table width, still named ``decode*``); the chunk's head yields
no token.  Every other family compiles to what is described below, argument
for argument.  Nothing selects any of it.  The two programs of an
engine, each compiled once per table width: ``decode`` (the decoding lanes
alone: argmax of the one row a lane, or with ``spec_tokens > 0`` the ``k + 1``
window through ``speculative_verify_greedy``) and ``decode_chunk`` (the same
lanes **and** one lane's padded chunk: also the argmax at ``n_real - 1`` and
the chunk's own ``ok``; a chunk with no live decoder rides it with the lanes
idle, which only a cold start sees).  Both groups of ``decode_chunk`` share
one table width, the wider of the two needs.  What a program returns beside
the pool is one int32 vector, so a tick reads one array back
(:meth:`ServingPrograms.unpack`), and the **feed**.  A family with a state takes its lanes' ``live``
flags (and ``decode_chunk`` the chunk's slot) behind those arguments.  Both
take a trailing per-lane poison vector when the NaN fault is armed; an unarmed
program is traced without it.

**The feed stays on the device.**  The engine dispatches tick N + 1 before it
has read tick N back (``serving/engine.py``), so the token a decoding lane
feeds next is, as a value, known to the device alone.  Each program returns
``feed``, int32 ``[max_slots + 1]``: every lane's next token and, in the last
place, the chunk's token (0 from ``decode``): one shape from both programs, so
either's output is the other's input and no program compiles twice for it.
Each takes the previous dispatch's ``feed`` and a per-lane ``source [S]`` and
selects the lanes' input tokens on the device: ``FEED_HOST`` (the host's
``tokens[:, 0]``, what every lane reads after a settle: the feed passed is
still the last dispatch's, so that a program has one signature, and no lane
reads it), ``FEED_LANE`` (the lane's own entry: it decoded in the previous
dispatch) or ``FEED_CHUNK`` (the last entry: the lane's prompt ended in the
previous dispatch's chunk).  A chunk's tokens are the prompt's and come from
the host always.  Under a verify window the entry is the last token a lane
accepted, which an engine that settles every tick never reads.

The profile names a program after its Python function (``jit_decode``,
``jit_decode_chunk``) and ``chipbench/`` selects operations by the prefix
``jit_decode``.
"""

from __future__ import annotations

import inspect
from dataclasses import dataclass
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..models.generation import (
    MASKED,
    STATE,
    WINDOW,
    block_unmask,
    extract_token_rows,
    gather_block_view,
    scatter_token_rows,
    scatter_window_rows,
    speculative_verify_greedy,
    write_state_rows,
)

__all__ = [
    "ATTN_COUNTERS", "DISPATCH_COUNTERS", "FEED_CHUNK", "FEED_HOST", "FEED_LANE", "MOE_COUNTERS", "SPARSE_COUNTERS",
    "WINDOW_COUNTERS", "ServingPrograms", "build_programs",
]

# Where a decoding lane's input token comes from (a program's per-lane ``source``): the host's ``tokens``, the lane's own
# entry of the previous dispatch's ``feed``, or that feed's last entry, the chunk's token.
FEED_HOST, FEED_LANE, FEED_CHUNK = 0, 1, 2

# What an expert family's ``apply_paged`` counts in a dispatch (``models/deepseek_v3.py:expert_counters``), each
# summed over its expert layers: token-expert pairs computed, experts with at least one row, the hottest expert's rows,
# and the row tiles the fused grouped product computed (0 for a dispatch whose expert layers ran ``lax.ragged_dot``: the
# counter says which product a program runs; ``moe_rows`` / (``moe_row_tiles`` x ``ops/pallas_moe.py:ROW_TILE``) is the
# tiles' fill), and every pair the routers made (``moe_rows`` again where a layer's experts are all held; under a share,
# ``moe_rows / moe_pairs_routed`` is the share's load).
MOE_COUNTERS = ("moe_rows", "moe_experts_hit", "moe_max_rows", "moe_row_tiles", "moe_pairs_routed")
# What a family with window leaves counts beside them (``models/afmoe.py``), summed over a dispatch's decoding lanes and
# window layers: the rows the window layers attended over, and the rows they would have had every layer read the whole
# context.
WINDOW_COUNTERS = ("window_rows_read", "context_rows")
# What the decoding lanes' attention read where it took the paged kernel (``generation.reads_in_place``): the rows the
# kernel copied, summed over the lanes and the layers that took it, the edge blocks whole; 0 for a dispatch that
# gathered its context (as ``moe_row_tiles`` is 0 for ``lax.ragged_dot``).
ATTN_COUNTERS = ("attn_rows_read",)
# What a family whose attention reads the rows an indexer selects counts (``models/keye_vl2.py:sparse_counters``), each
# summed over its layers and the lanes that hold a sequence: the index keys scored (a lane's context), the K/V rows the
# queries attended over (``min(position + 1, topk)`` a query), and the rows the least read of a lane's selection takes
# (``min(context, topk)`` a lane); ``context_rows`` above is what a full causal mask would have admitted; and the
# queries of a chunk whose selection left out a key tied at its threshold (``keye_vl2.apply_paged``: the exact select's
# one data-dependent path).
SPARSE_COUNTERS = ("index_rows_scored", "sparse_rows_read", "sparse_lane_rows", "select_tied_rows")
# Every counter a dispatch can carry behind its ``ok`` flags, in the order they are packed; a family that returns
# counters packs all of them, 0 for what it does not count, so that the host reads one layout.
DISPATCH_COUNTERS = MOE_COUNTERS + WINDOW_COUNTERS + ATTN_COUNTERS + SPARSE_COUNTERS

# No block table is narrower than this many rows.  Every width an engine meets compiles both of its programs, and on the
# host of a v5e that is one to two seconds of a process's set-up a width even with a warm compile cache (tracing and
# lowering are not cached; PERF.md section 6, PR 31), while under 256 rows of context the block gather of sixteen lanes is
# 0.4 ms of an 11 ms dispatch (the probe of PERF.md section 6, PR 29).
MIN_TABLE_ROWS = 256


@dataclass(frozen=True)
class ServingPrograms:
    """The jitted programs of one engine (the pool, argument 1, donated) and
    what a tick has to know about the back end they were built for."""

    backend: str  # "paged" | "dense": what the family decided
    decode: Callable  # (params, pool, tables [S, M], lengths [S], tokens [S, W], draft_len [S], feed [S + 1], source [S], *state, *poison)
    decode_chunk: Callable  # (..., source [S], table_row [M], start, chunk [1, C], n_real, *state, *poison) -> (packed, feed, pool), both
    stateful: bool  # the pool holds a state by slot: *state is (live [S],), with a chunk (live [S], slot)
    ring_blocks: int  # > 0: the pool holds window leaves, a sequence's window table is a ring this wide; *state is led by (window tables [S, Mw],), with a chunk (window tables, the chunk's window table [Mw])
    window: int  # W: 1, or k + 1 under speculation, or the family's block length
    max_slots: int
    min_blocks: int  # the narrowest table: MIN_TABLE_ROWS in blocks, a power of two
    max_blocks: int
    block: int = 1  # B > 1: a lane carries a block of B positions through denoising passes and a commit (the block heads)

    def table_width(self, blocks_needed: int) -> int:
        """The block-table width of a dispatch whose widest lane needs
        ``blocks_needed`` blocks.  Paged: the next power of two from
        ``min_blocks`` up, capped at the configured maximum — each width
        compiles once (jit caches per shape) and gather traffic scales with
        what live requests own.  Dense: the one static width, the view is
        always whole."""
        if self.backend == "dense":
            return self.max_blocks
        width = self.min_blocks
        while width < blocks_needed:
            width *= 2
        return min(width, self.max_blocks)

    def window_width(self, width: int) -> int:
        """The width of the window tables in a dispatch whose block tables are
        ``width`` wide: the ring's, or the block tables' own where they are
        narrower (then no sequence of the dispatch has wrapped, and its logical
        block ``j`` lies at entry ``j``).  What a window layer gathers and
        attends over is bounded by the ring, not by ``width``.  0 without
        window leaves."""
        return min(width, self.ring_blocks)

    def gathered_blocks(self, owned: Sequence[int]) -> int:
        """Blocks a decode dispatch gathers of one kind (full or window),
        ``owned`` being the blocks of that kind of each live lane.  Paged reads
        the blocks the tables name; dense gathers every lane's worst-case
        view, live or not."""
        if self.backend == "dense":
            return self.max_slots * self.max_blocks
        return sum(owned)

    def unpack(self, packed, with_chunk: bool) -> dict:
        """The host's view of the vector a program returned (the one sync
        point and the one read-back of a tick; it waits for that program
        alone, a later one queued behind it runs on): ``tokens [S, W]``, ``accepts
        [S]`` (zeros without speculation), ``ok [S]``, with a chunk
        ``chunk_token`` and ``chunk_ok``, and ``counters`` (what an expert
        family put behind them, else empty)."""
        flat = np.asarray(packed)
        s, w = self.max_slots, self.window
        spec = w > 1 and self.block == 1  # a block family's [S, W] is every lane's block state: nothing is accepted
        sizes = [("tokens", s * w), ("accepts", s if spec else 0), ("ok", s)]
        sizes += [("chunk_token", 1), ("chunk_ok", 1)] if with_chunk else []
        out, at = {}, 0
        for name, n in sizes:
            out[name] = flat[at : at + n]
            at += n
        out["tokens"] = out["tokens"].reshape(s, w)
        out["accepts"] = out["accepts"] if spec else np.zeros((s,), np.int32)
        out["counters"] = flat[at:]
        return out


def build_programs(
    apply_cached: Callable, config, leaf_names, serving, spec_tokens: int, stateful: bool = False, ring_blocks: int = 0
) -> ServingPrograms:
    """The programs of an engine that serves ``apply_cached``'s family over a
    pool with token leaves ``leaf_names`` (and, ``stateful``, a state by slot;
    ``ring_blocks > 0``, window leaves behind window tables that wide), at
    ``serving``'s geometry."""
    apply_paged = getattr(inspect.getmodule(apply_cached), "apply_paged", None)
    if apply_paged is not None:
        backend, forward = "paged", _paged_forward(apply_paged, config)
    elif stateful or ring_blocks:
        raise ValueError(
            "a family whose cache holds a state a sequence, or window leaves, is served by its apply_paged: it has none")
    else:
        backend, forward = "dense", _dense_forward(apply_cached, config, leaf_names)
    # The window of a decoding lane is the family's to say: a family generated by diffusion over blocks carries its
    # block length (and the id its masked positions read) in its model config; any other compiles to what it had.
    block = int(getattr(config, "block_length", 1))
    if ring_blocks and (stateful or block > 1):
        raise ValueError("window leaves beside a state a sequence, or under block generation, are not served")
    if block > 1:
        _check_block_geometry(block, backend, serving, spec_tokens, stateful)
        decode, decode_chunk = _block_heads(forward, int(config.mask_token_id))
    else:
        decode, decode_chunk = _heads(forward, stateful, bool(ring_blocks))
    return ServingPrograms(
        backend=backend,
        decode=jax.jit(decode, donate_argnums=(1,)),
        decode_chunk=jax.jit(decode_chunk, donate_argnums=(1,)),
        stateful=stateful,
        ring_blocks=ring_blocks,
        window=block if block > 1 else spec_tokens + 1,
        max_slots=serving.max_slots,
        min_blocks=1 << max(0, -(-MIN_TABLE_ROWS // serving.block_size) - 1).bit_length(),
        max_blocks=serving.resolved_max_blocks(),
        block=block,
    )


def _check_block_geometry(block: int, backend: str, serving, spec_tokens: int, stateful: bool) -> None:
    """What block generation cannot be served with, refused by name."""
    if spec_tokens:
        raise ValueError(
            f"spec_tokens={spec_tokens} with a family generated by diffusion over blocks (block_length {block}): a lane's "
            f"window is its block of masked positions, denoised in place and written once at the commit; a verify window "
            f"of drafted tokens accepted by prefix has no meaning there"
        )
    if serving.prefill_chunk % block or serving.block_size % block:
        raise ValueError(
            f"prefill_chunk ({serving.prefill_chunk}) and block_size ({serving.block_size}) must be multiples of the "
            f"family's block_length ({block}): a chunk and a pool block hold whole blocks, so that no row sees past them"
        )
    if stateful or backend != "paged":
        raise ValueError("block generation is served by a family's apply_paged over token rows alone (no state a sequence)")


# -- the two forwards ---------------------------------------------------------


def _paged_forward(apply_paged: Callable, config) -> Callable:
    def forward(params, pool, groups):
        logits, rows, *counters = apply_paged(params, groups, config, pool)
        return logits, counters, rows

    return forward


def _dense_forward(apply_cached: Callable, config, names) -> Callable:
    def one_group(params, pool, tokens, tables, starts):
        caches = {n: gather_block_view(pool[n], tables) for n in names}
        caches["index"] = starts

        def one(cache, toks):
            logits, new_cache = apply_cached(params, toks[None, :], config, cache)
            return logits[0], new_cache

        logits, new_caches = jax.vmap(one)(caches, tokens)
        return logits, {n: extract_token_rows(new_caches[n], starts, tokens.shape[1]) for n in names}

    def forward(params, pool, groups):
        logits, rows = zip(*(one_group(params, pool, *group) for group in groups))
        return logits, [], rows

    return forward


def _write_rows(pool: dict, rows: dict, tables, starts, count: int, *lanes, keep=None, wtables=None) -> dict:
    """The pool with what a forward wrote for one group written in.  Token
    rows are scattered through the group's tables: rows past a lane's accepted
    length (a verify window) or past a chunk's real tokens are stale by
    construction, the next dispatch at that position re-writes them before any
    mask admits them.  A state is no row: nothing re-writes it, so what comes
    back is already the state after the lane's last *real* row, and it is
    written by slot (``lanes`` = the group's slots and counts) for the lanes
    that advanced alone (``write_state_rows``).  With ``keep [B]`` only those
    lanes' token rows are written (a block family's committing lanes): a
    denoising pass leaves the pool bit for bit.  Rows under ``WINDOW`` go
    through the group's window tables ``wtables``, each position to its place
    in the ring."""
    new_pool = dict(pool)
    for n, r in rows.items():
        if n == STATE:
            new_pool[n] = write_state_rows(pool[n], r, *lanes)
        elif n == WINDOW:
            with jax.named_scope("kv_pool.window"):
                new_pool[n] = {
                    leaf: scatter_window_rows(pool[n][leaf], leaf_rows, wtables, starts, count) for leaf, leaf_rows in r.items()}
        else:
            new_pool[n] = scatter_token_rows(pool[n], r, tables, starts, count, keep)
    return new_pool


# -- the heads ----------------------------------------------------------------


def _packed(parts, counters):
    """What a tick reads back, as one int32 vector (:meth:`ServingPrograms.unpack`
    is its inverse): the heads' parts in order and, for a family whose
    ``apply_paged`` returns expert counters as a third value, those counters
    behind them.  A family without experts returns two values and compiles to
    a program without them."""
    if counters:
        parts = [*parts, jnp.stack([jnp.asarray(counters[0].get(name, 0), jnp.int32) for name in DISPATCH_COUNTERS])]
    return jnp.concatenate([jnp.ravel(p).astype(jnp.int32) for p in parts])


def _poisoned(logits, poison):
    """The NaN fault's lane (``resilience/faultinject.py``): a trace-time
    gate, an unarmed program carries no plumbing.  Every other lane
    multiplies by 1.0 and keeps its tokens bit for bit."""
    if not poison:
        return logits
    return logits * jnp.expand_dims(poison[0], tuple(range(1, logits.ndim)))


def _lanes_head(logits, tokens, draft_len, poison):
    """The decoding lanes' part of a head: ``(tokens [S, W], accepts [S] or
    nothing), ok [S], next [S]`` from their logits ``[S, W, V]``.  One row a lane emits
    its argmax; a ``k + 1`` window goes through the greedy accept rule.  The
    finiteness flag is per lane, folded into the same dispatch: a poisoned
    lane's token is never emitted.  ``next`` is the token a lane feeds next,
    the feed's entry: the argmax, or the last token a window accepted."""
    logits = _poisoned(logits, poison)
    ok = jnp.all(jnp.isfinite(logits), axis=(1, 2))
    if logits.shape[1] == 1:
        token = jnp.argmax(logits[:, -1], axis=-1)
        return [token], ok, token
    t, m = speculative_verify_greedy(logits, tokens[:, 1:], draft_len)
    return [t, m], ok, jnp.take_along_axis(t, m[:, None], axis=1)[:, 0]


def _fed(tokens, feed, source):
    """The lanes' tokens ``[S, W]`` with the first column taken from where
    ``source`` says: the host's own value, the lane's entry of the previous
    dispatch's ``feed``, or its last entry (the chunk's token)."""
    s = tokens.shape[0]
    first = jnp.where(source == FEED_LANE, feed[:s], jnp.where(source == FEED_CHUNK, feed[s], tokens[:, 0]))
    return tokens.at[:, 0].set(first)


def _feed(lanes_next, chunk_token):
    """What the next dispatch may read in place of host tokens: ``[S + 1]``."""
    return jnp.concatenate([lanes_next, jnp.reshape(chunk_token, (1,))]).astype(jnp.int32)


def _heads(forward: Callable, stateful: bool = False, windowed: bool = False):
    """``decode`` and ``decode_chunk`` over ``forward``: the names are the
    profile's (``jit_decode``, ``jit_decode_chunk``).  ``draft_len`` is read
    by a ``k + 1`` window only (jit drops an argument nothing reads).  With a
    state, ``rest`` leads with ``live [S]`` (1 where a lane decodes) and, in
    ``decode_chunk``, the chunk's slot, and the groups carry (slots, counts).
    With window leaves (never beside a state) ``rest`` leads with the lanes'
    window tables ``[S, Mw]`` and, in ``decode_chunk``, the chunk's ``[Mw]``,
    and a group carries its window tables behind its starts.  Both return
    ``(packed, feed, pool)``."""

    def decode(params, pool, tables, lengths, tokens, draft_len, feed, source, *rest):
        tokens = _fed(tokens, feed, source)
        state, wtables = (), None
        if windowed:
            wtables, *rest = rest
        if stateful:
            live, *rest = rest
            state = (jnp.arange(tokens.shape[0], dtype=jnp.int32), live)
        group = (tokens, tables, lengths, *((wtables,) if windowed else state))
        (logits,), counters, (rows,) = forward(params, pool, (group,))
        parts, ok, lanes_next = _lanes_head(logits, tokens, draft_len, rest)
        new_pool = _write_rows(pool, rows, tables, lengths, tokens.shape[1], *state, wtables=wtables)
        return _packed([*parts, ok], counters), _feed(lanes_next, 0), new_pool

    def decode_chunk(params, pool, tables, lengths, tokens, draft_len, feed, source, table_row, start, chunk, n_real, *rest):
        tokens = _fed(tokens, feed, source)
        chunk_tables, chunk_starts = table_row[None], start[None]
        state = chunk_state = ()
        wtables = chunk_wtables = None
        if windowed:
            wtables, wtable_row, *rest = rest
            chunk_wtables = wtable_row[None]
        if stateful:
            live, slot, *rest = rest
            state, chunk_state = (jnp.arange(tokens.shape[0], dtype=jnp.int32), live), (slot[None], n_real[None])
        groups = (
            (tokens, tables, lengths, *((wtables,) if windowed else state)),
            (chunk, chunk_tables, chunk_starts, *((chunk_wtables,) if windowed else chunk_state)))
        (logits, chunk_logits), counters, (rows, chunk_rows) = forward(params, pool, groups)
        parts, ok, lanes_next = _lanes_head(logits, tokens, draft_len, rest)
        chunk_token = jnp.argmax(chunk_logits[0, n_real - 1], axis=-1)
        chunk_ok = jnp.all(jnp.isfinite(chunk_logits))
        new_pool = _write_rows(pool, rows, tables, lengths, tokens.shape[1], *state, wtables=wtables)
        new_pool = _write_rows(new_pool, chunk_rows, chunk_tables, chunk_starts, chunk.shape[1], *chunk_state, wtables=chunk_wtables)
        return _packed([*parts, ok, chunk_token, chunk_ok], counters), _feed(lanes_next, chunk_token), new_pool

    return decode, decode_chunk


def _block_heads(forward: Callable, mask_token_id: int):
    """``decode`` and ``decode_chunk`` for a family generated by diffusion over
    blocks (``models/sdar_moe.py``; ``generation.block_generate_loop`` is the
    offline twin).  A decoding lane carries a **block state** ``[W]``: token ids,
    ``generation.MASKED`` where a position is still masked (the model reads
    ``mask_token_id`` there).  Per lane beside the tables and lengths:
    ``tokens [S, W]`` (the host's copy of the state: a block's opening state, or
    any state after a settle), ``count [S]`` (positions this pass unmasks, by
    the static schedule; 0 for a lane that commits or idles), ``feed [S, W]``
    and ``source [S]`` (``FEED_LANE``: the state the previous dispatch returned
    for this lane, on the device alone; else the host's), ``commit [S]`` (1: the
    lane's block is final, its ``W`` rows are written to the pool; 0: a
    denoising pass, **nothing of it is written**) and ``threshold [S]`` (float32;
    2.0 where the request has none).  The head (scope ``head.unmask``) is
    :func:`generation.block_unmask`: confidence and argmax at every masked
    position, the ``count`` most confident unmasked (or all over the threshold,
    if more).  What comes back: the packed vector (every lane's new state ``[S,
    W]``, ``ok [S]``, with a chunk a 0 in the chunk token's place and
    ``chunk_ok``; the expert counters) and the feed, the new states.  The chunk
    is the prompt's whole blocks under the same block-causal mask; **its head
    yields no token** (the first block opens on masks, not on a prediction) and
    its rows are written as any family's."""

    def opened(tokens, feed, source):
        state = jnp.where((source == FEED_LANE)[:, None], feed, tokens)
        return state, jnp.where(state == MASKED, jnp.int32(mask_token_id), state)

    def lanes_head(logits, state, count, threshold, poison):
        logits = _poisoned(logits, poison)
        ok = jnp.all(jnp.isfinite(logits), axis=(1, 2))
        with jax.named_scope("head.unmask"):
            return block_unmask(state, logits, count, threshold), ok

    def decode(params, pool, tables, lengths, tokens, count, feed, source, commit, threshold, *rest):
        state, ids = opened(tokens, feed, source)
        (logits,), counters, (rows,) = forward(params, pool, ((ids, tables, lengths),))
        state, ok = lanes_head(logits, state, count, threshold, rest)
        new_pool = _write_rows(pool, rows, tables, lengths, ids.shape[1], keep=commit)
        return _packed([state, ok], counters), state, new_pool

    def decode_chunk(params, pool, tables, lengths, tokens, count, feed, source, commit, threshold, table_row, start, chunk, n_real, *rest):
        state, ids = opened(tokens, feed, source)
        chunk_tables, chunk_starts = table_row[None], start[None]
        groups = ((ids, tables, lengths), (chunk, chunk_tables, chunk_starts))
        (logits, chunk_logits), counters, (rows, chunk_rows) = forward(params, pool, groups)
        state, ok = lanes_head(logits, state, count, threshold, rest)
        chunk_ok = jnp.all(jnp.isfinite(chunk_logits))
        new_pool = _write_rows(pool, rows, tables, lengths, ids.shape[1], keep=commit)
        new_pool = _write_rows(new_pool, chunk_rows, chunk_tables, chunk_starts, chunk.shape[1])
        return _packed([state, ok, 0, chunk_ok], counters), state, new_pool

    return decode, decode_chunk
