"""The engine's compiled programs: how a dispatch reads the pool.

A dispatch is one **forward** over the new tokens of its lanes and one
**head** that turns the logits into what the tick reads back.  The forward
depends on the cache back end, the head on the kind of dispatch; each is
written once here.

``forward(params, pool, tables [B, M], starts [B], tokens [B, T])`` gives
``(logits [B, T, V], counters, rows {leaf: [B, L, T, ...]})``: lane ``b``'s
tokens sit at positions ``starts[b] .. starts[b] + T - 1`` of the sequence
its table row names, and ``rows`` are the cache rows those tokens wrote.
The head scatters them into the donated pool (``_write_rows``) after it has
read the logits, so the new pool is the last thing a program computes.

- **paged** (a family with an ``apply_paged``: gpt2, llama, deepseek_v3):
  the family reads the pool in place through the block tables and returns
  the written rows; no per-slot view of the cache exists on either side of
  the dispatch.  An expert family returns its per-dispatch counters as a
  third value; they ride out behind the ``ok`` flags.
- **dense** (a family without one: mixtral, whose capacity routing depends
  on who shares the batch, so lanes must not be batched together): gather
  each lane's whole view through its table row, run the family's
  ``apply_cached`` lane by lane under ``vmap``, cut the written rows out of
  the updated views.

The family decides the back end; nothing selects it.  The three heads:
``decode`` (one token a lane: argmax of the last row, ``ok`` per lane),
``decode_spec`` (a ``k+1`` window a lane through
``speculative_verify_greedy``, ``ok`` per lane; built when ``spec_tokens >
0``) and ``prefill`` (one lane's padded chunk: argmax at ``n_real - 1``,
one ``ok``).  ``decode`` and ``decode_spec`` take a trailing per-lane poison
vector when the NaN fault is armed; an unarmed program is traced without it.

The profile names a program after its Python function (``jit_decode``,
``jit_prefill``) and ``chipbench/`` selects operations by those names.
"""

from __future__ import annotations

import inspect
from dataclasses import dataclass
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from ..models.generation import (
    extract_token_rows,
    gather_block_view,
    scatter_token_rows,
    speculative_verify_greedy,
)

__all__ = ["MOE_COUNTERS", "ServingPrograms", "build_programs"]

# What an expert family's ``apply_paged`` counts in a dispatch (``models/deepseek_v3.py:expert_counters``), each
# summed over its expert layers: token-expert pairs computed, experts with at least one row, the hottest expert's rows.
MOE_COUNTERS = ("moe_rows", "moe_experts_hit", "moe_max_rows")


@dataclass(frozen=True)
class ServingPrograms:
    """The jitted programs of one engine (the pool, argument 1, donated) and
    what a tick has to know about the back end they were built for."""

    backend: str  # "paged" | "dense": what the family decided
    decode: Callable
    prefill: Callable
    decode_spec: Optional[Callable]
    max_slots: int
    max_blocks: int

    def table_width(self, blocks_needed: int) -> int:
        """The block-table width of a dispatch whose widest lane needs
        ``blocks_needed`` blocks.  Paged: the next power of two, capped at
        the configured maximum — each width compiles once (jit caches per
        shape) and gather traffic scales with what live requests own.
        Dense: the one static width, the view is always whole."""
        if self.backend == "dense":
            return self.max_blocks
        width = 1
        while width < blocks_needed:
            width *= 2
        return min(width, self.max_blocks)

    def gathered_blocks(self, owned: Sequence[int]) -> int:
        """Blocks a decode dispatch gathers, ``owned`` being the blocks of
        each live lane.  Paged reads the blocks the tables name; dense
        gathers every lane's worst-case view, live or not."""
        if self.backend == "dense":
            return self.max_slots * self.max_blocks
        return sum(owned)


def build_programs(apply_cached: Callable, config, leaf_names, serving, spec_tokens: int) -> ServingPrograms:
    """The programs of an engine that serves ``apply_cached``'s family over a
    pool with leaves ``leaf_names``, at ``serving``'s geometry."""
    apply_paged = getattr(inspect.getmodule(apply_cached), "apply_paged", None)
    if apply_paged is not None:
        backend, forward = "paged", _paged_forward(apply_paged, config)
    else:
        backend, forward = "dense", _dense_forward(apply_cached, config, leaf_names)
    return ServingPrograms(
        backend=backend,
        decode=jax.jit(_decode_head(forward), donate_argnums=(1,)),
        prefill=jax.jit(_prefill_head(forward, serving.prefill_chunk), donate_argnums=(1,)),
        decode_spec=jax.jit(_verify_head(forward), donate_argnums=(1,)) if spec_tokens > 0 else None,
        max_slots=serving.max_slots,
        max_blocks=serving.resolved_max_blocks(),
    )


# -- the two forwards ---------------------------------------------------------


def _paged_forward(apply_paged: Callable, config) -> Callable:
    def forward(params, pool, tables, starts, tokens):
        logits, rows, *counters = apply_paged(params, tokens, config, pool, tables, starts)
        return logits, counters, rows

    return forward


def _dense_forward(apply_cached: Callable, config, names) -> Callable:
    def forward(params, pool, tables, starts, tokens):
        caches = {n: gather_block_view(pool[n], tables) for n in names}
        caches["index"] = starts

        def one(cache, toks):
            logits, new_cache = apply_cached(params, toks[None, :], config, cache)
            return logits[0], new_cache

        logits, new_caches = jax.vmap(one)(caches, tokens)
        rows = {n: extract_token_rows(new_caches[n], starts, tokens.shape[1]) for n in names}
        return logits, [], rows

    return forward


def _write_rows(pool: dict, rows: dict, tables, starts, count: int) -> dict:
    """The pool with the rows a forward wrote scattered in.  Rows past a
    lane's accepted length (a verify window) or past a chunk's real tokens
    are stale by construction: the next dispatch at that position re-writes
    them before any mask admits them."""
    new_pool = dict(pool)
    for n, r in rows.items():
        new_pool[n] = scatter_token_rows(pool[n], r, tables, starts, count)
    return new_pool


# -- the three heads ----------------------------------------------------------


def _ok_with_counters(ok, counters):
    """A dispatch's finiteness flags and, for a family whose ``apply_paged``
    returns expert counters as a third value, those counters behind them in
    one int32 vector: the read-back of ``ok`` that a tick makes anyway carries
    them to the host.  A family without experts returns two values, keeps its
    flags as they are and compiles to the program it always had."""
    if not counters:
        return ok
    behind = jnp.stack([counters[0][name] for name in MOE_COUNTERS]).astype(jnp.int32)
    return jnp.concatenate([jnp.atleast_1d(ok).astype(jnp.int32), behind])


def _poisoned(logits, poison):
    """The NaN fault's lane (``resilience/faultinject.py``): a trace-time
    gate, an unarmed program carries no plumbing.  Every other lane
    multiplies by 1.0 and keeps its tokens bit for bit."""
    if not poison:
        return logits
    return logits * jnp.expand_dims(poison[0], tuple(range(1, logits.ndim)))


def _decode_head(forward: Callable) -> Callable:
    def decode(params, pool, tables, lengths, tokens, *poison):
        logits, counters, rows = forward(params, pool, tables, lengths, tokens[:, None])
        logits = _poisoned(logits[:, -1], poison)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # Per-lane finiteness, folded into the same dispatch: a poisoned lane
        # is detected the tick it happens, before its token is emitted.
        ok = jnp.all(jnp.isfinite(logits), axis=-1)
        new_pool = _write_rows(pool, rows, tables, lengths, 1)
        return next_tok, _ok_with_counters(ok, counters), new_pool

    return decode


def _verify_head(forward: Callable) -> Callable:
    def decode(params, pool, tables, lengths, tokens, draft_len, *poison):  # a decode to the profile too
        logits, counters, rows = forward(params, pool, tables, lengths, tokens)  # [S, W, V]
        logits = _poisoned(logits, poison)
        t, m = speculative_verify_greedy(logits, tokens[:, 1:], draft_len)
        ok = jnp.all(jnp.isfinite(logits), axis=(1, 2))
        new_pool = _write_rows(pool, rows, tables, lengths, tokens.shape[1])
        return t, m, _ok_with_counters(ok, counters), new_pool

    return decode


def _prefill_head(forward: Callable, chunk_len: int) -> Callable:
    def prefill(params, pool, table_row, length, chunk, n_real):
        tables, starts = table_row[None], length[None]
        logits, counters, rows = forward(params, pool, tables, starts, chunk)
        next_tok = jnp.argmax(logits[0, n_real - 1], axis=-1).astype(jnp.int32)
        ok = jnp.all(jnp.isfinite(logits))
        new_pool = _write_rows(pool, rows, tables, starts, chunk_len)
        return next_tok, _ok_with_counters(ok, counters), new_pool

    return prefill
