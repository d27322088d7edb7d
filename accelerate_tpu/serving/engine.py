"""The serving engine: continuous batching over the paged KV cache.

One engine **tick** (:meth:`ServingEngine.step`) is:

1. **admit** — queue-head requests take free decode slots (FIFO);
2. **build** — at most ONE bounded chunk (``prefill_chunk`` tokens, padded
   to a static shape) of the oldest prefilling request, so a 10k-token
   prompt costs many small chunks interleaved with decode instead of one
   huge dispatch that stalls every in-flight request.  The chunk's size is
   one integer an engine, settled at construction: the caller's, or by
   default as many rows as ride in the decoders' dispatch nearly for free on
   this device (:func:`resolve_prefill_chunk`; 32 for a family with routed
   experts and off the TPU); then the decode batch:
   every decoding slot grown by one token (or one ``k + 1`` window),
   oldest first.  Growing the decoders may preempt the prefilling slot; its
   chunk is then dropped with it;
3. **dispatch** — ONE fused jitted program runs what the builds left.  A
   tick with a chunk and live decoders issues ``decode_chunk``: the chunk
   rides in the decode's forward, so everything that does not look at the
   cache (embedding, norms, projections, the MLP or the experts, the head)
   runs once over all rows of the tick and the weights stream **once a
   tick**; attention runs a group of lanes at a time (the decoders are one
   group, the chunk another).  Without a chunk the tick issues ``decode``;
   a chunk with no live decoder rides ``decode_chunk`` with the lanes idle
   (a cold start).  One launch, one sync, one read-back
   (``stats()["mixed_dispatches"]`` counts the ticks whose chunk rode
   along; ``prefill_dispatches + decode_dispatches - mixed_dispatches`` is
   the number of dispatches, one a tick).  A prompt whose last chunk rode
   in this dispatch starts decoding in the next tick;
4. **read back the tick before** — the one sync of the step waits for tick
   N - 1's vector while tick N runs;
5. **emit** — of tick N - 1: the chunk's bookkeeping (prefix registration,
   the first token after a last chunk), then every lane's tokens.

**A tick is read back one dispatch late.**  A request finishes by count (the
engine has no stop token), a decoding lane advances by a count known at
dispatch (one row a tick; a block-diffusion lane by its schedule, below), block
growth and tables depend on lengths and not on token values, and a chunk's
rows are the prompt's: so everything tick N + 1's build needs from tick N is
known when N is *dispatched*, except the token values, and those the device
has.  They stay there: each program returns a small ``feed`` (every lane's
next token, and the chunk's) and takes the previous dispatch's with a
per-lane ``source`` (``serving/programs.py``).  So ``step()`` builds tick N
from what is known at N - 1's dispatch, launches it, and only then reads
N - 1's vector and runs N - 1's emit with the values; while the host reads,
emits, publishes, returns to the caller, takes new requests and builds, the
device has a program queued behind the one it runs.  Booked at dispatch, by
count: a lane's ``cache_len`` + 1 and its token (``_Slot.unread``), a final
chunk's slot turning ``DECODING`` (it rides the next tick reading the chunk's
entry of the feed), and the slot whose last token this is **retiring**: out
of the next tick, its index free for admission, its blocks its own until the
token is read.  Left to the read-back: the values (``Request.emitted``,
``note_token`` with the real clock, the journal, the tracer, completion:
**a reply is handed over when its last token is read, one dispatch after
that token's program was launched**), the ``ok`` flags, prefix registration
(a chunk's blocks are published once its flag has read true) and the expert
counters.

**Settles.**  One method, ``_settle(reason)``, reads the tick in flight back
and applies it; every path that needs a token's value, or re-queues or
releases a request with a token unread, calls it first: a preemption or
migration victim (``preempt``: it re-prefills ``prompt + emitted``; also a
block shortage before anyone is evicted, since the read-back returns the
retiring lanes' blocks), deadline expiry of a live request (``deadline``),
:meth:`~ServingEngine.drain` (``drain``), :meth:`~ServingEngine.recover_from_journal`
(``recover``), a poisoned lane (``quarantine``: its flag is read one
dispatch late, the tick in flight computed one row more for it, which went
to its own blocks and is dropped; the scrub is ordered behind it on the
device's one stream), a step that leaves nothing to dispatch behind the tick
in flight (``idle``: the last replies are handed over at once, so
:meth:`~ServingEngine.run` ends with nothing unread),
:meth:`~ServingEngine.stats` (``stats``) and, for a block-diffusion family, a
tick in which a live request has a confidence threshold (``blocks``).
These are rare: the steady state never settles
(``stats()["pipelined_ticks"]``, ``["settles"]``).  **A verify-window engine
is the synchronous one**: with ``spec_tokens > 0`` the accepted count decides
``cache_len`` and the drafter reads the tokens, so every tick settles
(``spec``) before the next is built.  That is observed
(``programs.window > 1``), not configured: one engine, whose depth (one tick
ahead, or none) follows from what it sees.

**Generation by diffusion over blocks.**  A family whose model config carries
``block_length`` ``B > 1`` (``models/sdar_moe.py``) does not yield one token a
lane a tick.  ``(P // B) * B`` prompt tokens are prefilled under the
block-causal mask (``prefill_chunk`` and ``block_size`` multiples of ``B``); the
last chunk **yields no token**, and the prompt's remainder opens the first
block.  A ``DECODING`` lane carries a block (``_Block`` on its slot): ``B``
positions, ``MASKED`` where masked.  Each tick it runs a **denoising pass** (the
head unmasks the most confident masked positions, ``count`` by the static
schedule of the request's ``denoise_steps``; nothing is written to the pool;
``cache_len`` stays) or, when no mask is left, the **commit pass** (the block's
rows written; ``cache_len`` += ``B``; the next block opens on masks).  A block's
tokens are emitted together when its last denoising pass is read back, those
past ``max_new_tokens`` dropped, so TTFT is the first block's; a request's last
block needs no commit.  The feed is every lane's block state ``[max_slots,
B]``.  Under the static schedule every one of these is known **by count** when
the tick is dispatched (``_book_pass``), so the pipeline above keeps running;
with a ``confidence_threshold`` on a live request the count is a value, and the
engine settles every tick (``blocks``), observed and not configured, as the
verify window does.  Preemption re-prefills ``prompt + emitted`` (whole blocks;
the block in progress restarts to the same tokens), the prefix cache reuses
whole pool blocks below the prefilled rows, ``spec_tokens > 0`` is refused.
``generation.block_generate_loop`` is this path's equivalence oracle.

**The tick's record.**  A tick keeps ONE record of itself (``_tick``): its
milliseconds by phase, under the names of its ``serving.tick.*`` profiler
spans, and what its dispatch held.  The host's half of a dispatch is told
apart from its blocked time: inside the ``wait`` span ``launch`` is the jitted
call alone and ``read`` the blocking read (of the tick before, or a settle's),
so the wait's self time is the booking of what was sent.  ``_close_tick``
writes what the dispatch held once, as integers, onto the ``serving.tick`` span
(``rows_live`` of ``rows_computed``, ``width`` against ``width_lanes``,
``mixed``, ``pipelined``, ``settles``) and counts the dispatch's kind from it;
the times stand in the spans themselves and, without a profile, in
``phase_ms`` of the slowest ticks, which ``ServingTracer`` keeps.  The emit
span that yields a request's first token carries that token's account
(``_first_tokens``).  ``docs/usage_guides/telemetry.md`` has the tables.

``serving/programs.py`` builds the two programs and owns how a dispatch reads
the pool; the family decides which of its two back ends serves.  A family
with an ``apply_paged`` (gpt2, llama, deepseek_v3) is served **paged**:
``apply_paged`` reads pool K/V through the block tables
(``models/generation.py paged_cache_write``), and the pool is a constant of
the layer loop, never a scanned input of it.  Where the TPU holds the pool
block by block (bf16, ``hd`` a multiple of 128, ``K`` 1, 2, 4 or a multiple
of 8) it is addressed by (layer, block) in one flat view
(``address_paged_pool_by_layer``): a layer gathers the blocks its tables
name and nothing else of the pool is sliced, copied or re-tiled (as a
scanned input every layer's whole slice was: 47% of the device's time at
8192 blocks, PERF.md section 6, PR 27).  Any other pool (int8, ``hd`` 64,
odd ``K``) still has its layer's slice cut in the loop, a cost in
``num_blocks``, until the resident layout changes (ROADMAP A11).  No dense
per-slot cache view is ever materialized, no updated view ever flows back
out of the program — only the freshly written K/V rows, which scatter into
the donated pool.  Block tables are **bucketed** to the next power of two of
the widest lane of the dispatch (the chunk's lane and the decoders share one
width; no table is narrower than ``programs.MIN_TABLE_ROWS``, 256 rows: a
width costs two compiles, and a gather that short costs nothing), so
per-token gather traffic scales with the blocks requests actually own, not the worst-case table width or the pool's size
(``serving.decode_gather_bytes`` counts the blocks the decoders' tables name,
on the host).  The first dispatch at a width compiles BOTH programs at it
(``_note_bucket``): a warm-up of single requests run alone leaves nothing to
compile under load.  A pool need not be K and V per head:
``models/deepseek_v3.py`` pages latent rows (``ckv``, ``kr``) the same way,
and an expert family serves paged too when its routing is row by row, as
``ops/moe.py:routed_experts`` is (its per-dispatch expert counters ride out
behind the ``ok`` flags into ``stats()["moe_rows"]``, ``"moe_experts_hit"``,
``"moe_max_rows"``, ``"moe_row_tiles"`` -- the last 0 unless the dispatch ran the
fused kernel of ``ops/pallas_moe.py``, which the telemetry counter
``serving.moe_row_tiles`` carries too --; a mixed dispatch streams the experts its chunk and its
decoders hit once).  Where the decoding lanes read the pool through the paged
kernel of ``ops/pallas_paged_attention.py`` (``generation.reads_in_place``),
``stats()["attn_rows_read"]`` and the telemetry counter
``serving.attn_rows_read`` count the rows it copied; 0 where they gathered.  A family without an ``apply_paged``
(``models/mixtral.py``: capacity routing depends on who shares the batch) is
served **dense**: gather each slot's whole view at the one static table
width, ``vmap`` the family's ``apply_cached``, extract and scatter the
written rows — a group at a time inside the same one program, so the tick
has one path.  ``stats()["decode_path"]`` reports which.  Either way the
1-dispatch-per-tick invariant from ``make_train_step`` carries over — the
three dispatch counters are the proof hook.

**Prefix caching** (``ServingConfig.prefix_cache``, default on): full
prompt blocks are content-hashed (a chain hash — K/V rows depend on the
whole prefix) into a :class:`~accelerate_tpu.serving.blocks.PrefixCache`
shared across requests.  A new request's prefill skips the shared prefix
(its blocks are refcount-retained into the slot's table; TTFT collapses to
the unshared suffix), the partial tail block is reused via copy-on-write,
and cache-only blocks are reclaimable capacity the allocator evicts
LRU-first under pressure.  Quarantine's scrub becomes
**scrub-on-last-release**: a poisoned shared block keeps serving its live
readers (their own finiteness checks guard them) and is zeroed only when
the last reference drops — never under a live reader.

Token selection is **greedy** (argmax, inside the fused program): outputs are
token-identical to the offline ``generate_loop`` with ``temperature=0`` per
request, which is the engine's equivalence oracle (``tests/test_serving.py``,
``make serving-smoke``); for a block-diffusion family to
``block_generate_loop`` (``tests/test_serving_blocks.py``).

Chunked-prefill padding contract: chunks are padded to the static
``prefill_chunk`` length.  Padded queries produce ignored logits; padded K/V
rows land at positions past the real prefix — positions the causal mask hides
from every existing query and that sequential future writes overwrite before
any query of that position exists.  Pool writes for positions past the block
table route to the null block.  The scheduler's geometry validation
guarantees ``ceil(rows / prefill_chunk) * prefill_chunk <= max_blocks_per_seq
* block_size``, so the padded write never clamps inside the dense view.

SLO metrics per request — TTFT, inter-token latency, queue wait, tokens/s,
preemption count — publish through the telemetry registry
(``serving.*`` families) and each completion emits a
``serving.request_complete`` event, which the flight recorder mirrors into
its durable ring when enabled.

Production-robustness layer (overload / deadlines / quarantine / journal):

- **Overload protection** — ``ServingConfig.max_queue_depth`` bounds the
  admission queue; past it ``submit`` raises :class:`AdmissionRejected`
  (``serving.shed`` counter), so a traffic burst degrades to load-shedding
  instead of unbounded queue growth.
- **Deadlines** — per-request TTFT and total-latency deadlines (defaults on
  the config).  Expired QUEUED requests are shed before a prefill chunk is
  spent on them; expired in-flight requests are cancelled with their blocks
  freed.  Both complete with ``status="deadline_expired"``
  (``serving.deadline_expired`` counter); a TTFT expiry observes its
  elapsed wait into ``serving.ttft_ms`` so the PR 13 SLO burn-rate gauges
  see the violation instead of a survivorship-biased histogram.
- **Poison quarantine** — both compiled programs carry an in-program
  logit-finiteness check per decoding lane and one for the chunk (a
  reduction folded into the existing dispatch — zero extra dispatch, the
  health-guard trick).  A non-finite lane's request completes with
  ``status="quarantined"`` (``serving.quarantined`` counter + event) while
  every other lane, and the chunk that shared the forward, go on
  bit-identically (rows are independent); a poisoned chunk takes no
  decoding lane with it either.  The quarantined
  request's pool blocks are **scrubbed to zero before being freed**: the
  attention mask zeroes a hidden row's *probability*, but ``0 * NaN = NaN``
  in ``probs @ v``, so a NaN row left in a recycled block would poison its
  next owner.  ``ACCELERATE_TPU_FAULT_SERVING_NAN_REQUEST`` injects the
  poison for tests (trace-time-gated, like the train-step NaN knob).
- **Crash-recovery journal** — ``ServingConfig.journal_path`` arms a
  write-ahead journal (``serving/journal.py``): admissions and terminal
  transitions land on disk atomically, the drain path persists emitted
  progress, and a successor engine's :meth:`recover_from_journal` resubmits
  every non-terminal request and finishes it token-identically — even
  after a SIGKILL that skipped every handler.
"""

from __future__ import annotations

import contextlib
import math
import os
import time
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..models.generation import MASKED, denoise_schedule, window_ring_blocks, with_token_leaves, with_window_leaves
from ..telemetry import annotate, get_telemetry, ridge_rows
from .blocks import (
    NULL_BLOCK,
    BlockOutOfMemory,
    PagedKVCache,
    PrefixCache,
    blocks_for_tokens,
)
from .journal import JournalError, ServingJournal
from .programs import (
    ATTN_COUNTERS,
    DISPATCH_COUNTERS,
    FEED_CHUNK,
    FEED_LANE,
    MOE_COUNTERS,
    SPARSE_COUNTERS,
    WINDOW_COUNTERS,
    build_programs,
)
from .scheduler import Request, RequestState, Scheduler
from .tracing import ServingTracer, resolve_trace_dir, tracing_enabled

__all__ = [
    "AdmissionRejected",
    "ServingConfig",
    "resolve_prefill_chunk",
    "ServingEngine",
    "CompletedRequest",
]


class AdmissionRejected(RuntimeError):
    """Typed load-shedding rejection: the admission queue is at
    ``max_queue_depth``.  Deliberately NOT a ``ValueError`` — the request
    was well-formed; the engine is overloaded.  Callers retry with backoff
    or fail over; the ``serving.shed`` counter records every rejection."""


@dataclass
class ServingConfig:
    """Engine geometry (everything here is a static shape of the compiled
    programs — two programs, ``decode`` and ``decode_chunk``, over one token
    a lane or with ``spec_tokens`` the verify window, both compiled once per
    block-table width the engine meets, however many requests flow through).

    - ``block_size``: tokens per KV block.  Small blocks waste less tail
      space per request; large blocks shrink the tables.  16-64 is typical.
    - ``num_blocks``: pool size (one block is reserved as the null block).
      Pool HBM = ``num_blocks * block_size`` rows per layer — budget this
      like a dense cache of total length ``num_blocks * block_size`` shared
      by ALL requests, not tiled per request.
    - ``max_slots``: the decode batch width (static).  More slots = more
      requests advanced per decode dispatch.
    - ``max_blocks_per_seq``: block-table width (static); caps any single
      request at ``max_blocks_per_seq * block_size`` cache rows.
    - ``prefill_chunk``: prompt tokens a tick's one chunk holds (static).
      ``None`` (default): the engine chooses it once, at construction, by
      :func:`resolve_prefill_chunk` — as many rows as ride in the decoders'
      dispatch nearly for free on the device it runs on (64 beside sixteen
      lanes on a TPU v5e), 32 for a family with routed experts and off the TPU.  An
      integer is kept as given.  Either way ``engine.serving.prefill_chunk``
      and ``stats()["prefill_chunk"]`` hold the integer the engine runs.

    Robustness knobs (all host-side policy, no effect on the compiled
    programs):

    - ``max_queue_depth``: admission-queue bound; ``submit`` past it raises
      :class:`AdmissionRejected` (None = unbounded, the pre-overload
      behavior).
    - ``default_ttft_deadline_ms`` / ``default_deadline_ms``: deadlines
      applied to requests that do not pass their own (None = no deadline).
    - ``journal_path``: arm the crash-recovery write-ahead journal at this
      path (see ``serving/journal.py``).
    - ``host_blocks``: size of the host-DRAM KV tier (0 = disabled, the
      pre-tiering behavior).  With a tier, preemption **demotes** the
      victim's blocks to host memory instead of freeing them (re-admission
      promotes and resumes with zero re-prefill dispatches), cold
      prefix-cache chains demote on eviction pressure instead of dropping,
      and the free-and-re-prefill path survives only as the fallback when
      the host tier is full.  Host-side policy plus batched D2H/H2D copies
      between dispatches — the compiled programs are identical either way.
    - ``tier_demote_batch``: max cold prefix chains proactively demoted per
      tick when the allocator's raw free list falls under the headroom
      watermark (demote-before-shed; 0 disables the proactive sweep —
      on-demand demotion inside eviction still applies).

    Cache knobs:

    - ``prefix_cache``: share full prompt blocks across requests by content
      hash (copy-on-write tail, refcounted blocks, LRU reclaim).  Host-side
      policy only — the compiled programs are identical either way.

    Speculative decode knobs (draft-then-verify; token-identical to greedy
    by the accept rule — see ``models/generation.py
    speculative_verify_greedy``):

    - ``spec_tokens``: the draft window ``k``.  0 (default) disables; at
      ``k > 0`` each decode tick asks the drafter for up to ``k`` candidate
      tokens per slot and the target verifies all slots' ``k+1``-token
      windows in ONE fused dispatch, emitting 1..k+1 tokens per slot per
      tick.  Block budgeting grows by the worst-case ``k``-row overshoot
      (``Scheduler.max_rows``).
    - ``spec_ngram_max`` / ``spec_ngram_min``: n-gram match lengths for the
      default prompt-lookup drafter (``serving/drafter.py NgramDrafter``);
      ignored when a custom ``drafter=`` is passed to the engine.

    Tracing knobs (``serving/tracing.py`` — host-side interval bookkeeping,
    no effect on the compiled programs):

    - ``trace``: per-request phase tracing.  ``None`` (default) defers to
      ``ACCELERATE_TPU_SERVING_TRACE`` (default-on; ``0`` kills).
    - ``trace_dir``: where trace JSONL persists; ``None`` defers to
      ``ACCELERATE_TPU_SERVING_TRACE_DIR``, then the enabled telemetry run
      dir, else in-memory only.
    """

    block_size: int = 16
    num_blocks: int = 64
    max_slots: int = 4
    max_blocks_per_seq: Optional[int] = None
    prefill_chunk: Optional[int] = None
    max_queue_depth: Optional[int] = None
    default_ttft_deadline_ms: Optional[float] = None
    default_deadline_ms: Optional[float] = None
    journal_path: Optional[str] = None
    host_blocks: int = 0
    tier_demote_batch: int = 8
    prefix_cache: bool = True
    spec_tokens: int = 0
    spec_ngram_max: int = 3
    spec_ngram_min: int = 1
    trace: Optional[bool] = None
    trace_dir: Optional[str] = None

    def resolved_max_blocks(self) -> int:
        if self.max_blocks_per_seq is not None:
            return self.max_blocks_per_seq
        return self.num_blocks - 1


# The chunk of an engine that was given none and finds no free rows: what every engine ran before the rule.
DEFAULT_PREFILL_CHUNK = 32

# The share of the device's ridge that a tick's rows (every lane's window and the chunk) may fill.  Up to the ridge
# (``telemetry.ridge_rows``: 240 rows on a v5e) a dense matmul's time is that of its weights' bytes, so by the matmuls
# alone a dispatch could carry 240 rows for the price of 48.  What a chunk's rows do cost is what is not a matmul
# against the weights: the gather of the chunk's blocks and its attention, both over the dispatch's whole table width
# (in a trace of the chat cell 0.24 and 0.28 ms of the 0.58 ms that 32 more rows add).  On a v5e at the chat geometry (Qwen2.5-3B, sixteen lanes; PERF.md section 6, PR 37) the second 32 rows cost
# 0.36 ms of an 11.3 ms dispatch at a table of 64 blocks and 0.64 ms of 18.0 at 256, nearly what the first 32 cost
# (0.47 and 1.00).  What pays for them is the ticks they save, and what bounds them is the pace of the decoders' tokens,
# which every row lengthens: against a chunk of 32, 64 rows serve 43% more tokens a second at 0.30 of the time to a
# first token with token gaps 2.5% longer at their p95; 96 rows 53% more at gaps 6.1% longer; 128 rows 55% at 8.7%.
# The gaps may lengthen by 3.5%, so the share is the largest that gives sixteen lanes 64 rows and not 96: 16 + 64 = 80
# rows stay under a third of 240.5, 16 + 96 = 112 do not (any share from 0.333 to 0.465 gives the same chunk there).
CHUNK_RIDGE_FRACTION = 1 / 3


def resolve_prefill_chunk(
    requested: Optional[int],
    *,
    device_kind: str,
    max_slots: int,
    window: int,
    block_size: int,
    block_length: int = 1,
    routed_experts: int = 0,
) -> int:
    """The size of the one prefill chunk a tick carries (static: one size an
    engine, so one ``decode_chunk`` program a table width).  Arguments in,
    integer out; it asks no device anything, so it can be asked about any.

    An integer ``requested`` is kept as given.  Else the chunk is as many rows
    as the decoders' dispatch carries nearly for free:

    1. The weights are read once a dispatch whatever its rows, and up to the
       device's ridge their bytes set a dense matmul's time.  The chunk is the
       largest multiple of ``DEFAULT_PREFILL_CHUNK`` (and of ``block_size``
       and a block family's ``block_length``: a chunk and a pool block hold
       whole blocks) such that ``max_slots * window + chunk`` stays under
       ``CHUNK_RIDGE_FRACTION`` of the ridge; ``DEFAULT_PREFILL_CHUNK`` where
       not even one such multiple fits.
    2. With routed experts no row is free at these row counts: every further
       row pulls its ``top_k`` experts' matrices in, so a dispatch's bytes grow
       with its rows (a chunk of 32 rows costs an expert family 1.6-2.0 ms of
       a 9-18 ms dispatch, PERF.md section 5, against 0.5 ms of 11.5 for a
       dense one).  Weighing the experts more rows hit against the ticks they
       save is another rule (ROADMAP A14): such a family keeps
       ``DEFAULT_PREFILL_CHUNK``.
    3. Off the TPU, or on a device kind whose peaks the library's table lacks,
       nothing is known to be free: ``DEFAULT_PREFILL_CHUNK``.
    """
    if requested is not None:
        return int(requested)
    ridge = ridge_rows(device_kind)
    if ridge is None or routed_experts:
        return DEFAULT_PREFILL_CHUNK
    step = math.lcm(DEFAULT_PREFILL_CHUNK, block_size, block_length)
    free = math.ceil(CHUNK_RIDGE_FRACTION * ridge) - 1 - max_slots * window  # "under": the largest integer below
    return max(free // step * step, DEFAULT_PREFILL_CHUNK)


def _device_kind() -> str:
    """The kind of the device this process computes on, as JAX names it."""
    return jax.devices()[0].device_kind


def _routed_experts(config) -> int:
    """Routed experts a layer, as the family's model config counts them under
    either of the two names the families use; 0 for a dense family."""
    return int(getattr(config, "n_routed_experts", 0) or getattr(config, "num_experts", 0) or 0)


@dataclass
class CompletedRequest:
    """Completion record: the tokens plus the request's SLO timeline.

    ``status`` is ``"ok"`` for a normal completion, ``"deadline_expired"``
    for a request cancelled/shed past its deadline (``tokens`` holds
    whatever was emitted before expiry), or ``"quarantined"`` for a request
    whose decode produced non-finite logits (``tokens`` excludes the
    poisoned token — it was never meaningful); ``"in_flight"`` only in a
    record of :meth:`ServingEngine.unfinished`, a request as it stands."""

    id: int
    tokens: List[int]
    prompt_len: int
    new_tokens: int
    queue_wait_ms: float
    ttft_ms: Optional[float]
    mean_inter_token_ms: Optional[float]
    tokens_per_s: Optional[float]
    preemptions: int
    inter_token_ms: List[float] = field(default_factory=list)
    status: str = "ok"
    tag: Optional[str] = None
    # KV-tiering accounting: host-tier round-trips this request survived,
    # times the host tier was full so a preemption fell back to the plain
    # re-prefill, and prefill dispatches it consumed in total (the
    # zero-re-prefill oracle: a migrated resume adds none).
    migrations: int = 0
    fallback_reprefills: int = 0
    prefill_dispatches: int = 0
    # Generation by diffusion over blocks: of every new token, the denoising pass (0-based) of its block that
    # unmasked it, so that a checker can rebuild what each pass saw.  Empty for an autoregressive family.
    token_passes: List[int] = field(default_factory=list)


class _Block:
    """The block a decoding lane of a block-diffusion family carries.
    ``state`` is the host's copy of its ``W`` positions as last read back
    (token ids, ``MASKED`` where masked); ``passes`` the denoising pass that
    unmasked each position (-1: a prompt token, or still masked); ``new`` how
    many of the positions are the request's new tokens (all but the prompt's
    remainder, which opens the first block).  ``masked`` and ``t`` run ahead of
    ``state`` under the static schedule: positions still masked after, and
    denoising passes among, the passes *dispatched*; with a confidence
    threshold they are booked when the pass is read."""

    __slots__ = ("state", "passes", "new", "masked", "t", "schedule")

    def __init__(self, width: int, opening: List[int], schedule: List[int]):
        self.state = list(opening) + [MASKED] * (width - len(opening))
        self.passes = [-1] * width
        self.new = self.masked = width - len(opening)
        self.t = 0
        self.schedule = schedule

    def next_count(self) -> int:
        """Positions the next denoising pass unmasks by the static schedule; 0: no mask is left, the block commits."""
        return min(self.schedule[self.t], self.masked) if self.masked else 0


class _Pass(NamedTuple):
    """One lane's part of a dispatched tick of a block-diffusion family."""

    block: _Block
    commit: bool  # the block was final: its rows were written, nothing is unmasked
    t: int  # the denoising pass's number within its block
    emit: Optional[int]  # booked at dispatch, by count: None while the block has masks left, else the tokens it emits
    by_count: bool  # False for a request with a confidence threshold: what a pass unmasks is known when it is read


class _Chunk(NamedTuple):
    """The prefill chunk a tick built: slot ``idx``'s tokens ``start .. start +
    n_real`` padded to ``prefill_chunk``, and the blocks its padded write
    extent needs."""

    idx: int
    slot: object
    start: int
    n_real: int
    tokens: np.ndarray
    blocks: int


class _Lanes(NamedTuple):
    """The decode batch a tick built: the live slots, oldest first, the window
    of tokens (last emitted, then drafts) of every lane, and where each lane's
    first token comes from (``programs.FEED_*``: the host's value here, or the
    feed of the tick in flight, for a token the host has not read)."""

    live: List[int]
    tokens: np.ndarray
    draft_len: np.ndarray  # a block-diffusion family: the positions each lane's pass unmasks
    source: np.ndarray
    extra: tuple = ()  # a block-diffusion family: (commit [S], threshold [S])


class _Flight(NamedTuple):
    """A tick dispatched and not yet read back: what its program returned (on
    the device, the vector's copy to the host started) and what the read-back
    needs of the tick's shape."""

    packed: object  # the program's one int32 vector (ServingPrograms.unpack)
    chunk: Optional[_Chunk]
    final: bool  # the chunk ended its prompt: its token is the request's next
    lanes: list  # the decoding lanes' slots, oldest first
    draft_len: np.ndarray
    width: int
    fresh: bool
    t0: float  # the launch, for dispatch_ms
    tick: int  # the tick that dispatched it
    passes: Optional[List[_Pass]] = None  # a block-diffusion family: what each of ``lanes`` did


class _TickPhase:
    """One phase of a tick, twice over: a ``serving.tick.<name>`` span on the
    profiler's timeline (``telemetry.annotate``; ``with`` gives the span, for
    ``set_metadata``), and its milliseconds in the tick's record for
    ``ServingTracer``'s slow ticks.  Phases are counted back to back, each
    from the end of the one before, so they sum to the tick; a phase opened
    inside another (``launch`` and ``read`` inside a ``wait``; a settle's
    ``wait`` inside the phase that asked for it) takes its own time out of the
    outer one's; a name met twice in a tick (a settle inside it reads a second
    time) adds up."""

    __slots__ = ("engine", "name", "span", "outer")

    def __init__(self, engine: "ServingEngine", name: str, **meta):
        self.engine, self.name = engine, name
        self.span = annotate("serving.tick." + name, tick=engine.ticks, **meta)

    def _book(self, name: str) -> None:
        engine = self.engine
        if engine._phase_t0 is None:  # a settle between two ticks (stats(), a cancel): no tick's record is open
            return
        now = time.monotonic()
        phase_ms = engine._tick.setdefault("phase_ms", {})
        phase_ms[name] = phase_ms.get(name, 0.0) + (now - engine._phase_t0) * 1e3
        engine._phase_t0 = now

    def __enter__(self):
        engine = self.engine
        self.outer, engine._phase = engine._phase, self.name
        if self.outer is not None:
            self._book(self.outer)  # what has passed of the outer phase so far is its own
        return self.span.__enter__()

    def __exit__(self, *exc) -> bool:
        self.span.__exit__(*exc)
        self._book(self.name)
        self.engine._phase = self.outer
        return False


class ServingEngine:
    """Continuous-batching serving over a model family's
    ``apply_cached``/``init_cache`` pair: any family whose cache leaves are
    token rows ``[L, B, max_len, ...]`` — K and V per head (gpt2, llama,
    mixtral; fp or int8) or latent rows without a head axis (deepseek_v3:
    ``ckv`` and ``kr``, 576 values a token a layer).  A family's cache may
    hold a second kind of leaf beside them, under ``generation.STATE``: a
    **state** ``[L, B, ...]``, one entry a sequence whatever its length
    (lfm2_moe: the last two inputs of each short convolution, and K/V rows
    for its attention layers only).  The pool then carries token rows by
    block and the state by decode slot, one cache manager for both; a
    sequence that starts reads a zero state inside the program, so admission
    and preemption by free-and-re-prefill cost no host work.  What takes a
    sequence's whole past to be its blocks does not hold for such a family:
    no prefix cache is built (``stats()["prefix_cache_off"]`` says why;
    ``ServingConfig.prefix_cache`` keeps its meaning for every other
    family), and ``host_blocks > 0`` or ``spec_tokens > 0`` is refused at
    construction.  Whether a family has a state is read from its cache,
    nowhere else; ``stats()`` then carries ``state_bytes`` and
    ``state_resets``.  Token rows may be of two kinds too (afmoe: leaves
    under ``generation.WINDOW`` for the layers that attend over the last
    ``config.sliding_window`` positions): the pool then holds a second
    group of blocks with its own allocator, a sequence keeps of it a ring of
    ``window_ring_blocks`` blocks behind a window table
    (``_Slot.window_blocks``: allocated at first touch, overwritten in
    place, freed at the request's end), the scheduler admits, grows and
    preempts by both kinds, and ``stats()`` answers per kind
    (``pool_bytes_by_kind``, ``full_blocks_in_use``,
    ``window_blocks_in_use``, ``window_rows_read``, ``context_rows``).  The
    same three features are off or refused for it, for the same reason: a
    sequence's past is not its blocks alone.  A family with an
    ``apply_paged`` serves on the paged path, experts or not (llama, gpt2,
    deepseek_v3, lfm2_moe: dropless routing is row by row, so a token gets the
    same experts whatever the chunk and the batch); one without (mixtral)
    is served by the dense gather program (``serving/programs.py``),
    ``stats()["decode_path"]`` says which.  The token-identity-vs-``generate_loop`` guarantee needs a
    chunking-independent forward; capacity-limited MoE routing (mixtral)
    varies with prefill chunking here exactly as it does under offline
    ``prefill_chunk``.

    ::

        engine = ServingEngine(gpt2.apply_cached, gpt2.init_cache, params, cfg,
                               serving=ServingConfig(max_slots=8))
        rid = engine.submit(prompt_tokens, max_new_tokens=64)
        outputs = engine.run()          # {rid: full token list}

    or drive it tick-by-tick with :meth:`step` / :meth:`pop_finished`.
    """

    def __init__(
        self,
        apply_cached: Callable,
        init_cache: Callable,
        params,
        config,
        serving: Optional[ServingConfig] = None,
        drafter=None,
    ):
        sc = serving or ServingConfig()
        if sc.prefill_chunk is not None and sc.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {sc.prefill_chunk}")
        if sc.resolved_max_blocks() < 1:
            raise ValueError("max_blocks_per_seq must be >= 1")
        if sc.spec_tokens < 0:
            raise ValueError(f"spec_tokens must be >= 0, got {sc.spec_tokens}")
        if sc.host_blocks < 0:
            raise ValueError(f"host_blocks must be >= 0, got {sc.host_blocks}")
        # Every table-width bucket compiles on first use, in a request's
        # latency path: a bare engine gets the persistent cache the
        # Accelerator entry point turns on.
        from ..pipeline.compile_cache import enable_compile_cache

        enable_compile_cache()
        self.params = params
        self.spec_tokens = int(sc.spec_tokens)
        # A family generated by diffusion over blocks says so in its model config (1: every other family).
        self.block_length = int(getattr(config, "block_length", 1))
        # The chunk's size is settled here, once, and everything below reads the integer: the caller's config is
        # left as it was given (it may build another engine, of another family or on another device).
        self.serving = sc = replace(sc, prefill_chunk=resolve_prefill_chunk(
            sc.prefill_chunk,
            device_kind=_device_kind(),
            max_slots=sc.max_slots,
            window=self.block_length if self.block_length > 1 else self.spec_tokens + 1,
            block_size=sc.block_size,
            block_length=self.block_length,
            routed_experts=_routed_experts(config),
        ))
        # Token rows of two kinds: a family whose cache holds window leaves (generation.WINDOW) keeps of them a ring of
        # blocks a sequence.  The window is the model config's, the ring's width follows from it, the chunk and the
        # block; the window kind's pool holds every slot's ring (and its null block) unless num_blocks is smaller.
        window = int(getattr(config, "sliding_window", 0) or 0)
        ring = window_ring_blocks(window, sc.prefill_chunk, sc.block_size) if window else 0
        self.cache = PagedKVCache(
            init_cache, config, sc.num_blocks, sc.block_size,
            num_host_blocks=sc.host_blocks, num_slots=sc.max_slots,
            window_blocks=min(sc.num_blocks, sc.max_slots * ring + 1) if ring else 0,
        )
        self._ring_blocks = ring if self.cache.window_allocator is not None else 0
        # Whether the family carries a state a sequence is read from its cache (generation.STATE), nowhere else.
        # Three features take a sequence's whole past to be its blocks; with a state it is not.  The prefix cache
        # is not built (a hit would resume at a block boundary, where the state of that boundary is needed:
        # stats()["prefix_cache_off"] says so).  The host tier and the verify window are refused here rather than
        # served wrong: a demoted request would come back to another slot's state, and the state after a window
        # would be the one after its last row, not after the accepted ones.
        self._state_names = sorted(self.cache.state_leaves())
        if self._state_names and (sc.host_blocks or sc.spec_tokens):
            raise ValueError(
                f"this family's cache holds a state a sequence ({', '.join(self._state_names)}) beside its token rows: "
                f"host_blocks > 0 (the state does not ride with demoted blocks) and spec_tokens > 0 (the state after "
                f"the accepted rows is not kept) are not served for it; got host_blocks={sc.host_blocks}, "
                f"spec_tokens={sc.spec_tokens}"
            )
        # With window leaves a sequence's past is not its blocks either: the rows a window layer has let go are gone.
        # The same three features are off or refused (ROADMAP B4 says what each would take).
        if self._ring_blocks and (sc.host_blocks or sc.spec_tokens):
            raise ValueError(
                f"this family's cache holds window leaves (a ring of {self._ring_blocks} blocks a sequence) beside its "
                f"full token rows: host_blocks > 0 (the ring does not ride with demoted blocks) and spec_tokens > 0 (a "
                f"rejected draft's row has already overwritten the ring) are not served for it; got "
                f"host_blocks={sc.host_blocks}, spec_tokens={sc.spec_tokens}"
            )
        # A family whose attention reads the rows an indexer selects (keye_vl2) says so by its model config's top-k.
        # Its index keys are a token leaf, so the prefix cache and the host tier carry them like K/V; a verify window
        # would be a group of several rows a lane, which its forward attends over the gathered context, a path no
        # test holds to the oracle for it yet (ROADMAP B).
        self._index_topk = int(getattr(config, "index_topk", 0) or 0)
        if self._index_topk and sc.spec_tokens:
            raise ValueError(
                f"this family's attention reads the {self._index_topk} rows its indexer selects: spec_tokens > 0 (a "
                f"verify window over a selected set) is not served for it; got spec_tokens={sc.spec_tokens}"
            )
        self.state_resets = 0  # lanes dispatched at position 0, which read a zero state whatever their slot held
        self.sched = Scheduler(
            self.cache.allocator,
            num_slots=sc.max_slots,
            block_size=sc.block_size,
            max_blocks_per_seq=sc.resolved_max_blocks(),
            prefill_chunk=sc.prefill_chunk,
            spec_overshoot=self.block_length if self.block_length > 1 else self.spec_tokens,
            window_allocator=self.cache.window_allocator,
            ring_blocks=self._ring_blocks,
        )
        max_len = sc.resolved_max_blocks() * sc.block_size
        model_max = getattr(config, "max_seq_len", None)
        if model_max is not None and max_len > model_max:
            raise ValueError(
                f"max_blocks_per_seq * block_size = {max_len} exceeds the "
                f"model's max_seq_len {model_max}; shrink the table or blocks"
            )
        self._finished: List[CompletedRequest] = []
        self._preempted_published = 0
        self._preemption_guard = None
        self._drained = False
        self.requeue_journal: Optional[List[dict]] = None
        self.ticks = 0
        self.decode_dispatches = 0
        self.decode_emitted_tokens = 0
        self.decode_slot_ticks = 0
        # Of decode_slot_ticks, for a block-diffusion family: lane-ticks that denoised and that committed, blocks
        # written to the pool, and new tokens emitted (whole blocks, the last one's tail dropped).
        self.denoise_slot_ticks = 0
        self.commit_slot_ticks = 0
        self.blocks_committed = 0
        self.block_tokens_emitted = 0
        self.spec_rounds = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.prefill_dispatches = 0
        # Ticks whose chunk rode in the decode's forward.  A tick issues
        # (prefill + decode - mixed) dispatches: one, whatever it holds.
        self.mixed_dispatches = 0
        # The tick dispatched and not yet read back (None after a settle), the dispatches made with the one before
        # them still unread, and the settles by reason.
        self._flight: Optional[_Flight] = None
        # What the last dispatch returned as its feed, on the device: the next dispatch's, whose lanes read it where
        # their source says so (after a settle none does).  Zeros made as the pool's leaves are, until the first.
        feed_shape = (sc.max_slots, self.block_length) if self.block_length > 1 else (sc.max_slots + 1,)
        self._feed = jnp.zeros(feed_shape, jnp.int32)
        self.pipelined_ticks = 0
        self.settles: Dict[str, int] = {}
        self.shed_count = 0
        self.deadline_expired_count = 0
        self.quarantined_count = 0
        self.prefix_hits = 0
        self.prefix_blocks_reused = 0
        self.cow_copies = 0
        self.decode_gather_bytes = 0
        # What the expert layers of every dispatch did (MOE_COUNTERS); stays 0 for a family without experts.
        self.moe_counters = dict.fromkeys(MOE_COUNTERS, 0)
        # What the window layers of every decode dispatch read (WINDOW_COUNTERS); reported where the family has them.
        self.window_counters = dict.fromkeys(WINDOW_COUNTERS, 0)
        # What the decoding lanes' attention copied where it read the pool in place (ATTN_COUNTERS); 0 where it gathered.
        self.attn_counters = dict.fromkeys(ATTN_COUNTERS, 0)
        # What the indexer scored and the attention read where a family selects its rows (SPARSE_COUNTERS).
        self.sparse_counters = dict.fromkeys(SPARSE_COUNTERS, 0)
        # KV-tiering accounting (engine-side migrations; the prefix cache's
        # own demote/promote churn is folded in at publish time).
        self.tier_demotions = 0
        self.tier_promotions = 0
        self.tier_demoted_blocks = 0
        self.tier_fallback_reprefills = 0
        self._prefix_demotions_published = 0
        self._prefix_promotions_published = 0
        self._draining = False
        self._submissions = 0
        self._recovering = False
        # NaN poison injection is gated at TRACE time (the train-step trick):
        # the unarmed decode program carries no poison plumbing at all; the
        # in-program finiteness detection is always compiled in.
        from ..resilience import faultinject

        self._poison_ordinal = faultinject.serving_nan_ordinal()
        self.journal: Optional[ServingJournal] = (
            ServingJournal(sc.journal_path) if sc.journal_path else None
        )
        self._block_bytes = self.cache.block_bytes()
        self._window_block_bytes = self.cache.window_block_bytes()
        self._prefix: Optional[PrefixCache] = (
            PrefixCache(self.cache.allocator, sc.block_size)
            if sc.prefix_cache and not self._state_names and not self._ring_blocks else None
        )
        if self.cache.host is not None:
            # Wire the tiering policies in: eviction pressure demotes cold
            # prefix chains instead of dropping them, and preemption demotes
            # the victim's KV instead of freeing it (the scheduler falls
            # back to the plain free-and-re-prefill when the hook declines).
            if self._prefix is not None:
                self._prefix.attach_tier(self.cache)
            self.sched.on_migrate_out = self._migrate_out
        self.sched.settle = self._settle
        # Per-request phase tracing (host-side interval bookkeeping only).
        # The scheduler's preemption callback is the one eviction site every
        # preemption flavor funnels through (drain, block pressure, LIFO
        # victim), so the tracer sees them all without per-caller plumbing.
        self.tracer: Optional[ServingTracer] = None
        if tracing_enabled(sc.trace):
            self.tracer = ServingTracer(dir=resolve_trace_dir(sc.trace_dir))
            self.sched.on_preempt = (
                lambda req: self.tracer.on_preempt(req, time.monotonic())
            )
        # Per-width jit-cache bookkeeping for bucket-compile attribution:
        # a width this engine has not dispatched yet means the next dispatch
        # pays a trace+compile in the request's latency path (_note_bucket).
        self._warm_widths: set = set()
        self._decode_widths: set = set()  # widths the decoding lanes ran at: stats()["decode_bucket_widths"]
        self._tick: dict = {}  # what the running tick is doing: step() starts one
        self._phase_t0: Optional[float] = None  # the end of the phase before: None while no tick's record is open
        self._phase: Optional[str] = None  # the _TickPhase that is open
        # Live /debug endpoints: the metrics HTTP server asks registered
        # engines for request/block snapshots (weakly — a collected engine
        # just drops off the page).
        from ..telemetry import export as _export

        _export.register_debug_source(self)
        # HBM ledger: the pool is a first-class reservation (its backing
        # arrays live for the engine's life), the prefix-cache residents a
        # subset entry (their bytes are INSIDE the pool — counting them
        # twice would poison the conservation residual).  A second engine
        # replaces the entries (last constructed wins); weakref.finalize
        # drops them when the owning engine is collected, token-guarded so
        # a replacement registration survives its predecessor's GC.
        from ..telemetry.memledger import get_memory_ledger

        ledger = get_memory_ledger()
        pool_token = ledger.register(
            "serving.kv_pool",
            tree=self.cache.token_leaves(),
            detail={
                "num_blocks": sc.num_blocks,
                "block_size": sc.block_size,
                "block_bytes": self._block_bytes,
            },
        )
        prefix_token = ledger.register(
            "serving.prefix_cache", nbytes=0, subset_of="serving.kv_pool"
        )
        import weakref

        weakref.finalize(self, ledger.unregister, "serving.kv_pool", pool_token)
        weakref.finalize(self, ledger.unregister, "serving.prefix_cache", prefix_token)
        self._memledger_tokens = (pool_token, prefix_token)
        if self._state_names:
            # The state leaves are a reservation of their own: by slot, not by block, so no part of kv_pool.
            state_token = ledger.register(
                "serving.state_pool",
                tree=self.cache.state_leaves(),
                detail={"max_slots": sc.max_slots, "leaves": self._state_names},
            )
            weakref.finalize(self, ledger.unregister, "serving.state_pool", state_token)
            self._memledger_tokens += (state_token,)
        if self._ring_blocks:
            # The window kind's blocks are a reservation of their own: numbered apart, no part of kv_pool.
            window_token = ledger.register(
                "serving.kv_window_pool",
                tree=self.cache.window_leaves(),
                detail={
                    "num_blocks": self.cache.window_allocator.num_blocks, "block_size": sc.block_size,
                    "block_bytes": self._window_block_bytes, "ring_blocks": self._ring_blocks,
                },
            )
            weakref.finalize(self, ledger.unregister, "serving.kv_window_pool", window_token)
            self._memledger_tokens += (window_token,)
        if self.cache.host is not None:
            # The host tier's backing arrays live for the engine's life, so
            # the reservation is static — and it charges host DRAM, not HBM
            # (per_device stays empty; the conservation residual must not
            # absorb bytes that never touched a device).
            host_token = ledger.register(
                "serving.kv_host",
                per_device={},
                host_bytes=self.cache.host.pool_bytes(),
                detail={
                    "host_blocks": sc.host_blocks,
                    "block_size": sc.block_size,
                    "block_bytes": self._block_bytes,
                },
            )
            weakref.finalize(self, ledger.unregister, "serving.kv_host", host_token)
            self._memledger_tokens += (host_token,)
        self._low_headroom = False
        try:
            self._headroom_watermark_frac = float(
                os.environ.get("ACCELERATE_TPU_SERVING_HEADROOM_WATERMARK", "") or 0.1
            )
        except ValueError:
            self._headroom_watermark_frac = 0.1
        # Hysteresis band for re-arming the low-headroom event: re-arm only
        # after the pool recovers ABOVE 1.5x the watermark, so a pool
        # oscillating right at the line emits one event per genuine pressure
        # episode instead of one per tick-scale wobble.
        self._headroom_rearm_frac = min(self._headroom_watermark_frac * 1.5, 1.0)
        # The compiled programs and what the tick asks of their back end
        # (serving/programs.py): the family decides "paged" or "dense".  One
        # jitted wrapper each; bucketed table widths retrace under it (jit
        # caches per shape), so a tick is exactly one dispatch, of the program
        # matching the live bucket.  With speculation on, the lanes carry the
        # k+1-window INSTEAD of one token, fed by a host-side drafter.
        self.programs = build_programs(
            apply_cached, config, self.cache.leaf_names, sc, self.spec_tokens, stateful=bool(self._state_names),
            ring_blocks=self._ring_blocks,
        )
        self.decode_path = self.programs.backend
        self._drafter = None
        if self.spec_tokens > 0:
            if drafter is None:
                from .drafter import NgramDrafter

                drafter = NgramDrafter(
                    max_ngram=sc.spec_ngram_max, min_ngram=sc.spec_ngram_min
                )
            self._drafter = drafter
        # Pre-create the robustness + fast-path counters so the Prometheus
        # endpoint exposes them at 0 from the first scrape — a dashboard can
        # alert on rate() without waiting for the first incident (or the
        # first prefix hit) to make the series exist.
        tel = get_telemetry()
        if tel.enabled:
            for name in (
                "serving.shed", "serving.deadline_expired",
                "serving.quarantined", "serving.journal_recoveries",
                "serving.prefix_hits", "serving.prefix_blocks_reused",
                "serving.prefix_cow_copies", "serving.decode_gather_bytes",
                "serving.mixed_dispatches", "serving.pipelined_ticks", "serving.settles",
                "serving.spec.proposed", "serving.spec.accepted",
                "serving.spec.rounds",
                "serving.tier.demotions", "serving.tier.promotions",
                "serving.tier.demoted_blocks", "serving.tier.fallback_reprefills",
            ):
                tel.registry.counter(name)
            if self.block_length > 1:
                for name in (
                    "serving.denoise_slot_ticks", "serving.commit_slot_ticks",
                    "serving.blocks_committed", "serving.block_tokens_emitted",
                ):
                    tel.registry.counter(name)
            tel.registry.gauge("serving.spec.acceptance_rate").set(0.0)
            tel.registry.gauge("serving.tokens_per_dispatch").set(0.0)
            tel.registry.gauge("serving.tier.host_bytes").set(0)
            tel.registry.gauge("serving.tier.host_occupancy").set(0.0)

    # -- request API ---------------------------------------------------------

    def install_preemption_guard(self, guard) -> None:
        """Honor a resilience :class:`PreemptionGuard`
        (``accelerator.enable_preemption_handling()`` installs one): once the
        fleet agrees a preemption signal arrived, the next :meth:`step` call
        DRAINS the engine instead of ticking — admission stops, in-flight
        slots are preempted back to the queue with their emitted tokens
        carried, and a ``serving.drained`` event records the requeue journal
        of incomplete requests so a successor process can resubmit them
        (re-prefilling prompt+emitted rebuilds each cache bit-identically,
        the same path a block-pressure preemption takes)."""
        if self._drained:
            raise RuntimeError(
                "engine already drained: the requeue journal is final and "
                "admission is closed — build a successor engine instead of "
                "re-arming this one."
            )
        self._preemption_guard = guard

    @property
    def drained(self) -> bool:
        return self._drained

    def submit(
        self,
        prompt_ids,
        max_new_tokens: int,
        arrival_t: Optional[float] = None,
        *,
        tag: Optional[str] = None,
        ttft_deadline_ms: Optional[float] = None,
        deadline_ms: Optional[float] = None,
        denoise_steps: Optional[int] = None,
        confidence_threshold: Optional[float] = None,
    ) -> int:
        """Queue one request; returns its id.  ``max_new_tokens == 0``
        completes immediately (the offline loop's contract).

        ``denoise_steps`` and ``confidence_threshold`` are for a family
        generated by diffusion over blocks alone (``ValueError`` for any
        other): the denoising passes a block takes (1 .. block length; default
        one a position) and the confidence over which a masked position is
        unmasked ahead of that schedule (default none: the static schedule, by
        which the engine stays one tick ahead; with a threshold on any live
        lane it settles every tick, ``settles["blocks"]``).

        Raises :class:`AdmissionRejected` when the queue is at
        ``max_queue_depth`` (load shedding — ``serving.shed``); ``ValueError``
        when the request's geometry can never be served.  Deadlines default
        from the :class:`ServingConfig`; an explicit per-request value wins
        (``None`` means "use the default", so a config default cannot be
        waived per request).  ``tag`` is an opaque caller label carried
        into the :class:`CompletedRequest`, the journal, and the
        ``serving.request_complete`` event — the stable identity across a
        journal recovery, where engine ids change."""
        if self._drained:
            raise RuntimeError(
                "engine drained after a preemption signal: admission is closed "
                "and the requeue journal is final — resubmit to a successor "
                "engine (see engine.requeue_journal)."
            )
        sc = self.serving
        if (
            sc.max_queue_depth is not None
            and not self._recovering
            and self.sched.pending >= sc.max_queue_depth
        ):
            self.shed_count += 1
            tel = get_telemetry()
            if tel.enabled:
                tel.registry.counter("serving.shed").inc()
            raise AdmissionRejected(
                f"admission queue full ({self.sched.pending} >= "
                f"max_queue_depth {sc.max_queue_depth}): request shed"
            )
        if self.block_length > 1:
            denoise_schedule(self.block_length, denoise_steps)  # refuses a count outside 1 .. block length
        elif denoise_steps is not None or confidence_threshold is not None:
            raise ValueError(
                "denoise_steps and confidence_threshold are for a family generated by diffusion over blocks "
                "(a model config with block_length > 1); this engine's family decodes one token a step"
            )
        req = Request(
            list(np.asarray(prompt_ids).reshape(-1)),
            max_new_tokens,
            arrival_t,
            tag=tag,
            denoise_steps=denoise_steps,
            confidence_threshold=confidence_threshold,
            ttft_deadline_ms=(
                ttft_deadline_ms if ttft_deadline_ms is not None
                else sc.default_ttft_deadline_ms
            ),
            deadline_ms=(
                deadline_ms if deadline_ms is not None else sc.default_deadline_ms
            ),
        )
        if req.max_new_tokens == 0:
            now = time.monotonic()
            req.state = RequestState.DONE
            req.admit_t = req.finish_t = now
        else:
            self.sched.submit(req)  # geometry validation may reject — count after
        self._submissions += 1
        if self._poison_ordinal is not None and self._submissions == self._poison_ordinal:
            req._poison_pending = True  # fires on this request's first decode
        # Write-ahead: the admission lands on disk BEFORE the id is returned,
        # so every acknowledged request is recoverable after a SIGKILL.
        if self.journal is not None:
            self.journal.record_admit(req)
        if self.tracer is not None:
            self.tracer.on_submit(req)
        tel = get_telemetry()
        if tel.enabled:
            tel.registry.counter("serving.requests").inc()
        if req.state == RequestState.DONE:
            self._complete(req)
        return req.id

    def step(self) -> List[CompletedRequest]:
        """One engine tick: admit, build one prefill chunk and the decode
        batch, ONE fused dispatch of both, then the read-back of the tick
        *before* (under a verify window: of this one too).  Returns the
        requests that completed during the call: those whose last token was
        read.  With an
        installed :class:`PreemptionGuard` whose signal has arrived, the
        tick drains instead (no admission, no dispatch)."""
        now = time.monotonic()
        done_before = len(self._finished)
        if self._drained or self._drain_requested():
            self.drain()
            return self._finished[done_before:]
        self.ticks += 1
        states = [slot.request.state for slot in self.sched.slots.values()]
        # The tick's ONE record: _TickPhase fills phase_ms, _dispatch_tick the dispatch's shape, _settle the reads
        # beyond the pipelined one; _close_tick writes the serving.tick span's stats and the counters from it, and
        # ServingTracer keeps it if it is among the slowest.
        self._tick = tick = {
            "tick": self.ticks,
            "prefilling": states.count(RequestState.PREFILLING),
            "live": 0, "width": None, "width_lanes": 0, "width_window": 0, "rows_live": 0, "rows_computed": 0,
            "fresh": False, "mixed": False, "pipelined": False, "settle": None, "settles": 0,
            "phase_ms": {},
        }
        self._phase_t0 = now
        with annotate(
            "serving.tick", tick=self.ticks, queued=self.sched.pending,
            prefilling=tick["prefilling"], decoding=states.count(RequestState.DECODING),
        ) as tick_span:
            with _TickPhase(self, "admit") as span:
                if self.tracer is not None:
                    self.tracer.begin_tick(now)
                self._drain_scrubs()
                # Deadline expiry FIRST: an expired queued request is shed before a
                # slot, a prefill chunk, or any blocks are spent on it.
                self._expire_deadlines(now)
                # Demote-before-shed: with the raw free list under the watermark,
                # batch-demote cold prefix chains to host DRAM BEFORE admission, so
                # the allocations this tick makes hit the free list instead of
                # dropping cached content on demand.
                self._pressure_relief()
                admitted = self.sched.admit(now)
                for idx in admitted:
                    req = self.sched.slots[idx].request
                    if req.admit_tick is None:
                        req.admit_tick = self.ticks
                if self.tracer is not None:
                    admit_t = time.monotonic()
                    for idx in admitted:
                        self.tracer.on_admit(self.sched.slots[idx].request, admit_t, idx)
                for idx in admitted:
                    # Host-tier round-trip first: a re-admitted migration victim
                    # promotes its demoted KV back and resumes exactly where it
                    # stopped (zero re-prefill dispatches); _attach_prefix then
                    # skips it (its cache_len is already set).
                    self._promote_admitted(idx)
                for idx in admitted:
                    self._attach_prefix(idx)
                self._observe_requeue_waits(admitted)
                span.set_metadata(admitted=len(admitted))
            # Both builds come before the one dispatch: the chunk of the
            # oldest prefilling slot, then the decoding lanes (whose growth
            # may preempt that slot: its chunk is dropped with it).
            chunk = self._build_chunk()
            batch = self._build_decode()
            if chunk is not None and self.sched.slots.get(chunk.idx) is not chunk.slot:
                chunk = None
            dispatched = chunk is not None or batch is not None
            if dispatched:
                self._dispatch_tick(chunk, batch)
            if self._flight is not None and not (dispatched and (self.sched.slots or self.sched.queue)):
                # Nothing was, or nothing is left to be, dispatched behind the tick in flight (its lanes are
                # retiring; or the queue's head waits for the blocks they hold): the next step() could only wait
                # for it, so it is read now and the replies are handed over.
                self._settle("idle")
            with annotate("serving.tick.publish", tick=self.ticks):
                self._drain_scrubs()
                self._publish_gauges()
                # Last, so that the tick's record holds all of the tick but the tracer's own bookkeeping.
                end = time.monotonic()
                account = self._close_tick(now, end)
                if self.tracer is not None:
                    self.tracer.end_tick(end, self.sched.slots, tick, unread=self._unread_requests())
            tick_span.set_metadata(**account)
        return self._finished[done_before:]

    def _close_tick(self, start: float, end: float) -> dict:
        """The tick's record closed at ``end``: ``publish`` takes what is left,
        ``phase_ms`` sums to ``total_ms``, and no later read books into it.
        From the record, and from nothing else, come the ``serving.tick``
        span's stats (returned: integers, what the dispatch held; the times are
        the spans' own) and the counters of what the dispatch was."""
        tick = self._tick
        phase_ms = tick["phase_ms"]
        phase_ms["publish"] = phase_ms.get("publish", 0.0) + (end - self._phase_t0) * 1e3
        tick["total_ms"] = (end - start) * 1e3
        self._phase_t0 = None
        account = {
            "rows_live": tick["rows_live"], "rows_computed": tick["rows_computed"],
            "width": tick["width"] or 0, "width_lanes": tick["width_lanes"], "width_window": tick["width_window"],
            "mixed": int(tick["mixed"]), "pipelined": int(tick["pipelined"]), "settles": tick["settles"],
        }
        self.mixed_dispatches += account["mixed"]
        self.pipelined_ticks += account["pipelined"]
        tel = get_telemetry()
        # (the telemetry names stand here as literals: tests/test_metric_names.py reads the emit sites)
        if tel.enabled and account["mixed"]:
            tel.registry.counter("serving.mixed_dispatches").inc()
        if tel.enabled and account["pipelined"]:
            tel.registry.counter("serving.pipelined_ticks").inc()
        return account

    def run(self, max_ticks: Optional[int] = None) -> Dict[int, List[int]]:
        """Drive ticks until every submitted request completes; returns
        ``{request_id: full token list (prompt + generated)}``.  A
        preemption-triggered drain ends the loop early: completed requests
        are returned, incomplete ones are in :attr:`requeue_journal`."""
        ticks = 0
        while not self.sched.idle():
            self.step()
            if self._drained:
                break
            ticks += 1
            if max_ticks is not None and ticks >= max_ticks:
                raise RuntimeError(
                    f"engine did not drain within {max_ticks} ticks "
                    f"(active {self.sched.active}, queued {self.sched.pending})"
                )
        return {c.id: c.tokens for c in self._finished}

    def _drain_requested(self) -> bool:
        """Whether the installed guard says stop.  For a multi-host
        COORDINATED guard the LOCAL flag is consulted, never should_stop():
        that path gates a cross-host collective on a per-guard call counter
        that every process must hit in lockstep, and engine tick counts are
        data-dependent (queue depth differs per host) — one desynchronized
        gather would hang the fleet.  Fleet-wide stop agreement belongs to
        the training loop's check_preemption(); the drain itself is a local
        action (each host journals its own queue)."""
        guard = self._preemption_guard
        if guard is None:
            return False
        coordinated = getattr(guard, "_coordination_on", None)
        if coordinated is not None and coordinated():
            return guard.preempted_locally()
        return guard.should_stop()

    def drain(self) -> List[dict]:
        """Graceful drain: stop admission, preempt every in-flight slot back
        to the queue (blocks freed, emitted tokens carried — the oldest
        request ends up at the queue FRONT, preserving FIFO priority), and
        publish the requeue journal of incomplete requests as a
        ``serving.drained`` event.  Idempotent; returns the journal."""
        if self._drained:
            return self.requeue_journal or []
        self._settle("drain")  # the journal carries every token dispatched
        # Migration is pointless past this line: host DRAM dies with the
        # process, so demoting a drained slot would spend a D2H copy on
        # bytes no successor can read — and leak the host blocks at exit.
        # The flag makes _migrate_out decline; every slot takes the classic
        # free-and-requeue path, and already-demoted queued victims release
        # their host blocks below (the journal recorded their progress).
        self._draining = True
        while self.sched.slots:
            self.sched.preempt_one()
        for req in self.sched.queue:
            self._release_demoted(req)
        journal = [
            {
                "id": req.id,
                # Full prompt + emitted tokens: a successor engine resubmits
                # prompt+emitted with max_new=remaining and greedy decode
                # finishes the request token-identically (the engine's own
                # re-prefill path).
                "prompt": list(req.prompt),
                "emitted": list(req.emitted),
                "remaining": req.remaining,
                "preemptions": req.preemptions,
                "tag": req.tag,
            }
            for req in self.sched.queue
        ]
        self._drained = True
        self.requeue_journal = journal
        self._drain_scrubs()
        if self.journal is not None:
            # Persist emitted progress so the successor resumes mid-request
            # (prompt+emitted) instead of re-decoding from the prompt.
            self.journal.record_progress(self.sched.queue)
        tel = get_telemetry()
        if tel.enabled:
            tel.registry.counter("serving.drains").inc()
            tel.event(
                "serving.drained",
                incomplete=len(journal),
                completed=len(self._finished),
                journal=journal,
            )
        if self.tracer is not None:
            # Snapshot every still-live timeline: the successor's stitcher
            # needs this life's partial phases even though no terminal
            # record will ever land here.
            self.tracer.flush()
        self._publish_gauges()
        return journal

    def pop_finished(self) -> List[CompletedRequest]:
        out, self._finished = self._finished, []
        return out

    # -- crash recovery ------------------------------------------------------

    def recover_from_journal(self, path: Optional[str] = None) -> Dict[int, int]:
        """Rebuild a dead predecessor's queue from its write-ahead journal:
        every journaled request with no terminal record is resubmitted as
        ``prompt + emitted`` with ``max_new = remaining`` (the bit-exact
        re-prefill path), so this engine finishes each one token-identically
        to the uninterrupted run.  Returns ``{old id: new id}``.

        Call BEFORE the first ``submit`` when this engine journals to the
        same path — the first admission overwrites the file.  Deadlines
        restart from recovery time (the predecessor's arrival clock died
        with it); a request that already blew its deadline there was either
        already shed (terminal in the journal) or gets a fresh budget here.
        Terminal requests — completed, shed, quarantined — are never
        replayed."""
        path = path or self.serving.journal_path
        if path is None:
            raise ValueError("no journal path: pass one or set ServingConfig.journal_path")
        if self.journal is not None and self.journal.flushed and os.path.abspath(
            path
        ) == os.path.abspath(self.journal.path):
            raise JournalError(
                "this engine already overwrote the journal at "
                f"{path!r}; recover_from_journal must run before the first submit"
            )
        self._settle("recover")
        state = ServingJournal.load(path)
        pending = ServingJournal.pending(state)
        mapping: Dict[int, int] = {}
        # Recovery resubmissions bypass the max_queue_depth shed (a dead
        # engine's backlog is not a traffic burst — shedding here would
        # silently LOSE acknowledged requests) and batch the journal into
        # ONE atomic flush: flushing per resubmit would overwrite the
        # predecessor's file after the first one, so a SIGKILL mid-recovery
        # would strand the rest with no journal anywhere.
        batch = self.journal.deferred() if self.journal is not None else contextlib.nullcontext()
        self._recovering = True
        try:
            with batch:
                for rec in pending:
                    emitted = rec.get("emitted") or []
                    rid = self.submit(
                        rec["prompt"] + list(emitted),
                        rec["max_new_tokens"] - len(emitted),
                        tag=rec.get("tag"),
                        ttft_deadline_ms=rec.get("ttft_deadline_ms"),
                        deadline_ms=rec.get("deadline_ms"),
                        denoise_steps=rec.get("denoise_steps"),
                        confidence_threshold=rec.get("confidence_threshold"),
                    )
                    mapping[rec["id"]] = rid
                    if self.tracer is not None:
                        self.tracer.on_recover(rid, rec)
        finally:
            self._recovering = False
        if self.tracer is not None:
            # Land the recovered requests' snapshot lines immediately: the
            # stitcher can already pair this life with the victim's even if
            # this engine is itself killed before any completes.
            self.tracer.flush()
        tel = get_telemetry()
        if tel.enabled:
            tel.registry.counter("serving.journal_recoveries").inc()
            tel.event(
                "serving.journal_recovered",
                path=path,
                recovered=len(mapping),
                terminal=len(state["done"]),
            )
        return mapping

    # -- KV tiering (host-DRAM second tier) ----------------------------------

    def _migrate_out(self, slot) -> bool:
        """Preemption-as-migration (the scheduler's ``on_migrate_out`` hook):
        copy the victim slot's blocks to the host tier, release the device
        references, and stash the host ids + resume state on the request —
        re-admission then promotes and resumes with zero re-prefill
        dispatches.  Declines (→ plain free-and-re-prefill) during a drain
        (host DRAM dies with the process; demoting would waste a copy and
        leak at exit), when any block is quarantine-dirty (a possibly
        poisoned block must be rebuilt clean, never tiered), or when the
        host tier cannot fit even after dropping cold cached prefixes (a
        live request outranks a cold chain)."""
        req = slot.request
        blocks = slot.blocks
        if self._draining or not blocks:
            return False
        alloc = self.cache.allocator
        tel = get_telemetry()
        if any(alloc.is_dirty(b) for b in blocks):
            req.fallback_reprefills += 1
            self.tier_fallback_reprefills += 1
            if tel.enabled:
                tel.registry.counter("serving.tier.fallback_reprefills").inc()
            return False
        n = len(blocks)
        if not self.cache.host_can_fit(n) and self._prefix is not None and self.cache.host is not None:
            need = n - self.cache.host.free_blocks
            if 0 < need <= self._prefix.host_count:
                self._prefix.drop_host_entries(need)
        if not self.cache.host_can_fit(n):
            req.fallback_reprefills += 1
            self.tier_fallback_reprefills += 1
            if tel.enabled:
                tel.registry.counter("serving.tier.fallback_reprefills").inc()
            return False
        host_ids = self.cache.demote(blocks)
        req.demoted_blocks = host_ids
        req.demoted_rows = slot.cache_len
        req.demoted_registered = slot.registered_blocks
        req.migrations += 1
        alloc.free(blocks)  # demotion copied; release the slot's device refs
        self.tier_demotions += 1
        self.tier_demoted_blocks += n
        if tel.enabled:
            tel.registry.counter("serving.tier.demotions").inc()
            tel.registry.counter("serving.tier.demoted_blocks").inc(n)
        if self.journal is not None:
            self.journal.record_tier(req, "host")
        return True

    def _promote_admitted(self, idx: int) -> None:
        """Re-admission half of preemption-as-migration: allocate device
        blocks for a demoted request, copy its KV back from the host tier,
        and restore the slot exactly as preemption found it — cache_len,
        registration cursor, and DECODING state when the cache already
        covers every fed token but the last emitted one (the decode
        invariant), so no prefill dispatch is ever spent on the resume.
        When the device pool cannot grant the blocks, the request falls
        back to the PR 9 re-prefill (host blocks released, counted)."""
        slot = self.sched.slots.get(idx)
        if slot is None:
            return
        req = slot.request
        host_ids = req.demoted_blocks
        if not host_ids:
            return
        tel = get_telemetry()
        try:
            dst = self.cache.allocator.alloc(len(host_ids))
        except BlockOutOfMemory:
            self._release_demoted(req)
            req.fallback_reprefills += 1
            self.tier_fallback_reprefills += 1
            if tel.enabled:
                tel.registry.counter("serving.tier.fallback_reprefills").inc()
            if self.journal is not None:
                self.journal.record_tier(req, "device")
            return
        self.cache.promote(host_ids, dst)
        slot.blocks = dst
        slot.cache_len = req.demoted_rows
        slot.registered_blocks = req.demoted_registered
        req.demoted_blocks = None
        req.demoted_rows = 0
        req.demoted_registered = 0
        if req.emitted and slot.cache_len == len(req.to_feed) - 1:
            # Mid-decode victim: the only unwritten row is the last emitted
            # token's (the next decode dispatch writes it) — resume DECODING
            # with zero re-prefill dispatches.
            req.state = RequestState.DECODING
        # else: mid-prefill victim — admit() already set PREFILLING; the
        # next chunk continues from cache_len, no rows recomputed.
        self.tier_promotions += 1
        if tel.enabled:
            tel.registry.counter("serving.tier.promotions").inc()
        if self.journal is not None:
            self.journal.record_tier(req, "device")

    def _release_demoted(self, req: Request, dirty: bool = False) -> None:
        """Free a request's demoted host blocks (deadline expiry of a queued
        victim, promotion fallback, drain, or defensively at quarantine).
        ``dirty=True`` routes them through the host tier's synchronous
        zero-scrub — the host half of the two-tier scrub contract."""
        if req.demoted_blocks:
            if dirty:
                self.cache.host.mark_dirty(req.demoted_blocks)
            self.cache.host.free(req.demoted_blocks)
        req.demoted_blocks = None
        req.demoted_rows = 0
        req.demoted_registered = 0

    def _pressure_relief(self) -> None:
        """Proactive demote-before-shed: when the allocator's RAW free list
        (free_blocks minus reclaimable cache blocks) falls under the
        headroom watermark, demote up to ``tier_demote_batch`` cold cache
        chains to host DRAM in one batch — the D2H copies happen here, off
        the allocation path, so this tick's grants pop the free list instead
        of dropping cached prefixes on demand.  The admission waterfall is
        demote → evict-drop (host full) → preempt-migrate → preempt-free
        (fallback) → terminal OOM."""
        if (
            self._prefix is None
            or self.cache.host is None
            or self.serving.tier_demote_batch <= 0
        ):
            return
        alloc = self.cache.allocator
        raw_free = alloc.free_blocks - self._prefix.reclaimable_count
        if raw_free / max(alloc.capacity, 1) >= self._headroom_watermark_frac:
            return
        reclaim = min(self.serving.tier_demote_batch, self._prefix.reclaimable_count)
        if reclaim > 0:
            self._prefix.evict(reclaim)

    # -- deadline / quarantine enforcement -----------------------------------

    def _observe_requeue_waits(self, admitted: List[int]) -> None:
        """Land the re-queue wait samples of just-(re)admitted requests in
        ``serving.requeue_wait_ms`` — the preemption-wait blind spot that
        first-admission-only ``queue_wait_ms`` cannot see."""
        tel = get_telemetry()
        if not tel.enabled:
            return
        hist = tel.registry.histogram("serving.requeue_wait_ms")
        for idx in admitted:
            slot = self.sched.slots.get(idx)
            if slot is None:
                continue
            for sample in slot.request.pop_requeue_waits():
                hist.observe(sample)

    def _expire_deadlines(self, now: float) -> None:
        """Shed expired QUEUED requests (no prefill chunk is ever spent on a
        corpse) and cancel expired in-flight ones (blocks freed, slot
        returned to the pool)."""
        expired_queued = [req for req in self.sched.queue if req.expired(now)]
        for req in expired_queued:
            self.sched.cancel_queued(req)
            self._finish_expired(req, now)
        if any(slot.request.expired(now) for slot in self.sched.slots.values()):
            # A live request is cancelled with every token dispatched for it: read the tick in flight first (a
            # first token among them meets a TTFT deadline late rather than never).  A queued one has none unread.
            self._settle("deadline")
        for idx in list(self.sched.slots):
            req = self.sched.slots[idx].request
            if req.expired(now):
                self.sched.finish(idx, now)  # frees the blocks
                self._finish_expired(req, now)

    def _finish_expired(self, req: Request, now: float) -> None:
        # A queued migration victim dies with KV still in the host tier —
        # release it or the tier leaks a dead request's blocks forever.
        self._release_demoted(req)
        req.state = RequestState.DONE
        req.finish_t = now
        self.deadline_expired_count += 1
        tel = get_telemetry()
        if tel.enabled:
            tel.registry.counter("serving.deadline_expired").inc()
            if req.first_token_t is None:
                # Feed the violation into the TTFT histogram so the SLO
                # burn-rate gauges see it: without this, expired requests
                # never observe a latency and the burn rate only measures
                # the survivors.
                tel.registry.histogram("serving.ttft_ms").observe(
                    (now - req.arrival_t) * 1e3
                )
        self._complete(req, status="deadline_expired")

    def _quarantine(self, slot, now: float) -> None:
        """A slot's logits came back non-finite: complete its request with an
        error status and mark its pool blocks for a zero-scrub.  The scrub is
        load-bearing, not hygiene — the attention mask zeroes a hidden row's
        probability, but ``0 * NaN = NaN`` in ``probs @ v``, so a NaN row
        left in a recycled block would corrupt the block's next owner.
        (Finite garbage in recycled blocks is safe for exactly that reason,
        which is why normal frees never scrub.)

        With prefix sharing the scrub happens **on last release**: a block
        another request is still reading is never zeroed under it (the live
        reader's own finiteness check guards it — if the shared content were
        truly poisoned, that reader quarantines itself the same way).  The
        block is dropped from the prefix cache immediately, so no NEW reader
        can attach to it.

        The flag is read one dispatch late: the tick already in flight
        computed one row more for this slot (or its next chunk).  That row
        went to the slot's own blocks, the scrub is ordered behind it on the
        device's one stream, and its tokens are dropped at their read-back
        (the request is ``DONE``)."""
        if self._prefix is not None:
            self._prefix.invalidate_blocks(slot.blocks)
        self.cache.allocator.mark_dirty(slot.blocks)
        if slot.window_blocks:
            self.cache.window_allocator.mark_dirty(slot.window_blocks)
        req = self.sched.release(slot, now)
        # Defensive: a slotted request holds no demoted blocks by invariant
        # (promotion clears them at admission), but if any exist they route
        # through the host tier's dirty scrub — the two-tier contract.
        self._release_demoted(req, dirty=True)
        # Unshared blocks just hit refcount 0 and are scrubbed right here;
        # the null block is always included (a poisoned request's padded
        # prefill rows scatter past its table into block 0).
        self._drain_scrubs(always_null=True)
        self.quarantined_count += 1
        tel = get_telemetry()
        if tel.enabled:
            tel.registry.counter("serving.quarantined").inc()
            tel.event(
                "serving.quarantined",
                request=req.id,
                tag=req.tag,
                emitted=len(req.emitted),
                prompt_len=len(req.prompt),
            )
        self._complete(req, status="quarantined")

    def _scrub_blocks(self, blocks: List[int], window: bool = False) -> None:
        # The NULL block is always scrubbed too: a poisoned request's padded
        # prefill rows route PAST its block table into block 0 (the
        # scatter's explicit overflow target), so genuine NaN K/V — unlike
        # the logits-only injection — can land in the one block every slot's
        # gathered view shares.  Zero is always safe there: null-block rows
        # are only ever read at masked positions.
        # A state leaf is no block and needs no scrub: its slot's next request starts at position 0 and reads
        # zeros by a select (generation.read_state_rows), a NaN left there included.
        # The window kind's blocks are numbered apart and have a null block of their own: the same scrub, on its leaves.
        idx = jnp.asarray(sorted(set(blocks) | {NULL_BLOCK}), jnp.int32)
        over = with_window_leaves if window else with_token_leaves
        self.cache.pool = over(self.cache.pool, lambda leaf: leaf.at[:, idx].set(0))

    def _drain_scrubs(self, always_null: bool = False) -> None:
        """Scrub-on-last-release: zero the dirty blocks whose final reference
        dropped since the previous drain and hand them back to the free
        list.  They are not allocatable in between, so a dirty block can
        never be granted unscrubbed."""
        for alloc, window in ((self.cache.allocator, False), (self.cache.window_allocator, True)):
            if alloc is None:  # no window leaves
                continue
            pending = alloc.pop_pending_scrub()
            if pending or always_null:
                self._scrub_blocks(pending, window)
                alloc.finish_scrub(pending)

    # -- prefix cache --------------------------------------------------------

    def _attach_prefix(self, idx: int) -> None:
        """On admission, reuse the cached prefix of the slot's feed: matched
        full blocks are refcount-shared into the slot's table wholesale, a
        reusable partial tail is claimed via copy-on-write, and
        ``cache_len`` starts past the shared rows — prefill (and TTFT)
        collapse to the unshared suffix.  At least one feed token is always
        left to process: the final chunk's logits ARE the next token."""
        if self._prefix is None:
            return
        slot = self.sched.slots.get(idx)
        if slot is None:
            return
        if slot.blocks:
            # A promoted migration victim already owns its table and
            # cache_len — the cached-prefix attach is for EMPTY slots only.
            return
        feed = slot.request.to_feed
        max_rows = len(feed) - 1
        if self.block_length > 1:
            # Whole pool blocks of the rows that are prefilled: under the block-causal mask a pool block's rows depend
            # on nothing past its own end (block_size is a multiple of the block length), and the chunk yields no
            # token, so nothing has to be left to process.  No copy-on-write tail: a lane's rows start a block.
            max_rows = self._prefill_rows(feed) // self.serving.block_size * self.serving.block_size
        if max_rows < self.serving.block_size:
            return
        blocks, rows, cow_src = self._prefix.lookup(feed, max_rows)
        reused = len(blocks)
        registered = len(blocks)  # leading blocks came FROM the cache
        if cow_src is not None:
            dst = None
            try:
                dst = self.cache.allocator.alloc(1)[0]
            except BlockOutOfMemory:
                pass  # best effort: prefill the tail instead of copying it
            if dst is not None:
                self._copy_block(cow_src, dst)
                blocks.append(dst)
                rows = max_rows
                reused += 1
                self.cow_copies += 1
            # Release the lookup's temporary reference on the source either
            # way (the copy is done, or we declined it).
            self.cache.allocator.free([cow_src])
        if not blocks:
            return
        slot.blocks = blocks
        slot.cache_len = rows
        slot.registered_blocks = registered
        self.prefix_hits += 1
        self.prefix_blocks_reused += reused
        tel = get_telemetry()
        if tel.enabled:
            tel.registry.counter("serving.prefix_hits").inc()
            tel.registry.counter("serving.prefix_blocks_reused").inc(reused)
            if rows > registered * self.serving.block_size:
                tel.registry.counter("serving.prefix_cow_copies").inc()

    def _copy_block(self, src: int, dst: int) -> None:
        """Copy-on-write: duplicate one physical block across every pool
        leaf so the new owner can keep writing where the shared prefix
        stops.  Runs on the admission path, never inside the decode
        dispatch."""
        self.cache.pool = with_token_leaves(self.cache.pool, lambda leaf: leaf.at[:, dst].set(leaf[:, src]))

    def _register_prefix_blocks(self, slot, rows: int) -> None:
        """Publish the slot's freshly prefilled FULL blocks under their chain
        hashes.  Only blocks entirely below ``rows`` (the real rows of the
        chunk just read back — the padded tail of a chunk never counts, nor
        a later chunk dispatched and not yet read) are registered, and writes only
        move forward from there, so a registered block is never
        written again."""
        if self._prefix is None:
            return
        bs = self.serving.block_size
        feed = slot.request.to_feed
        full = min(rows, len(feed)) // bs
        if full <= slot.registered_blocks:
            return
        keys = PrefixCache.chain_keys(feed, bs, limit=full)
        for i in range(slot.registered_blocks, full):
            self._prefix.register(keys[i], slot.blocks[i])
        slot.registered_blocks = full

    # -- tick phases ---------------------------------------------------------

    def _note_bucket(self, kind: str, width: int) -> bool:
        """Record a dispatch at this table width; returns True when the
        width is FRESH: the per-width jit cache misses and the dispatch pays
        a trace+compile in the request's latency path.  The
        ``serving.bucket_compile`` event makes that TTFT spike attributable
        even with tracing disabled.

        A fresh width compiles **every** program the engine can dispatch at
        it, not only the one that met it (``kind``): each runs once on idle
        lanes, whose rows land in the null block.  A warm-up of single
        requests run alone meets a width with a chunk or with a decoder, never
        with both; the first tick under load that holds both must not compile."""
        if width in self._warm_widths:
            return False
        self._warm_widths.add(width)
        tel = get_telemetry()
        if tel.enabled:
            # "dispatch" not "kind": event() reserves "kind" for the record
            # envelope, and a field named kind would shadow it in the JSONL.
            tel.event("serving.bucket_compile", dispatch=kind, width=width)
        self._tick["fresh"] = True
        lanes = self._idle_lanes(width)
        chunk = [
            np.zeros((width,), np.int32), np.int32(0),
            np.zeros((1, self.serving.prefill_chunk), np.int32), np.int32(1),
        ]
        poison = [] if self._poison_ordinal is None else [np.ones((self.serving.max_slots,), np.float32)]
        # with a state: no lane live, and a chunk of no real row in slot 0: nothing is written by slot
        state, chunk_state = self._state_args([], None), self._state_args([], 0)
        if state:
            chunk[-1] = np.int32(0)
        if self._ring_blocks:  # every window table names the window kind's null block alone
            wide = self.programs.window_width(width)
            state = [np.zeros((self.serving.max_slots, wide), np.int32)]
            chunk_state = state + [np.zeros((wide,), np.int32)]
        for program, args in ((self.programs.decode, lanes + state), (self.programs.decode_chunk, lanes + chunk + chunk_state)):
            _, _, self.cache.pool = program(self.params, self.cache.pool, *args, *poison)
        return True

    def _state_args(self, live: List[int], chunk_slot: Optional[int]) -> list:
        """What a program of a family with a state takes behind its other
        arguments: ``live [S]`` (1 where a lane decodes) and, with a chunk, the
        slot it prefills.  Nothing for any other family."""
        if not self.programs.stateful:
            return []
        flags = np.zeros((self.serving.max_slots,), np.int32)
        flags[live] = 1
        return [flags] if chunk_slot is None else [flags, np.int32(chunk_slot)]

    def _idle_lanes(self, width: int) -> list:
        """``[tables, lengths, tokens, draft_len, feed, source]`` of a dispatch
        none of whose lanes is live: every table names the null block alone,
        every lane reads the host's token (``FEED_HOST`` is 0), and the feed is
        the last dispatch's, as in every dispatch (one signature a program)."""
        s = self.serving.max_slots
        lanes = [
            np.zeros((s, width), np.int32), np.zeros((s,), np.int32),
            np.zeros((s, self.programs.window), np.int32), np.zeros((s,), np.int32),
            self._feed, np.zeros((s,), np.int32),
        ]
        if self.block_length > 1:  # no lane commits, no threshold a confidence reaches
            lanes += [np.zeros((s,), np.int32), np.full((s,), 2.0, np.float32)]
        return lanes

    def _prefill_rows(self, feed: List[int]) -> int:
        """How many of a request's ``to_feed`` rows are prefilled in chunks: all
        of them, or for a block-diffusion family its whole blocks (the
        remainder opens the first block a lane carries)."""
        return len(feed) // self.block_length * self.block_length

    def _open_blocks(self, slot) -> None:
        """The slot's prompt is in the pool up to its last whole block: it
        decodes from the next tick on, carrying the first block, which opens on
        the rest of the prompt and then masks."""
        req = slot.request
        req.state = RequestState.DECODING
        slot.block = _Block(
            self.block_length, req.to_feed[slot.cache_len :], denoise_schedule(self.block_length, req.denoise_steps))

    def _build_chunk(self) -> Optional[_Chunk]:
        """The tick's prefill chunk: the next ``prefill_chunk`` tokens of the
        oldest prefilling slot, its blocks grown to hold them.  None when no
        slot is prefilling, or the slot itself was preempted to find blocks."""
        sched = self.sched
        with _TickPhase(self, "prefill.build") as span:
            if self.block_length > 1:
                # nothing left to prefill (a prompt shorter than a block, a prefix hit on every whole block, a
                # promoted migration victim): the slot decodes at once
                for slot in sched.slots.values():
                    if slot.request.state == RequestState.PREFILLING and slot.cache_len >= self._prefill_rows(slot.request.to_feed):
                        self._open_blocks(slot)
            candidates = [
                (slot.admit_seq, idx)
                for idx, slot in sched.slots.items()
                if slot.request.state == RequestState.PREFILLING
            ]
            if not candidates:
                return None
            _, idx = min(candidates)
            slot = sched.slots[idx]
            feed = slot.request.to_feed
            start = slot.cache_len
            chunk_len = self.serving.prefill_chunk
            n_real = min(chunk_len, self._prefill_rows(feed) - start)
            if not sched.grow_to(idx, start + n_real):
                return None
            tokens = np.zeros((1, chunk_len), np.int32)
            tokens[0, :n_real] = feed[start : start + n_real]
            span.set_metadata(request=slot.request.id, start=start, rows=n_real)
            # The table covers the chunk's padded write extent: the gather
            # reads the blocks this prefill can actually touch.
            return _Chunk(idx, slot, start, n_real, tokens, blocks_for_tokens(start + chunk_len, self.serving.block_size))

    def _build_decode(self) -> Optional[_Lanes]:
        """The tick's decode batch: every decoding slot grown by one window,
        oldest first, and the survivors' tables, lengths and tokens.  None
        when no lane is live."""
        sched = self.sched
        with _TickPhase(self, "decode.build") as span:
            decoding = sorted(
                (idx for idx, slot in sched.slots.items()
                 if slot.request.state == RequestState.DECODING),
                key=lambda i: sched.slots[i].admit_seq,
            )
            # Speculative drafts come BEFORE block growth: a spec engine's every
            # decode tick is a k+1-window verify dispatch whose write extent is
            # the full window for EVERY live slot (the program scatters all
            # rows), so growth must budget window rows whether or not a given
            # slot has drafts of its own.  Draft-less slots (and draft-less
            # ticks) ride the same program with ``draft_len = 0`` — the window
            # is FIXED at k+1 whenever speculation is on, so each bucket has
            # exactly one decode program shape and a rare draft-less tick can
            # never trigger a fresh single-token compile mid-serve.  A draft
            # never exceeds remaining-1 — the window position after the last
            # accepted draft must still be emittable.
            k = self.spec_tokens
            drafts: Dict[int, List[int]] = {}
            if k > 0:
                for idx in decoding:
                    req = sched.slots[idx].request
                    want = min(k, req.remaining - 1)
                    if want <= 0:
                        continue
                    d = self._drafter.propose(req.to_feed, want)
                    if d:
                        drafts[idx] = [int(t) for t in d[:want]]
            window = self.programs.window
            # Grow oldest-first so older requests steal blocks from younger ones
            # (matching the LIFO victim policy), then re-collect the survivors.
            for idx in decoding:
                if idx in sched.slots and sched.slots[idx].request.state == RequestState.DECODING:
                    sched.grow_to(idx, sched.slots[idx].cache_len + window)
            live = [
                idx for idx in decoding
                if idx in sched.slots and sched.slots[idx].request.state == RequestState.DECODING
            ]
            if not live:
                return None
            if self.block_length > 1:
                span.set_metadata(live=len(live), drafted=0)
                return self._block_lanes(live)
            s = self.serving.max_slots
            tokens = np.zeros((s, window), np.int32)
            draft_len = np.zeros((s,), np.int32)
            source = np.zeros((s,), np.int32)
            flight = self._flight  # read here, after the growth: a preemption in it settles
            for idx in live:
                slot = sched.slots[idx]
                if slot.unread:
                    # The lane's last token is a value on the device alone: the tick in flight holds it, in the
                    # chunk's place if the lane's prompt ended there, else in the lane's own.
                    source[idx] = FEED_CHUNK if flight.final and flight.chunk.slot is slot else FEED_LANE
                else:
                    tokens[idx, 0] = slot.request.emitted[-1]
                d = drafts.get(idx)
                if d:
                    tokens[idx, 1 : 1 + len(d)] = d
                    draft_len[idx] = len(d)
            span.set_metadata(live=len(live), drafted=len(drafts))
            return _Lanes(live, tokens, draft_len, source)

    def _block_lanes(self, live: List[int]) -> _Lanes:
        """The decode batch of a block-diffusion family: of every live lane
        its block's state (the host's copy, or ``FEED_LANE`` where a pass of
        this block is in the tick in flight and the newer state is on the
        device alone), how many positions this pass unmasks by the static
        schedule (0: no mask is left, the lane commits), and the request's
        confidence threshold (2.0, which no confidence reaches, without one)."""
        s = self.serving.max_slots
        tokens = np.zeros((s, self.block_length), np.int32)
        count, source, commit = (np.zeros((s,), np.int32) for _ in range(3))
        threshold = np.full((s,), 2.0, np.float32)
        flight = self._flight
        unread = {id(p.block) for p in flight.passes or ()} if flight is not None else ()
        for idx in live:
            slot = self.sched.slots[idx]
            block = slot.block
            if id(block) in unread:
                source[idx] = FEED_LANE
            else:
                tokens[idx] = block.state
            count[idx] = block.next_count()
            commit[idx] = block.masked == 0
            if slot.request.confidence_threshold is not None:
                threshold[idx] = slot.request.confidence_threshold
        return _Lanes(live, tokens, count, source, (commit, threshold))

    def _dispatch_tick(self, chunk: Optional[_Chunk], batch: Optional[_Lanes]) -> None:
        """The tick's ONE dispatch, of whatever the two builds left: the
        decoding lanes with the chunk riding in their forward
        (``decode_chunk``), the lanes alone (``decode``), or a chunk with no
        live decoder (``decode_chunk`` with the lanes idle: a cold start).
        One launch; what the dispatch decides by count alone is booked here
        (a lane's row, a final chunk's turn to decode, the slot whose last
        token this is); then the ONE read-back of the step, of the tick
        dispatched *before* this one, and that tick's emit.  Under a verify
        window this tick is read back too, before the next is built.

        On the profiler's timeline the tables are filled under no span of their
        own (the tick's self time), and the ``wait`` span holds two children,
        ``launch`` (the jitted call alone) and ``read`` (the blocking read of
        the tick before), and the booking between them as its self time."""
        sched, programs, sc = self.sched, self.programs, self.serving
        live = batch.live if batch else []
        # Both groups share one table width, the wider of the two needs: the
        # tables are as wide as the widest lane needs, so gather traffic (and
        # attention width) scale with the blocks requests own.
        owned = [len(sched.slots[idx].blocks) for idx in live]
        width_lanes = programs.table_width(max(owned)) if live else 0
        width = max(width_lanes, programs.table_width(chunk.blocks) if chunk else 0)
        width_window = programs.window_width(width)  # what a window layer gathers: the ring at most, whatever the width
        # Rows of the dispatch that belong to a request: the lanes' window (under a verify window a lane's token and
        # its drafts), and the chunk's real rows; the program computes every lane's window and the whole padded chunk.
        if live and programs.window > 1 and self.block_length == 1:
            rows_live = len(live) + int(batch.draft_len[live].sum())
        else:
            rows_live = len(live) * programs.window
        prev = self._flight
        self._tick.update(
            live=len(live), width=width, width_lanes=width_lanes, width_window=width_window, mixed=bool(chunk and live),
            pipelined=prev is not None,
            rows_live=rows_live + (chunk.n_real if chunk else 0),
            rows_computed=sc.max_slots * programs.window + (sc.prefill_chunk if chunk else 0),
        )
        fresh = self._note_bucket("decode_chunk" if chunk else "decode", width)
        args = self._idle_lanes(width)
        tables, lengths = args[:2]
        for idx in live:
            slot = sched.slots[idx]
            tables[idx, : len(slot.blocks)] = slot.blocks
            lengths[idx] = slot.cache_len
        if batch:
            args[2:4] = [batch.tokens, batch.draft_len]
            args[5] = batch.source
            args[6:] = batch.extra
        if chunk:
            table_row = np.zeros((width,), np.int32)
            table_row[: len(chunk.slot.blocks)] = chunk.slot.blocks
            args += [table_row, np.int32(chunk.start), chunk.tokens, np.int32(chunk.n_real)]
        if programs.stateful:
            args += self._state_args(live, chunk.idx if chunk else None)
            self.state_resets += bool(chunk and chunk.start == 0)
        if width_window:
            wtables = np.zeros((sc.max_slots, width_window), np.int32)
            for idx in live:
                held = sched.slots[idx].window_blocks
                wtables[idx, : len(held)] = held
            args.append(wtables)
            if chunk:
                wtable_row = np.zeros((width_window,), np.int32)
                wtable_row[: len(chunk.slot.window_blocks)] = chunk.slot.window_blocks
                args.append(wtable_row)
        if self._poison_ordinal is not None:
            # Armed: the program was traced with the poison lane.  NaN rides
            # into exactly one slot's logits on that request's first decode
            # dispatch; every other lane multiplies by 1.0 (lanes are
            # independent, so their tokens are bit-identical to unarmed).
            poison = np.ones((sc.max_slots,), np.float32)
            for idx in live:
                req = sched.slots[idx].request
                if getattr(req, "_poison_pending", False):
                    poison[idx] = np.nan
                    req._poison_pending = False  # fires once
            args.append(poison)
        # the readers' names: a dispatch with decoding lanes waits under
        # decode.wait, a chunk dispatched alone under prefill.wait.  The span
        # opens at the launch and no earlier: the benchmark's idle metrics
        # intersect device idle with it, so what it covers is their yardstick
        # (the tables above are filled under no span of their own)
        with _TickPhase(self, "decode.wait" if live else "prefill.wait", live=len(live), width=width):
            with _TickPhase(self, "launch", program="decode_chunk" if chunk else "decode", fresh=int(fresh)):
                t0 = time.monotonic()
                program = programs.decode_chunk if chunk else programs.decode
                packed, self._feed, self.cache.pool = program(self.params, self.cache.pool, *args)
                packed.copy_to_host_async()  # the read-back comes one dispatch later: the copy starts when the program ends
            lanes = [sched.slots[idx] for idx in live]
            final = False
            tel = get_telemetry()
            # a mixed dispatch is a prefill dispatch and a decode dispatch too: what reads either says what it said
            # (the telemetry names stand here as literals: tests/test_metric_names.py reads the emit sites)
            if chunk:
                self.prefill_dispatches += 1
                req = chunk.slot.request
                req.prefill_dispatches += 1  # per-request: the zero-re-prefill oracle
                chunk.slot.cache_len = chunk.start + chunk.n_real
                # Final chunk: its last real logits row IS the next token (a prefilling request has no token
                # unread, so its feed is whole here).  The slot decodes from the next tick on, its first input the
                # chunk's entry of this dispatch's feed.
                final = chunk.slot.cache_len == self._prefill_rows(req.to_feed)
                if final and self.block_length > 1:
                    self._open_blocks(chunk.slot)  # the chunk's head yields no token: the first block opens on masks
                elif final:
                    req.state = RequestState.DECODING
                    self._sent(chunk.slot)
                if tel.enabled:
                    tel.registry.counter("serving.prefill_dispatches").inc()
            passes = None
            if self.block_length > 1:
                passes = [self._book_pass(slot) for slot in lanes]
                self._tick.update(
                    denoising=sum(not p.commit for p in passes), committing=sum(p.commit for p in passes))
            else:
                for slot in lanes:
                    slot.cache_len += 1  # a verify window's accepted drafts are added when they are read
                    self._sent(slot)
            if live:
                gather_bytes = programs.gathered_blocks(owned) * self._block_bytes
                if width_window:
                    gather_bytes += programs.gathered_blocks([len(slot.window_blocks) for slot in lanes]) * self._window_block_bytes
                self.decode_dispatches += 1
                self.decode_gather_bytes += gather_bytes
                self._decode_widths.add(width)
                if tel.enabled:
                    tel.registry.counter("serving.decode_dispatches").inc()
                    tel.registry.counter("serving.decode_gather_bytes").inc(gather_bytes)
                    tel.registry.gauge("serving.decode_bucket_width").set(width)
            draft_len = batch.draft_len if batch else None
            self._flight = _Flight(packed, chunk, final, lanes, draft_len, width, fresh, t0, self.ticks, passes)
            out = self._read(prev) if prev is not None else None  # host sync point: the tick BEFORE this one is done here
        if prev is not None:
            self._apply(prev, out)
        if passes is not None:
            if not all(p.by_count for p in passes):
                # Under a confidence threshold what a pass unmasks depends on values: whether a lane's block is
                # done, and with it the next tick's shape, is known when the pass is read.  Observed, not configured.
                self._settle("blocks")
        elif programs.window > 1:
            # The accepted count decides cache_len and the drafter reads the tokens: a verify-window engine is the
            # synchronous one, every tick read back before the next is built.
            self._settle("spec")

    def _sent(self, slot) -> None:
        """One more token of the slot's request is dispatched.  A request
        finishes by count: when this is its last, the lane retires (it rides
        no later tick and its index is free for admission) and the request
        completes when the token is read."""
        slot.unread += 1
        if slot.request.remaining == slot.unread:
            self.sched.retire(slot.idx)

    def _book_pass(self, slot) -> _Pass:
        """One lane of a block-diffusion family is dispatched: a commit of its
        finished block (``cache_len`` advances by the block, the next block
        opens on masks) or denoising pass ``t``, whose count the static
        schedule gives: everything the next tick's build needs is booked
        here.  Under a confidence threshold the count is a value: booked at the
        read-back (:meth:`_emit_blocks`), before which nothing else is built."""
        block = slot.block
        t, by_count = block.t, slot.request.confidence_threshold is None
        if block.masked == 0:
            slot.cache_len += self.block_length
            slot.block = _Block(self.block_length, [], block.schedule)
            return _Pass(block, True, t, None, by_count)
        emit = self._advance_block(slot, block, block.next_count()) if by_count else None
        return _Pass(block, False, t, emit, by_count)

    def _advance_block(self, slot, block: _Block, unmasked: int) -> Optional[int]:
        """A denoising pass unmasked ``unmasked`` positions of the slot's block.
        None while masks are left; else the block is done and the number of
        tokens it emits: its new positions, cut at the request's last.  They
        are booked as sent, and the lane retires if they end the request (a
        request's last block needs no commit)."""
        block.masked -= unmasked
        block.t += 1
        if block.masked:
            return None
        count = min(block.new, slot.request.remaining - slot.unread)
        slot.unread += count
        if slot.request.remaining == slot.unread:
            self.sched.retire(slot.idx)
        return count

    def _read(self, flight: _Flight, settle: Optional[str] = None) -> dict:
        """The host's view of what ``flight``'s program returned: the sync
        point of that tick alone, whatever is queued behind it.  All of the
        host's blocked time lies in the ``read`` span; it says which tick it
        read, and why if a settle asked."""
        reason = {"settle": settle} if settle else {}
        with _TickPhase(self, "read", of=flight.tick, **reason):
            out = self.programs.unpack(flight.packed, with_chunk=flight.chunk is not None)
        counts = {name: int(value) for name, value in zip(DISPATCH_COUNTERS, out["counters"])}
        for name, value in counts.items():
            kind = next(c for c in (self.moe_counters, self.window_counters, self.attn_counters, self.sparse_counters)
                        if name in c)
            kind[name] += value
        if counts and get_telemetry().enabled:
            registry = get_telemetry().registry
            if counts["moe_row_tiles"]:
                registry.counter("serving.moe_row_tiles").inc(counts["moe_row_tiles"])
            if counts["attn_rows_read"]:
                registry.counter("serving.attn_rows_read").inc(counts["attn_rows_read"])
            if self._index_topk:
                registry.counter("serving.index_rows_scored").inc(counts["index_rows_scored"])
                registry.counter("serving.sparse_rows_read").inc(counts["sparse_rows_read"])
                registry.counter("serving.context_rows").inc(counts["context_rows"])
        return out

    def _apply(self, flight: _Flight, out: dict) -> None:
        """A tick's read-back applied: the chunk's bookkeeping, then the
        lanes' tokens as values, each with the moment it was read."""
        quarantined = self.quarantined_count
        dispatch_ms = (time.monotonic() - flight.t0) * 1e3
        if flight.chunk:
            self._emit_chunk(flight, int(out["chunk_token"][0]), bool(out["chunk_ok"][0]))
        if flight.lanes and flight.passes is not None:
            self._emit_blocks(flight, out, dispatch_ms)
        elif flight.lanes:
            self._emit_decode(flight, out, dispatch_ms)
        if self.quarantined_count > quarantined:
            # The tick in flight computed a row more for the poisoned slot: read it now, so that the dropped row
            # is off the books before anything else is built.
            self._settle("quarantine")

    def _settle(self, reason: str) -> bool:
        """Read the tick in flight back and apply it, so that every token
        dispatched is a value on the host and every count exact.  Whatever
        needs a token's value, or re-queues or releases a request with a token
        unread, calls this first; the steady state never does.  True when a
        tick was in flight."""
        flight = self._flight
        if flight is None:
            return False
        self._flight = None
        self.settles[reason] = self.settles.get(reason, 0) + 1
        if self._phase_t0 is not None:  # in the record of the tick that paid for it; between two ticks no record is open
            self._tick["settle"] = reason
            self._tick["settles"] += 1
        tel = get_telemetry()
        if tel.enabled:
            tel.registry.counter("serving.settles").inc()
        with _TickPhase(self, "decode.wait" if flight.lanes else "prefill.wait", settle=reason):
            out = self._read(flight, settle=reason)
        self._apply(flight, out)
        return True

    def _unread_requests(self) -> set:
        """The requests with a token (or a chunk) in the tick in flight."""
        flight = self._flight
        if flight is None:
            return set()
        slots = flight.lanes + ([flight.chunk.slot] if flight.chunk else [])
        return {slot.request.id for slot in slots}

    def _emit_chunk(self, flight: _Flight, token: int, ok: bool) -> None:
        chunk = flight.chunk
        slot = chunk.slot
        req = slot.request
        if req.state == RequestState.DONE:
            return  # quarantined by an earlier chunk's flag, read after this one was dispatched: dropped
        with _TickPhase(self, "prefill.emit", request=req.id) as span:
            if self.tracer is not None:
                self.tracer.on_prefill(
                    req, slot.idx, time.monotonic(),
                    padded_rows=self.serving.prefill_chunk - chunk.n_real, width=flight.width, fresh=flight.fresh,
                )
            if not ok:
                self._quarantine(slot, time.monotonic())
                return
            self._register_prefix_blocks(slot, chunk.start + chunk.n_real)
            yields = flight.final and self.block_length == 1  # a block-diffusion family's chunk yields no token
            firsts = [(req, req.prefill_dispatches)] if yields and not req.emitted else []
            if yields:
                # The first generated token of a fresh request (TTFT lands
                # here) or the resume token of a re-prefilled one.
                self._emit(slot, [token], time.monotonic())
            span.set_metadata(first_token=len(firsts), **self._first_tokens(flight, firsts))

    def _emit_decode(self, flight: _Flight, out: dict, dispatch_ms: float) -> None:
        window, draft_len = self.programs.window, flight.draft_len
        tokens, accepts, oks = out["tokens"], out["accepts"], out["ok"]
        # a lane quarantined at the read-back before this one computed a row too many here: dropped
        lanes = [slot for slot in flight.lanes if slot.request.state != RequestState.DONE]
        with _TickPhase(self, "decode.emit") as span:
            emit_t = time.monotonic()
            if self.tracer is not None:
                # emit_t is PAST the read-back's sync point, so the interval
                # covers the real device work despite async dispatch.
                self.tracer.on_decode(
                    [(slot.request, slot.idx) for slot in lanes],
                    emit_t, co_batch=len(flight.lanes), width=flight.width, fresh=flight.fresh,
                    dispatch_ms=dispatch_ms,
                    phase="verify" if window > 1 else "decode",
                )
            # rounds counts verify DISPATCHES (with >= 1 healthy lane);
            # proposed/accepted are per-slot sums over the healthy lanes.
            spec_rounds = spec_proposed = spec_accepted = emitted = 0
            for slot in lanes:
                idx, req = slot.idx, slot.request
                # Accept bookkeeping: the emitted chunk is t[:count] where
                # count = accepted drafts + the correction/bonus row, capped
                # at remaining (count == remaining finishes the request on
                # its exact last token).  cache_len advances by count — the
                # rewind; rows past it are stale and re-written before read.
                # Without speculation accepts are 0 and count is 1, the row
                # the dispatch booked.
                count = min(int(accepts[idx]) + 1, req.remaining)
                slot.cache_len += count - 1
                if not bool(oks[idx]):
                    # Quarantine instead of emitting the garbage argmax; the
                    # other slots' emissions proceed untouched.
                    self._quarantine(slot, emit_t)
                    continue
                if window > 1:
                    spec_rounds = 1
                    spec_proposed += int(draft_len[idx])
                    spec_accepted += int(accepts[idx])
                self.decode_emitted_tokens += count
                self.decode_slot_ticks += 1
                emitted += count
                self._emit(slot, [int(t) for t in tokens[idx, :count]], emit_t)
            if spec_rounds:
                self.spec_rounds += spec_rounds
                self.spec_proposed += spec_proposed
                self.spec_accepted += spec_accepted
                tel = get_telemetry()
                if tel.enabled:
                    tel.registry.counter("serving.spec.rounds").inc(spec_rounds)
                    if spec_proposed:
                        tel.registry.counter("serving.spec.proposed").inc(spec_proposed)
                    if spec_accepted:
                        tel.registry.counter("serving.spec.accepted").inc(spec_accepted)
            span.set_metadata(tokens=emitted)

    def _emit_blocks(self, flight: _Flight, out: dict, dispatch_ms: float) -> None:
        """The read-back of a block-diffusion family's lanes: every lane's new
        block state.  A denoising pass's newly unmasked positions get its
        number; a pass that left no mask emits the block's new tokens together,
        those past the request's last dropped."""
        states, oks = out["tokens"], out["ok"]
        # a lane quarantined at the read-back before this one computed a pass too many here: dropped
        lanes = [(slot, p) for slot, p in zip(flight.lanes, flight.passes) if slot.request.state != RequestState.DONE]
        denoised = committed = emitted = 0
        firsts = []  # the requests whose first block this read yields, each with the dispatches that carried its rows
        with _TickPhase(self, "decode.emit") as span:
            emit_t = time.monotonic()
            if self.tracer is not None:
                self.tracer.on_decode(
                    [(slot.request, slot.idx) for slot, _ in lanes],
                    emit_t, co_batch=len(flight.lanes), width=flight.width, fresh=flight.fresh, dispatch_ms=dispatch_ms,
                    denoising=sum(not p.commit for _, p in lanes), committing=sum(p.commit for _, p in lanes),
                )
            for slot, p in lanes:
                if not bool(oks[slot.idx]):
                    self._quarantine(slot, emit_t)
                    continue
                committed += p.commit
                denoised += not p.commit
                if p.commit:
                    continue
                block, state = p.block, states[slot.idx].tolist()
                fresh = [i for i, (old, new) in enumerate(zip(block.state, state)) if old == MASKED and new != MASKED]
                for i in fresh:
                    block.passes[i] = p.t
                block.state = state
                count = p.emit if p.by_count else self._advance_block(slot, block, len(fresh))
                if count is not None:
                    first = self.block_length - block.new
                    emitted += count
                    if count and not slot.request.emitted:
                        firsts.append((slot.request, slot.request.prefill_dispatches + p.t + 1))
                    self._emit(slot, state[first : first + count], emit_t, sent=count, passes=block.passes[first : first + count])
            span.set_metadata(tokens=emitted, **self._first_tokens(flight, firsts))
        self.decode_slot_ticks += denoised + committed
        self.denoise_slot_ticks += denoised
        self.commit_slot_ticks += committed
        self.blocks_committed += committed
        self.decode_emitted_tokens += emitted
        self.block_tokens_emitted += emitted
        tel = get_telemetry()
        if tel.enabled:
            tel.registry.counter("serving.denoise_slot_ticks").inc(denoised)
            tel.registry.counter("serving.commit_slot_ticks").inc(committed)
            tel.registry.counter("serving.blocks_committed").inc(committed)
            tel.registry.counter("serving.block_tokens_emitted").inc(emitted)

    # -- completion / metrics ------------------------------------------------

    @staticmethod
    def _first_tokens(flight: _Flight, firsts: list) -> dict:
        """The first token's account, for the emit span that yields it: sums
        over ``firsts``, the requests whose first token ``flight``'s read gave,
        each with the dispatches that carried its own rows up to it (its
        chunks; a block family's passes of the first block).  ``held_ticks``:
        the ticks from its admission to the one read, both counted;
        ``own_ticks`` of them were its own, the rest it waited for its turn.
        Nothing without a first token."""
        if not firsts:
            return {}
        return {
            "first_tokens": len(firsts),
            "held_ticks": sum(flight.tick - req.admit_tick + 1 for req, _ in firsts),
            "own_ticks": sum(own for _, own in firsts),
        }

    def _emit(self, slot, tokens: List[int], now: float, sent: int = 1, passes: Optional[List[int]] = None) -> None:
        """The values of the tokens one dispatch produced for ``slot``, read at
        ``now`` (``sent`` of them were booked as dispatched: one, or a block's).
        The request completes when its last token is a value."""
        req = slot.request
        slot.unread -= sent
        if passes is not None:
            req.token_passes.extend(passes)
        tel = get_telemetry()
        for token in tokens:
            req.emitted.append(token)
            req.note_token(now)
            if tel.enabled:
                tel.registry.counter("serving.tokens").inc()
                if len(req.emitted) == 1 and req.arrival_t is not None:
                    tel.registry.histogram("serving.ttft_ms").observe(
                        (now - req.arrival_t) * 1e3
                    )
                elif req.inter_token_ms:
                    tel.registry.histogram("serving.inter_token_ms").observe(
                        req.inter_token_ms[-1]
                    )
        if req.remaining == 0:
            self.sched.release(slot, now)  # live, or retiring since its last token was dispatched
            self._complete(req)

    def _complete(self, req: Request, status: str = "ok") -> None:
        rec = self._record(req, status)
        self._finished.append(rec)
        if self.journal is not None:
            self.journal.record_done(req.id, status)
        tel = get_telemetry()
        if tel.enabled:
            reg = tel.registry
            reg.counter("serving.completed").inc()
            reg.histogram("serving.queue_wait_ms").observe(rec.queue_wait_ms)
            if rec.tokens_per_s is not None:
                reg.histogram("serving.tokens_per_s").observe(rec.tokens_per_s)
            tel.event(
                "serving.request_complete",
                request=req.id,
                tag=req.tag,
                status=status,
                prompt_len=len(req.prompt),
                new_tokens=len(req.emitted),
                ttft_ms=round(rec.ttft_ms, 3) if rec.ttft_ms is not None else None,
                queue_wait_ms=round(rec.queue_wait_ms, 3),
                preemptions=req.preemptions,
            )
        if self.tracer is not None:
            self.tracer.on_terminal(req, status)

    def unfinished(self) -> List[CompletedRequest]:
        """A record of every submitted request that has not completed, as it
        stands (``status`` ``"in_flight"``): the tokens read so far and their
        moments.  Nothing changes, nothing is read back: a token still on the
        device is not in it, and the requests go on.  For a caller that stops
        ticking before every reply is in, as a benchmark does at the close of
        its window."""
        live = [slot.request for slot in sorted([*self.sched.slots.values(), *self.sched.retiring], key=lambda s: s.idx)]
        ids = {req.id for req in live}
        return [self._record(req, "in_flight") for req in [*live, *(r for r in self.sched.queue if r.id not in ids)]]

    def _record(self, req: Request, status: str) -> CompletedRequest:
        ttft_ms = None
        if req.first_token_t is not None and req.arrival_t is not None:
            ttft_ms = (req.first_token_t - req.arrival_t) * 1e3
        queue_wait_ms = (
            (req.admit_t - req.arrival_t) * 1e3
            if req.admit_t is not None and req.arrival_t is not None
            else 0.0
        )
        mean_itl = (
            sum(req.inter_token_ms) / len(req.inter_token_ms)
            if req.inter_token_ms
            else None
        )
        tps = None
        if (
            req.finish_t is not None
            and req.first_token_t is not None
            and req.finish_t > req.first_token_t
            and len(req.emitted) > 1
        ):
            tps = (len(req.emitted) - 1) / (req.finish_t - req.first_token_t)
        return CompletedRequest(
            id=req.id,
            tokens=req.output,
            prompt_len=len(req.prompt),
            new_tokens=len(req.emitted),
            queue_wait_ms=queue_wait_ms,
            ttft_ms=ttft_ms,
            mean_inter_token_ms=mean_itl,
            tokens_per_s=tps,
            preemptions=req.preemptions,
            inter_token_ms=list(req.inter_token_ms),
            status=status,
            tag=req.tag,
            migrations=req.migrations,
            fallback_reprefills=req.fallback_reprefills,
            prefill_dispatches=req.prefill_dispatches,
            token_passes=list(req.token_passes),
        )

    def _publish_gauges(self) -> None:
        tel = get_telemetry()
        if not tel.enabled:
            return
        reg = tel.registry
        alloc = self.cache.allocator
        reg.gauge("serving.active_slots").set(self.sched.active)
        reg.gauge("serving.queue_depth").set(self.sched.pending)
        reg.gauge("serving.blocks_used").set(alloc.used_blocks)
        reg.gauge("serving.block_occupancy").set(round(alloc.occupancy, 4))
        reg.gauge("serving.prefix_cache_blocks").set(
            len(self._prefix) if self._prefix is not None else 0
        )
        reg.gauge("serving.spec.acceptance_rate").set(
            round(self.spec_accepted / max(self.spec_proposed, 1), 4)
        )
        # Per slot-lane, not per fused dispatch: continuous batching already
        # lands co_batch tokens per dispatch; this gauge isolates the
        # SPECULATIVE gain (1.0 == plain greedy, >1 == accepted drafts).
        reg.gauge("serving.tokens_per_dispatch").set(
            round(self.decode_emitted_tokens / max(self.decode_slot_ticks, 1), 4)
        )
        # HBM ledger + headroom: refresh the prefix-cache resident bytes
        # (a subset of the pool reservation) and publish the serving
        # headroom — free pool bytes, further clamped by measured free HBM
        # when the backend reports stats (absent on CPU builds, where the
        # pool bound is the whole truth).
        from ..telemetry.memledger import get_memory_ledger

        ledger = get_memory_ledger()
        prefix_blocks = len(self._prefix) if self._prefix is not None else 0
        ledger.update_bytes(
            "serving.prefix_cache",
            prefix_blocks * self._block_bytes,
            token=self._memledger_tokens[1],
        )
        headroom = alloc.free_blocks * self._block_bytes
        hbm_free = ledger.min_device_headroom()
        if hbm_free is not None:
            headroom = min(headroom, hbm_free)
        reg.gauge("serving.headroom_bytes").set(headroom)
        # Low-headroom watermark (the tiering control signal): one event per
        # pressure EPISODE, with hysteresis — the event re-arms only after
        # free capacity recovers above the re-arm line (1.5x the watermark,
        # capped at 1.0), so a pool oscillating right at the watermark
        # cannot spam the ring, while each genuine dip-recover-dip cycle
        # under tiering emits its own event instead of being silently
        # swallowed after the first.
        free_frac = alloc.free_blocks / max(alloc.capacity, 1)
        if free_frac < self._headroom_watermark_frac:
            if not self._low_headroom:
                self._low_headroom = True
                tel.event(
                    "memory.low_headroom",
                    source="serving",
                    headroom_bytes=headroom,
                    free_blocks=alloc.free_blocks,
                    capacity=alloc.capacity,
                    watermark_frac=self._headroom_watermark_frac,
                )
        elif self._low_headroom and free_frac >= self._headroom_rearm_frac:
            self._low_headroom = False
        # KV host tier: occupancy gauges plus the prefix cache's own
        # demote/promote churn (which happens inside allocator eviction,
        # out of counter reach) folded into the tier counters as deltas.
        host = self.cache.host
        if host is not None:
            reg.gauge("serving.tier.host_bytes").set(host.used_bytes())
            reg.gauge("serving.tier.host_occupancy").set(round(host.occupancy, 4))
            if self._prefix is not None:
                d = self._prefix.host_demotions - self._prefix_demotions_published
                if d > 0:
                    reg.counter("serving.tier.demotions").inc(d)
                    reg.counter("serving.tier.demoted_blocks").inc(d)
                self._prefix_demotions_published = self._prefix.host_demotions
                p = self._prefix.host_promotions - self._prefix_promotions_published
                if p > 0:
                    reg.counter("serving.tier.promotions").inc(p)
                self._prefix_promotions_published = self._prefix.host_promotions
        # Publish only preemptions since the last publish: a registry.reset()
        # (e.g. scoping a measurement window) must not be re-inflated with
        # engine-lifetime history.
        new_preempted = self.sched.preempted_count - self._preempted_published
        if new_preempted > 0:
            reg.counter("serving.preempted").inc(new_preempted)
        self._preempted_published = self.sched.preempted_count

    # -- introspection -------------------------------------------------------

    def debug_requests(self) -> List[dict]:
        """Live request snapshot for the ``/debug/requests`` endpoint: every
        queued and slotted request with its state, age, and (when tracing is
        on) its phase-so-far decomposition.  Host-side reads only — safe to
        call from the metrics server thread between ticks, which is why it
        does not settle: ``emitted`` counts the tokens read, ``unread`` those
        dispatched and still on the device (at most one, the tick in flight)."""
        now = time.monotonic()
        out = []
        seen = set()
        for slot in sorted([*self.sched.slots.values(), *self.sched.retiring], key=lambda slot: slot.idx):
            req = slot.request
            seen.add(req.id)
            out.append(dict(self._debug_request(req, now, slot=slot.idx), unread=slot.unread))
        for req in self.sched.queue:
            if req.id not in seen:
                out.append(self._debug_request(req, now, slot=None))
        return out

    def _debug_request(self, req: Request, now: float, slot: Optional[int]) -> dict:
        rec = {
            "id": req.id,
            "tag": req.tag,
            "state": req.state.name,
            "slot": slot,
            "age_ms": round((now - req.arrival_t) * 1e3, 3),
            "prompt_len": len(req.prompt),
            "emitted": len(req.emitted),
            "max_new": req.max_new_tokens,
            "preemptions": req.preemptions,
        }
        if self.tracer is not None:
            rec["trace"] = self.tracer.snapshot_request(req.id, now)
        return rec

    def debug_blocks(self) -> dict:
        """Pool snapshot for ``/debug/blocks``: occupancy, per-block
        refcounts (shared prefix blocks show >1), and the prefix-cache
        chains with their reclaimability."""
        alloc = self.cache.allocator
        refcounts = {
            str(b): n for b, n in sorted(alloc._ref.items()) if n > 0
        }
        out = {
            "capacity": alloc.capacity,
            "free": alloc.free_blocks,
            "used": alloc.used_blocks,
            "occupancy": round(alloc.occupancy, 4),
            "pending_scrub": sorted(alloc._pending_scrub),
            "refcounts": refcounts,
            "slots": {
                str(idx): {
                    "request": slot.request.id,
                    "blocks": list(slot.blocks),
                    **({"window_blocks": list(slot.window_blocks)} if self._ring_blocks else {}),
                    "cache_len": slot.cache_len,
                }
                for idx, slot in sorted(self.sched.slots.items())
            },
        }
        if self._prefix is not None:
            out["prefix_cache"] = {
                "blocks": len(self._prefix),
                "reclaimable": self._prefix.reclaimable_count,
                # LRU order, oldest first: block plus its live refcount so a
                # stuck chain (refcount pinned > 1) is visible at a glance.
                "chain": [
                    {"block": b, "refcount": alloc.refcount(b)}
                    for b in self._prefix._entries.values()
                ],
            }
            if self.cache.host is not None:
                out["prefix_cache"]["host_entries"] = self._prefix.host_count
        if self.cache.host is not None:
            host = self.cache.host
            out["host_tier"] = {
                "capacity": host.capacity,
                "free": host.free_blocks,
                "used": host.used_blocks,
                "occupancy": round(host.occupancy, 4),
                # Which live requests currently own host-resident blocks
                # (demoted mid-flight, awaiting re-admission).
                "demoted_requests": {
                    str(req.id): len(req.demoted_blocks or ())
                    for req in self.sched.queue
                    if req.demoted_blocks
                },
            }
        return out

    def export_chrome_trace(self, path: str) -> str:
        """Dump every traced request (completed ring + live) as a
        Chrome/Perfetto trace; see ``serving/tracing.py``."""
        from .tracing import export_chrome_trace

        if self.tracer is None:
            raise RuntimeError("tracing is disabled on this engine")
        return export_chrome_trace(path, self.tracer.traces())

    def _state_stats(self) -> dict:
        """What ``stats()`` says of a family whose cache holds a state a
        sequence; a family without one carries none of these keys."""
        if not self._state_names:
            return {}
        out = {"state_bytes": self.cache.state_bytes(), "state_resets": self.state_resets}
        if self.serving.prefix_cache:
            out["prefix_cache_off"] = (
                f"the cache holds a state a sequence ({', '.join(self._state_names)}): a prefix hit would resume at a "
                f"block boundary without the state of that boundary, so no prefix cache is built"
            )
        return out

    def _window_stats(self) -> dict:
        """What ``stats()`` says of a family whose cache holds window leaves
        beside its full token rows: bytes and blocks in use by kind, what the
        window layers read, and why no prefix cache was built.  A family
        without window leaves carries none of these keys."""
        if not self._ring_blocks:
            return {}
        full, window = self.cache.allocator, self.cache.window_allocator
        out = {
            "pool_bytes_by_kind": {"full": self.cache.pool_bytes(), "window": self.cache.window_pool_bytes()},
            "free_pool_bytes_by_kind": {
                "full": full.free_blocks * self._block_bytes, "window": window.free_blocks * self._window_block_bytes},
            "full_blocks_in_use": full.used_blocks,
            "window_blocks_in_use": window.used_blocks,
            "window_ring_blocks": self._ring_blocks,
            **self.window_counters,
        }
        if self.serving.prefix_cache:
            out["prefix_cache_off"] = (
                f"the cache holds window leaves (a ring of {self._ring_blocks} blocks a sequence): a prefix hit would "
                f"resume behind rows the window layers have let go, so no prefix cache is built"
            )
        return out

    def _sparse_stats(self) -> dict:
        """What ``stats()`` says of a family whose attention reads the rows an
        indexer selects: the index keys scored, the rows attended over and the
        rows a full causal mask would have admitted, each summed over the
        layers, the least rows of the lanes' selections, and the chunk's
        queries whose selection cut a key tied at its threshold; any other
        family carries none of these keys."""
        if not self._index_topk:
            return {}
        return {"index_topk": self._index_topk, **self.sparse_counters, "context_rows": self.window_counters["context_rows"]}

    def _block_stats(self) -> dict:
        """What ``stats()`` says of a family generated by diffusion over
        blocks; any other family carries none of these keys."""
        if self.block_length == 1:
            return {}
        return {
            "block_length": self.block_length,
            "denoise_slot_ticks": self.denoise_slot_ticks,
            "commit_slot_ticks": self.commit_slot_ticks,
            "blocks_committed": self.blocks_committed,
            "block_tokens_emitted": self.block_tokens_emitted,
        }

    def stats(self) -> dict:
        """The engine's counters, exact: a tick in flight is read back first
        (``settles["stats"]``), so every token dispatched is counted and every
        reply due is in ``completed``.  Poll it a few times a window, not a tick."""
        self._settle("stats")
        alloc = self.cache.allocator
        return {
            "ticks": self.ticks,
            "decode_dispatches": self.decode_dispatches,
            "prefill_dispatches": self.prefill_dispatches,
            "mixed_dispatches": self.mixed_dispatches,
            # dispatches made with the tick before them still unread (the device had a program queued while the
            # host read, emitted and built), and the read-backs forced early, by what forced them
            "pipelined_ticks": self.pipelined_ticks,
            "settles": dict(self.settles),
            "active_slots": self.sched.active,
            "queue_depth": self.sched.pending,
            "blocks_used": alloc.used_blocks,
            "block_occupancy": round(alloc.occupancy, 4),
            "completed": len(self._finished),
            "preempted": self.sched.preempted_count,
            "shed": self.shed_count,
            "deadline_expired": self.deadline_expired_count,
            "quarantined": self.quarantined_count,
            "pool_bytes": self.cache.pool_bytes(),
            "prefill_chunk": self.serving.prefill_chunk,  # the integer the engine runs: given, or resolved at construction
            **self._state_stats(),
            **self._window_stats(),
            **self._sparse_stats(),
            **self._block_stats(),
            "free_pool_bytes": alloc.free_blocks * self._block_bytes,
            "decode_path": self.decode_path,
            "decode_gather_bytes": self.decode_gather_bytes,
            **self.moe_counters,
            **self.attn_counters,
            "prefix_hits": self.prefix_hits,
            "prefix_blocks_reused": self.prefix_blocks_reused,
            "prefix_cow_copies": self.cow_copies,
            "prefix_cached_blocks": len(self._prefix) if self._prefix else 0,
            "decode_bucket_widths": sorted(self._decode_widths),
            "spec": {
                "window": self.spec_tokens,
                "rounds": self.spec_rounds,
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "acceptance_rate": round(
                    self.spec_accepted / max(self.spec_proposed, 1), 4
                ),
                # Per slot-lane: mean tokens a slot advances per fused decode
                # dispatch it rode (1.0 == plain greedy; the speculative gain
                # net of batch width).
                "tokens_per_dispatch": round(
                    self.decode_emitted_tokens / max(self.decode_slot_ticks, 1), 4
                ),
            },
            "tiering": (
                {
                    "host_blocks": self.cache.host.capacity,
                    "host_used": self.cache.host.used_blocks,
                    "host_free": self.cache.host.free_blocks,
                    "host_occupancy": round(self.cache.host.occupancy, 4),
                    "host_bytes": self.cache.host.used_bytes(),
                    "demotions": self.tier_demotions
                    + (self._prefix.host_demotions if self._prefix else 0),
                    "promotions": self.tier_promotions
                    + (self._prefix.host_promotions if self._prefix else 0),
                    "demoted_blocks": self.tier_demoted_blocks
                    + (self._prefix.host_demotions if self._prefix else 0),
                    "fallback_reprefills": self.tier_fallback_reprefills,
                    "prefix_host_entries": (
                        self._prefix.host_count if self._prefix else 0
                    ),
                    "prefix_host_drops": (
                        self._prefix.host_drops if self._prefix else 0
                    ),
                }
                if self.cache.host is not None
                else None
            ),
            "trace_blame": (
                dict(self.tracer.blame_counts) if self.tracer is not None else None
            ),
            "slow_ticks": self.tracer.slow_ticks() if self.tracer is not None else None,
        }
