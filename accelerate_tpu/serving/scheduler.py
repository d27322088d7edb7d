"""Continuous-batching request scheduler: admission queue, slot map, preemption.

State machine per request::

    QUEUED --admit--> PREFILLING --last chunk--> DECODING --max_new reached--> DONE
       ^                  |                          |
       +---- preempt -----+------------ preempt ----+

For a family generated by diffusion over blocks a ``DECODING`` lane has a phase
of its own, which the engine keeps on the slot (``_Slot.block``): denoising pass
``t`` of the block it carries, or that block's commit; ``cache_len`` advances by
a whole block at a commit and by nothing otherwise, and a preempted request
re-prefills ``prompt + emitted`` (whole blocks; the block in progress restarts).

A fixed number of **slots** (the fused decode step's static batch axis) holds
the in-flight requests; new requests join as others finish — the decode batch
never drains to refill.  Preemption is the block-pressure valve: when the
allocator runs dry mid-flight, the most recently admitted request is evicted
(LIFO — the oldest request always makes progress, so the policy cannot
livelock), its blocks are freed, and it re-enters the queue FRONT carrying
the tokens it already emitted.  Re-prefilling ``prompt + emitted`` rebuilds a
bit-identical cache (K/V rows depend only on the prefix), so preemption never
changes a request's output — the equivalence oracle in
``tests/test_serving.py`` covers exactly this path.

With the engine's KV host tier enabled, preemption first offers the victim to
the ``on_migrate_out`` hook: the engine demotes the victim's blocks to host
DRAM (stashing the host ids on the request) before the device references are
released, and re-admission promotes them back and resumes decode with zero
re-prefill dispatches.  The free-and-re-prefill path above survives as the
fallback whenever the host tier cannot take the blocks.

The scheduler is pure host-side bookkeeping: admission/preemption decisions
happen between dispatches and the jitted decode step never sees them (slots
simply flip their active mask).

For a family whose cache holds window leaves (``generation.WINDOW``) blocks are
of two kinds, each with its allocator: a slot's ``blocks`` (a block a
``block_size`` rows, for the whole sequence) and its ``window_blocks`` (a ring
of ``ring_blocks`` at most).  Every count of blocks a request needs is a pair
(:meth:`Scheduler.blocks_by_kind`), admission and growth ask both allocators,
either running dry preempts, and release frees both.

The engine reads a tick back one dispatch late (``serving/engine.py``), so a
slot's bookkeeping runs ahead of the tokens the host has read: ``cache_len``
counts the rows *dispatched*, ``unread`` the tokens dispatched and not yet
read.  A request finishes by count, so the slot whose last token is in flight
**retires** when that token is dispatched (:meth:`Scheduler.retire`): its lane
is free for admission at once, its blocks stay its own until the token is read
and :meth:`Scheduler.release` completes it.  Whatever evicts a request needs
its tokens' values: :attr:`Scheduler.settle`, the engine's, reads the tick in
flight back first."""

from __future__ import annotations

import itertools
import time
from collections import deque
from enum import Enum
from typing import Callable, Deque, Dict, List, Optional

from .blocks import BlockAllocator, BlockOutOfMemory, blocks_for_tokens

__all__ = ["Request", "RequestState", "Scheduler"]


class RequestState(Enum):
    QUEUED = "queued"
    PREFILLING = "prefilling"
    DECODING = "decoding"
    DONE = "done"


class Request:
    """One serving request plus its lifecycle bookkeeping.

    ``emitted`` accumulates generated tokens across preemptions; the tokens a
    slot must (re)prefill are always ``prompt + emitted`` — the final chunk's
    logits produce the next emitted token, whether that is the first token of
    a fresh request or the resume point of a preempted one.

    Deadlines are relative to ``arrival_t``: ``ttft_deadline_ms`` bounds the
    wait for the FIRST token, ``deadline_ms`` bounds the whole request.  The
    engine sheds expired queued requests before spending a prefill chunk on
    them and cancels expired in-flight ones (blocks freed) — see
    :meth:`ServingEngine.step`."""

    _ids = itertools.count()

    def __init__(
        self,
        prompt_ids: List[int],
        max_new_tokens: int,
        arrival_t: Optional[float] = None,
        tag: Optional[str] = None,
        ttft_deadline_ms: Optional[float] = None,
        deadline_ms: Optional[float] = None,
        denoise_steps: Optional[int] = None,
        confidence_threshold: Optional[float] = None,
    ):
        if max_new_tokens < 0:
            raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
        if not prompt_ids:
            raise ValueError("empty prompt")
        self.id = next(Request._ids)
        self.prompt = [int(t) for t in prompt_ids]
        self.max_new_tokens = int(max_new_tokens)
        self.arrival_t = time.monotonic() if arrival_t is None else arrival_t
        self.tag = tag
        self.ttft_deadline_ms = ttft_deadline_ms
        self.deadline_ms = deadline_ms
        self.emitted: List[int] = []
        # For a family generated by diffusion over blocks (serving/engine.py): how many denoising passes a block
        # takes (None: one a position), the confidence over which a position is unmasked ahead of the schedule
        # (None: the static schedule alone), and of every emitted token the pass of its block that unmasked it.
        self.denoise_steps = denoise_steps
        self.confidence_threshold = confidence_threshold
        self.token_passes: List[int] = []
        self.state = RequestState.QUEUED
        # SLO timeline (monotonic seconds; None until the event happens).
        self.admit_t: Optional[float] = None  # FIRST admission only
        self.admit_tick: Optional[int] = None  # the engine's tick of that admission (the first token's account)
        self.first_token_t: Optional[float] = None
        self.finish_t: Optional[float] = None
        self.last_token_t: Optional[float] = None
        self.inter_token_ms: List[float] = []
        self.preemptions = 0
        # Re-queue wait accounting: ``admit_t`` records the FIRST admission
        # only, so time spent re-queued after a preemption would otherwise be
        # invisible to the queue-wait metrics.  ``requeued_t`` marks each
        # re-queue; re-admission moves the elapsed wait into
        # ``requeue_waits_ms``, which the engine drains into the
        # ``serving.requeue_wait_ms`` histogram (one sample per re-admission).
        self.requeued_t: Optional[float] = None
        self.requeue_waits_ms: List[float] = []
        # KV host-tier residency (engine/blocks.py tiering): while the
        # request sits re-queued after a preemption-as-migration, its cache
        # lives in host DRAM as ``demoted_blocks`` (host block ids, table
        # order) covering ``demoted_rows`` cache rows with the prefix-cache
        # registration cursor parked at ``demoted_registered``.  Re-admission
        # promotes the blocks back and restores the slot exactly; the fields
        # clear on promotion (or on the host-full re-prefill fallback).
        self.demoted_blocks: Optional[List[int]] = None
        self.demoted_rows = 0
        self.demoted_registered = 0
        # Robustness accounting: prefill dispatches this request consumed
        # (the zero-re-prefill oracle for migrated resumes), migrations it
        # survived, and times the host tier was full so it fell back to a
        # plain re-prefill.
        self.prefill_dispatches = 0
        self.migrations = 0
        self.fallback_reprefills = 0

    def pop_requeue_waits(self) -> List[float]:
        out, self.requeue_waits_ms = self.requeue_waits_ms, []
        return out

    def expired(self, now: float) -> Optional[str]:
        """``"deadline"`` / ``"ttft"`` when the matching deadline has passed
        (total first: a request past its overall budget is expired even if
        its first token already landed), else None."""
        elapsed_ms = (now - self.arrival_t) * 1e3
        if self.deadline_ms is not None and elapsed_ms > self.deadline_ms:
            return "deadline"
        if (
            self.ttft_deadline_ms is not None
            and self.first_token_t is None
            and elapsed_ms > self.ttft_deadline_ms
        ):
            return "ttft"
        return None

    @property
    def to_feed(self) -> List[int]:
        return self.prompt + self.emitted

    @property
    def remaining(self) -> int:
        return self.max_new_tokens - len(self.emitted)

    @property
    def output(self) -> List[int]:
        # Today the served output IS the feed sequence (prompt echoed +
        # everything emitted); keep one definition so they can't diverge.
        return self.to_feed

    def note_token(self, now: float) -> None:
        """Record one emitted token's latency sample (TTFT for the first,
        inter-token for the rest)."""
        if self.first_token_t is None:
            self.first_token_t = now
        elif self.last_token_t is not None:
            self.inter_token_ms.append((now - self.last_token_t) * 1e3)
        self.last_token_t = now


class _Slot:
    """One decode-batch lane: the bound request, its block table, and how many
    cache rows have been written (dispatched: the engine reads a tick back one
    dispatch late).  ``registered_blocks`` is the prefix-cache
    registration cursor — leading full blocks up to it are already published
    (or were attached FROM the cache) and are never re-registered.
    ``unread`` counts the request's tokens that were dispatched and whose
    values the host has not read: ``request.remaining - unread`` is what is
    left to dispatch.  ``block`` is the block a lane of a block-diffusion
    family carries (the engine's; None for every other family): ``cache_len``
    then counts the rows *committed*, whole blocks.  ``window_blocks`` is the
    slot's window table for a family with window leaves (else empty): the
    blocks of the window kind it holds, entry ``j mod ring`` for logical block
    ``j``, never more than the ring's width."""

    __slots__ = ("request", "idx", "blocks", "window_blocks", "cache_len", "admit_seq", "registered_blocks", "unread", "block")

    def __init__(self, request: Request, admit_seq: int, idx: int):
        self.request = request
        self.idx = idx
        self.blocks: List[int] = []
        self.window_blocks: List[int] = []
        self.cache_len = 0
        self.admit_seq = admit_seq
        self.registered_blocks = 0
        self.unread = 0
        self.block = None


class Scheduler:
    """Slot map + admission queue over a shared :class:`BlockAllocator`."""

    def __init__(
        self,
        allocator: BlockAllocator,
        num_slots: int,
        block_size: int,
        max_blocks_per_seq: int,
        prefill_chunk: int,
        spec_overshoot: int = 0,
        window_allocator: Optional[BlockAllocator] = None,
        ring_blocks: int = 0,
    ):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.allocator = allocator
        # Token rows of two kinds (a family with window leaves): the window kind's allocator and the width of a
        # sequence's ring.  Every count of blocks below is a pair (full, window); window is 0 without the kind.
        self.window_allocator = window_allocator
        self.ring_blocks = ring_blocks if window_allocator is not None else 0
        self.num_slots = num_slots
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.prefill_chunk = prefill_chunk
        self.spec_overshoot = max(int(spec_overshoot), 0)
        self.queue: Deque[Request] = deque()
        self.slots: Dict[int, _Slot] = {}  # slot index -> lane
        # Lanes whose last token is dispatched and not yet read: out of ``slots`` (the index is free for admission), blocks held.
        self.retiring: List[_Slot] = []
        self._admit_seq = itertools.count()
        self.preempted_count = 0
        # Observer hook: called with the evicted Request on every preemption
        # (the engine wires its tracer here — one site sees the LIFO victim,
        # the self-preemption, and the drain flavors alike).
        self.on_preempt: Optional[Callable[[Request], None]] = None
        # Migration hook: offered the victim's slot BEFORE its blocks are
        # freed.  Returning True means the hook demoted the KV to the host
        # tier and released the device references itself (the request now
        # carries ``demoted_blocks``); False falls through to the plain
        # free-and-re-prefill preemption.
        self.on_migrate_out: Optional[Callable[[_Slot], bool]] = None
        # The engine's settle (read the tick in flight back and apply it; True when there was one), called with its
        # reason before anything that needs a request's tokens as values or gives its blocks away.
        self.settle: Optional[Callable[[str], bool]] = None

    # -- capacity validation -------------------------------------------------

    def max_rows(self, request: Request) -> int:
        """Worst-case cache rows the request ever needs: the prompt plus every
        generated token except the last (which is emitted but never fed),
        plus the speculative verify window's overshoot (``spec_overshoot`` is
        the engine's draft window ``k`` — a verify dispatch writes ``k+1``
        rows starting at the last fed position, so the final dispatch can
        write ``k`` rows past the plain-greedy extent; for a block-diffusion
        family the block length: the last block is held whole), rounded up to the
        prefill-chunk boundary a re-admission after maximal preemption would
        pad to."""
        rows = (
            len(request.prompt)
            + max(request.max_new_tokens - 1, 0)
            + self.spec_overshoot
        )
        chunks = blocks_for_tokens(rows, self.prefill_chunk)
        return chunks * self.prefill_chunk

    def blocks_by_kind(self, rows: int) -> tuple:
        """Blocks a sequence of ``rows`` cache rows holds, (full, window): a
        block a ``block_size`` rows on the full leaves, and on the window
        leaves the same up to the ring's width, which a sequence never exceeds
        (0 where the family has no window leaves)."""
        full = blocks_for_tokens(rows, self.block_size)
        return full, min(full, self.ring_blocks)

    def max_blocks(self, request: Request) -> tuple:
        """:meth:`max_rows` in blocks of each kind, (full, window)."""
        return self.blocks_by_kind(self.max_rows(request))

    def validate(self, request: Request) -> None:
        """Reject requests the engine geometry can never serve (otherwise a
        sole OOM-ing request would preempt itself forever)."""
        need, need_window = self.max_blocks(request)
        if self.window_allocator is not None and need_window > self.window_allocator.capacity:
            raise ValueError(
                f"request needs {need_window} window blocks > window pool capacity {self.window_allocator.capacity}"
            )
        if need > self.max_blocks_per_seq:
            raise ValueError(
                f"request needs {need} blocks > max_blocks_per_seq "
                f"{self.max_blocks_per_seq} (prompt {len(request.prompt)} + "
                f"max_new {request.max_new_tokens}, block_size {self.block_size})"
            )
        if need > self.allocator.capacity:
            raise ValueError(
                f"request needs {need} blocks > pool capacity "
                f"{self.allocator.capacity}"
            )

    # -- queue / admission ---------------------------------------------------

    def submit(self, request: Request) -> None:
        self.validate(request)
        self.queue.append(request)

    def free_slot_indices(self) -> List[int]:
        return [i for i in range(self.num_slots) if i not in self.slots]

    def admit(self, now: float) -> List[int]:
        """Move queue-head requests into free slots while blocks for their
        first prefill chunk are available.  FIFO order is preserved —
        skipping the head to admit a smaller request behind it would starve
        long prompts."""
        admitted = []
        for idx in self.free_slot_indices():
            if not self.queue:
                break
            head = self.queue[0]
            first_chunk = min(len(head.to_feed), self.prefill_chunk)
            full, window = self.blocks_by_kind(first_chunk)
            if full > self.allocator.free_blocks or (window and window > self.window_allocator.free_blocks):
                break
            self.queue.popleft()
            head.state = RequestState.PREFILLING
            if head.admit_t is None:
                head.admit_t = now
            if head.requeued_t is not None:
                head.requeue_waits_ms.append((now - head.requeued_t) * 1e3)
                head.requeued_t = None
            self.slots[idx] = _Slot(head, next(self._admit_seq), idx)
            admitted.append(idx)
        return admitted

    def cancel_queued(self, request: Request) -> None:
        """Remove a QUEUED request (deadline shed); the caller completes it
        with its error status.  Raises ValueError when it is not queued."""
        self.queue.remove(request)

    # -- preemption ----------------------------------------------------------

    def preempt_one(self) -> Optional[int]:
        """Evict the most recently admitted in-flight request: free its
        blocks, push it back onto the queue FRONT (it keeps priority — it
        already waited), carrying its emitted tokens.  Returns the freed slot
        index, or None when nothing is in flight."""
        if self.settle is not None:
            self.settle("preempt")  # the victim re-prefills prompt + emitted: every token read first
        if not self.slots:
            return None
        return self.preempt_slot(max(self.slots, key=lambda i: self.slots[i].admit_seq))

    def preempt_slot(self, idx: int) -> int:
        """Evict slot ``idx`` specifically (the LIFO victim policy lives in
        :meth:`preempt_one`; the engine's graceful drain evicts EVERY slot):
        demote its blocks to the host tier when the ``on_migrate_out`` hook
        accepts the victim, else free them; either way the request re-enters
        the queue FRONT, emitted tokens carried."""
        if self.settle is not None:
            self.settle("preempt")
        slot = self.slots.pop(idx, None)
        if slot is None:  # the settle completed it
            return idx
        migrated = False
        if slot.blocks and self.on_migrate_out is not None:
            migrated = self.on_migrate_out(slot)
        if slot.blocks and not migrated:
            self.allocator.free(slot.blocks)
        if slot.window_blocks:  # a family with window leaves has no host tier: never migrated
            self.window_allocator.free(slot.window_blocks)
        req = slot.request
        req.state = RequestState.QUEUED
        req.preemptions += 1
        req.requeued_t = time.monotonic()
        self.preempted_count += 1
        self.queue.appendleft(req)
        if self.on_preempt is not None:
            self.on_preempt(req)
        return idx

    def grow_to(self, idx: int, rows: int) -> bool:
        """Ensure slot ``idx``'s block table covers ``rows`` cache rows, and
        its window table the same rows up to the ring's width, allocating (and
        preempting LIFO victims) as needed, whichever kind runs dry.  Returns
        False when the slot itself was preempted to satisfy the growth — the
        caller must drop it from this tick."""
        slot = self.slots.get(idx)
        while slot is not None:
            full, window = self.blocks_by_kind(rows)
            need, need_window = full - len(slot.blocks), window - len(slot.window_blocks)
            if need <= 0 and need_window <= 0:
                return True
            try:
                if need > 0:
                    slot.blocks.extend(self.allocator.alloc(need))
                if need_window > 0:  # a ring entry is allocated at first touch and overwritten in place after that
                    slot.window_blocks.extend(self.window_allocator.alloc(need_window))
                return True
            except BlockOutOfMemory as exc:
                if self.settle is not None and self.settle("preempt"):
                    # The tick read back returned its retiring lanes' blocks: ask again before evicting anyone.
                    slot = self.slots.get(idx)
                    continue
                victim = self.preempt_one()
                if victim is None:
                    # Terminal pool exhaustion (nothing left to evict —
                    # geometry validation failed us): snapshot the ranked
                    # HBM ledger before the engine dies on this raise.
                    from ..telemetry.memledger import get_memory_ledger

                    get_memory_ledger().note_oom(
                        source="serving.admission",
                        error=exc,
                        slot=idx,
                        rows=rows,
                        free_blocks=self.allocator.free_blocks,
                        capacity=self.allocator.capacity,
                    )
                    raise
                slot = self.slots.get(idx)  # self-preemption returns None
        return False

    def finish(self, idx: int, now: float) -> Request:
        """Release slot ``idx``; the request is complete."""
        return self.release(self.slots[idx], now)

    def retire(self, idx: int) -> _Slot:
        """The slot's last token is dispatched: the lane leaves ``slots`` (a
        queued request may take the index in the next tick), the blocks stay
        the slot's until the token is read and :meth:`release` is called."""
        slot = self.slots.pop(idx)
        self.retiring.append(slot)
        return slot

    def release(self, slot: _Slot, now: float) -> Request:
        """The request is complete, live or retiring: free its blocks."""
        if self.slots.get(slot.idx) is slot:
            del self.slots[slot.idx]
        else:
            self.retiring.remove(slot)
        if slot.blocks:
            self.allocator.free(slot.blocks)
        if slot.window_blocks:
            self.window_allocator.free(slot.window_blocks)
        req = slot.request
        req.state = RequestState.DONE
        req.finish_t = now
        return req

    # -- introspection -------------------------------------------------------

    @property
    def active(self) -> int:
        return len(self.slots)

    @property
    def pending(self) -> int:
        return len(self.queue)

    def idle(self) -> bool:
        return not self.slots and not self.queue and not self.retiring
