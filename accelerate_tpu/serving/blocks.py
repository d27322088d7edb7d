"""Paged KV-cache storage: a block pool, a refcounting allocator, and a
content-addressed prefix cache.

The resident KV cache is a pool of ``num_blocks`` fixed-size blocks shared by
every in-flight request (``[L, num_blocks, block_size, ...]`` per leaf — the
int8 codes+scale layout from ``quantize_kv`` pages identically), with a
per-request **block table** mapping logical token positions to physical
blocks.  A request holding ``n`` tokens costs ``ceil(n / block_size)`` blocks
instead of ``max_len`` rows, so a 32-token request and a 2k-token request can
share the pool that a dense cache would tile to 2k each.

Fixed-size blocks mean external fragmentation is structurally zero: any free
block serves any request, and the only waste is the tail of the last block
(< ``block_size`` rows per request).  The allocator is plain host Python —
allocation decisions happen between dispatches, never inside the jitted
decode step.

Blocks are **refcounted** so physical blocks can be shared: a fresh ``alloc``
grants refcount 1, :meth:`BlockAllocator.retain` adds a reader (prefix
sharing), and ``free`` releases one reference — the block returns to the free
list only when the last holder lets go.  Two additional states ride the
refcounts:

- **dirty** (:meth:`mark_dirty`) — the quarantine path poisons a block's
  K/V; a dirty block must be scrubbed to zero before any reuse.  With
  sharing this becomes **scrub-on-last-release**: a dirty block that still
  has live readers keeps serving them (their own finiteness checks guard
  them) and is zeroed only when its refcount hits 0, so a shared block is
  never scrubbed under a live reader.  Such blocks land in a
  ``pending_scrub`` set the engine drains (the scrub is a device write) and
  re-enters the free list via :meth:`finish_scrub`.
- **reclaimable** — blocks whose only reference is the
  :class:`PrefixCache`.  They count as free capacity (``free_blocks``):
  ``alloc`` evicts them LRU-first when the free list runs dry, so caching
  never causes an OOM a cacheless pool would not have had.

Block 0 is reserved as the **null block**: it is never handed out, block
tables are padded with it, and inactive decode slots write their garbage row
into it, so stray gathers/scatters can never touch a live request's KV.

:class:`PrefixCache` shares **full prompt blocks across requests by
content**: block ``i`` of a request's token feed is keyed by a chain hash
``h_i = H(h_{i-1} || tokens[i*bs:(i+1)*bs])`` — K/V rows depend on the whole
prefix, so the chain (not the block's own tokens) is the sound identity.  A
lookup walks the chain until the first miss, retains every matched block for
the new reader, and the engine starts that request's prefill past the shared
prefix (TTFT collapses to the unshared suffix).  The partial tail is handled
with **copy-on-write**: when the cached chain covers more rows than the new
request may reuse wholesale (it must keep >= 1 token to feed), the next
chain block is copied into a private block and writing continues there —
shared blocks are never written after registration (writes always move
forward from ``cache_len``; every shared block ends before it).

**Host tier.**  :class:`PagedKVCache` can carry a second, host-DRAM block
pool (:class:`HostBlockPool`) mirroring the device pool's leaf layout, with
explicit :meth:`PagedKVCache.demote` / :meth:`PagedKVCache.promote` block
migrations (batched device_get / device-scatter per call — never inside the
fused decode dispatch).  The host tier has no refcounts: every host block has
exactly one owner (a preempted request's demoted KV, or a cold prefix-cache
chain entry), and the scrub contract carries over — a host block marked dirty
is zeroed synchronously on free, so quarantined content can never leak into a
later resident.  On real accelerators the host leaves live in pinned host
memory (``memory_kind="pinned_host"``); here they are numpy arrays so the
D2H/H2D copies are real transfers on every backend, including the CPU one
where host *is* the default memory kind and a same-kind ``device_put`` would
silently commit the leaf instead of moving it.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..telemetry import annotate

__all__ = [
    "BlockAllocator",
    "BlockOutOfMemory",
    "HostBlockPool",
    "PagedKVCache",
    "PrefixCache",
    "blocks_for_tokens",
]

NULL_BLOCK = 0


class BlockOutOfMemory(RuntimeError):
    """No free block available; the caller decides (preempt, queue, reject)."""


def blocks_for_tokens(tokens: int, block_size: int) -> int:
    """ceil(tokens / block_size) — blocks needed to hold ``tokens`` rows."""
    return -(-tokens // block_size)


class BlockAllocator:
    """Refcounting LIFO free-list over block ids ``1..num_blocks-1`` (0 is
    the null block).  LIFO keeps recently-freed (cache-warm) blocks hot, and
    makes alloc/free O(1).  ``free`` releases ONE reference; a block shared
    via :meth:`retain` stays allocated until its last holder frees it."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (one null + one usable), got {num_blocks}"
            )
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}
        self._dirty: set = set()
        self._pending_scrub: List[int] = []
        self._cache: Optional["PrefixCache"] = None

    def attach_cache(self, cache: "PrefixCache") -> None:
        """Wire a :class:`PrefixCache` in: its cache-only blocks count as
        reclaimable free capacity and are evicted LRU-first on pressure."""
        self._cache = cache

    @property
    def capacity(self) -> int:
        """Usable blocks (excludes the null block)."""
        return self.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        """Immediately allocatable blocks: the free list plus cache-only
        (reclaimable) blocks an ``alloc`` would evict on demand."""
        n = len(self._free)
        if self._cache is not None:
            n += self._cache.reclaimable_count
        return n

    @property
    def used_blocks(self) -> int:
        """Blocks held by at least one non-cache reference."""
        n = len(self._ref)
        if self._cache is not None:
            n -= self._cache.reclaimable_count
        return n

    @property
    def occupancy(self) -> float:
        """Fraction of usable blocks currently allocated (cache-only blocks
        are reclaimable and therefore not counted)."""
        return self.used_blocks / self.capacity

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def alloc(self, n: int = 1) -> List[int]:
        """Pop ``n`` free blocks (each at refcount 1); evicts cache-only
        blocks when the free list alone cannot cover the grant.  Raises
        :class:`BlockOutOfMemory` (allocating NOTHING) when fewer than ``n``
        are reachable — partial grants would leak on the error path."""
        if n < 0:
            raise ValueError(f"alloc count must be >= 0, got {n}")
        if n > len(self._free) and self._cache is not None:
            self._cache.evict(n - len(self._free))
        if n > len(self._free):
            raise BlockOutOfMemory(
                f"need {n} blocks, {self.free_blocks} free of {self.capacity}"
            )
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def retain(self, block: int) -> None:
        """Add one reference to an allocated block (prefix sharing)."""
        if block == NULL_BLOCK:
            raise ValueError("cannot retain the null block")
        if block not in self._ref:
            raise ValueError(f"retain of unallocated block: {block}")
        if self._ref[block] == 1 and self._cache is not None:
            self._cache._note_first_reader(block)
        self._ref[block] += 1

    def free(self, blocks: List[int]) -> None:
        """Release one reference per block; the last release returns the
        block to the free list (or to ``pending_scrub`` when it was marked
        dirty — scrub-on-last-release).  Releasing the null block or a block
        with no references is a hard error (scheduler corruption)."""
        for b in blocks:
            if b == NULL_BLOCK:
                raise ValueError("cannot free the null block")
            if b not in self._ref:
                raise ValueError(f"double free / foreign block: {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                if b in self._dirty:
                    self._pending_scrub.append(b)
                else:
                    self._free.append(b)
            elif self._ref[b] == 1 and self._cache is not None:
                self._cache._note_last_reader_left(b)

    # -- dirty blocks (quarantine scrub-on-last-release) ----------------------

    def mark_dirty(self, blocks: List[int]) -> None:
        """Mark blocks as needing a zero-scrub before reuse.  Blocks still
        referenced keep serving their live readers; they are scrubbed when
        the last reference releases."""
        for b in blocks:
            if b in self._ref:
                self._dirty.add(b)

    def is_dirty(self, block: int) -> bool:
        """Whether a block is quarantine-poisoned (pending its scrub).  The
        tiering paths refuse to demote dirty blocks — copying possibly
        poisoned KV into the host tier would outlive the device scrub."""
        return block in self._dirty

    def pop_pending_scrub(self) -> List[int]:
        """Dirty blocks whose last reference released since the previous
        drain.  The caller (the engine) zeroes them on device and hands them
        back via :meth:`finish_scrub`; until then they are NOT allocatable."""
        out, self._pending_scrub = self._pending_scrub, []
        for b in out:
            self._dirty.discard(b)
        return out

    def finish_scrub(self, blocks: List[int]) -> None:
        """Return scrubbed blocks to the free list."""
        self._free.extend(blocks)


class HostBlockPool:
    """Host-DRAM mirror of the device block pool: one numpy leaf per pool
    leaf with the same ``[L, num_blocks, block_size, *rest]`` layout (fp and
    int8 codes+scale alike), plus a LIFO free-list allocator over ids
    ``0..num_blocks-1`` (no null block — host blocks are never gathered
    through a block table, only copied wholesale).

    There are no refcounts: a host block has exactly one owner at a time —
    either a preempted request's demoted KV or a cold prefix-cache chain
    entry — so ownership transfers are plain id hand-offs.  The scrub
    contract from the device tier carries over in synchronous form: a block
    marked dirty (:meth:`mark_dirty`) is zeroed at :meth:`free` time, before
    it can ever be re-allocated, because host writes are cheap and need no
    deferred drain stage."""

    def __init__(self, pool: dict, num_blocks: int):
        if num_blocks < 1:
            raise ValueError(f"host tier needs >= 1 block, got {num_blocks}")
        self.num_blocks = num_blocks
        self.leaves: Dict[str, np.ndarray] = {
            name: np.zeros(
                (leaf.shape[0], num_blocks) + tuple(leaf.shape[2:]),
                dtype=np.dtype(leaf.dtype),
            )
            for name, leaf in pool.items()
        }
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._used: set = set()
        self._dirty: set = set()

    @property
    def capacity(self) -> int:
        return self.num_blocks

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return len(self._used)

    @property
    def occupancy(self) -> float:
        return len(self._used) / self.num_blocks

    def block_bytes(self) -> int:
        """Bytes behind ONE host block across every leaf and layer (equal to
        the device pool's per-block footprint by construction)."""
        return sum(
            (leaf.size // self.num_blocks) * leaf.dtype.itemsize
            for leaf in self.leaves.values()
        )

    def pool_bytes(self) -> int:
        return sum(leaf.size * leaf.dtype.itemsize for leaf in self.leaves.values())

    def used_bytes(self) -> int:
        return len(self._used) * self.block_bytes()

    def alloc(self, n: int = 1) -> List[int]:
        """Pop ``n`` free host blocks; all-or-nothing like the device
        allocator so a failed demotion never strands a partial grant."""
        if n < 0:
            raise ValueError(f"alloc count must be >= 0, got {n}")
        if n > len(self._free):
            raise BlockOutOfMemory(
                f"host tier needs {n} blocks, {len(self._free)} free of {self.num_blocks}"
            )
        out = [self._free.pop() for _ in range(n)]
        self._used.update(out)
        return out

    def mark_dirty(self, ids: List[int]) -> None:
        """Mark host blocks as quarantine-poisoned: they are zeroed at free
        time, before any reuse (the host half of the two-tier scrub)."""
        for i in ids:
            if i in self._used:
                self._dirty.add(i)

    def free(self, ids: List[int]) -> None:
        """Return host blocks to the free list, zero-scrubbing dirty ones
        synchronously.  Freeing an unallocated id is a hard error (tier
        bookkeeping corruption)."""
        for i in ids:
            if i not in self._used:
                raise ValueError(f"host double free / foreign block: {i}")
            self._used.discard(i)
            if i in self._dirty:
                self._dirty.discard(i)
                for leaf in self.leaves.values():
                    leaf[:, i] = 0
            self._free.append(i)


class PrefixCache:
    """Content-addressed cache of full prompt blocks for cross-request
    sharing (see the module docstring for the chain-hash identity and the
    copy-on-write tail rule).

    The cache holds ONE allocator reference per cached block, so a finished
    request's prefix blocks survive it; :meth:`evict` releases cache-only
    blocks LRU-first when the allocator needs room.  Evicting a middle chain
    block strands the later entries of that chain (a lookup stops at the
    first miss); they age out of the same LRU order.

    With a host tier attached (:meth:`attach_tier`), eviction pressure
    **demotes** cold cache-only chains to host DRAM instead of dropping them
    — the chain key moves to a host-side LRU map, the device block is freed,
    and a later lookup that walks onto the demoted key **promotes** it back
    (one device block allocation + wholesale H2D copy) and keeps sharing.
    The chain-hash identity and the device-side refcounts are untouched; the
    effective prefix cache simply grows past HBM by the host pool's size.
    """

    def __init__(self, allocator: BlockAllocator, block_size: int):
        self.allocator = allocator
        self.block_size = block_size
        self._entries: "OrderedDict[bytes, int]" = OrderedDict()  # LRU: oldest first
        self._by_block: Dict[int, bytes] = {}
        # Cache-only block count, maintained incrementally: the scheduler
        # reads free_blocks (and the gauges occupancy) several times per
        # tick, so an O(cached-blocks) refcount scan here would put an O(N)
        # walk on the per-tick host path the allocator promises is O(1).
        self._reclaimable = 0
        # Host tier: chain key -> host block id, LRU oldest first.  Entries
        # live in exactly one of _entries / _host_entries at a time.
        self._host_entries: "OrderedDict[bytes, int]" = OrderedDict()
        self._kv: Optional["PagedKVCache"] = None
        # Monotonic tiering counters; the engine publishes per-tick deltas.
        self.host_demotions = 0
        self.host_promotions = 0
        self.host_drops = 0  # evictions that fell through to a plain drop
        allocator.attach_cache(self)

    def attach_tier(self, kv: "PagedKVCache") -> None:
        """Enable host-tier spillover through ``kv`` (which must have its
        host tier enabled): eviction demotes instead of dropping, and lookups
        promote demoted chain entries back on a hit."""
        if kv.host is None:
            raise ValueError("attach_tier requires an enabled host tier")
        self._kv = kv

    @staticmethod
    def chain_keys(tokens: List[int], block_size: int, limit: Optional[int] = None) -> List[bytes]:
        """Chain hash per FULL block of ``tokens``: ``h_i`` digests every
        token up to and including block ``i`` — the identity of a block's
        K/V content, which depends on the entire prefix."""
        nb = len(tokens) // block_size
        if limit is not None:
            nb = min(nb, limit)
        h = hashlib.sha256()
        keys = []
        for i in range(nb):
            h.update(np.asarray(
                tokens[i * block_size:(i + 1) * block_size], np.int64
            ).tobytes())
            keys.append(h.digest())
        return keys

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def reclaimable_count(self) -> int:
        """Cached blocks whose ONLY reference is this cache (free capacity
        in waiting).  O(1): tracked on the allocator's 1<->2 refcount
        transitions of cached blocks and this cache's own entry churn."""
        return self._reclaimable

    @property
    def host_count(self) -> int:
        """Chain entries currently demoted to the host tier."""
        return len(self._host_entries)

    def _note_first_reader(self, block: int) -> None:
        """Allocator hook: a block at refcount 1 gained a reader — if that
        lone reference was ours, the block just stopped being reclaimable."""
        if block in self._by_block:
            self._reclaimable -= 1

    def _note_last_reader_left(self, block: int) -> None:
        """Allocator hook: a block dropped back to refcount 1 — if the
        survivor is our reference, the block is reclaimable again."""
        if block in self._by_block:
            self._reclaimable += 1

    def lookup(self, tokens: List[int], max_rows: int) -> Tuple[List[int], int, Optional[int]]:
        """Longest cached chain over the full blocks of ``tokens``, capped at
        ``max_rows`` reusable rows.  Returns ``(blocks, rows, cow_src)``:
        ``blocks`` are the wholesale-shared full blocks (each retained for
        the caller), ``rows = len(blocks) * block_size``, and ``cow_src`` —
        also retained, the caller MUST release it after copying — is the next
        chain block when a partial tail (``max_rows % block_size`` rows of
        it) is still reusable via copy-on-write."""
        bs = self.block_size
        matched: List[Tuple[bytes, int]] = []
        for key in self.chain_keys(tokens, bs, limit=blocks_for_tokens(max_rows, bs)):
            block = self._entries.get(key)
            if block is None:
                block = self._promote_entry(key)
            if block is None:
                break
            # Retain NOW, not in a second pass: promoting the NEXT key
            # allocates a device block, and that allocation may evict
            # cache-only blocks — an unretained earlier match could be freed
            # out from under this walk.
            self.allocator.retain(block)
            self._entries.move_to_end(key)
            matched.append((key, block))
        if not matched:
            return [], 0, None
        full_usable = min(len(matched), max_rows // bs)
        blocks = [block for _, block in matched[:full_usable]]
        extra = matched[full_usable:]
        cow_src = None
        if extra and max_rows % bs:
            cow_src = extra[0][1]
            extra = extra[1:]
        for _, block in extra:  # matched past the reusable window: release
            self.allocator.free([block])
        return blocks, full_usable * bs, cow_src

    def _promote_entry(self, key: bytes) -> Optional[int]:
        """Promote a host-demoted chain entry back to the device tier on a
        lookup hit: allocate one device block (may itself evict LRU cache
        blocks; a device OOM degrades to a miss), copy the host block's rows
        back, and re-enter the device LRU.  Returns the device block, or
        ``None`` when the key is not host-resident or no device block is
        reachable."""
        if self._kv is None:
            return None
        host_id = self._host_entries.get(key)
        if host_id is None:
            return None
        try:
            block = self.allocator.alloc(1)[0]
        except BlockOutOfMemory:
            return None
        self._kv.promote([host_id], [block])
        del self._host_entries[key]
        # Same ordering invariant as register(): the alloc granted refcount
        # 1 and that lone reference is now the cache's, so the block is
        # reclaimable until the caller retains it (the 1->2 hook then
        # decrements — net zero).
        self._entries[key] = block
        self._by_block[block] = key
        self._reclaimable += 1
        self.host_promotions += 1
        return block

    def register(self, chain_key: bytes, block: int) -> bool:
        """Publish a fully-written prompt block under its chain key; returns
        False when the key (a concurrent prefill of the same prefix) or the
        block is already cached.  The block must never be written again —
        the engine registers only blocks entirely below ``cache_len``, and
        writes only move forward from there."""
        if chain_key in self._entries or block in self._by_block:
            return False
        stale = self._host_entries.pop(chain_key, None)
        if stale is not None and self._kv is not None and self._kv.host is not None:
            # The chain was demoted, a lookup could not bring it back (no device block to promote into), and its
            # rows were prefilled anew: the device copy is the entry now, and the host copy's block goes back to the
            # tier (left in the map it was overwritten, and its block lost, at this entry's next demotion).
            self._kv.host.free([stale])
        self.allocator.retain(block)
        self._entries[chain_key] = block
        self._by_block[block] = chain_key
        return True

    def evict(self, n: int) -> int:
        """Release up to ``n`` cache-only blocks, least recently used first;
        returns how many were released.  Blocks with live readers are never
        touched.  With a host tier attached, a clean victim's content is
        demoted to host DRAM first (the chain key moves to the host LRU map)
        so the eviction costs a D2H copy instead of the cached prefix —
        only when the host tier is also full (or the block is quarantine
        dirty) does the entry drop outright."""
        released = 0
        for key in list(self._entries):
            if released >= n:
                break
            block = self._entries[key]
            if self.allocator.refcount(block) != 1:
                continue
            if self._kv is not None:
                host_ids = (
                    self._kv.try_demote([block])
                    if not self.allocator.is_dirty(block)
                    else None  # never spill quarantine-dirty rows to host
                )
                if host_ids is not None:
                    self._host_entries[key] = host_ids[0]
                    self._host_entries.move_to_end(key)
                    self.host_demotions += 1
                else:
                    self.host_drops += 1
            del self._entries[key]
            del self._by_block[block]
            self._reclaimable -= 1
            self.allocator.free([block])
            released += 1
        return released

    def drop_host_entries(self, n: Optional[int] = None) -> int:
        """Free up to ``n`` host-demoted chain entries (all of them when
        ``n`` is None), least recently used first; returns how many were
        dropped.  The engine uses this to reclaim host room for request
        migrations (a live request outranks a cold cached prefix) and to
        leave the host tier empty at drain."""
        dropped = 0
        for key in list(self._host_entries):
            if n is not None and dropped >= n:
                break
            host_id = self._host_entries.pop(key)
            if self._kv is not None and self._kv.host is not None:
                self._kv.host.free([host_id])
            dropped += 1
        return dropped

    def invalidate_blocks(self, blocks: List[int]) -> None:
        """Drop cached entries for ``blocks`` (quarantine: no new sharers may
        attach to a possibly-poisoned block) and release the cache's
        reference."""
        for b in blocks:
            key = self._by_block.pop(b, None)
            if key is not None:
                del self._entries[key]
                if self.allocator.refcount(b) == 1:
                    self._reclaimable -= 1
                self.allocator.free([b])


class PagedKVCache:
    """The device-side block pool plus its allocator.

    ``init_cache`` is a model family's cache constructor (``models/*.py``);
    the pool leaves are derived from its batch-1 template, so the fp and
    int8-quantized layouts both page without special cases
    (:func:`accelerate_tpu.models.generation.make_paged_pool`).  Where the
    family's cache also holds a **state** a sequence (``generation.STATE``), the
    pool carries it by decode slot, ``num_slots`` entries a leaf, beside the
    token rows by block.  ``generation.py`` tells the kinds apart; everything
    here that counts, copies or mirrors **blocks** asks it for the token leaves
    (:meth:`token_leaves`), and a state leaf is no block.  Where the family's
    cache holds **window** leaves (``generation.WINDOW``: token rows of layers
    that attend over a window), they are a second group of ``window_blocks``
    blocks with an allocator of its own (:attr:`window_allocator`, block 0 its
    null block): a block of one kind is no block of the other, and everything
    that counts bytes or blocks answers per kind.

    With ``num_host_blocks > 0`` (or a later :meth:`enable_host_tier`) the
    cache carries a second, host-DRAM tier mirroring the pool's leaf layout;
    :meth:`demote` and :meth:`promote` move whole blocks between the tiers
    as batched copies on the host path between dispatches.
    """

    def __init__(
        self,
        init_cache: Callable,
        config,
        num_blocks: int,
        block_size: int,
        num_host_blocks: int = 0,
        num_slots: int = 0,
        window_blocks: int = 0,
    ):
        from ..models.generation import make_paged_pool

        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.block_size = block_size
        self.allocator = BlockAllocator(num_blocks)
        self.pool = make_paged_pool(init_cache, config, num_blocks, block_size, num_slots, window_blocks)
        # The window kind's blocks, where the family has window leaves (make_paged_pool has checked their number).
        self.window_allocator: Optional[BlockAllocator] = BlockAllocator(window_blocks) if self.window_leaves() else None
        self.host: Optional[HostBlockPool] = None
        if num_host_blocks:
            self.enable_host_tier(num_host_blocks)

    def enable_host_tier(self, num_host_blocks: int) -> HostBlockPool:
        """Attach a host-DRAM block pool of ``num_host_blocks`` blocks with
        the same leaf layout as the device pool."""
        if self.host is not None:
            raise ValueError("host tier already enabled")
        self.host = HostBlockPool(self.token_leaves(), num_host_blocks)
        return self.host

    def host_can_fit(self, n: int) -> bool:
        """Whether a demotion of ``n`` blocks can be granted right now.
        False when no host tier is attached, when the tier lacks room, or
        when the ``SERVING_HOST_FULL`` fault arm forces the host-exhausted
        fallback paths for testing."""
        if self.host is None or self.host.free_blocks < n:
            return False
        from ..resilience import faultinject

        if faultinject.serving_host_full():
            return False
        return True

    def demote(self, blocks: List[int]) -> List[int]:
        """Copy device ``blocks`` into freshly-allocated host blocks (one
        batched D2H gather per leaf) and return the host ids, in order.  The
        caller keeps its device references and decides when to release them
        — demotion is a copy, not a move, so refcounted sharing survives.
        Raises :class:`BlockOutOfMemory` when the host tier cannot fit."""
        from ..models.generation import demote_pool_blocks

        if not blocks:
            return []
        if not self.host_can_fit(len(blocks)):
            free = self.host.free_blocks if self.host is not None else 0
            cap = self.host.capacity if self.host is not None else 0
            raise BlockOutOfMemory(
                f"host tier cannot fit {len(blocks)} blocks ({free} free of {cap})"
            )
        with annotate("serving.tier.demote", blocks=len(blocks)):
            host_ids = self.host.alloc(len(blocks))
            rows = demote_pool_blocks(self.pool, blocks)
            for name, leaf in self.host.leaves.items():
                leaf[:, host_ids] = rows[name]
        return host_ids

    def try_demote(self, blocks: List[int]) -> Optional[List[int]]:
        """:meth:`demote`, returning ``None`` instead of raising when the
        host tier cannot fit (the waterfall callers fall through to the
        free/drop path)."""
        if not self.host_can_fit(len(blocks)):
            return None
        return self.demote(blocks)

    def promote(self, host_ids: List[int], dst_blocks: List[int]) -> None:
        """Copy host blocks back into already-allocated device blocks
        ``dst_blocks`` (one batched H2D scatter per leaf) and free the host
        ids.  The caller owns ``dst_blocks``' references."""
        from ..models.generation import promote_pool_blocks

        if len(host_ids) != len(dst_blocks):
            raise ValueError(
                f"promote id mismatch: {len(host_ids)} host vs {len(dst_blocks)} device"
            )
        if not host_ids:
            return
        if self.host is None:
            raise ValueError("promote without a host tier")
        with annotate("serving.tier.promote", blocks=len(host_ids)):
            rows = {name: leaf[:, host_ids] for name, leaf in self.host.leaves.items()}
            self.pool = promote_pool_blocks(self.pool, rows, dst_blocks)
            self.host.free(host_ids)

    def token_leaves(self) -> dict:
        """The pool's leaves that are paged by block (every leaf, for most families)."""
        from ..models.generation import token_leaves

        return token_leaves(self.pool)

    def state_leaves(self) -> dict:
        """The pool's leaves held by decode slot: one entry a sequence (most families have none)."""
        from ..models.generation import state_leaves

        return state_leaves(self.pool)

    def window_leaves(self) -> dict:
        """The pool's token leaves of the layers that keep a window of rows (most families have none)."""
        from ..models.generation import window_leaves

        return window_leaves(self.pool)

    @property
    def leaf_names(self) -> list:
        return sorted(self.token_leaves())

    def pool_bytes(self) -> int:
        return sum(leaf.size * leaf.dtype.itemsize for leaf in self.token_leaves().values())

    def state_bytes(self) -> int:
        return sum(leaf.size * leaf.dtype.itemsize for leaf in self.state_leaves().values())

    def window_pool_bytes(self) -> int:
        return sum(leaf.size * leaf.dtype.itemsize for leaf in self.window_leaves().values())

    def block_bytes(self) -> int:
        """Bytes of pool data behind ONE block across every token leaf and
        layer — the unit of the ``serving.decode_gather_bytes`` accounting."""
        return _block_bytes(self.token_leaves())

    def window_block_bytes(self) -> int:
        """Bytes behind ONE block of the window kind, across its leaves and layers (0 without window leaves)."""
        return _block_bytes(self.window_leaves())


def _block_bytes(leaves: dict) -> int:
    if not leaves:
        return 0
    num_blocks = next(iter(leaves.values())).shape[1]
    return sum((leaf.size // num_blocks) * leaf.dtype.itemsize for leaf in leaves.values())
