"""Host-side radix index over prompt token ids → KV block chains.

The RadixAttention idea (SGLang), at block granularity (vLLM's paged
unit): a tree whose every node owns exactly ONE pool block — the K/V of
``block_size`` tokens — keyed by those tokens, so a root-to-node path
spells a prompt prefix and the path's block ids are the chain the
engine gathers into a new request's slot. Host-side only: the tree
holds ids and token tuples, never device arrays.

Invariants (property-tested in ``tests/test_prefix_cache.py``):

- **Accounting**: every non-scratch pool block is either on the free
  list or owned by exactly one live node; ``blocks_live + blocks_free
  == num_blocks - 1`` at all times.
- **Refcounts**: ``pin(node)`` increments every node on the root path,
  ``unpin`` decrements it; a request pins the deepest node it matched
  or extended for its whole slot residency, so every ancestor of an
  in-use chain is protected.
- **Eviction**: only LEAF nodes with ``ref == 0`` are evictable, least
  recently accessed first — an interior node always outlives its
  children, so a stored chain can never lose an ancestor block while a
  descendant (or a pinned user) remains.
- **Reclaim cost**: the evictable leaves are kept in LRU order as the
  index changes (:class:`RadixPrefixCache` says how), so handing out a
  block under pressure costs what it frees, not the size of the index.

Single-threaded by design, like the engine that drives it: the engine
is caller-driven (``step()``), so no locking — and because the device
GATHER copies blocks into the slot before admission returns, eviction
of an unpinned chain is always safe even if a past hit is still
decoding from its private copy.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

from pddl_tpu.serve.kvcache.block_pool import SCRATCH_BLOCK


class _Node:
    """One cached block: ``key`` is its block's token tuple, ``block_id``
    its pool row. The root is a sentinel (no key, no block)."""

    __slots__ = ("key", "block_id", "parent", "children", "ref",
                 "last_access", "queued")

    def __init__(self, key: Optional[tuple], block_id: Optional[int],
                 parent: Optional["_Node"]):
        self.key = key
        self.block_id = block_id
        self.parent = parent
        self.children: Dict[tuple, "_Node"] = {}
        self.ref = 0
        self.last_access = 0
        self.queued = False  # has an entry in the index's LRU heap


@dataclasses.dataclass
class PrefixMatch:
    """Longest stored chain for a prompt: ``node`` is the deepest match
    (the root for a full miss), ``block_ids`` its root path's blocks."""

    node: _Node
    block_ids: List[int]

    @property
    def n_blocks(self) -> int:
        return len(self.block_ids)


class RadixPrefixCache:
    """Refcounted, LRU-evicted radix index over a block pool.

    **How a reclaim finds its victims.** The evictable nodes (not the
    root, ``ref == 0``, no child left) sit in a binary heap of
    ``(stamp, seq, node)`` entries, validated when popped — a lazy heap
    rather than an ordered dict or a linked list, because a node that
    BECOMES evictable (an unpin, a parent whose last child was evicted)
    carries an old stamp and belongs in the middle of the order, where
    only a heap inserts in O(log n). A node has at most one entry
    (``_Node.queued``), pushed when it becomes evictable and left alone
    when that ends (a pin, a new child) or when ``match`` / ``descend``
    / ``extend`` restamp it: stamps only grow, so an entry's stamp is
    never later than its node's, it surfaces no later than it is due,
    and a pop that finds its node unevictable drops the entry, one that
    finds it restamped pushes it back under the new stamp. The heap
    never holds more entries than the index holds nodes; nothing is
    ever scanned or compacted.

    No tie-break orders the victims: a stamp belongs to one call and a
    call stamps one root path, so two nodes share a stamp only as
    ancestor and descendant, and an ancestor is no leaf while its
    descendant is in the index. ``seq`` only keeps the heap from
    comparing nodes (and the visit counts deterministic).

    ``reclaims`` counts the allocations that met a short free list and
    ``reclaim_visited`` the heap entries popped plus the parents those
    evictions exposed — visited per eviction is the witness that a
    reclaim costs what it frees (about 1–2; the walk this replaced
    visited the whole index per pass).

    Args:
      block_size: tokens per block (the pool's token granularity).
      num_blocks: pool rows INCLUDING the reserved scratch sink (id 0),
        so ``num_blocks - 1`` blocks are allocatable.
    """

    def __init__(self, block_size: int, num_blocks: int):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block {SCRATCH_BLOCK} is the "
                f"scratch sink), got {num_blocks}")
        self.block_size = block_size
        self.num_blocks = num_blocks
        self._free: Deque[int] = deque(range(1, num_blocks))
        self._root = _Node(None, None, None)
        self._clock = itertools.count(1)
        self._lru: List[Tuple[int, int, _Node]] = []
        self._seq = itertools.count()
        self.evictions = 0
        self.reclaims = 0
        self.reclaim_visited = 0
        # Demotion hook (ISSUE 13, `kvcache/hosttier.py`): called once
        # per reclaim pass with the LIST of victims BEFORE their block
        # ids are freed — each node still attached (parent chain
        # walkable, block id readable), so the engine can spill the
        # blocks' K/V D2H into the host tier in ONE batched gather
        # (per-victim calls measured ~7x slower on the admission
        # path). The callback must not touch this index. None
        # (default) keeps eviction a plain free; the degraded flush
        # NEVER calls it (`flush_unpinned` — spilling during an OOM
        # response would defeat the shedding).
        self.on_evict = None

    # ------------------------------------------------------------ stats
    @property
    def blocks_free(self) -> int:
        return len(self._free)

    @property
    def blocks_live(self) -> int:
        return (self.num_blocks - 1) - len(self._free)

    # ------------------------------------------------------------ match
    def match(self, tokens: Sequence[int],
              max_blocks: Optional[int] = None) -> PrefixMatch:
        """Walk the longest stored chain of full-block matches of
        ``tokens`` (optionally capped at ``max_blocks``), refreshing the
        chain's LRU stamps. Never pins — callers pin explicitly."""
        now = next(self._clock)
        node = self._root
        ids: List[int] = []
        limit = len(tokens) // self.block_size
        if max_blocks is not None:
            limit = min(limit, max_blocks)
        for j in range(limit):
            key = tuple(int(t) for t in
                        tokens[j * self.block_size:(j + 1) * self.block_size])
            child = node.children.get(key)
            if child is None:
                break
            node = child
            node.last_access = now
            ids.append(node.block_id)
        return PrefixMatch(node, ids)

    def descend(self, node: _Node, tokens: Sequence[int],
                start_block: int) -> Tuple[_Node, int]:
        """Walk already-stored children of ``node`` along ``tokens``
        from ``start_block`` on, refreshing LRU stamps; returns the
        deepest stored node and its block depth. The donation-side
        dedup: chunks the index already holds (e.g. beyond a capped
        gather match, or stored by an earlier identical prompt) must
        not have fresh blocks allocated — under a full pool that
        allocation would LRU-evict a USEFUL block to supply one that
        ``extend`` would immediately hand back."""
        now = next(self._clock)
        j = start_block
        while True:
            key = tuple(int(t) for t in
                        tokens[j * self.block_size:
                               (j + 1) * self.block_size])
            if len(key) != self.block_size:
                break
            child = node.children.get(key)
            if child is None:
                break
            node = child
            node.last_access = now
            j += 1
        return node, j

    def chain_tokens(self, node: _Node) -> List[int]:
        """Root-path token ids of ``node`` (``depth * block_size`` of
        them) — the chain identity the host tier (`hosttier.py`) keys a
        demoted block under, and the prompt slice a promotion re-keys
        it back from."""
        keys: List[tuple] = []
        while node is not self._root:
            keys.append(node.key)
            node = node.parent
        keys.reverse()
        return [int(t) for key in keys for t in key]

    def chain_depth(self, node: _Node) -> int:
        """Block count of ``node``'s root path (0 for the root)."""
        depth = 0
        while node is not self._root:
            depth += 1
            node = node.parent
        return depth

    def chain_ids(self, node: _Node) -> List[int]:
        """Root-path block ids of ``node``, root-first — the stored
        chain a paged slot's block table must point at after donation
        (the paged engine swaps duplicate private blocks onto the
        stored chain; token-identity implies bit-identical KV, so the
        swap is token-exact by the position-absolute cache contract)."""
        ids: List[int] = []
        while node is not self._root:
            ids.append(node.block_id)
            node = node.parent
        ids.reverse()
        return ids

    # -------------------------------------------------------- refcounts
    def pin(self, node: _Node) -> None:
        """Protect ``node`` and its whole root path from eviction (one
        live user). Pinning the root is a no-op chain of length 0."""
        while node is not self._root:
            node.ref += 1
            node = node.parent

    def unpin(self, node: _Node) -> None:
        tip = node
        while node is not self._root:
            if node.ref <= 0:
                raise RuntimeError(
                    "unpin without a matching pin (refcount underflow) — "
                    "an engine slot released its prefix chain twice")
            node.ref -= 1
            node = node.parent
        # ``ref`` moved along the whole path, but every node above the
        # tip has a child: only the tip can have become evictable.
        self._queue_if_evictable(tip)

    def flush_unpinned(self) -> int:
        """Degraded-mode flush: evict EVERY unpinned block (the chains
        live slots still pin stay — their gathered copies are already
        private, but their index entries must remain consistent until
        unpin). Returns the number of blocks freed. Used by the engine
        when a RESOURCE_EXHAUSTED surfaces: the prefix cache is the one
        large optional HBM consumer, so shedding it is the graceful
        response before any request has to fail.

        BYPASSES demotion deliberately (``demote=False`` below): this
        path runs inside the OOM response, where the point is to shed
        work, and a D2H spill per evicted block would spend transfers
        — and host memory — exactly when the engine is trying to
        survive. Degraded-mode eviction is a hard free, pinned
        discriminatively by ``tests/test_kv_tier.py``."""
        before = self.blocks_free
        self._reclaim(self.blocks_live, demote=False)
        return self.blocks_free - before

    # ------------------------------------------------------- allocation
    def release(self, block_ids: List[int]) -> None:
        """Return ids from :meth:`allocate` that were never attached via
        :meth:`extend` (a failed donation unwinding). Releasing an
        attached block this way would double-own it — that path must go
        through eviction instead."""
        for bid in block_ids:
            if bid == SCRATCH_BLOCK:
                raise ValueError("the scratch block is never allocated")
            self._free.append(bid)

    def allocate(self, n: int) -> List[int]:
        """Up to ``n`` free block ids, LRU-evicting unpinned leaves as
        needed. May return FEWER than asked (everything else is pinned)
        — the caller donates a shorter chain prefix, never fails."""
        if len(self._free) < n:
            self.reclaims += 1
            self._reclaim(n - len(self._free))
        take = min(n, len(self._free))
        return [self._free.popleft() for _ in range(take)]

    def _queue_if_evictable(self, node: _Node) -> None:
        """Give ``node`` its entry in the LRU heap if it is evictable
        and has none. Called wherever a node can BECOME evictable: the
        tip ``extend`` leaves, the tip ``unpin`` releases, a parent
        whose last child a reclaim took."""
        if (node is not self._root and node.ref == 0
                and not node.children and not node.queued):
            self._push(node)

    def _push(self, node: _Node) -> None:
        node.queued = True
        heapq.heappush(self._lru,
                       (node.last_access, next(self._seq), node))

    def _reclaim(self, need: int, demote: bool = True) -> None:
        """Evict up to ``need`` unpinned LEAVES, least recently accessed
        first, in passes: a pass takes up to ``need`` of the leaves
        evictable at its start; evicting a leaf can expose its parent,
        which becomes a candidate only in the NEXT pass, so passes
        repeat until satisfied or nothing is evictable. Victims come
        off the LRU heap (the class docstring has its rules); nothing
        but victims, stale entries and exposed parents is looked at.

        With a demotion hook installed (``on_evict``), eviction is a
        POLICY DECISION rather than a free: the WHOLE reclaim's victim
        set — all passes, eviction order — is offered to the hook in
        ONE call, still attached, block ids still valid, before any id
        returns to the free list, so reuse-worthy chains spill to the
        host tier instead of dying and the spill's D2H read is one
        batched transfer per allocation shortfall rather than one per
        pass (passes often take 1-2 leaves each, and the hook's
        device round trip sits on the admission path). Victims are
        counted off their parents, not detached, between passes
        (``left``), so exposing a parent as the next pass's leaf needs
        no tree mutation before the hook runs.
        ``demote=False`` (the degraded flush) skips the hook
        unconditionally."""
        call_hook = demote and self.on_evict is not None
        heap = self._lru
        all_taken: List[_Node] = []
        left: Dict[_Node, int] = {}  # parent -> children not yet taken
        while need > 0:
            took = 0
            exposed: List[_Node] = []
            while took < need and heap:
                stamp, _, node = heapq.heappop(heap)
                self.reclaim_visited += 1
                node.queued = False
                if node.ref or left.get(node, len(node.children)):
                    continue  # pinned or extended since it was queued
                if stamp != node.last_access:
                    self._push(node)  # restamped since it was queued
                    continue
                all_taken.append(node)
                took += 1
                parent = node.parent
                n = left.get(parent, len(parent.children)) - 1
                left[parent] = n
                if n == 0 and parent is not self._root and parent.ref == 0:
                    exposed.append(parent)
            # An exposed parent cannot already be queued: an entry from
            # its own days as a leaf is older than any child it has had
            # since and was popped before them.
            self.reclaim_visited += len(exposed)
            for parent in exposed:
                self._push(parent)
            if took == 0:
                break
            need -= took
        if not all_taken:
            return
        try:
            if call_hook:
                self.on_evict(all_taken)
        finally:
            # The victims are off the heap and their parents counted
            # down: a hook that raises must not leave them half-taken
            # (in the index, evictable, and never found again), so they
            # are freed all the same and the exception goes on up.
            for victim in all_taken:
                del victim.parent.children[victim.key]
                self._free.append(victim.block_id)
                self.evictions += 1

    # --------------------------------------------------------- insertion
    def extend(self, node: _Node, tokens: Sequence[int],
               block_ids: Sequence[int]) -> _Node:
        """Attach ``len(block_ids)`` new child blocks under ``node``,
        one per consecutive ``block_size``-token chunk of ``tokens``
        (the donated suffix blocks, in chain order). Returns the new
        chain tip. ``tokens`` may cover more chunks than ids (a partial
        donation when the allocator ran dry); extra chunks are simply
        not stored."""
        now = next(self._clock)
        try:
            for j, bid in enumerate(block_ids):
                if bid == SCRATCH_BLOCK:
                    raise ValueError(
                        "the scratch block cannot join the index")
                key = tuple(int(t) for t in
                            tokens[j * self.block_size:
                                   (j + 1) * self.block_size])
                if len(key) != self.block_size:
                    raise ValueError(
                        f"chunk {j} has {len(key)} tokens, need a full "
                        f"{self.block_size}-token block")
                if key in node.children:
                    # A concurrent admission in the same tick already
                    # stored this chunk: keep the existing node, return
                    # the id to the free list (ours was never written
                    # into the tree).
                    self._free.append(bid)
                    node = node.children[key]
                else:
                    child = _Node(key, bid, node)
                    node.children[key] = child
                    node = child
                node.last_access = now
        finally:
            # The parent stopped being a leaf (its heap entry, if any,
            # is dropped when popped); the tip reached — the new one,
            # an existing one on a dedup, the last attached if a chunk
            # was refused — may be one.
            self._queue_if_evictable(node)
        return node
