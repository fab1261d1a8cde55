"""Shared-prefix KV cache: radix-tree page reuse with refcounts,
copy-on-write, and LRU eviction.

The serving stack's missing policy layer over the paged pool
(kernels/paged_kv.py mechanics + kv_cache.PagedSlotCache layout): in a
multi-tenant server most prefill work is re-computing KV for prompts
that share a system prompt or few-shot header. vLLM's PagedAttention
makes physical sharing cheap (a page-granular pool behind per-slot
tables); SGLang's RadixAttention turns that sharing into AUTOMATIC
cross-request reuse by keying a radix tree on token ids. This module is
that pair for the TPU serving stack:

- `RefcountedPages`: a refcount layer over the hardened `PageAllocator`
  free list. A physical page may back many slots' page tables AND many
  tree nodes at once; it returns to the free list only at refcount
  zero. One page id means the same row in every layer's pool, for
  every kv head of the slot (PagedSlotCache) — a page is the sharing
  unit.

- `RadixPrefixTree`: token-granular radix tree whose nodes carry the
  pages backing their span. Matching a new prompt returns the
  longest cached prefix and the pages to map read-only into the
  slot's table; the LAST page is only partially valid when the match
  ends mid-page — the admission copy-on-writes it into a fresh page
  (the boundary page will receive the diverging request's own writes,
  which must never touch the shared original). Node splits on insert
  may leave a boundary page referenced by two nodes — refcounts make
  that safe. Retired sequences (prompt + generated) are inserted back,
  donating the slot's page refs to the tree.

- LRU eviction: when an admission would exhaust the pool, the least
  recently matched leaves are evicted until enough pages free up (or
  nothing evictable remains, and the admission is rejected). Evicting
  a node only drops the TREE's refs — pages still mapped by in-flight
  slots survive until those slots retire.

- Host-RAM tier (models/kv_tier.py `HostKVPool` — the SGLang/HiCache
  hierarchical-cache layer; the design Mooncake, arXiv:2407.00079,
  runs in production KV-centric serving and CachedAttention,
  arXiv:2403.19708, applies to multi-turn sessions): with
  `host_pool_pages` set, eviction DEMOTES a span instead of dropping
  it — the node's page content is extracted to pinned host memory
  (one d2h gather across every layer's pool, Engine.extract_pages_
  host) and its device refs released; the node stays in the tree with
  a HOST residency bit (`_Node.host` = the pool handle). A later
  lookup on a host-resident path PROMOTES before matching: fresh
  device pages are allocated (evicting/demoting colder spans if
  needed — the matched path is pinned) and filled by one h2d install
  program (Engine.restore_pages_host), after which the node is an
  ordinary DEVICE node again and the existing CoW/refcount machinery
  applies untouched. True drop happens only from the host tier's own
  LRU (bounded by host_pool_pages). The d2h -> h2d round trip moves
  raw pool-dtype bytes, so warm-from-host streams are BITWISE equal
  to HBM-hit and cold-recompute streams (tests/test_kv_tier.py).

- KV FORK (parallel sampling, models/structured.py + scheduler
  `Request(n=N)`): `PagedDecodeSlots.fork` is the third consumer of
  this module's refcount/CoW machinery — a fork child RETAINS the
  parent slot's full prompt pages (refcount+1, mapped into its
  own table exactly like a tree hit) and copy-on-writes the
  partially-filled boundary page, so n decode streams share one
  prompt's physical KV. The fork records its skipped prefill through
  the same `record()` accounting a tree hit uses, and a fork child
  that cannot fork NOW falls back to ordinary admission whose tree
  match rebuilds the identical mapping — which is what keeps forked
  and sequential streams bitwise (tests/test_structured.py).

Exactness contract (tests/test_prefix_cache.py): reused prefix KV is
bitwise the KV the donor request computed for the same (token, position)
pairs, and the suffix forward runs the same program as a cache-off
admission with kv_start as traced data — so cache-on token streams are
bitwise identical to cache-off, greedy and sampled, including under
eviction pressure.

All host-side numpy: policy changes page TABLES (data), never programs.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from triton_dist_tpu.kernels.paged_kv import PageAllocator
from triton_dist_tpu.models.kv_tier import HostKVPool


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


class PoolExhausted(ValueError):
    """A paged admission could not get pages even after LRU eviction.

    Raised (instead of a generic ValueError) so the scheduler can tell
    RECOVERABLE pressure — preempt a victim slot and retry — from the
    hard rejections (over-capacity request, empty prompt) that no
    amount of preemption can fix. The chaos harness
    (runtime/chaos.py::FaultInjector) raises it too, to force the
    preemption path without actually draining the pool."""


def _common_prefix(a: np.ndarray, b: np.ndarray) -> int:
    L = min(len(a), len(b))
    if L == 0:
        return 0
    neq = np.nonzero(a[:L] != b[:L])[0]
    return int(neq[0]) if len(neq) else L


class RefcountedPages:
    """Refcounting layer over the PageAllocator free list (the
    "physical page backs many tables" half of the design). The trash
    page is reserved at construction and never refcounted — it is the
    write sink for retired slots, not storage.

    shards > 1 (sequence-parallel serving): the allocator partitions
    the id space per sp shard and rotates fresh pages across shards
    (kernels/paged_kv.PageAllocator) — this layer stays id-blind, it
    only surfaces the per-shard accounting the telemetry and the
    per-shard zero-leak invariant read."""

    def __init__(self, num_pages: int, shards: int = 1):
        self._alloc = PageAllocator(num_pages, shards=shards)
        self._ref: Dict[int, int] = {}
        # shard 0 allocates first, so the trash is page 0 of shard 0
        # whatever the shard count
        self.trash = self._alloc.alloc(1)[0]

    @property
    def num_pages(self) -> int:
        return self._alloc.num_pages

    @property
    def shards(self) -> int:
        return self._alloc.shards

    @property
    def pages_per_shard(self) -> int:
        return self._alloc.pages_per_shard

    @property
    def available(self) -> int:
        return self._alloc.available

    @property
    def available_by_shard(self):
        return self._alloc.available_by_shard

    @property
    def outstanding_by_shard(self):
        return self._alloc.outstanding_by_shard

    @property
    def pages_in_use(self) -> int:
        return len(self._ref)

    @property
    def pages_in_use_by_shard(self):
        """Refcounted (slot- or tree-referenced) pages per sp shard —
        the `sp_pages_resident{shard=}` gauge; 0 on every shard at
        idle IS the per-shard zero-leak invariant."""
        out = [0] * self._alloc.shards
        for p in self._ref:
            out[self._alloc.shard_of(p)] += 1
        return out

    @property
    def outstanding(self) -> int:
        """Pages held out of the free list (refcounted pages + the
        reserved trash page). Conservation invariant — the chaos
        harness's no-leak check (tests/test_resilience.py):
        ``available + outstanding == num_pages`` after ANY sequence of
        admissions, retirements, preemptions, evictions, and faults."""
        return self._alloc.outstanding

    def alloc_page(self) -> int:
        """One fresh writable page (its id at refcount 1)."""
        p = int(self._alloc.alloc(1)[0])
        self._ref[p] = 1
        return p

    def retain(self, page) -> None:
        p = int(page)
        if p not in self._ref:
            raise ValueError(
                f"retain of unreferenced page {p}: only pages live "
                f"from alloc_page (refcount >= 1) can gain refs — "
                f"a retain after the last release would resurrect "
                f"a page the allocator may have re-issued")
        self._ref[p] += 1

    def release(self, page) -> None:
        """Drop one ref of the page; at zero it goes back to the free
        list (the allocator re-checks double-frees). A release past
        zero raises BEFORE touching the pool — the silent failure mode
        is a page freed while a radix-tree node still maps it."""
        p = int(page)
        if p not in self._ref:
            raise ValueError(
                f"refcount underflow: release of page {p} at "
                f"refcount 0 (already fully released, or never "
                f"allocated) — some holder released a page twice")
        c = self._ref[p] - 1
        if c:
            self._ref[p] = c
        else:
            del self._ref[p]
            self._alloc.free([p])

    def refcount(self, page) -> int:
        return self._ref.get(int(page), 0)


class _Node:
    """One radix-tree edge: tokens `key` spanning absolute positions
    [start, start + len(key)), backed by `pages` — one page id
    per page index floor(start/page) .. ceil(end/page)-1. When
    start is mid-page the first page is SHARED in span with the
    parent's last page (the same physical page after a pure split, or
    the diverging request's copy-on-write page).

    Residency state machine (host tier, models/kv_tier.py): `host` is
    None for a DEVICE-resident node (pages hold device page ids) and
    a HostKVPool handle for a HOST-resident one (pages is empty — the
    span's bytes live in the host pool until promotion restores them
    into fresh device pages, or the host LRU truly drops them). Host
    nodes are opaque to insert (no descend, no split), so no DEVICE
    descendant can ever appear below one — the invariant that makes a
    host drop a clean subtree removal."""

    __slots__ = ("parent", "children", "start", "key", "pages",
                 "last_use", "host")

    def __init__(self, parent: Optional["_Node"], start: int,
                 key: np.ndarray, pages: List[int]):
        self.parent = parent
        self.children: Dict[int, "_Node"] = {}
        self.start = start
        self.key = key
        self.pages = pages
        self.last_use = 0
        self.host: Optional[int] = None


class RadixPrefixTree:
    """Token-keyed radix tree over the refcounted page pool. Each node
    holds one pool ref per page it references; matching never touches
    refcounts (callers retain what they map)."""

    def __init__(self, pool: RefcountedPages, page: int, *,
                 host_pool=None, fault=None, telemetry=None):
        self.pool = pool
        self.page = page
        # optional runtime/telemetry.py bundle: demote/promote/drop
        # show up as timeline instants when tracing is on (trace-off
        # is a guarded no-op inside Telemetry.instant)
        self.tele = telemetry
        self.root = _Node(None, 0, np.zeros((0,), np.int32), [])
        self._tick = 0
        self.evictions = 0
        # host tier (models/kv_tier.py): the bounded host pool, the
        # engine-wired copy callbacks (PrefixCache.attach_host_tier),
        # the handle -> node map driving true drops, and the pin set
        # protecting a promotion's matched path from the demotions its
        # own page allocation can trigger. fault: chaos hook
        # (runtime/chaos.py::FaultInjector.host_demotion) forcing the
        # true-drop path without actually filling the host pool.
        self.host_pool = host_pool
        self.fault = fault
        self._extract_fn = None    # pages -> payload (d2h gather)
        self._restore_fn = None    # (payload, pages) -> None (h2d)
        # per-promote_path restore time (alloc + h2d install only —
        # NOT the victim demotions evict_until may run to make room),
        # accumulated here so PrefixCache's EMA reports what the
        # gauge's name claims
        self.restore_ms_accum = 0.0
        self._host_nodes: Dict[int, _Node] = {}
        self._pinned: Dict[int, _Node] = {}
        self.demotions = 0
        self.promotions = 0
        self.host_drops = 0

    def _touch(self, node: _Node) -> None:
        self._tick += 1
        node.last_use = self._tick

    # ------------------------------------------------------------------
    # match
    # ------------------------------------------------------------------

    def match(self, tokens, cap: Optional[int] = None
              ) -> Tuple[int, List[int]]:
        """Longest cached prefix of `tokens` (≤ cap): returns
        (m, pages) with pages covering page indices
        0 .. ceil(m/page)-1. When m is mid-page the last page is only
        partially valid — the caller must copy-on-write it before the
        slot writes anything. Touches the matched path for LRU.

        A HOST-resident child ends the match (its pages are not on the
        device): callers that want host spans promoted first run
        promote_path (PrefixCache.lookup does) — after promotion the
        node is an ordinary device node and matches normally."""
        tokens = np.asarray(tokens, np.int32)
        node = self.root
        m = 0
        pages: List[int] = []
        while m < len(tokens):
            child = node.children.get(int(tokens[m]))
            if child is None or child.host is not None:
                break
            L = _common_prefix(child.key, tokens[m:m + len(child.key)])
            if child.start % self.page:
                # the child's first page is its own complete version
                # of the boundary page (see _Node docstring) — it
                # overrides the parent's
                pages.pop()
            first_pg = child.start // self.page
            n_pg = _ceil_div(child.start + L, self.page) - first_pg
            pages.extend(child.pages[:n_pg])
            m += L
            self._touch(child)
            if L < len(child.key):
                break
            node = child
        if cap is not None and m > cap:
            m = cap
            pages = pages[:_ceil_div(m, self.page)]
        return m, pages

    # ------------------------------------------------------------------
    # insert
    # ------------------------------------------------------------------

    def insert(self, tokens, pages_by_tile: List[int]) -> int:
        """Insert a finished sequence (prompt + generated): walk the
        matched path, split a node if the sequence diverges inside it,
        and attach the unmatched suffix as a new leaf whose pages are
        the caller's pages for that span (the tree RETAINS them — the
        caller keeps its own refs and releases them at retire). Returns
        the number of newly cached tokens."""
        tokens = np.asarray(tokens, np.int32)
        node = self.root
        m = 0
        while m < len(tokens):
            child = node.children.get(int(tokens[m]))
            if child is not None and child.host is not None:
                # host-resident nodes are opaque to insert (splitting
                # or descending would need pages that are not on the
                # device): stop — caching the remainder is best-effort
                # bookkeeping, never a correctness requirement
                return 0
            if child is None:
                leaf_pages = [
                    int(g)
                    for g in pages_by_tile[m // self.page:
                                            _ceil_div(len(tokens),
                                                      self.page)]]
                leaf = _Node(node, m, tokens[m:].copy(), leaf_pages)
                for g in leaf_pages:
                    self.pool.retain(g)
                node.children[int(tokens[m])] = leaf
                self._touch(leaf)
                return len(tokens) - m
            L = _common_prefix(child.key, tokens[m:m + len(child.key)])
            self._touch(child)
            if L < len(child.key):
                if m + L == len(tokens):
                    return 0          # sequence ends inside the node
                child = self._split(child, L)    # descend into the head
            m += L
            node = child
        return 0

    def _split(self, child: _Node, L: int) -> "_Node":
        """Split `child` at key offset L into head [start, start+L) +
        tail [start+L, end): the tail keeps the node object (so its
        children stay wired), the head takes its place under the
        parent. A mid-page split leaves the boundary page referenced by
        BOTH nodes — one extra pool ref covers the second reference."""
        s = child.start
        cut = s + L
        first_pg = s // self.page
        head_pages = child.pages[:_ceil_div(cut, self.page) - first_pg]
        head = _Node(child.parent, s, child.key[:L], head_pages)
        head.last_use = child.last_use
        child.parent.children[int(child.key[0])] = head
        tail_first = cut // self.page
        child.pages = child.pages[tail_first - first_pg:]
        child.start = cut
        child.key = child.key[L:]
        child.parent = head
        head.children[int(child.key[0])] = child
        if cut % self.page:
            # boundary page now appears in head.pages[-1] AND
            # child.pages[0] (same physical page)
            self.pool.retain(head.pages[-1])
        return head

    # ------------------------------------------------------------------
    # LRU eviction
    # ------------------------------------------------------------------

    def evict_until(self, pages_needed: int) -> bool:
        """Evict least-recently-matched device spans until the
        allocator has `pages_needed` free pages (or nothing evictable
        remains — returns False, the admission's rejection signal).
        With a host tier attached each victim is DEMOTED (d2h snapshot
        + device refs released, node stays in the tree host-resident)
        and only falls back to a true drop when demotion is refused
        (host pool too small for the span, or a chaos fault).
        Releasing a span's pages only drops the tree's refs; a page
        still mapped read-only by an in-flight slot stays allocated
        until that slot retires.

        One tree walk seeds a min-heap of nodes whose SUBTREES hold no
        other device pages (plain leaves, and parents whose children
        were all demoted earlier) by last_use; a parent joins the heap
        the moment its last device-holding child is demoted or dropped
        — O(n + k log n) for k evictions instead of a full rescan.
        Nodes pinned by an in-flight promotion are skipped."""
        import heapq
        if self.pool.available >= pages_needed:
            return True
        heap = []
        order = []
        stack = [self.root]
        while stack:
            nd = stack.pop()
            order.append(nd)
            stack.extend(nd.children.values())
        # children appear after their parent in the DFS order, so the
        # reverse sweep sees children first: a node "blocks" its parent
        # while its subtree still holds device pages
        blockers: Dict[int, int] = {}
        subtree_dev: Dict[int, bool] = {}
        for nd in reversed(order):
            pend = sum(1 for c in nd.children.values()
                       if subtree_dev[id(c)])
            blockers[id(nd)] = pend
            subtree_dev[id(nd)] = bool(nd.pages) or pend > 0
            if nd is not self.root and nd.pages and pend == 0:
                heap.append((nd.last_use, id(nd), nd))
        heapq.heapify(heap)
        while self.pool.available < pages_needed and heap:
            _, _, nd = heapq.heappop(heap)
            if id(nd) in self._pinned:
                continue
            parent = nd.parent
            if self._try_demote(nd):
                self.demotions += 1
                if self.tele is not None:
                    self.tele.instant("kv_demote")
            else:
                self._drop_node(nd)
                self.evictions += 1
                if self.tele is not None:
                    self.tele.instant("kv_evict")
            blockers[id(parent)] -= 1
            if parent is not self.root and parent.pages \
                    and blockers[id(parent)] == 0:
                heapq.heappush(heap, (parent.last_use, id(parent),
                                      parent))
        return self.pool.available >= pages_needed

    def _try_demote(self, nd: _Node) -> bool:
        """Demote one device span to the host tier: make room in the
        host pool (true-dropping ITS least-recently-used spans — the
        only place KV is actually forgotten), snapshot the span's pages
        (the wired d2h gather), release the device refs, and flip the
        node's residency bit. False = demotion unavailable (no tier,
        span too big for the whole host pool, everything pinned, or a
        chaos-injected host exhaustion) — the caller true-drops."""
        hp = self.host_pool
        if hp is None or self._extract_fn is None or not nd.pages:
            return False
        n_pages = len(nd.pages)
        if n_pages > hp.capacity:
            return False
        if self.fault is not None and \
                not getattr(self.fault, "host_demotion",
                            lambda n: True)(n_pages):
            return False
        pinned_handles = {n.host for n in self._pinned.values()
                          if n.host is not None}
        while hp.room < n_pages:
            h = hp.victim(pinned=pinned_handles)
            if h is None:
                return False
            self._drop_host_subtree(self._host_nodes[h])
        payload = self._extract_fn(nd.pages)
        h = hp.put(payload, n_pages=n_pages)
        self._host_nodes[h] = nd
        for g in nd.pages:
            self.pool.release(g)
        nd.pages = []
        nd.host = h
        return True

    def _drop_node(self, nd: _Node) -> None:
        """True-drop a device span (no tier, or demotion refused):
        release its device refs and remove it from the tree. Any
        children are host-resident (the eligibility sweep guarantees
        the subtree holds no other device pages) and go with it —
        orphaned host spans could never be matched again."""
        for g in nd.pages:
            self.pool.release(g)
        nd.pages = []
        for c in list(nd.children.values()):
            self._drop_host_subtree(c)
        del nd.parent.children[int(nd.key[0])]

    def _drop_host_subtree(self, nd: _Node) -> None:
        """Remove a host-resident node AND its subtree from tree and
        host pool (descendants of a host node are host-resident by the
        insert-opacity invariant — see _Node)."""
        del nd.parent.children[int(nd.key[0])]
        stack = [nd]
        while stack:
            x = stack.pop()
            stack.extend(x.children.values())
            if x.pages:         # defensive: never true by invariant
                for g in x.pages:
                    self.pool.release(g)
                x.pages = []
                self.evictions += 1
            if x.host is not None:
                self.host_pool.drop(x.host)
                del self._host_nodes[x.host]
                x.host = None
                self.host_drops += 1

    # ------------------------------------------------------------------
    # promotion (host -> device)
    # ------------------------------------------------------------------

    def promote_path(self, tokens, cap: int) -> int:
        """Walk the match path of `tokens` (up to `cap`) and PROMOTE
        every host-resident node on it back to device residency, in
        path order, so the match that follows sees ordinary device
        nodes. The whole visited path is PINNED while promoting: the
        page allocation a promotion needs may itself evict/demote, and
        must not cannibalize the spans this lookup is about to map.
        Returns the number of nodes promoted (0 = pure HBM path).
        Stops early when a promotion fails (device pool too small even
        after eviction) — the match then ends at that node, exactly as
        if the span had been dropped."""
        if self.host_pool is None or not self._host_nodes:
            return 0           # nothing demoted: skip the extra walk
        tokens = np.asarray(tokens, np.int32)
        # pre-walk the WHOLE path and pin it before promoting anything:
        # an early promotion's room-making may otherwise true-drop the
        # deeper host spans this same lookup is about to restore
        node, m = self.root, 0
        path: List[_Node] = []
        while m < cap:
            child = node.children.get(int(tokens[m]))
            if child is None:
                break
            L = _common_prefix(child.key, tokens[m:m + len(child.key)])
            if L == 0:
                break
            path.append(child)
            m += L
            if L < len(child.key):
                break
            node = child
        if not any(c.host is not None for c in path):
            return 0
        self._pinned = {id(c): c for c in path}
        try:
            promoted = 0
            for child in path:
                if child.host is not None:
                    if not self._promote(child):
                        break
                    promoted += 1
            return promoted
        finally:
            self._pinned = {}

    def _promote(self, nd: _Node) -> bool:
        """Restore one host span into fresh device pages: free-list
        headroom (evicting/demoting unpinned spans), alloc the pages,
        run the wired h2d install, and flip residency. The host entry
        is popped only after the install is dispatched — a failure
        leaves the span host-resident (and LRU-touched) for the next
        attempt."""
        if self._restore_fn is None:
            return False
        entry = self.host_pool.get(nd.host)        # touches host LRU
        need = entry.n_pages
        if not self.evict_until(need):
            return False
        pages: List[int] = []
        t0 = time.perf_counter()
        try:
            for _ in range(entry.n_pages):
                pages.append(self.pool.alloc_page())
            self._restore_fn(entry.payload, pages)
        except Exception:
            # release-before-raise (the _reserve_pages convention):
            # pages referenced by neither the node nor any slot would
            # otherwise leak past every drain
            for g in pages:
                self.pool.release(g)
            raise
        self.restore_ms_accum += (time.perf_counter() - t0) * 1e3
        self.host_pool.pop(nd.host)
        del self._host_nodes[nd.host]
        nd.host = None
        nd.pages = pages
        self.promotions += 1
        if self.tele is not None:
            self.tele.instant("kv_promote")
        self._touch(nd)
        return True

    # introspection (tests)

    def nodes(self) -> List[_Node]:
        out = []
        stack = [self.root]
        while stack:
            nd = stack.pop()
            if nd is not self.root:
                out.append(nd)
            stack.extend(nd.children.values())
        return out


class PrefixCache:
    """The serving-facing facade: pool + tree + hit/skip counters.
    `enabled=False` keeps the identical pool/alloc path but never
    matches or inserts — the cache-off configuration runs the SAME
    device programs, which is what makes the bitwise cache-on/off
    comparison meaningful."""

    def __init__(self, num_pages: int, page: int, *,
                 enabled: bool = True, host_pool_pages: int = 0,
                 fault=None, telemetry=None, shards: int = 1):
        """host_pool_pages > 0 attaches the host-RAM capacity tier
        (models/kv_tier.py): eviction demotes spans to a host pool of
        that many (device-page-sized) buffers instead of dropping, and
        lookups on host-resident paths promote them back. The owner
        must also wire the device copy callbacks (attach_host_tier) —
        until then demotion stays disabled and eviction drops as
        before. fault: chaos hook (runtime/chaos.py::FaultInjector)
        whose host_demotion() can force the true-drop path.

        telemetry (runtime/telemetry.py): the hit/skip counters below
        live in its metrics registry — PagedDecodeSlots passes the
        scheduler's bundle so one stats() registry snapshot covers
        the cache; a bare PrefixCache gets a private registry.

        shards: the sp mesh size of a SEQUENCE-PARALLEL pool
        (kv_cache.PagedSlotCache SP SHARDING) — the allocator then
        partitions the page-id space per shard and rotates fresh
        pages across shards, and stats() grows per-shard
        `sp_pages_resident{shard=}` gauges (resident 0 on every shard
        at idle is the per-shard zero-leak invariant)."""
        from triton_dist_tpu.runtime.telemetry import Telemetry
        self.pool = RefcountedPages(num_pages,
                                    shards=shards)
        self.page = page
        self.enabled = enabled
        self.tele = telemetry if telemetry is not None else Telemetry()
        self.host = HostKVPool(host_pool_pages) if host_pool_pages \
            else None
        self.tree = RadixPrefixTree(self.pool, page,
                                    host_pool=self.host, fault=fault,
                                    telemetry=self.tele)
        reg = self.tele.registry
        self.admissions = reg.counter(
            "admissions", "successful paged admissions")
        self.hits = reg.counter(
            "hits", "admissions with a non-empty prefix match")
        self.host_hits = reg.counter(
            "host_hits", "lookups that promoted host-resident spans")
        self._g_restore = reg.gauge(
            "restore_latency_ms", "EMA over promoting lookups' h2d "
                                  "restore work")
        self.prompt_tokens = reg.counter(
            "prompt_tokens", "prompt tokens across admissions")
        self.prefill_tokens_skipped = reg.counter(
            "prefill_tokens_skipped", "prompt tokens served from "
                                      "cached prefixes")
        self.tokens_inserted = reg.counter(
            "tokens_inserted", "new tokens donated to the radix tree")

    def attach_host_tier(self, extract, restore) -> None:
        """Wire the device-side copy callbacks into the residency
        machine: `extract(pages) -> payload` gathers their
        bytes to host memory (demotion), `restore(payload, pages)`
        installs a payload into freshly allocated device pages
        (promotion). PagedDecodeSlots binds these to
        Engine.extract_pages_host / restore_pages_host over its own
        paged cache."""
        self.tree._extract_fn = extract
        self.tree._restore_fn = restore

    def lookup(self, prompt) -> Tuple[int, List[int]]:
        """Longest cached prefix for an admission (capped to n-1: the
        last prompt token is always recomputed so the slot has fresh
        next-token logits). With the host tier attached, host-resident
        spans on the path are PROMOTED first (h2d install into fresh
        pages), so the returned pages are always device pages and the
        caller's CoW/refcount flow is tier-oblivious."""
        if not self.enabled:
            return 0, []
        cap = max(len(prompt) - 1, 0)
        if self.host is not None:
            self.tree.restore_ms_accum = 0.0
            if self.tree.promote_path(prompt, cap):
                self.host_hits.inc()
                # EMA over the pure restore work (alloc + h2d install)
                # of this lookup's promotions — victim-demotion time
                # evict_until spends making room is excluded, so the
                # gauge reports what its name claims
                dt = self.tree.restore_ms_accum
                cur = self._g_restore.value
                self._g_restore.set(dt if cur == 0.0
                                    else 0.9 * cur + 0.1 * dt)
        return self.tree.match(prompt, cap=cap)

    def record(self, n_prompt: int, n_matched: int) -> None:
        """Count one SUCCESSFUL admission (rejected requests don't
        skew the hit/skip rates)."""
        self.admissions.inc()
        self.prompt_tokens.inc(n_prompt)
        self.prefill_tokens_skipped.inc(n_matched)
        self.hits.inc(int(bool(n_matched)))

    def insert(self, tokens, pages_by_tile) -> int:
        if not self.enabled:
            return 0
        new = self.tree.insert(tokens, pages_by_tile)
        self.tokens_inserted.inc(new)
        return new

    def ensure_pages(self, n_pages: int) -> bool:
        """Free-list headroom for an admission: evict LRU leaves when
        short. False = not satisfiable (reject the admission)."""
        if self.pool.available >= n_pages:
            return True
        if not self.enabled:
            return False
        return self.tree.evict_until(n_pages)

    @property
    def restore_latency_ms(self) -> float:
        """EMA over promoting lookups (registry gauge; the old float
        attribute's read API, kept for callers)."""
        return self._g_restore.value

    def stats(self) -> dict:
        """Hit/skip counters + structural gauges. The counters live in
        the telemetry registry; the structural values (pool occupancy,
        tree/tier counters) are refreshed into registry gauges here so
        a registry snapshot taken right after (ContinuousScheduler.
        stats(), the /metrics exposition) is one consistent cut."""
        reg = self.tele.registry
        total = max(self.prompt_tokens.value, 1)
        with reg.lock:
            reg.gauge("pages_in_use").set(self.pool.pages_in_use)
            reg.gauge("pages_free").set(self.pool.available)
            reg.gauge("pages_outstanding").set(self.pool.outstanding)
            reg.gauge("evictions").set(self.tree.evictions)
            reg.gauge("demotions").set(self.tree.demotions)
            reg.gauge("promotions").set(self.tree.promotions)
            reg.gauge("host_drops").set(self.tree.host_drops)
            host = (self.host.stats() if self.host is not None
                    else HostKVPool.empty_stats())
            for k, v in host.items():
                reg.gauge(k).set(v)
            if self.pool.shards > 1:
                # per-shard residency (sp pools): refcounted pages on
                # each sp shard — resident 0 everywhere at idle IS the
                # per-shard zero-leak invariant
                for s, npg in enumerate(self.pool.pages_in_use_by_shard):
                    reg.gauge(
                        "sp_pages_resident",
                        "refcounted pages per sp shard",
                        labels={"shard": str(s)}).set(npg)
        out = {
            "enabled": self.enabled,
            "admissions": self.admissions.value,
            "hits": self.hits.value,
            "hit_rate": self.hits.value / max(self.admissions.value, 1),
            "prompt_tokens": self.prompt_tokens.value,
            "prefill_tokens_skipped":
                self.prefill_tokens_skipped.value,
            "prefill_skip_frac":
                self.prefill_tokens_skipped.value / total,
            "evictions": self.tree.evictions,
            "pages_in_use": self.pool.pages_in_use,
            "pages_free": self.pool.available,
            "pages_outstanding": self.pool.outstanding,
            # host tier gauges (zeros when the tier is off, via the
            # pool's canonical key set) — the operator's live view of
            # demote/promote behaviour
            **HostKVPool.empty_stats(),
            "host_hits": self.host_hits.value,
            "demotions": self.tree.demotions,
            "promotions": self.tree.promotions,
            "host_drops": self.tree.host_drops,
            "restore_latency_ms": round(self._g_restore.value, 3),
        }
        if self.pool.shards > 1:
            out["sp_pages_resident"] = self.pool.pages_in_use_by_shard
            out["sp_pages_free_by_shard"] = self.pool.available_by_shard
        # NB the pool defines __len__, so this must test `is not None`
        # (an EMPTY pool is falsy)
        if self.host is not None:
            out.update(self.host.stats())
        return out
