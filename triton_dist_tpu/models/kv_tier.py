"""Host-RAM KV tier: the capacity layer under the radix prefix cache.

At production scale the shared-prefix working set exceeds HBM by orders
of magnitude, so the radix tree's LRU eviction (models/prefix_cache.py)
used to throw away KV the next request would recompute from scratch.
This module is the second tier of the SGLang/HiCache hierarchical-cache
design (and the pattern Mooncake, arXiv:2407.00079, runs in production
KV-centric serving; CachedAttention, arXiv:2403.19708, is the same idea
for multi-turn sessions): eviction DEMOTES an unreferenced page
span to pinned host memory (one d2h gather of the span's pages across
every layer's pool) instead of dropping it, and a later prefix match on
a host-resident path PROMOTES it back — fresh device pages are
allocated and filled by one h2d install program before the uncached
suffix prefill runs. Only the host tier's own LRU (bounded by
``host_pool_pages``) truly drops KV.

`HostKVPool` is the host half: a bounded store of demoted page
payloads (per-layer K/V extracted from the device pools, kept in the
pool dtype so the d2h -> h2d round trip is BITWISE exact) with
second-level LRU ordering and page-denominated accounting. On a
SEQUENCE-PARALLEL pool (ISSUE 14 — kv_cache.PagedSlotCache SP
SHARDING) a demoted span is really S per-chip page sets: the d2h
gather assembles each page from its owning sp shard (one psum of
owned-or-zero contributions — exact) and the h2d restore scatters
owned pages back comm-free (engine._gather_pages_fn /
_restore_pages_fn sp branches), so the tier stays bitwise and
layout-blind whatever the mesh. It is
policy-free about tree structure — the residency state machine lives in
`models/prefix_cache.py` (`_Node.host`, demote-on-evict,
promote-on-match), which owns the handle -> node map and drives drops
through `victim()`.

Zero-leak contract across both tiers (tests/test_kv_tier.py,
tests/test_resilience.py): the device invariant
``available + outstanding == num_pages`` is untouched (demotion
releases device refs like a drop did), and the host invariant
``pages_resident == sum(entry pages) <= capacity`` holds after any
sequence of demotions, promotions, drops, and injected faults
(runtime/chaos.py::FaultInjector.host_demotion).

Telemetry (runtime/telemetry.py): the counters below stay plain ints
because ``pages_resident``/``room`` gate the demote/promote logic and
the invariant checks compare them directly; ``PrefixCache.stats()``
publishes `stats()`'s key set into the owning scheduler's metrics
registry as gauges on every snapshot (so `/metrics` and the stats()
registry cut carry ``host_pages_resident`` / ``host_puts`` /
``host_pops`` / ``host_drops_pool`` live), and demote/promote/drop
fire timeline instants on the tree's telemetry hook when tracing.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Iterable, Optional


class _HostEntry:
    """One demoted span: an opaque payload (the engine's extracted
    per-layer K/V arrays) plus the page accounting the pool needs."""

    __slots__ = ("payload", "n_pages")

    def __init__(self, payload, n_pages: int):
        self.payload = payload
        self.n_pages = n_pages


class HostKVPool:
    """Bounded host-RAM store of demoted page payloads with LRU
    ordering (the capacity tier's own second-level LRU: a true drop
    happens only here). Sizes are in DEVICE PAGES so ``host_pool_pages``
    composes directly with the device pool's ``num_pages`` — the
    effective cache is ``num_pages + host_pool_pages`` pages.

    The pool never decides WHAT to drop into the void: the radix tree
    asks ``victim()`` for the least-recently-used unpinned handle and
    removes the corresponding subtree itself (a dropped interior span
    orphans its host-resident descendants, which must go with it)."""

    def __init__(self, capacity_pages: int):
        if capacity_pages < 1:
            raise ValueError(
                f"host_pool_pages must be >= 1, got {capacity_pages}")
        self.capacity = int(capacity_pages)
        self._entries: "OrderedDict[int, _HostEntry]" = OrderedDict()
        self._next = 0
        self.pages_resident = 0
        # lifetime counters (PrefixCache.stats() surfaces these)
        self.puts = 0
        self.pops = 0
        self.drops = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def room(self) -> int:
        return self.capacity - self.pages_resident

    def _check(self) -> None:
        """Host-tier conservation bound, O(1) so mass demotion stays
        linear; the exhaustive form (resident pages == sum of live
        entries) is recomputed by the chaos/no-leak tests."""
        assert 0 <= self.pages_resident <= self.capacity, \
            f"host pool over capacity: {self.pages_resident}" \
            f"/{self.capacity}"

    def victim(self, pinned: Iterable[int] = ()) -> Optional[int]:
        """Least-recently-used handle not in `pinned` (the promotion
        path's in-flight handles), or None when nothing is droppable."""
        pinned = set(pinned)
        for h in self._entries:          # OrderedDict: LRU first
            if h not in pinned:
                return h
        return None

    def put(self, payload, *, n_pages: int) -> int:
        """Store one demoted span; the caller has already made room
        (victim()/drop()). Returns the handle the tree keys its
        residency bit on."""
        if n_pages > self.room:
            raise ValueError(
                f"host pool exhausted: want {n_pages} pages, have "
                f"{self.room} of {self.capacity}")
        h = self._next
        self._next += 1
        self._entries[h] = _HostEntry(payload, int(n_pages))
        self.pages_resident += int(n_pages)
        self.puts += 1
        self._check()
        return h

    def get(self, handle: int) -> _HostEntry:
        """Read an entry and touch its LRU position (a matched span is
        hot — keep it resident if promotion fails this time)."""
        e = self._entries[handle]
        self._entries.move_to_end(handle)
        return e

    def pop(self, handle: int) -> _HostEntry:
        """Remove an entry on successful PROMOTION (its bytes now live
        in freshly allocated device pages)."""
        e = self._entries.pop(handle)
        self.pages_resident -= e.n_pages
        self.pops += 1
        self._check()
        return e

    def drop(self, handle: int) -> None:
        """TRUE DROP: the only place in the two-tier cache where KV is
        actually forgotten (the tree removes the node; a later request
        recomputes)."""
        e = self._entries.pop(handle)
        self.pages_resident -= e.n_pages
        self.drops += 1
        self._check()

    @classmethod
    def empty_stats(cls) -> dict:
        """The gauge key set at zero — what PrefixCache.stats() reports
        with the tier off, kept here so tier-off and tier-on stats can
        never drift apart."""
        return {
            "host_pool_pages": 0,
            "host_pages_resident": 0,
            "host_entries": 0,
            "host_puts": 0,
            "host_pops": 0,
            "host_drops_pool": 0,
        }

    def stats(self) -> dict:
        out = self.empty_stats()
        out.update({
            "host_pool_pages": self.capacity,
            "host_pages_resident": self.pages_resident,
            "host_entries": len(self._entries),
            "host_puts": self.puts,
            "host_pops": self.pops,
            "host_drops_pool": self.drops,
        })
        return out
