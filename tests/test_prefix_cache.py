"""Shared-prefix KV cache (models/prefix_cache.py): the radix tree,
refcounting and eviction must be INVISIBLE in the tokens — cache-on
streams bitwise equal cache-off, greedy and sampled, mid-stream refill,
divergence mid-page (copy-on-write), and under forced LRU eviction —
while the skip counter proves the prefill work actually went away.

Host-side property tests (no jax) pin the allocator/refcount
accounting: no page is ever leaked, double-freed, or writable by two
slots at once."""

import jax
import numpy as np
import pytest

from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler, Engine,
                                    Request)
from triton_dist_tpu.models.config import tiny_qwen3
from triton_dist_tpu.models.prefix_cache import (PrefixCache,
                                                 RefcountedPages)

mesh1 = None
_MODELS = {}


def setup_module(module):
    global mesh1
    mesh1 = jax.make_mesh((1,), ("tp",))


def _model(n=1):
    if n not in _MODELS:
        m = mesh1 if n == 1 else jax.make_mesh((n,), ("tp",))
        cfg = tiny_qwen3(n)
        _MODELS[n] = (cfg, AutoLLM.from_config(cfg, m))
    return _MODELS[n]


def _shared_prefix_requests(rng, cfg, prefix_len, spec, seed0=100):
    """Requests whose prompts share one random prefix_len-token head."""
    prefix = rng.randint(0, cfg.vocab_size,
                         size=(prefix_len,)).astype(np.int32)
    reqs = []
    for i, (tail, g) in enumerate(spec):
        ids = np.concatenate(
            [prefix, rng.randint(0, cfg.vocab_size, size=(tail,))]
        ).astype(np.int32)
        reqs.append(Request(rid=i, ids=ids, gen_len=g, seed=seed0 + i))
    return prefix, reqs


# ----------------------------------------------------------------------
# host-side radix tree / refcount units (no jax programs)
# ----------------------------------------------------------------------


def test_radix_match_insert_split_refcounts():
    page = 4
    pc = PrefixCache(64, page)
    pool = pc.pool
    seq = np.arange(10, dtype=np.int32)          # pages 0..2 (10 tokens)
    pages = [pool.alloc_page() for _ in range(3)]
    assert pc.insert(seq, pages) == 10
    # tree holds one ref on top of ours
    assert all(pool.refcount(p) == 2 for p in pages)
    # full / partial / capped matches
    m, g = pc.tree.match(seq)
    assert m == 10 and len(g) == 3
    m, g = pc.tree.match(seq[:6])
    assert m == 6 and len(g) == 2
    m, g = pc.lookup(seq)                        # cap = n-1 = 9 -> 3 pages
    assert m == 9 and len(g) == 3
    # divergence mid-node at token 7 (mid-page): insert splits, and the
    # boundary page (page 1) gains a ref for the second node
    seq2 = np.concatenate([seq[:7], np.asarray([99, 98, 97], np.int32)])
    g2_cow, g2_tail = pool.alloc_page(), pool.alloc_page()
    # the diverging branch supplies its own complete boundary page (the
    # CoW page); index 0 of its page list is never read (leaf starts in
    # page 1)
    assert pc.insert(seq2, [None, g2_cow, g2_tail]) == 3
    m, g = pc.tree.match(seq2)
    assert m == 10
    assert g[1] == g2_cow                        # the CoW page, not pages[1]
    m, g = pc.tree.match(seq)                    # original branch intact
    assert m == 10 and g[1] == pages[1]
    # boundary page 1 of the ORIGINAL chain: ours + head node + tail node
    assert pool.refcount(pages[1]) == 3
    # release our refs; evict everything; pool must drain to empty
    for grp in pages + [g2_cow, g2_tail]:
        pool.release(grp)
    assert not pc.tree.evict_until(10 ** 9)      # cannot satisfy, drains all
    assert pool.pages_in_use == 0
    assert pool.available == 64 - 1              # only trash stays reserved


def test_refcount_random_admit_retire_evict():
    """Property test (satellite): a randomized admit/retire/evict
    driver over the pure host bookkeeping. Invariants after every op:
    allocator conservation (free + outstanding == num_pages), refcount
    table mirrors outstanding pages exactly, and no page is writable
    by two live slots at once."""
    rng = np.random.RandomState(0)
    page, num_pages = 4, 20
    pc = PrefixCache(num_pages, page)
    pool = pc.pool
    alloc = pool._alloc
    vocab = 6                        # tiny vocab -> heavy prefix overlap
    live = {}                        # slot -> (tokens, pages, writable)

    def check():
        assert alloc.available + alloc.outstanding == num_pages
        assert pool.pages_in_use == alloc.outstanding - 1   # - trash
        writable = [p for (_, _, w) in live.values() for p in w]
        assert len(writable) == len(set(writable)), \
            "page writable by two slots"

    for step in range(300):
        op = rng.rand()
        if op < 0.5 and len(live) < 4:
            n = int(rng.randint(3, 20))
            gen = int(rng.randint(1, 8))
            toks = rng.randint(0, vocab, size=(n,)).astype(np.int32)
            m, shared = pc.lookup(toks)
            full, r = m // page, m % page
            retained = [g for g in shared[:full]]
            for g in retained:
                pool.retain(g)
            boundary = shared[full] if r else None
            if boundary is not None:
                pool.retain(boundary)
            need = -(-(n + gen + 3) // page) - full
            if not pc.ensure_pages(need):
                for g in retained + ([boundary] if r else []):
                    pool.release(g)
                check()
                continue
            fresh = [pool.alloc_page() for _ in range(need)]
            if boundary is not None:
                pool.release(boundary)
            pages = retained + fresh
            # generated tokens extend the sequence before insert
            toks_full = np.concatenate(
                [toks, rng.randint(0, vocab, size=(gen,))]
            ).astype(np.int32)
            pc.insert(toks, pages[:-(-n // page)])
            live[step] = (toks_full, pages, fresh)
        elif op < 0.85 and live:
            slot = list(live)[int(rng.randint(len(live)))]
            toks_full, pages, _ = live.pop(slot)
            pc.insert(toks_full,
                      pages[:-(-len(toks_full) // page)])
            for g in pages:
                pool.release(g)
        else:
            pc.tree.evict_until(pool.available + int(rng.randint(1, 9)))
        check()
    # drain: retire everything, evict the whole tree -> zero leaks
    for toks_full, pages, _ in live.values():
        for g in pages:
            pool.release(g)
    pc.tree.evict_until(10 ** 9)
    assert pool.pages_in_use == 0
    assert alloc.available == num_pages - 1      # only trash outstanding


# ----------------------------------------------------------------------
# end-to-end exactness: cache-on tokens bitwise == cache-off
# ----------------------------------------------------------------------


def test_paged_prefix_greedy_matches_serve_and_cache_off():
    """6 shared-prefix requests through 4 paged slots with the radix
    cache on: every stream must equal (a) the same workload with the
    prefix cache OFF (same paged programs, no sharing) and (b) a
    sequential B-tiled Engine.serve() — bitwise, including the requests
    admitted into recycled slots mid-stream. And the skip counter must
    show real prefill work went away."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    rng = np.random.RandomState(0)
    prefix_len, page = 13, 8
    _, reqs = _shared_prefix_requests(
        rng, cfg, prefix_len,
        [(4, 6), (7, 9), (2, 4), (9, 7), (5, 8), (3, 10)])
    runs = {}
    for pc_on in (False, True):
        sched = ContinuousScheduler(eng, batch=4, chunk=4, paged=True,
                                    prefix_cache=pc_on, page=page)
        runs[pc_on] = sched.run(reqs)
        if pc_on:
            st = sched.stats()
            assert st["hits"] >= 5, st
            assert st["prefill_tokens_skipped"] >= \
                5 * (prefix_len - page), st
    for r in reqs:
        np.testing.assert_array_equal(
            runs[True][r.rid], runs[False][r.rid],
            err_msg=f"cache-on != cache-off, rid={r.rid}")
        want = np.asarray(eng.serve(np.tile(r.ids[None], (4, 1)),
                                    r.gen_len))[0]
        np.testing.assert_array_equal(runs[True][r.rid], want,
                                      err_msg=f"rid={r.rid}")


def test_paged_prefix_sampled_bitwise():
    """Sampled decode: per-slot PRNG chains never see the cache layout,
    so cache-on == cache-off == a batch-1 serve at the slot's seed."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla", sampling="top_k",
                 temperature=0.8)
    rng = np.random.RandomState(1)
    _, reqs = _shared_prefix_requests(
        rng, cfg, 11, [(5, 7), (3, 5), (8, 9), (2, 6), (6, 5)])
    runs = {}
    for pc_on in (False, True):
        sched = ContinuousScheduler(eng, batch=3, chunk=4, paged=True,
                                    prefix_cache=pc_on, page=8)
        runs[pc_on] = sched.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(
            runs[True][r.rid], runs[False][r.rid],
            err_msg=f"cache-on != cache-off, rid={r.rid}")
        want = np.asarray(eng.serve(r.ids[None], r.gen_len,
                                    seed=r.seed))[0]
        np.testing.assert_array_equal(runs[True][r.rid], want,
                                      err_msg=f"rid={r.rid}")


def test_second_request_skips_prefix_prefill():
    """The acceptance counter: after request 1 caches a P-token prefix,
    request 2 sharing it must provably skip >= P - page prefill tokens
    (its admission computes only the uncached suffix)."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    rng = np.random.RandomState(2)
    P, page = 21, 8
    prefix, reqs = _shared_prefix_requests(rng, cfg, P,
                                           [(6, 5), (4, 5)])
    sched = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                prefix_cache=True, page=page)
    got = sched.run(reqs)
    st = sched.stats()
    assert st["hits"] >= 1
    assert st["prefill_tokens_skipped"] >= P - page, st
    for r in reqs:
        want = np.asarray(eng.serve(np.tile(r.ids[None], (2, 1)),
                                    r.gen_len))[0]
        np.testing.assert_array_equal(got[r.rid], want,
                                      err_msg=f"rid={r.rid}")


def test_cow_divergence_mid_page():
    """Two prompts diverge INSIDE a page (prefix 13, page 8): the
    second request maps page 0 read-only, copy-on-writes the 5
    matched rows of page 1 into its own page, and recomputes only from
    token 13 — and the donor's cached pages must be bitwise unharmed
    (a third request re-using the ORIGINAL prompt still matches)."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    rng = np.random.RandomState(3)
    page = 8
    prefix, reqs = _shared_prefix_requests(rng, cfg, 13,
                                           [(5, 6), (7, 6)])
    # third request: the FIRST prompt again (hits its full n-1 tokens)
    reqs.append(Request(rid=2, ids=reqs[0].ids.copy(), gen_len=6,
                        seed=102))
    sched = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                prefix_cache=True, page=page)
    got = sched.run(reqs)
    st = sched.stats()
    # rid 1 matched 13 (mid-page -> CoW); rid 2 matched n-1 = 17
    assert st["prefill_tokens_skipped"] >= 13 + (len(reqs[0].ids) - 1), st
    for r in reqs:
        want = np.asarray(eng.serve(np.tile(r.ids[None], (2, 1)),
                                    r.gen_len))[0]
        np.testing.assert_array_equal(got[r.rid], want,
                                      err_msg=f"rid={r.rid}")


def test_eviction_pressure_stays_bitwise():
    """A pool sized for barely 2 worst-case slots, 10 requests: the LRU
    evictor must fire, admissions must keep succeeding, and every
    stream must still equal the cache-off run (which gets a full-size
    pool — eviction is invisible in the tokens)."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    rng = np.random.RandomState(7)
    page = 8
    pre_a = rng.randint(0, cfg.vocab_size, size=(11,)).astype(np.int32)
    pre_b = rng.randint(0, cfg.vocab_size, size=(9,)).astype(np.int32)
    reqs = []
    for i in range(10):
        pre = pre_a if i % 2 == 0 else pre_b
        ids = np.concatenate(
            [pre, rng.randint(0, cfg.vocab_size, size=(3 + i,))]
        ).astype(np.int32)
        reqs.append(Request(rid=i, ids=ids, gen_len=5 + (i % 3), seed=i))
    worst = -(-(22 + 7 + 3) // page)
    num_pages = 2 * worst + 1 + 1
    runs = {}
    for pc_on, npages in ((False, None), (True, num_pages)):
        sched = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                    prefix_cache=pc_on, page=page,
                                    num_pages=npages)
        runs[pc_on] = sched.run(reqs)
        if pc_on:
            st = sched.stats()
            assert st["evictions"] > 0, st
            assert st["pages_in_use"] + st["pages_free"] + 1 == num_pages
    for r in reqs:
        np.testing.assert_array_equal(
            runs[True][r.rid], runs[False][r.rid],
            err_msg=f"rid={r.rid}")


def test_paged_prefix_flash_backend():
    """The Pallas paged-decode kernel path (flash_decode_paged walks
    its own pages through the table): same bitwise contract."""
    cfg, model = _model()
    eng = Engine(model, max_seq=48, backend="flash")
    rng = np.random.RandomState(4)
    _, reqs = _shared_prefix_requests(rng, cfg, 12,
                                      [(4, 5), (6, 5), (3, 5)])
    runs = {}
    for pc_on in (False, True):
        sched = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                    prefix_cache=pc_on, page=8)
        runs[pc_on] = sched.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(
            runs[True][r.rid], runs[False][r.rid],
            err_msg=f"rid={r.rid}")
        want = np.asarray(eng.serve(np.tile(r.ids[None], (2, 1)),
                                    r.gen_len))[0]
        np.testing.assert_array_equal(runs[True][r.rid], want,
                                      err_msg=f"rid={r.rid}")


def test_paged_prefix_multi_device_mesh(ndev):
    """The paged path on the full virtual-device mesh (replicated pool,
    GSPMD-partitioned attend): tokens still bitwise equal serve()."""
    if ndev == 1:
        pytest.skip("single-device run covers this above")
    cfg, model = _model(ndev)
    eng = Engine(model, max_seq=48, backend="xla")
    rng = np.random.RandomState(5)
    _, reqs = _shared_prefix_requests(rng, cfg, 10, [(4, 5), (5, 5)])
    sched = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                prefix_cache=True, page=8)
    got = sched.run(reqs)
    assert sched.stats()["hits"] >= 1
    for r in reqs:
        want = np.asarray(eng.serve(np.tile(r.ids[None], (2, 1)),
                                    r.gen_len))[0]
        np.testing.assert_array_equal(got[r.rid], want,
                                      err_msg=f"rid={r.rid}")


def test_pool_exhaustion_preempts_instead_of_rejecting():
    """When eviction cannot free enough pages (everything pinned by
    live slots), the scheduler PREEMPTS a victim and re-queues it
    (tests/test_resilience.py has the full exactness matrix): with a
    pool fitting ONE worst-case slot, BOTH requests now complete
    bitwise-exactly, time-sliced through preemption. preempt=False
    restores the old hard-reject contract — the rejection REASON is
    recorded for the serving layer (a zero-token stream must not look
    like a legitimate completion)."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    rng = np.random.RandomState(6)
    page = 8
    ids = rng.randint(0, cfg.vocab_size, size=(2, 20)).astype(np.int32)
    num_pages = -(-(20 + 6 + 3) // page) + 1
    reqs = lambda: [Request(rid=i, ids=ids[i], gen_len=6)
                    for i in range(2)]
    sched = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                prefix_cache=True, page=page,
                                num_pages=num_pages)
    got = sched.run(reqs())
    assert sched.preemptions > 0
    assert not sched.rejected, sched.rejected
    for r in reqs():
        want = np.asarray(eng.serve(np.tile(r.ids[None], (2, 1)),
                                    6))[0]
        np.testing.assert_array_equal(got[r.rid], want,
                                      err_msg=f"rid={r.rid}")
    # preempt=False: the old contract — second admission rejects
    sched = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                prefix_cache=True, page=page,
                                num_pages=num_pages, preempt=False)
    got = sched.run(reqs())
    lens = sorted(len(got[r.rid]) for r in reqs())
    assert lens[0] == 0 and lens[1] == 6, lens
    assert any("page pool exhausted" in v
               for v in sched.rejected.values()), sched.rejected


def test_empty_prompt_rejected_gracefully():
    """An empty-prompt request must be REJECTED (finished with no
    tokens), not crash the poll loop, and must not leak pool pages."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    sched = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                prefix_cache=True, page=8)
    rng = np.random.RandomState(8)
    good = Request(rid="ok", ids=rng.randint(
        0, cfg.vocab_size, size=(5,)).astype(np.int32), gen_len=4)
    got = sched.run([Request(rid="empty",
                             ids=np.zeros((0,), np.int32), gen_len=4),
                     good])
    assert len(got["empty"]) == 0
    assert "empty prompt" in sched.rejected["empty"]
    want = np.asarray(eng.serve(np.tile(good.ids[None], (2, 1)), 4))[0]
    np.testing.assert_array_equal(got["ok"], want)
    st = sched.stats()
    assert st["pages_free"] + st["pages_in_use"] + 1 == \
        sched.slots.cache.num_pages
