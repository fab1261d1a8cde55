"""Host-RAM KV tier (models/kv_tier.py + the residency state machine
in models/prefix_cache.py): demotion and promotion must be INVISIBLE
in the tokens — warm-from-host streams bitwise equal cold-recompute
AND HBM-hit streams, greedy, sampled and spec=K, with mid-stream
refill, eviction pressure, preemption and chaos-forced host exhaustion
in the mix — while the tier counters prove spans actually moved
through host RAM and came back.

Host-side units (no jax programs) pin the two-tier bookkeeping: the
pool LRU, the demote -> promote round trip, cascaded true drops, and
the cross-tier zero-leak invariant (device
``available + outstanding == num_pages`` AND host
``pages_resident == sum(entries) <= capacity``)."""

import jax
import numpy as np
import pytest

from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler, Engine,
                                    Request)
from triton_dist_tpu.models.config import tiny_qwen3
from triton_dist_tpu.models.kv_tier import HostKVPool
from triton_dist_tpu.models.prefix_cache import PrefixCache
from triton_dist_tpu.runtime.chaos import FaultInjector

mesh1 = None
_MODELS = {}

PAGE, CHUNK = 8, 4


def setup_module(module):
    global mesh1
    mesh1 = jax.make_mesh((1,), ("tp",))


def _model():
    if 1 not in _MODELS:
        cfg = tiny_qwen3(1)
        _MODELS[1] = (cfg, AutoLLM.from_config(cfg, mesh1))
    return _MODELS[1]


def _assert_no_leak_two_tier(sched):
    """The cross-tier zero-leak invariant after a drained scheduler:
    device conservation, host accounting == live entries, tree handle
    map == pool entries, and a full drain (which now DEMOTES into the
    host tier) still releases every device page."""
    prefix = sched.slots.prefix
    pool = prefix.pool
    assert pool.available + pool.outstanding == pool.num_pages
    assert not sched.slots.occupied
    hp = prefix.host
    if hp is not None:
        assert hp.pages_resident == sum(
            e.n_pages for e in hp._entries.values())
        assert hp.pages_resident <= hp.capacity
        assert set(prefix.tree._host_nodes) == set(hp._entries), \
            "tree residency map out of sync with the host pool"
    prefix.tree.evict_until(10 ** 9)
    assert pool.pages_in_use == 0, "leaked device page refs"
    assert pool.available == pool.num_pages - 1    # trash stays reserved
    if hp is not None:
        assert hp.pages_resident == sum(
            e.n_pages for e in hp._entries.values()) <= hp.capacity
        assert set(prefix.tree._host_nodes) == set(hp._entries)


# ----------------------------------------------------------------------
# host-side units (no jax programs)
# ----------------------------------------------------------------------


def test_host_pool_accounting_and_lru():
    hp = HostKVPool(10)
    h1 = hp.put("a", n_pages=4)
    h2 = hp.put("b", n_pages=4)
    assert hp.pages_resident == 8 and len(hp) == 2 and hp.room == 2
    with pytest.raises(ValueError):
        hp.put("c", n_pages=4)       # no room: caller evicts
    assert hp.victim() == h1                     # LRU first
    assert hp.victim(pinned={h1}) == h2          # pins respected
    assert hp.get(h1).payload == "a"             # touch -> h2 is now LRU
    assert hp.victim() == h2
    hp.drop(h2)
    assert hp.pages_resident == 4 and hp.drops == 1
    e = hp.pop(h1)
    assert e.payload == "a" and e.n_pages == 4
    assert hp.pages_resident == 0 and hp.pops == 1
    assert hp.victim() is None
    with pytest.raises(ValueError):
        HostKVPool(0)


def test_demote_promote_roundtrip_bookkeeping():
    """Pure host bookkeeping with fake copy callbacks: eviction under a
    host tier demotes (device refs released, node host-resident, pool
    invariants intact) and a lookup promotes the span back into fresh
    pages — with the EXACT payload the demotion extracted handed to
    the restore callback."""
    page = 4
    pc = PrefixCache(8, page, host_pool_pages=32)
    extracted, restored = [], []
    pc.attach_host_tier(
        lambda pages: extracted.append(list(pages)) or len(extracted) - 1,
        lambda payload, pages: restored.append((payload, list(pages))))
    pool = pc.pool
    seq = np.arange(10, dtype=np.int32)          # 3 pages
    pages = [pool.alloc_page() for _ in range(3)]
    assert pc.insert(seq, pages) == 10
    for g in pages:
        pool.release(g)
    assert pc.tree.evict_until(pool.available + 3)   # forces demotion
    st = pc.stats()
    assert st["demotions"] == 1 and st["evictions"] == 0
    assert st["host_pages_resident"] == 3 and st["host_entries"] == 1
    assert pool.pages_in_use == 0
    assert pool.available + pool.outstanding == pool.num_pages
    # the demoted node stayed in the tree but is unmatchable raw...
    m, g = pc.tree.match(seq)
    assert m == 0 and not g
    # ...until lookup() promotes it
    m, g = pc.lookup(seq)
    assert m == 9 and len(g) == 3
    st = pc.stats()
    assert st["promotions"] == 1 and st["host_hits"] == 1
    assert st["host_entries"] == 0 and st["host_pages_resident"] == 0
    assert st["restore_latency_ms"] > 0.0
    # the restore got the demotion's payload and 3 fresh pages
    (payload, fresh_pages), = restored
    assert payload == 0 and len(fresh_pages) == 3
    assert pool.available + pool.outstanding == pool.num_pages
    # the promoted node matches like any device node now
    m2, _ = pc.tree.match(seq)
    assert m2 == 10


def test_host_pool_true_drop_and_insert_opacity():
    """A host pool too small for the working set TRUE-DROPS its LRU
    spans (the only place KV is forgotten); insert stops at a
    host-resident child instead of splitting/descending it."""
    page = 4
    pc = PrefixCache(32, page, host_pool_pages=4)        # 4 pages max
    pc.attach_host_tier(lambda pages: None,
                        lambda payload, pages: None)
    pool = pc.pool
    seq = np.arange(10, dtype=np.int32)
    pages = [pool.alloc_page() for _ in range(3)]
    pc.insert(seq, pages)
    seq2 = np.concatenate([seq[:7], np.asarray([99, 98, 97], np.int32)])
    g2_cow, g2_tail = pool.alloc_page(), pool.alloc_page()
    pc.insert(seq2, [None, g2_cow, g2_tail])
    for grp in pages + [g2_cow, g2_tail]:
        pool.release(grp)
    assert pc.tree.evict_until(10 ** 9) is False  # drains every span
    st = pc.stats()
    assert st["demotions"] >= 2
    assert st["host_drops"] >= 1, "4-page host pool must have dropped"
    assert st["host_pages_resident"] <= 4
    assert pool.pages_in_use == 0
    assert pool.available == 32 - 1
    assert set(pc.tree._host_nodes) == set(pc.host._entries)
    # insert through a host-resident child is a no-op (opacity)
    more = np.concatenate([seq, np.asarray([7, 7, 7], np.int32)])
    fresh = [pool.alloc_page() for _ in range(4)]
    kept = pc.insert(more, fresh)
    assert kept == 0
    for g in fresh:
        pool.release(g)
    assert pool.pages_in_use == 0


def test_chaos_fault_forces_true_drop_bookkeeping():
    """FaultInjector.host_demotion refusals turn demotions into plain
    drops — the tierless eviction path — without corrupting either
    tier's accounting."""
    page = 4
    fault = FaultInjector(exhaust_host_demotions=(0,))
    pc = PrefixCache(16, page, host_pool_pages=32, fault=fault)
    pc.attach_host_tier(lambda pages: None,
                        lambda payload, pages: None)
    pool = pc.pool
    for start in (0, 100):
        seq = np.arange(start, start + 8, dtype=np.int32)
        pages = [pool.alloc_page() for _ in range(2)]
        pc.insert(seq, pages)
        for g in pages:
            pool.release(g)
    assert pc.tree.evict_until(10 ** 9) is False
    st = pc.stats()
    assert fault.injected["host_exhausted"] == 1
    assert st["evictions"] == 1 and st["demotions"] == 1
    assert pool.pages_in_use == 0
    assert pool.available == 16 - 1


# ----------------------------------------------------------------------
# end-to-end exactness: warm-from-host == cold-recompute == HBM-hit
# ----------------------------------------------------------------------


def _tiered_requests(cfg, n_prefixes=3, n_reqs=8, seed=0,
                     repetitive=False):
    """Round-robin over distinct shared prefixes: with a device pool
    sized below the prefix working set, a prefix's span is demoted
    between its uses and must come back from host RAM."""
    rng = np.random.RandomState(seed)
    if repetitive:
        pres = [np.tile(rng.randint(0, cfg.vocab_size, size=(4,)), 5)
                [:17].astype(np.int32) for _ in range(n_prefixes)]
    else:
        pres = [rng.randint(0, cfg.vocab_size,
                            size=(17,)).astype(np.int32)
                for _ in range(n_prefixes)]
    out = []
    for i in range(n_reqs):
        pre = pres[i % n_prefixes]
        ids = np.concatenate(
            [pre, rng.randint(0, cfg.vocab_size, size=(3 + i % 4,))]
        ).astype(np.int32)
        out.append(Request(rid=i, ids=ids, gen_len=4 + (i % 3),
                           seed=100 + i))
    return out


def _run_three_ways(eng, cfg, reqs_fn, *, num_pages, spec=0,
                    host_pool_pages=512, expect_preempt=False):
    """The acceptance matrix: the SAME workload through (a) the paged
    pool with the cache off (cold recompute), (b) an ample-pool prefix
    cache (pure HBM hits), and (c) a pressure-sized pool with the host
    tier (demote/promote active). All three streams must be bitwise
    identical per request; (c) must actually have moved spans through
    host RAM."""
    runs, st_tier, preempts = {}, None, 0
    cases = (("off", dict(prefix_cache=False)),
             ("hbm", dict(prefix_cache=True)),
             ("tier", dict(prefix_cache=True, num_pages=num_pages,
                           host_pool_pages=host_pool_pages)))
    for label, kw in cases:
        sched = ContinuousScheduler(eng, batch=2, chunk=CHUNK,
                                    paged=True, page=PAGE, spec=spec,
                                    **kw)
        runs[label] = sched.run(reqs_fn())
        assert not sched.rejected, (label, sched.rejected)
        if label == "tier":
            st_tier = sched.stats()
            preempts = sched.preemptions
            _assert_no_leak_two_tier(sched)
    assert st_tier["demotions"] > 0, st_tier
    assert st_tier["promotions"] > 0, st_tier
    assert st_tier["host_hits"] >= 1, st_tier
    assert st_tier["restore_latency_ms"] > 0.0, st_tier
    if expect_preempt:
        assert preempts > 0, "pool sizing failed to force preemption"
    for r in reqs_fn():
        np.testing.assert_array_equal(
            runs["tier"][r.rid], runs["off"][r.rid],
            err_msg=f"rid={r.rid}: warm-from-host != cold-recompute")
        np.testing.assert_array_equal(
            runs["tier"][r.rid], runs["hbm"][r.rid],
            err_msg=f"rid={r.rid}: warm-from-host != HBM-hit")
    return runs["tier"], st_tier


def _pressure_pool(cfg, slots_worth, max_prompt=24, max_gen=6):
    worst = -(-(max_prompt + max_gen + CHUNK - 1) // PAGE)
    return slots_worth * worst + 1 + 1


def test_warm_from_host_bitwise_greedy():
    """Greedy + mid-stream refill: 8 requests over 3 prefixes through
    2 slots on a pool fitting ~2 worst-case slots — the tier demotes
    and promotes continuously, and every stream equals cache-off,
    HBM-hit, AND a sequential Engine.serve()."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    got, _ = _run_three_ways(
        eng, cfg, lambda: _tiered_requests(cfg),
        num_pages=_pressure_pool(cfg, 2))
    for r in _tiered_requests(cfg):
        want = np.asarray(eng.serve(np.tile(r.ids[None], (2, 1)),
                                    r.gen_len))[0]
        np.testing.assert_array_equal(got[r.rid], want,
                                      err_msg=f"rid={r.rid}")


def test_warm_from_host_bitwise_sampled():
    """Sampled mode: per-slot PRNG chains never see the tier, so
    warm-from-host equals cache-off equals a batch-1 serve at the
    slot's seed."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla", sampling="top_k",
                 temperature=0.8)
    got, _ = _run_three_ways(
        eng, cfg, lambda: _tiered_requests(cfg, seed=1),
        num_pages=_pressure_pool(cfg, 2))
    for r in _tiered_requests(cfg, seed=1):
        want = np.asarray(eng.serve(r.ids[None], r.gen_len,
                                    seed=r.seed))[0]
        np.testing.assert_array_equal(got[r.rid], want,
                                      err_msg=f"rid={r.rid}")


def test_warm_from_host_bitwise_spec():
    """spec=K over repetitive prefixes: the verify windows read
    promoted pages like any others — streams bitwise across the
    matrix."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    _run_three_ways(
        eng, cfg,
        lambda: _tiered_requests(cfg, seed=2, repetitive=True),
        num_pages=_pressure_pool(cfg, 2), spec=2)


def test_warm_from_host_with_preemption_bitwise():
    """The tier composes with KV-pressure preemption: a pool fitting
    ~1 worst-case slot forces preempt/resume WHILE spans shuttle
    between tiers — still bitwise."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    _run_three_ways(
        eng, cfg,
        lambda: _tiered_requests(cfg, n_prefixes=2, n_reqs=5, seed=3),
        num_pages=_pressure_pool(cfg, 1), expect_preempt=True)


def test_capacity_multiplier_over_hbm():
    """The tier's reason to exist: a prefix working set LARGER than the
    device pool. Without the tier the returning prefixes were evicted
    (recompute); with it they come back from host RAM — strictly more
    prefill skipped, at equal (bitwise) streams."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    rng = np.random.RandomState(4)
    pres = [rng.randint(0, cfg.vocab_size, size=(17,)).astype(np.int32)
            for _ in range(4)]

    def reqs():
        r = np.random.RandomState(5)
        out = []
        # two passes over 4 distinct prefixes, one slot's worth of pool:
        # pass 2 can only hit via the host tier
        for i in range(8):
            ids = np.concatenate(
                [pres[i % 4], r.randint(0, cfg.vocab_size, size=(3,))]
            ).astype(np.int32)
            out.append(Request(rid=i, ids=ids, gen_len=4, seed=100 + i))
        return out

    num_pages = _pressure_pool(cfg, 1)
    skipped = {}
    runs = {}
    for tier in (0, 512):
        sched = ContinuousScheduler(eng, batch=1, chunk=CHUNK,
                                    paged=True, page=PAGE,
                                    num_pages=num_pages,
                                    host_pool_pages=tier)
        runs[tier] = sched.run(reqs())
        st = sched.stats()
        skipped[tier] = st["prefill_tokens_skipped"]
        if tier:
            assert st["host_hits"] >= 3, st
            assert st["promotions"] >= 3, st
            _assert_no_leak_two_tier(sched)
    assert skipped[512] > skipped[0], skipped
    for r in reqs():
        np.testing.assert_array_equal(runs[512][r.rid], runs[0][r.rid],
                                      err_msg=f"rid={r.rid}")


def test_warm_from_host_chunked_prefill_bitwise():
    """The tier composes with chunked prefill (prefill_budget): the
    chunk-0 table install maps promoted pages exactly like HBM-hit
    ones, and the mixed ticks prefill only the uncached suffix —
    streams bitwise chunked+tier == monolithic tierless."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    reqs_fn = lambda: _tiered_requests(cfg, seed=7)
    base = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                               page=PAGE, prefix_cache=False)
    want = base.run(reqs_fn())
    sched = ContinuousScheduler(
        eng, batch=2, chunk=CHUNK, paged=True, page=PAGE,
        num_pages=_pressure_pool(cfg, 2), host_pool_pages=512,
        prefill_budget=6)
    got = sched.run(reqs_fn())
    st = sched.stats()
    assert st["demotions"] > 0 and st["promotions"] > 0, st
    assert st["max_prefill_tokens_per_poll"] <= 6, st
    for r in reqs_fn():
        np.testing.assert_array_equal(got[r.rid], want[r.rid],
                                      err_msg=f"rid={r.rid}")
    _assert_no_leak_two_tier(sched)


def test_chaos_host_exhaustion_stays_bitwise():
    """Chaos-forced host exhaustion (FaultInjector.host_demotion
    refusals) plus a TINY real host pool: demotions fall back to true
    drops mid-workload, streams stay bitwise, and the cross-tier
    zero-leak invariant holds under exhaustion of BOTH tiers."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    reqs_fn = lambda: _tiered_requests(cfg, seed=6)
    base = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                               page=PAGE, prefix_cache=False)
    want = base.run(reqs_fn())
    fault = FaultInjector(exhaust_host_demotions=(0, 2, 3))
    sched = ContinuousScheduler(
        eng, batch=2, chunk=CHUNK, paged=True, page=PAGE,
        num_pages=_pressure_pool(cfg, 2),
        host_pool_pages=4,                       # fits ~4 pages: drops
        fault=fault)
    got = sched.run(reqs_fn())
    st = sched.stats()
    assert fault.injected["host_exhausted"] >= 1
    assert st["evictions"] > 0, st       # the true-drop path ran
    assert st["demotions"] > 0, st       # and the tier still worked
    for r in reqs_fn():
        np.testing.assert_array_equal(got[r.rid], want[r.rid],
                                      err_msg=f"rid={r.rid}")
    _assert_no_leak_two_tier(sched)


# ----------------------------------------------------------------------
# TP-sharded pool: the gather-to-host layout — extract_pages_host
# assembles every page's heads from the chips that hold them
# ([NP, Hkv, page, d] sharded on the head axis), and the restore lands
# each head's bytes back on its chip, so the d2h -> h2d round trip is
# bitwise on multi-chip pools too.
# ----------------------------------------------------------------------


def test_extract_restore_bitwise_on_sharded_pool():
    import dataclasses as _dc

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = min(4, len(jax.devices()))
    mesh = jax.make_mesh((n,), ("tp",))
    cfg = tiny_qwen3(n)
    model = AutoLLM.from_config(cfg, mesh)
    eng = Engine(model, max_seq=32, backend="flash")
    pc = eng.make_paged_slot_cache(2, page=PAGE)
    Hkv = cfg.num_kv_heads
    NP, page, d = pc.num_pages, pc.page, cfg.head_dim
    assert pc.kv_heads == Hkv
    assert pc.pages_k[0].sharding.shard_shape(
        pc.pages_k[0].shape) == (NP, Hkv // n, page, d)
    # distinct bytes per (layer, page, head): a gather that read
    # another chip's heads (or summed them) cannot reproduce them
    rng = np.random.RandomState(0)
    shd = NamedSharding(model.mesh, P(None, model.axis, None, None))
    pats_k = [rng.randn(NP, Hkv, page, d).astype(np.float32)
              for _ in pc.pages_k]
    pats_v = [rng.randn(NP, Hkv, page, d).astype(np.float32)
              for _ in pc.pages_v]
    pc = _dc.replace(
        pc,
        pages_k=tuple(jax.device_put(jnp.asarray(p), shd) for p in pats_k),
        pages_v=tuple(jax.device_put(jnp.asarray(p), shd) for p in pats_v))
    ids = np.asarray([3, 1, 5], np.int32)
    out = eng.extract_pages_host(pc, ids)
    k, v = out[0], out[1]
    assert k.shape == (cfg.num_layers, len(ids), Hkv, page, d)
    for li in range(cfg.num_layers):
        np.testing.assert_array_equal(k[li], pats_k[li][ids])
        np.testing.assert_array_equal(v[li], pats_v[li][ids])
    # restore into DIFFERENT pages of a zeroed pool, re-extract: the
    # round trip is bitwise through the sharded layout
    pc2 = eng.make_paged_slot_cache(2, page=PAGE)
    ids2 = np.asarray([2, 6, 4], np.int32)
    pc2 = eng.restore_pages_host(pc2, ids2, k, v)
    out2 = eng.extract_pages_host(pc2, ids2)
    np.testing.assert_array_equal(out2[0], k)
    np.testing.assert_array_equal(out2[1], v)
