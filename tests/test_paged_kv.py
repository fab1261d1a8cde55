"""Paged KV cache + paged flash decode vs the contiguous oracle
(reference analog: mega_triton_kernel paged_kv_cache.py tests), and
the continuous-batching slot paths: free-list page allocation, per-slot
writes/appends, per-slot attention lengths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.kernels.flash_attn import attention_cached_ref
from triton_dist_tpu.kernels.paged_kv import (PageAllocator, PagedKVCache,
                                              flash_decode_paged)


def test_paged_decode_vs_contiguous_oracle():
    B, Hq, Hkv, d, page, T = 2, 4, 2, 128, 16, 64
    rng = np.random.RandomState(0)
    cache = PagedKVCache.create(B, Hkv, T, d, page=page,
                                dtype=jnp.float32)
    kv_len = 37
    ks = rng.randn(B, Hkv, kv_len, d).astype(np.float32) * 0.5
    vs = rng.randn(B, Hkv, kv_len, d).astype(np.float32) * 0.5
    for t in range(kv_len):
        cache = cache.append(jnp.asarray(ks[:, :, t:t + 1]),
                             jnp.asarray(vs[:, :, t:t + 1]))
    q = jnp.asarray(rng.randn(B, 1, Hq, d), jnp.float32) * 0.5
    out = jax.jit(flash_decode_paged)(q, cache.pages_k, cache.pages_v,
                                      cache.table, jnp.int32(kv_len))
    # contiguous oracle on the same values
    kc = jnp.zeros((B, Hkv, T, d), jnp.float32).at[:, :, :kv_len].set(ks)
    vc = jnp.zeros((B, Hkv, T, d), jnp.float32).at[:, :, :kv_len].set(vs)
    ref = attention_cached_ref(q, kc, vc, jnp.int32(kv_len))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_paged_cache_scattered_table():
    """The indirection is real: a permuted page table must read the
    permuted physical pages."""
    B, Hq, Hkv, d, page, T = 1, 2, 2, 128, 8, 32
    rng = np.random.RandomState(1)
    cache = PagedKVCache.create(B, Hkv, T, d, page=page,
                                dtype=jnp.float32)
    kv_len = 17
    ks = rng.randn(B, Hkv, kv_len, d).astype(np.float32) * 0.5
    vs = rng.randn(B, Hkv, kv_len, d).astype(np.float32) * 0.5
    for t in range(kv_len):
        cache = cache.append(jnp.asarray(ks[:, :, t:t + 1]),
                             jnp.asarray(vs[:, :, t:t + 1]))
    # permute physical pages and the table consistently
    NP = cache.pages_k.shape[0]
    perm = np.asarray(rng.permutation(NP), np.int32)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(NP, dtype=np.int32)
    table2 = jnp.asarray(inv)[cache.table]
    pk = np.zeros_like(np.asarray(cache.pages_k))
    pv = np.zeros_like(np.asarray(cache.pages_v))
    pk[inv] = np.asarray(cache.pages_k)
    pv[inv] = np.asarray(cache.pages_v)
    q = jnp.asarray(rng.randn(B, 1, Hq, d), jnp.float32) * 0.5
    out1 = jax.jit(flash_decode_paged)(q, cache.pages_k, cache.pages_v,
                                       cache.table, jnp.int32(kv_len))
    out2 = jax.jit(flash_decode_paged)(q, jnp.asarray(pk),
                                       jnp.asarray(pv), table2,
                                       jnp.int32(kv_len))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               atol=1e-6, rtol=1e-6)


def test_paged_decode_stream_batch_widths():
    """The batched page walk (W slots per grid step) at W=4 (4 slots
    of 2 heads: 8 streams a step) and the W=1 fallback (3 slots,
    coprime to every batch width) must both match the contiguous
    oracle."""
    for B, Hkv in ((4, 2), (3, 1)):       # B=4 -> W=4; B=3 -> W=1
        Hq, d, page, T = 2 * Hkv, 128, 16, 64
        rng = np.random.RandomState(B)
        cache = PagedKVCache.create(B, Hkv, T, d, page=page,
                                    dtype=jnp.float32)
        kv_len = 41
        ks = rng.randn(B, Hkv, kv_len, d).astype(np.float32) * 0.5
        vs = rng.randn(B, Hkv, kv_len, d).astype(np.float32) * 0.5
        for t in range(kv_len):
            cache = cache.append(jnp.asarray(ks[:, :, t:t + 1]),
                                 jnp.asarray(vs[:, :, t:t + 1]))
        q = jnp.asarray(rng.randn(B, 1, Hq, d), jnp.float32) * 0.5
        out = jax.jit(flash_decode_paged)(
            q, cache.pages_k, cache.pages_v, cache.table,
            jnp.int32(kv_len))
        kc = jnp.zeros((B, Hkv, T, d), jnp.float32
                       ).at[:, :, :kv_len].set(ks)
        vc = jnp.zeros((B, Hkv, T, d), jnp.float32
                       ).at[:, :, :kv_len].set(vs)
        ref = attention_cached_ref(q, kc, vc, jnp.int32(kv_len))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg=f"B={B} Hkv={Hkv}")
        # slots per step only regroups streams: bitwise the same
        for bw in (w for w in (1, 2) if B % w == 0):
            again = flash_decode_paged(
                q, cache.pages_k, cache.pages_v, cache.table,
                jnp.int32(kv_len), block_w=bw)
            assert _bits(again) == _bits(out), f"block_w={bw}"


def _fill_contiguous(lens, ks, vs, Hkv, T, d):
    B = len(lens)
    kc = np.zeros((B, Hkv, T, d), np.float32)
    vc = np.zeros((B, Hkv, T, d), np.float32)
    for b, L in enumerate(lens):
        kc[b, :, :L] = ks[b]
        vc[b, :, :L] = vs[b]
    return jnp.asarray(kc), jnp.asarray(vc)


def test_paged_slots_mixed_lengths_share_pool():
    """Continuous-batching slot contract: slots of very different
    lengths draw pages from ONE free-list pool (PageAllocator), write
    their prompts through their own table rows (write_slot), append
    decode rows at per-slot positions (append_slots), and attend with
    per-slot lengths (kv_lens) — all matching the contiguous oracle."""
    B, Hq, Hkv, d, page, T = 3, 4, 2, 128, 16, 64
    rng = np.random.RandomState(0)
    cache = PagedKVCache.create(B, Hkv, T, d, page=page,
                                dtype=jnp.float32)
    alloc = PageAllocator(cache.pages_k.shape[0])
    lens = [37, 9, 50]
    for b, L in enumerate(lens):
        cache = cache.set_slot_table(
            b, alloc.alloc_slot(L + 1, page))
    ks = [rng.randn(Hkv, L, d).astype(np.float32) * 0.5 for L in lens]
    vs = [rng.randn(Hkv, L, d).astype(np.float32) * 0.5 for L in lens]
    for b in range(B):
        cache = cache.write_slot(b, jnp.asarray(ks[b]),
                                 jnp.asarray(vs[b]))
    q = jnp.asarray(rng.randn(B, 1, Hq, d), jnp.float32) * 0.5
    kvl = jnp.asarray(lens, jnp.int32)
    out = jax.jit(lambda q, l: flash_decode_paged(
        q, cache.pages_k, cache.pages_v, cache.table, jnp.max(l),
        kv_lens=l))(q, kvl)
    kc, vc = _fill_contiguous(lens, ks, vs, Hkv, T, d)
    ref = attention_cached_ref(q, kc, vc, kvl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)
    # one decode append per slot, each at its own position
    kn = rng.randn(B, Hkv, 1, d).astype(np.float32) * 0.5
    vn = rng.randn(B, Hkv, 1, d).astype(np.float32) * 0.5
    cache = cache.append_slots(jnp.asarray(kn), jnp.asarray(vn), kvl)
    kc2 = np.asarray(kc).copy()
    vc2 = np.asarray(vc).copy()
    for b, L in enumerate(lens):
        kc2[b, :, L] = kn[b, :, 0]
        vc2[b, :, L] = vn[b, :, 0]
    out2 = jax.jit(lambda q, l: flash_decode_paged(
        q, cache.pages_k, cache.pages_v, cache.table, jnp.max(l),
        kv_lens=l))(q, kvl + 1)
    ref2 = attention_cached_ref(q, jnp.asarray(kc2), jnp.asarray(vc2),
                                kvl + 1)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                               atol=2e-4, rtol=2e-4)


def test_paged_retire_returns_pages_to_free_list():
    """Retiring a slot frees its pages; the next admission REUSES them
    (physically) while live slots' data stays intact — the allocator
    half of the continuous-batching story."""
    B, Hq, Hkv, d, page, T = 2, 2, 1, 128, 8, 48
    rng = np.random.RandomState(1)
    cache = PagedKVCache.create(B, Hkv, T, d, page=page,
                                dtype=jnp.float32)
    alloc = PageAllocator(cache.pages_k.shape[0])
    # slot 0: long-lived; slot 1: short request that retires
    blk0 = alloc.alloc_slot(33, page)
    blk1 = alloc.alloc_slot(10, page)
    cache = cache.set_slot_table(0, blk0).set_slot_table(1, blk1)
    k0 = rng.randn(Hkv, 30, d).astype(np.float32) * 0.5
    v0 = rng.randn(Hkv, 30, d).astype(np.float32) * 0.5
    cache = cache.write_slot(0, jnp.asarray(k0), jnp.asarray(v0))
    cache = cache.write_slot(
        1, jnp.asarray(rng.randn(Hkv, 9, d), jnp.float32),
        jnp.asarray(rng.randn(Hkv, 9, d), jnp.float32))
    # retire slot 1 -> its pages go back; a bigger request reuses them
    freed = blk1.ravel().tolist()
    alloc.free(freed)
    blk2 = alloc.alloc_slot(25, page)
    assert set(blk2.ravel()) & set(freed), \
        "readmission must draw from the freed pages"
    cache = cache.set_slot_table(1, blk2)
    k2 = rng.randn(Hkv, 24, d).astype(np.float32) * 0.5
    v2 = rng.randn(Hkv, 24, d).astype(np.float32) * 0.5
    cache = cache.write_slot(1, jnp.asarray(k2), jnp.asarray(v2))
    q = jnp.asarray(rng.randn(B, 1, Hq, d), jnp.float32) * 0.5
    lens = jnp.asarray([30, 24], jnp.int32)
    out = jax.jit(lambda q, l: flash_decode_paged(
        q, cache.pages_k, cache.pages_v, cache.table, jnp.max(l),
        kv_lens=l))(q, lens)
    kc, vc = _fill_contiguous([30, 24], [k0, k2], [v0, v2], Hkv, T, d)
    ref = attention_cached_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_page_allocator_exhaustion():
    alloc = PageAllocator(4)
    alloc.alloc(3)
    try:
        alloc.alloc(2)
    except ValueError:
        pass
    else:
        raise AssertionError("over-allocation must raise")
    alloc.free([0, 1])
    assert alloc.available == 3


def test_page_allocator_rejects_double_free():
    """A double-freed page would be handed to two slots and silently
    corrupt the pool — the allocator must refuse, both for a page
    already on the free list and for a duplicate within one call."""
    alloc = PageAllocator(4)
    pages = alloc.alloc(2)
    alloc.free([pages[0]])
    for bad in ([pages[0]],                 # already free
                [pages[1], pages[1]]):      # duplicate in one call
        try:
            alloc.free(bad)
        except ValueError as e:
            assert "double free" in str(e)
        else:
            raise AssertionError(f"double free {bad} must raise")
    # the failed calls must not have corrupted the pool
    assert alloc.available + alloc.outstanding == alloc.num_pages
    alloc.free([pages[1]])
    assert alloc.available == 4


def test_page_allocator_rejects_out_of_range_free():
    alloc = PageAllocator(4)
    alloc.alloc(1)
    for bad in (-1, 4, 7):
        try:
            alloc.free([bad])
        except ValueError as e:
            assert "out-of-range" in str(e)
        else:
            raise AssertionError(f"free({bad}) must raise")
    assert alloc.available + alloc.outstanding == alloc.num_pages


def test_page_allocator_in_use_invariant():
    """available + outstanding == num_pages through a mixed
    alloc/free workload (the conservation law a corrupted free list
    breaks first)."""
    rng = np.random.RandomState(0)
    alloc = PageAllocator(32)
    held = []
    for _ in range(200):
        if held and rng.rand() < 0.5:
            k = rng.randint(1, len(held) + 1)
            back, held = held[:k], held[k:]
            alloc.free(back)
        else:
            want = int(rng.randint(1, 5))
            if want <= alloc.available:
                held.extend(alloc.alloc(want))
        assert alloc.available + alloc.outstanding == alloc.num_pages
        assert alloc.outstanding == len(held)
    alloc.free(held)
    assert alloc.available == 32 and alloc.outstanding == 0


def test_page_allocator_error_message_texts():
    """The error strings ARE the operator interface (ISSUE 15
    satellite): exhaustion names want/have, shard misfit names the
    divisibility fix, and the conservation assert names the corrupted
    ledger — pin them so a refactor cannot silently blunt them."""
    alloc = PageAllocator(4)
    alloc.alloc(3)
    try:
        alloc.alloc(2)
    except ValueError as e:
        assert "page pool exhausted" in str(e)
        assert "want 2" in str(e) and "have 1" in str(e)
    else:
        raise AssertionError("must raise")
    try:
        PageAllocator(10, shards=4)
    except ValueError as e:
        assert "cannot split over" in str(e)
        assert "multiple of the sp axis" in str(e)
    else:
        raise AssertionError("must raise")
    # the conservation invariant's own message (simulate corruption)
    alloc2 = PageAllocator(4)
    alloc2._in_use.add(99)
    try:
        alloc2._check()
    except AssertionError as e:
        assert "page pool corrupted" in str(e)
    else:
        raise AssertionError("must raise")


def test_refcounted_pages_error_paths():
    """RefcountedPages (models/prefix_cache.py): refcount underflow
    and retain-of-unreferenced must raise with actionable messages
    BEFORE the pool is touched, and the conservation invariant must
    hold after every refused call."""
    from triton_dist_tpu.models.prefix_cache import RefcountedPages
    pool = RefcountedPages(8)
    g = pool.alloc_page()
    pool.retain(g)
    pool.release(g)
    pool.release(g)            # refcount 2 -> 0: pages freed
    for op, msg in ((pool.release, "refcount underflow"),
                    (pool.retain, "retain of unreferenced page")):
        try:
            op(g)
        except ValueError as e:
            assert msg in str(e), (msg, str(e))
        else:
            raise AssertionError(f"{msg} must raise")
        assert pool.available + pool.outstanding == pool.num_pages
    # double-release of one live page: first release frees, the
    # second underflows without corrupting the ledger
    g2 = pool.alloc_page()
    pool.release(g2)
    try:
        pool.release(g2)
    except ValueError as e:
        assert "refcount underflow" in str(e)
        assert "released a page twice" in str(e)
    else:
        raise AssertionError("double release must raise")
    assert pool.available + pool.outstanding == pool.num_pages
    # the trash page is reserved and never refcounted
    assert pool.refcount(pool.trash) == 0
    assert pool.outstanding >= 1       # trash held out of the free list


def test_paged_decode_int8_scales_vs_dequant_oracle():
    """INT8 pool (kv_cache.PagedSlotCache layout): per-position scale
    planes ride the same table indirection as the payload, and the
    kernel's logit/P-scaling dequant must equal attending the
    explicitly dequantized values — exactly (the dequant is linear, so
    the only difference vs the oracle is float accumulation order)."""
    from triton_dist_tpu.kernels.quant import (dequantize_kv_int8,
                                               quantize_kv_int8)
    B, Hq, Hkv, d, page, T = 2, 4, 2, 128, 16, 64
    rng = np.random.RandomState(3)
    maxp = T // page
    NP = 1 + B * maxp                    # page 0 = trash
    lens = [37, 23]
    ks = rng.randn(B, Hkv, T, d).astype(np.float32) * 0.5
    vs = rng.randn(B, Hkv, T, d).astype(np.float32) * 0.5
    k8, k_s = quantize_kv_int8(jnp.asarray(ks))
    v8, v_s = quantize_kv_int8(jnp.asarray(vs))
    # lay the quantized streams out as pages + scale planes behind a
    # sequential table (slot b, tile t -> page 1 + b*maxp + t)
    pk = np.zeros((NP, Hkv, page, d), np.int8)
    pv = np.zeros((NP, Hkv, page, d), np.int8)
    sk = np.zeros((NP, Hkv, page), np.float32)
    sv = np.zeros((NP, Hkv, page), np.float32)
    table = np.zeros((B, maxp), np.int32)
    for b in range(B):
        for t in range(maxp):
            pid = 1 + b * maxp + t
            table[b, t] = pid
            sl = slice(t * page, (t + 1) * page)
            pk[pid] = np.asarray(k8)[b, :, sl]
            pv[pid] = np.asarray(v8)[b, :, sl]
            sk[pid] = np.asarray(k_s)[b, :, sl]
            sv[pid] = np.asarray(v_s)[b, :, sl]
    q = jnp.asarray(rng.randn(B, 1, Hq, d), jnp.float32) * 0.5
    kvl = jnp.asarray(lens, jnp.int32)
    out = jax.jit(lambda q, l: flash_decode_paged(
        q, jnp.asarray(pk), jnp.asarray(pv), jnp.asarray(table),
        jnp.max(l), kv_lens=l, k_scale=jnp.asarray(sk),
        v_scale=jnp.asarray(sv)))(q, kvl)
    kd = dequantize_kv_int8(k8, k_s)     # [B, Hkv, T, d] f32, exact
    vd = dequantize_kv_int8(v8, v_s)
    ref = attention_cached_ref(q, kd, vd, kvl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------
# the length-bounded multi-page walk: one launch, scattered pages, every
# variant against gather + attention_cached_ref
# ---------------------------------------------------------------------

_PAGE, _D = 16, 128
_BLOCK = 128            # positions of one block of pages (C * page)


def _bits(a):
    return np.ascontiguousarray(np.asarray(a)).tobytes()


def _paged(a, maxp):
    """[B, Hkv, maxp*page(, d)] streams as pages [B, maxp, Hkv,
    page(, d)]: a page holds a slot's positions for all its heads."""
    B, Hkv = a.shape[:2]
    a = a.reshape((B, Hkv, maxp, _PAGE) + a.shape[3:])
    return np.moveaxis(a, 2, 1)


def _scattered_pool(rng, ks, vs, maxp, extra=5):
    """Contiguous [B, Hkv, maxp*page, d] streams laid out as pages in
    a random physical order behind a table (page 0 and `extra` more
    stay unused, holding noise no stream may read)."""
    B, Hkv = ks.shape[:2]
    NP = 1 + B * maxp + extra
    table = (1 + rng.permutation(NP - 1)[:B * maxp]
             ).astype(np.int32).reshape(B, maxp)
    pk = rng.randn(NP, Hkv, _PAGE, _D).astype(ks.dtype)
    pv = rng.randn(NP, Hkv, _PAGE, _D).astype(vs.dtype)
    pk[table] = _paged(ks, maxp)
    pv[table] = _paged(vs, maxp)
    return jnp.asarray(pk), jnp.asarray(pv), jnp.asarray(table)


def _streams(rng, B, Hkv, maxp):
    shape = (B, Hkv, maxp * _PAGE, _D)
    return (rng.randn(*shape).astype(np.float32) * 0.5,
            rng.randn(*shape).astype(np.float32) * 0.5)


def _walk_ragged(rng):
    """Lengths that are multiples of neither the page nor the block,
    on either side of both, the table's whole capacity, and none at
    all: with Hkv = 2 a step is four slots, so the first step has no
    block to walk (it must start no copy that nothing waits for), the
    second is all live, and the third mixes both."""
    Hq, Hkv, maxp = 4, 2, 12
    lens = [0, 0, 0, 0, 1, 15, 16, 17,
            _BLOCK - 1, 0, _BLOCK + 1, maxp * _PAGE]
    B = len(lens)
    live = np.asarray(lens) > 0
    ks, vs = _streams(rng, B, Hkv, maxp)
    pk, pv, table = _scattered_pool(rng, ks, vs, maxp)
    q = jnp.asarray(rng.randn(B, 1, Hq, _D), jnp.float32) * 0.5
    kvl = jnp.asarray(lens, jnp.int32)
    out = np.asarray(jax.jit(lambda q, l: flash_decode_paged(
        q, pk, pv, table, jnp.max(l), kv_lens=l))(q, kvl))
    ref = np.asarray(attention_cached_ref(
        q, jnp.asarray(ks), jnp.asarray(vs), kvl))
    np.testing.assert_allclose(out[live], ref[live], atol=2e-4,
                               rtol=2e-4)
    assert (out[~live] == 0).all()      # nothing attended: zeros


def _walk_per_stream_invariant(rng):
    """A stream's rows depend on its own queries, pages and lengths
    alone: the same two slots under another table width, among other
    neighbours and in another grouping of slots per step come out
    BITWISE the same."""
    Hq, Hkv = 4, 2
    lens = [37, 150]
    ks, vs = _streams(rng, 2, Hkv, 12)
    q = rng.randn(2, 1, Hq, _D).astype(np.float32) * 0.5

    def run(maxp, before, after, block_w):
        """the two slots between `before` and `after` neighbour
        lengths, in a table of maxp columns"""
        nb = len(before) + len(after)
        nk, nv = _streams(rng, nb, Hkv, maxp)
        pad = ((0, 0), (0, 0), (0, (maxp - 12) * _PAGE), (0, 0))
        at = len(before)

        def order(n, own):
            return np.concatenate([n[:at], own, n[at:]])

        kk = order(nk, np.pad(ks, pad))
        vv = order(nv, np.pad(vs, pad))
        qq = order(rng.randn(nb, 1, Hq, _D).astype(np.float32), q)
        pk, pv, table = _scattered_pool(rng, kk, vv, maxp)
        kvl = jnp.asarray(before + lens + after, jnp.int32)
        out = flash_decode_paged(jnp.asarray(qq), pk, pv, table,
                                 jnp.max(kvl), kv_lens=kvl,
                                 block_w=block_w)
        return _bits(out[at:at + 2])

    alone = run(12, [], [], None)                   # B=2: W=2
    assert run(20, [300], [5], None) == alone       # B=4: W=4
    assert run(12, [], [90, 1], 2) == alone         # other neighbours
    assert run(20, [7, 200], [], 1) == alone


def _walk_query_windows(rng):
    """Windows of one row and of several (and an empty one) in one
    launch: spec verify and chunked prefill ride this mask. A parked
    prefill slot that the chunk budget starved arrives as kv length 0
    and window 0 (scheduler._build_mixed_window): a whole step of
    those, then a live step, then a mixed one."""
    Hq, Hkv, maxp, S = 4, 2, 12, 4
    lens = [0, 0, 0, 0, 40, _BLOCK + 2, 17, 9, 150, 0, 0, 60]
    qls = [0, 0, 0, 0, 1, 3, 4, 0, 2, 0, 0, 1]
    B = len(lens)
    live = np.asarray(lens) > 0
    ks, vs = _streams(rng, B, Hkv, maxp)
    pk, pv, table = _scattered_pool(rng, ks, vs, maxp)
    q = jnp.asarray(rng.randn(B, S, Hq, _D), jnp.float32) * 0.5
    kvl, ql = jnp.asarray(lens, jnp.int32), jnp.asarray(qls, jnp.int32)
    out = np.asarray(jax.jit(lambda q, l, w: flash_decode_paged(
        q, pk, pv, table, jnp.max(l), kv_lens=l, q_lens=w))(q, kvl, ql))
    ref = np.asarray(attention_cached_ref(
        q, jnp.asarray(ks), jnp.asarray(vs), kvl, q_lens=ql))
    np.testing.assert_allclose(out[live], ref[live], atol=2e-4,
                               rtol=2e-4)
    assert (out[~live] == 0).all()


def _walk_int8(rng):
    """The int8 pool: a page's scales arrive beside its payload."""
    from triton_dist_tpu.kernels.quant import (dequantize_kv_int8,
                                               quantize_kv_int8)
    Hq, Hkv, maxp = 4, 2, 12
    lens = [1, 17, _BLOCK + 1, maxp * _PAGE]
    B = len(lens)
    ks, vs = _streams(rng, B, Hkv, maxp)
    k8, k_s = quantize_kv_int8(jnp.asarray(ks))
    v8, v_s = quantize_kv_int8(jnp.asarray(vs))
    pk, pv, table = _scattered_pool(rng, np.asarray(k8), np.asarray(v8),
                                    maxp)
    sk = rng.rand(pk.shape[0], Hkv, _PAGE).astype(np.float32)
    sv = rng.rand(pk.shape[0], Hkv, _PAGE).astype(np.float32)
    sk[np.asarray(table)] = _paged(np.asarray(k_s), maxp)
    sv[np.asarray(table)] = _paged(np.asarray(v_s), maxp)
    q = jnp.asarray(rng.randn(B, 1, Hq, _D), jnp.float32) * 0.5
    kvl = jnp.asarray(lens, jnp.int32)
    out = jax.jit(lambda q, l: flash_decode_paged(
        q, pk, pv, table, jnp.max(l), kv_lens=l,
        k_scale=jnp.asarray(sk), v_scale=jnp.asarray(sv)))(q, kvl)
    ref = attention_cached_ref(q, dequantize_kv_int8(k8, k_s),
                               dequantize_kv_int8(v8, v_s), kvl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def _walk_partial(rng):
    """The SP partial: two chips own alternate tiles; the one-tile
    stream is all chip 1's, so chip 0 returns the combine's neutral
    element for it, and the partials combine to the full softmax. At
    one slot a step the empty slot is a step with no block."""
    from triton_dist_tpu.kernels.flash_attn import lse_combine
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged_partial
    Hq, Hkv, maxp, S = 4, 2, 12, 2
    lens, qls = [9, 0, _BLOCK + 30, 60], [1, 0, 2, 1]
    B = len(lens)
    live = np.asarray(lens) > 0
    ks, vs = _streams(rng, B, Hkv, maxp)
    pk, pv, table = _scattered_pool(rng, ks, vs, maxp)
    q = jnp.asarray(rng.randn(B, S, Hq, _D), jnp.float32) * 0.5
    kvl, ql = jnp.asarray(lens, jnp.int32), jnp.asarray(qls, jnp.int32)
    tile = np.arange(maxp)[None].repeat(B, 0)
    parts = []
    for chip in (0, 1):
        owned = (tile % 2 != chip).astype(np.int32)   # tile 0 -> chip 1
        # a tile this chip does not own may name any page: never read
        local = np.where(owned != 0, np.asarray(table), 0)
        parts.append(flash_decode_paged_partial(
            q, pk, pv, jnp.asarray(local), kv_lens=kvl, q_lens=ql,
            tile_owned=jnp.asarray(owned), block_w=1))
    acc0, m0, l0 = (np.asarray(a) for a in parts[0])
    assert (acc0[:2] == 0).all() and (l0[:2] == 0).all()
    assert (m0[:2] == np.float32(-1e30)).all()
    out = np.asarray(lse_combine(*(jnp.stack(a) for a in zip(*parts))))
    ref = np.asarray(attention_cached_ref(
        q, jnp.asarray(ks), jnp.asarray(vs), kvl, q_lens=ql))
    np.testing.assert_allclose(out[live], ref[live], atol=2e-4,
                               rtol=2e-4)


@pytest.mark.parametrize("case", [
    _walk_ragged, _walk_per_stream_invariant, _walk_query_windows,
    _walk_int8, _walk_partial], ids=lambda f: f.__name__[6:])
def test_paged_walk(case):
    case(np.random.RandomState(30))


# ---------------------------------------------------------------------
# the cells' head counts: a page holds 8 (one chip of Qwen3-1.7B), 2 (a
# TP=4 chip) or 10 (Phi-4's paired heads) heads of its slot, and the
# walk picks W = 1 / 4 / 1 slots a step from them
# ---------------------------------------------------------------------

def _cell_walk(rng, Hkv, rep, block_w=None, lens=None):
    """One launch of 12 slots at `Hkv` heads a page: empty slots as a
    whole grid step at every W the walk may pick (slots 0..3) and
    beside live ones. Returns (out, ref, live)."""
    maxp = 10
    if lens is None:
        lens = [0, 0, 0, 0, 1, 0, _BLOCK + 1, 17,
                maxp * _PAGE, 0, 33, _BLOCK]
    B = len(lens)
    ks, vs = _streams(rng, B, Hkv, maxp)
    pk, pv, table = _scattered_pool(rng, ks, vs, maxp)
    q = jnp.asarray(rng.randn(B, 1, rep * Hkv, _D), jnp.float32) * 0.5
    kvl = jnp.asarray(lens, jnp.int32)
    out = np.asarray(jax.jit(lambda q, l: flash_decode_paged(
        q, pk, pv, table, jnp.max(l), kv_lens=l, block_w=block_w))(q, kvl))
    ref = np.asarray(attention_cached_ref(
        q, jnp.asarray(ks), jnp.asarray(vs), kvl))
    return out, ref, np.asarray(lens) > 0


@pytest.mark.parametrize("Hkv,rep,W", [(8, 2, 1), (2, 8, 4), (10, 4, 1)])
def test_paged_walk_at_the_cells_heads(Hkv, rep, W):
    from triton_dist_tpu.kernels.paged_kv import _slot_block
    assert _slot_block("flash_decode_paged", None, 12, Hkv, None) == W
    out, ref, live = _cell_walk(np.random.RandomState(35), Hkv, rep)
    np.testing.assert_allclose(out[live], ref[live], atol=2e-4,
                               rtol=2e-4)
    assert (out[~live] == 0).all()      # nothing attended: zeros


@pytest.mark.parametrize("Hkv,rep", [(8, 2), (2, 8), (10, 4)])
def test_paged_walk_bitwise_whatever_w_and_neighbours(Hkv, rep):
    """A stream's output is bitwise independent of W and of which
    slots share its step: the same launch at every W that divides it,
    and the live slots alone in another order of neighbours."""
    base, _, live = _cell_walk(np.random.RandomState(36), Hkv, rep)
    for w in (2, 12):
        again, _, _ = _cell_walk(np.random.RandomState(36), Hkv, rep,
                                 block_w=w)
        assert _bits(again) == _bits(base), f"block_w={w}"


def test_allocator_hands_out_one_id_a_tile_and_conserves_them():
    """A slot of n positions takes ceil(n / page) ids, whatever its
    head count (a page holds them all), no id twice, and every id comes
    back: available + outstanding == num_pages throughout."""
    alloc = PageAllocator(64)
    held = {}
    for slot, n in enumerate((1, 15, 16, 17, 128, 129, 300)):
        row = alloc.alloc_slot(n, 16)
        assert row.shape == (-(-n // 16),) and row.dtype == np.int32
        held[slot] = row
        assert alloc.available + alloc.outstanding == alloc.num_pages
    ids = np.concatenate(list(held.values()))
    assert len(set(ids.tolist())) == len(ids) == alloc.outstanding
    for row in held.values():
        alloc.free(row)
    assert alloc.available == 64 and alloc.outstanding == 0
