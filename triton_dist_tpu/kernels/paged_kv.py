"""Paged KV cache + flash decode over a page table.

TPU-native re-design of the reference megakernel's paged KV cache
(`python/triton_dist/mega_triton_kernel/models/paged_kv_cache.py:28` —
logical KV blocks indirected through a page table so sequences share a
physical pool and grow without reallocation).

Design: a physical page is [H, page, d] — `page` contiguous KV
positions of ONE slot (batch row) for ALL of the kv heads the pool
holds of it — so a layer's pool plane is [NP, H, page, d]; an int32
page table [B, max_pages], one row a slot, maps logical tiles to
physical pages. Under TP the plane is sharded on the head axis and a
chip's shard [NP, H/tp, page, d] is the pool this kernel is handed: H
is whatever the chip holds (8 on one chip of Qwen3-1.7B, 2 at TP=4, 10
paired heads for Phi-4).

The walk is LENGTH-BOUNDED and MULTI-PAGE. The grid runs over blocks
of W slots and nothing else; the pools stay in HBM (`pl.ANY`). Inside
a step a loop runs over the blocks of C pages (one softmax tile of
_KV_TILE positions) that the step's longest slot really has — a
slot of 18 pages does the work of 18, whatever the table's width (a
step whose slots are all empty walks one masked block) — and for
every block the kernel reads each slot's page ids from the
scalar-prefetched table and fetches its OWN pages of that block, K and
V, with ONE `make_async_copy` a page and plane for all H heads
(H*page*d*2 bytes: 32 KiB at 8 heads of page 16) into a
[W, H, C*page, d] VMEM buffer (the TPU analog of the reference's
in-kernel `page_table[block_idx]` load). The buffer has two halves:
block i+1's copies are started before block i's are waited for, so
they fly under its compute. The QK and PV dots then run on
[rows, d] x [C*page, d] for all W*H (slot, head) streams at once, with
one online-softmax update per block, the accumulators carried in
registers through the loop.

A stream's result depends on its own queries, pages and lengths alone:
not on the table's width, not on which slots share its step, not on W
(the block is fixed). The scheduler's bitwise differentials (sync vs
overlap, preempt/resume, prefix hit vs miss) lean on that.

Why the heads share a page: the walk is bound by how many copies the
scalar core issues, not by what they carry (~20 ns a 4 KiB copy against
the 5 ns its bytes take; PERF.md, PR 30 and PR 35). A page of one
(slot, head) made a call issue H times the copies for the same bytes.
W = the largest of (8, 4, 2, 1) dividing B that keeps W*H <= 8 streams
a step, unless the tune store says otherwise. C is not a tunable: on
the chip 16 pages a block was slower than 8 at both served shapes
(PERF.md, PR 30).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.runtime import interpret_mode


# Positions per block of pages, and so per online-softmax update.
# FIXED, not a tunable: it regroups the float summation (like
# flash_decode's block_t), and a stream's output must not depend on how
# the launch was scheduled. A page larger than this is a block alone.
_KV_TILE = 128


def _block_pages(page: int) -> int:
    """C: pages of one block."""
    return max(1, _KV_TILE // page)


def _paged_kernel(scale: float, rep: int, page: int, W: int, maxp: int,
                  quant: bool, partial: bool, v_cols: Optional[int],
                  fused: bool, selected: bool, s_ref, *refs):
    """Grid (B // W,): one step walks W slots — W*H (slot, kv-head)
    streams, H the heads of the pool it is handed — through THEIR OWN
    pages, C pages at a time (module docstring). refs = q
    [W*H, rows, d], lens [W*H, 1, 2] of (kv length, query length), the
    K and V pools [NP, H, page, d] in HBM, [the K and V scale planes
    [NP, H, page] in HBM], [own [W*H, 1, L] per-position ownership], o,
    [m, l], then scratch: K and V blocks [2, W, H, C*page, d], [scale
    blocks [2, W, H, 1, C*page]], one DMA semaphore per buffer half.
    s_ref holds the B kv lengths, then the page table row by row.

    A slot's streams mask to its OWN lengths, so slots at different
    sequence positions share one launch. q_len == 1 is plain decode; q_len > 1
    is a prefill-shaped window — the speculative-verify draft
    (models/spec_decode.py) or a chunked-prefill prompt chunk
    (models/scheduler.py step_mixed): row s of the stream's q_len query
    rows sits at kv_len - q_len + s and attends causally within the
    window; padded rows clamp to the last valid row (outputs discarded
    by the caller).

    The step runs ceil(longest of its W slots / (C*page)) blocks, and
    every slot fetches C pages in each, one copy a page and plane for
    all of its heads: past its last page a slot fetches that page
    again, so the buffer always holds pages of its own and the copies
    need no branch. A block (or a column) past a slot's end masks to a
    bitwise no-op of its streams' accumulators; a step whose slots are
    all empty still walks one such block, so every copy that is started
    is waited for.

    quant=True (int8 pool — kv_cache.PagedSlotCache scale planes): a
    page's [page] f32 scales are fetched beside its payload, through
    the same table entry. Dequant mirrors the contiguous kernel
    (_flash_decode_kernel) exactly: K's per-position scale multiplies
    the logits column-wise, V's folds into p before the PV contraction
    — the int8->bf16 convert happens in VMEM, so KV HBM traffic is
    halved.

    partial=True (the SEQUENCE-PARALLEL serving walk — the split-KV
    partial of the inter-chip LSE combine, kernels/sp_flash_decode.py):
    a table entry below zero is a tile another chip holds. It is not
    fetched (its rows of the buffer keep an earlier block's pages, or
    the first step's zeros), its positions are masked by the
    per-position ownership operand, and the epilogue emits the
    UNNORMALIZED accumulator plus the (m, l) softmax stats instead of
    the normalized output. Tiles a chip does not own are a bitwise
    no-op of its accumulator, so the n per-chip partials LSE-combine to
    exactly the full softmax.

    v_cols (a LATENT pool — kv_cache.LatentSlotCache, layers/mla_attn.py:
    one plane a layer, no V pool): a page row is [c_kv | k_pe | pad], the
    keys are the whole row and the values its first v_cols columns, so
    K and V are read from the SAME block of the buffer: one copy a page,
    QK over d, PV over v_cols, the output v_cols wide. With one latent
    head and rep = every query head, a block is an MXU-shaped
    [rep, d] x [d, C*page] and [rep, C*page] x [C*page, v_cols].

    fused (kv_cache.IndexedSlotCache: K and V in ONE plane, a page
    [2H, page, d] holding the slot's H key heads and then its H value
    heads): one copy a page fetches both, and a stream's keys and
    values are two head rows of the same block of the buffer.

    selected (learned sparse attention, layers/sparse_attn.py): `sel`
    [W*H, 1, L] int32 marks, per position, what the slot's indexer
    chose; a position it did not choose is masked like one past the
    slot's end. The walk still fetches every page of the context."""
    q_ref, lens_ref = refs[:2]
    n_payload = 1 if (v_cols is not None or fused) else 2
    pools = list(refs[2:2 + n_payload])
    rest = refs[2 + n_payload:]
    if quant:
        pools += rest[:2]
        rest = rest[2:]
    if partial:
        own_ref, o_ref, m_ref, l_ref = rest[:4]
        rest = rest[4:]
    else:
        if selected:
            sel_ref, rest = rest[0], rest[1:]
        o_ref = rest[0]
        rest = rest[1:]
    *bufs, sem = rest
    kbuf = bufs[0]
    scale_bufs = bufs[n_payload:]
    x = pl.program_id(0)
    B = pl.num_programs(0) * W      # slots of the call
    WH, rows, d = q_ref.shape
    C = _block_pages(page)
    CP = C * page

    if partial:
        # a page that is not fetched leaves its rows of the buffer as
        # they were: an earlier block's, or these zeros, never a NaN
        # that 0 * v would keep
        @pl.when(x == 0)
        def _zero():
            for buf in bufs:
                buf[...] = jnp.zeros(buf.shape, buf.dtype)

    n_pages = [(s_ref[x * W + j] + (page - 1)) // page for j in range(W)]
    # at least one block, so that the copies started below are waited
    # for: a step of empty streams (kv length 0: a parked or
    # budget-starved slot) walks one block, all of it masked. A branch
    # round the first block's copies would do, and reads 29 s more
    # set-up per served program on the chip (PERF.md, PR 30)
    nblk = functools.reduce(
        jnp.maximum, [(n + (C - 1)) // C for n in n_pages] + [1])
    # each slot's last page and its row of the table, in s_ref
    last = [jnp.maximum(n - 1, 0) for n in n_pages]
    row0 = [B + (x * W + j) * maxp for j in range(W)]

    def for_block(i, half, act):
        """act(copy) for the C pages of block i of each of the step's
        slots: a page is [H, page, d], all of the slot's heads in one
        copy. Branch-free on the served path: past its last page a
        slot fetches that page again (the mask drops it), which the
        chip takes better than a loop over the pages it really has."""
        for j in range(W):
            for c in range(C):
                pid = s_ref[row0[j] + jnp.minimum(i * C + c, last[j])]
                at = pl.ds(c * page, page)

                def copies():
                    for n, (pool, buf) in enumerate(zip(pools, bufs)):
                        dst = (buf.at[half, j, :, at] if n < n_payload
                               else buf.at[half, j, :, 0, at])
                        act(pltpu.make_async_copy(pool.at[pid], dst,
                                                  sem.at[half]))
                if partial:
                    pl.when(pid >= 0)(copies)
                else:
                    copies()

    def start(i, half):
        for_block(i, half, lambda cp: cp.start())

    def wait(i, half):
        if partial:
            # as many waits as copies were started
            for_block(i, half, lambda cp: cp.wait())
        else:
            # every copy of the block signals one semaphore by its
            # bytes: one wait per plane, for the whole half's
            for buf in bufs:
                pltpu.make_async_copy(buf.at[half], buf.at[half],
                                      sem.at[half]).wait()

    start(0, 0)

    q = q_ref[...]                                   # [W*H, rows, d]
    lens = lens_ref[...]                             # [W*H, 1, 2]
    kvl, ql = lens[:, :, 0:1], lens[:, :, 1:2]
    # row s's causal frontier within its stream's query window;
    # q_len == 1 degenerates to col < kv_len
    row = jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1) // rep
    frontier = kvl - ql + jnp.minimum(row, ql - 1)   # [W*H, rows, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, CP), 2)

    def block(i, carry):
        half = jax.lax.rem(i, 2)

        @pl.when(i + 1 < nblk)
        def _ahead():
            start(i + 1, 1 - half)

        wait(i, half)
        m, l, acc = carry
        pos = i * CP
        mask = (pos + lane) <= frontier              # [W*H, rows, CP]
        if partial:
            mask = mask & (own_ref[
                :, :, pl.ds(pl.multiple_of(pos, CP), CP)] != 0)
        if selected:
            mask = mask & (sel_ref[
                :, :, pl.ds(pl.multiple_of(pos, CP), CP)] != 0)
        # a slot's heads lie side by side in the buffer: its streams
        H = WH // W
        k = (kbuf[half, :, :H] if fused else kbuf[half]).reshape(
            WH, CP, d)
        if quant:
            k = k.astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        if quant:
            # K's per-position scale multiplies the logits column-wise
            # (exact: (q . k_int8) * s == q . k_deq)
            s = s * scale_bufs[0][half].reshape(WH, 1, CP)
        m_new = jnp.maximum(
            m, jnp.max(jnp.where(mask, s, -1e30), -1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l = l * alpha + jnp.sum(p, -1, keepdims=True)
        if v_cols is not None:
            v = k[:, :, :v_cols]
        elif fused:
            v = kbuf[half, :, H:].reshape(WH, CP, d)
        else:
            v = bufs[1][half].reshape(WH, CP, d)
        if quant:
            # V's scale folds into p (diag(sv) V == V rows scaled); the
            # convert to the compute dtype happens in VMEM
            v = v.astype(q.dtype)
            p = p * scale_bufs[1][half].reshape(WH, 1, CP)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, nblk, block,
        (jnp.full((WH, rows, 1), -1e30, jnp.float32),
         jnp.zeros((WH, rows, 1), jnp.float32),
         jnp.zeros((WH, rows, v_cols or d), jnp.float32)))
    if partial:
        # the SP partial contract: unnormalized accumulator + softmax
        # stats, combined across chips by lse_combine
        # (kernels/flash_attn.py) / sp_combine_partials
        o_ref[...] = acc
        m_ref[...] = m
        l_ref[...] = l
    else:
        o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_decode_paged(q, pages_k, pages_v, page_table, kv_len, *,
                       scale: Optional[float] = None, kv_lens=None,
                       q_lens=None, k_scale=None, v_scale=None,
                       block_w: Optional[int] = None,
                       v_cols: Optional[int] = None,
                       fused: bool = False, sel=None):
    """Cached GQA decode attention through a page table.

    pages_v=None with fused: K and V share ONE plane [NP, 2 Hkv, page,
    d], a page's first Hkv head rows the keys and its last Hkv the
    values (kv_cache.IndexedSlotCache). sel [B, L] (L >= the table's
    positions, a multiple of the softmax tile): nonzero where slot b
    attends position s; needs kv_lens.

    pages_v=None with v_cols: a LATENT pool (one plane; the values are
    the first v_cols columns of the key rows): returns [B, S, Hq,
    v_cols].

    q: [B, S, Hq, d] (S == 1 unless q_lens is given); pages_k/v:
    [NP, Hkv, page, d] (Hkv = the kv heads this pool holds of every
    slot); page_table: [B, max_pages] int32 (physical page of each
    logical tile of a slot; entries beyond ceil(kv_len/page) may hold
    anything, but column 0, which an empty slot still fetches, names a
    page of the pool); kv_len: traced scalar — valid positions
    INCLUDING the current query. Returns [B, S, Hq, d].

    k_scale/v_scale: per-position dequant scale planes [NP, Hkv, page] f32
    for an INT8 page pool (pages_k/v int8 —
    kv_cache.PagedSlotCache.scales_k/v): a page's scales ride behind
    the same table indirection as its payload, and dequant folds into
    the logits / the P matrix inside the kernel exactly as the
    contiguous int8 path does (kernels/flash_attn.py) — halving the
    decode step's paged-KV HBM traffic without changing a single
    emitted token (the quantizer is shared: quantize_kv_int8).

    kv_lens: optional per-BATCH-ROW lengths [B] int32 (continuous
    batching: each slot is a different request at a different sequence
    position). Row b attends exactly kv_lens[b] positions of its own
    streams (kv_len is then unused). The walk of a grid step is as
    long as the longest of ITS streams and no longer, and never reads
    a table column past a stream's last page.

    q_lens: optional per-BATCH-ROW query-window lengths [B] int32
    (requires kv_lens): slot b's first q_lens[b] of the S query rows
    are a window at positions kv_lens[b] - q_lens[b] ..
    kv_lens[b] - 1, attending prior positions plus causally within
    the window — the speculative-verify draft (models/spec_decode.py)
    and the chunked-prefill prompt chunk (models/scheduler.py
    step_mixed) both ride this mask; padded rows (and whole q_len == 0
    budget-starved rows) are discarded by the caller.
    """
    return _flash_decode_paged_call(
        q, pages_k, pages_v, page_table, kv_len, scale=scale,
        kv_lens=kv_lens, q_lens=q_lens, k_scale=k_scale,
        v_scale=v_scale, tile_owned=None, block_w=block_w,
        v_cols=v_cols, fused=fused, sel=sel)


def flash_decode_paged_partial(q, pages_k, pages_v, page_table, *,
                               kv_lens, tile_owned,
                               scale: Optional[float] = None,
                               q_lens=None, k_scale=None, v_scale=None,
                               block_w: Optional[int] = None):
    """Split-KV PARTIAL of the paged walk — the sequence-parallel
    serving kernel (ROADMAP long-context item; the per-rank split-KV
    partial of the reference's inter-rank combine, flash_decode.py:130
    -> :482, over a PAGED pool instead of a contiguous shard).

    Same per-stream contract as flash_decode_paged(kv_lens=..,
    q_lens=..), with two changes for the sp-sharded pool
    (kv_cache.PagedSlotCache SP SHARDING):

    - pages_k/v are THIS CHIP'S local pool shard and page_table holds
      LOCAL page ids (a non-owned tile's entry may be anything: it is
      never read);
    - tile_owned [B, maxp] int32 marks which logical tiles this
      chip owns: a non-owned tile is not fetched at all and is a
      bitwise no-op of the stream's accumulator, so the returned (acc [B, S, Hq, d] f32 unnormalized,
      m [B, S, Hq], l [B, S, Hq]) LSE-combine across chips
      (sp_flash_decode.sp_combine_partials / flash_attn.lse_combine)
      to exactly the full-pool softmax. A stream none of whose tiles
      are owned returns (0, -1e30, 0) — the combine's neutral element.
    """
    assert kv_lens is not None
    return _flash_decode_paged_call(
        q, pages_k, pages_v, page_table, None, scale=scale,
        kv_lens=kv_lens, q_lens=q_lens, k_scale=k_scale,
        v_scale=v_scale, tile_owned=tile_owned, block_w=block_w,
        tune_name="flash_decode_paged_partial")


def _slot_block(tune_name, dims, B, H, block_w, streams: int = 8):
    """W: slots per grid step. Resolution: explicit block_w >
    contextual profile > tune cache (tools/sweep) > the largest W
    dividing B with W*H <= `streams` streams a step (1 slot on a chip
    that holds 8 or more kv heads of every slot, 4 where it holds 2; a
    latent walk, whose one stream a slot carries every query head,
    asks for 2; a fused K/V plane, one copy a page for both, for 16:
    4 slots of 4 heads read 1.85 ms a call where 2 read 2.02 and 1
    2.62, PERF.md PR 39). W only
    regroups slots across grid steps and never changes a stream's
    result. Strictness splits by provenance: an indivisible W that was
    pinned explicitly or installed in the contextual profile is a loud
    error (the sweep pruner probes configs through the profile and
    relies on this trace failing), while a DISK-cache winner is a hint
    from whatever shape it was swept at (bucket fallback, another head
    count) and re-clamps to the default instead of failing at serving
    time — the tuned_choice contract: perf may degrade, never
    correctness. The two-step lookup below mirrors
    sweep.resolve_config's precedence, split so provenance is known."""
    from triton_dist_tpu.tools.tune import contextual_choice
    cfg = contextual_choice(tune_name)
    strict = cfg is not None or block_w is not None
    if cfg is None:
        from triton_dist_tpu.tools.sweep import tuned_choice
        cfg = tuned_choice(tune_name, dims) or {}
    W = cfg.get("block_w") if block_w is None else block_w
    if W is not None and B % W:
        if strict:
            raise ValueError(
                f"{tune_name}: block_w={W} does not divide the "
                f"slot count B={B}")
        W = None
    if W is None:
        W = next(w for w in (8, 4, 2, 1)
                 if B % w == 0 and (w * H <= streams or w == 1))
    return int(W)


def _flash_decode_paged_call(q, pages_k, pages_v, page_table, kv_len, *,
                             scale, kv_lens, q_lens, k_scale, v_scale,
                             tile_owned, block_w=None, v_cols=None,
                             fused=False, sel=None,
                             tune_name="flash_decode_paged"):
    B, S, Hq, d = q.shape
    partial = tile_owned is not None
    latent = v_cols is not None
    one_plane = latent or fused
    assert not (latent and fused)
    assert one_plane == (pages_v is None), \
        "one plane holds keys and values: pages_v=None with v_cols " \
        "(a latent pool) or fused (K and V heads in one page)"
    assert not one_plane or (k_scale is None and not partial), \
        "the one-plane walks serve the bf16 pool on one chip"
    assert sel is None or (kv_lens is not None and not partial), \
        "a selection rides on per-slot kv_lens"
    if latent:
        tune_name = "flash_decode_paged_latent"
    if q_lens is not None:
        assert kv_lens is not None, "q_lens rides on per-slot kv_lens"
    elif not partial:
        assert S == 1, "paged walk without q_lens is decode (S == 1)"
    assert not partial or kv_lens is not None, \
        "flash_decode_paged_partial requires per-slot kv_lens"
    quant = k_scale is not None
    assert (k_scale is None) == (v_scale is None), \
        "int8 pool carries BOTH scale planes"
    NP, Hp, page, _ = pages_k.shape
    H = Hp // 2 if fused else Hp    # (slot, kv-head) streams a slot
    assert page_table.shape[0] == B, "the table has one row a slot"
    maxp = page_table.shape[1]
    X = B * H
    rep = Hq // H
    if scale is None:
        scale = d ** -0.5
    rows = S * rep
    qx = (q.reshape(B, S, H, rep, d)
           .transpose(0, 2, 1, 3, 4)
           .reshape(X, rows, d))
    W = _slot_block(tune_name, (X, B * Hq, NP * page), B, H, block_w,
                    streams=2 if latent else 16 if fused else 8)
    WH = W * H
    CP = _block_pages(page) * page
    # every slot carries its own (kv length, query length): a launch
    # without kv_lens is all slots at kv_len, one query row each
    lens_b = (jnp.asarray(kv_lens, jnp.int32) if kv_lens is not None
              else jnp.full((B,), kv_len, jnp.int32))
    qlens_b = (jnp.ones_like(lens_b) if q_lens is None
               else jnp.asarray(q_lens, jnp.int32))
    table = page_table.astype(jnp.int32)
    if partial:
        # a tile another chip holds is a table entry below zero: the
        # walk skips its copy outright
        owned = jnp.asarray(tile_owned, jnp.int32) != 0
        table = jnp.where(owned, table, -1)
    # scalars: [lens..., table...]. The lens appear TWICE on purpose:
    # here, one a slot, for the walk's bounds, and beside the query
    # lengths as a [X, 1, 2] operand for the in-kernel mask (a vector
    # per stream, which scalars cannot be broadcast into cheaply).
    scalars = jnp.concatenate([lens_b, table.reshape(-1)])

    def per_step(*tail):
        return pl.BlockSpec((WH,) + tail, lambda x, s_ref: (x, 0, 0))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    payload = [pages_k] if one_plane else [pages_k, pages_v]
    dv = v_cols if latent else d
    in_specs = [per_step(rows, d), per_step(1, 2)] + [hbm] * len(payload)
    args = [qx, jnp.repeat(jnp.stack([lens_b, qlens_b], 1), H,
                           axis=0).reshape(X, 1, 2)] + payload
    scratch = [pltpu.VMEM((2, W, Hp, CP, d), p.dtype) for p in payload]
    if quant:
        in_specs += [hbm, hbm]
        args += [k_scale, v_scale]
        scratch += [pltpu.VMEM((2, W, H, 1, CP), k_scale.dtype),
                    pltpu.VMEM((2, W, H, 1, CP), v_scale.dtype)]
    if partial:
        # ownership per POSITION, out to a whole number of blocks, the
        # slot's row once for each of its streams
        L = -(-maxp * page // CP) * CP
        own = jnp.repeat(owned.astype(jnp.int32), page, axis=1)
        own = jnp.pad(own, ((0, 0), (0, L - maxp * page)))
        in_specs.append(per_step(1, L))
        args.append(jnp.repeat(own, H, axis=0).reshape(X, 1, L))
        out_specs = (per_step(rows, dv), per_step(rows, 1),
                     per_step(rows, 1))
        out_shape = (jax.ShapeDtypeStruct((X, rows, d), jnp.float32),
                     jax.ShapeDtypeStruct((X, rows, 1), jnp.float32),
                     jax.ShapeDtypeStruct((X, rows, 1), jnp.float32))
    else:
        if sel is not None:
            # the slot's row once for each of its streams, like `own`
            L = sel.shape[1]
            assert L % CP == 0 and L >= maxp * page, (L, CP, maxp, page)
            in_specs.append(per_step(1, L))
            args.append(jnp.repeat(jnp.asarray(sel, jnp.int32), H,
                                   axis=0).reshape(X, 1, L))
        out_specs = per_step(rows, dv)
        out_shape = jax.ShapeDtypeStruct((X, rows, dv), q.dtype)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, float(scale), rep, page, W,
                          maxp, quant, partial, v_cols, fused,
                          sel is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // W,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=out_shape,
        # the steps are independent, but for the partial walk, whose
        # first step zeroes the block buffers for every later one
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if partial
                                 else "parallel",)),
        interpret=interpret_mode(),
    )(scalars, *args)

    def unfold(a):
        tail = a.shape[2:]
        return (a.reshape((B, H, S, rep) + tail)
                 .transpose(0, 2, 1, 3, *range(4, 4 + len(tail)))
                 .reshape((B, S, Hq) + tail))

    if partial:
        acc, m, l = out
        return unfold(acc), unfold(m[..., 0]), unfold(l[..., 0])
    return unfold(out)


def set_page_rows(pool, pidx, r, u):
    """Write rows into a paged pool plane [NP, h, page(, d)]
    (kv_cache.PagedSlotCache): u [..., h(, d)] lands at in-page row
    r [...] of every head of page pidx [...], whatever else the page
    holds left alone. An id past the pool (a padded window row, a tile
    another chip owns) drops.

    The scatter runs over the plane viewed as [NP*h, page(, d)], its
    (page, head) pairs in a row: two LEADING index dims update in
    place — index for index the append of a pool whose pages were one
    (slot, head)'s, and in the served decode scan it reads what that
    one read (1.07 ms a step of 56 appends against 1.14; PERF.md, PR
    35). A single index over [NP*h*page(, d)] read 1.43 there, and a
    head axis between the two indexed ones 90.7 us an append against
    52.0 at Phi-4's shape (models/phi4flash.py's rings met the
    same). The decode step of the two-plane pool on a kernel backend
    writes through append_page_rows instead (PR 44)."""
    NP, h = pool.shape[:2]
    rows = pidx[..., None] * h + jnp.arange(h)           # [..., h]
    flat = pool.reshape((NP * h,) + pool.shape[2:])
    return flat.at[rows, r[..., None]].set(
        u.astype(pool.dtype)).reshape(pool.shape)


def _append_kernel(B: int, pidx_ref, r_ref, k_ref, v_ref, pk_in, pv_in,
                   pk_ref, pv_ref, kbuf, vbuf, sem):
    """One launch, every slot: read each slot's page of both planes
    into VMEM, overlay its row, write the pages back. refs: the new
    rows [B, H, 1, d] in VMEM, the planes [NP, H, page, d] in HBM (the
    outputs alias them), the page buffers [B, H, page, d], one DMA
    semaphore a plane."""
    del pk_in, pv_in     # the same buffers as pk_ref, pv_ref (aliased)
    planes = ((pk_ref, kbuf, k_ref), (pv_ref, vbuf, v_ref))

    def copy_all(read: bool):
        def body(b, c):
            for n, (pool, buf, _) in enumerate(planes):
                src, dst = pool.at[pidx_ref[b]], buf.at[b]
                if not read:
                    src, dst = dst, src
                pltpu.make_async_copy(src, dst, sem.at[n]).start()
            return c

        jax.lax.fori_loop(0, B, body, 0)
        # every copy of a plane signals its semaphore by its bytes: one
        # wait a plane, for all B pages
        for n, (_, buf, _) in enumerate(planes):
            pltpu.make_async_copy(buf, buf, sem.at[n]).wait()

    copy_all(read=True)
    row = jax.lax.broadcasted_iota(jnp.int32, kbuf.shape[1:], 1)

    def overlay(b, c):
        for _, buf, new in planes:
            buf[b] = jnp.where(row == r_ref[b], new[b], buf[b])
        return c

    jax.lax.fori_loop(0, B, overlay, 0)
    copy_all(read=False)


def append_page_rows(pages_k, pages_v, pidx, r, k, v):
    """The decode append of a two-plane pool [NP, H, page, d], in place:
    k, v [B, H, d] land at in-page row r [B] of every head of page
    pidx [B] — set_page_rows on both planes, as ONE aliased Pallas
    launch in place of two XLA scatters of B*H rows (which run their
    rows one after another, ~75 ns a row on a v5e; PERF.md, PR 44).

    Each slot's whole page ([H, page, d]: 32 KiB at 8 heads of page 16)
    comes into VMEM, takes its row there and goes back: a page of 16
    bf16 rows is one native tile a head, so every copy is tile-aligned,
    where a single row at a traced sublane offset is not (kv_update).
    Every read is started before any is waited for, and every write
    after the last overlay. Every pidx must be a page of the pool (a
    decode table's entries are); a page two slots share (retired slots
    on the trash page) holds the last writer's row alone."""
    B, H, d = k.shape
    full = pl.BlockSpec((B, H, 1, d), lambda i, p, r: (0, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_append_kernel, B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[full, full, hbm, hbm],
            out_specs=(hbm, hbm),
            scratch_shapes=[pltpu.VMEM((B,) + pages_k.shape[1:],
                                       pages_k.dtype),
                            pltpu.VMEM((B,) + pages_v.shape[1:],
                                       pages_v.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        # the planes stay in HBM (and, aliased, the inputs with them):
        # left to XLA, a TP=4 chip's 33.6 MB plane fits VMEM, and the
        # 16-layer scan moved 1.5 GB of whole planes into it a step to
        # rewrite 256 KiB of pages a plane (compile, PR 44)
        out_shape=(pltpu.HBM(pages_k.shape, pages_k.dtype),
                   pltpu.HBM(pages_v.shape, pages_v.dtype)),
        input_output_aliases={4: 0, 5: 1},
        # what the launch moves: XLA places the next weights' prefetch
        # into VMEM by its cost model, and costed as nothing this call
        # hid none, so half the down projections read HBM (PERF.md, PR
        # 44)
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=4 * B * math.prod(pages_k.shape[1:])
            * pages_k.dtype.itemsize),
        name="kv_append",
        interpret=interpret_mode(),
    )(jnp.asarray(pidx, jnp.int32), jnp.asarray(r, jnp.int32),
      k.astype(pages_k.dtype)[:, :, None],
      v.astype(pages_v.dtype)[:, :, None], pages_k, pages_v)


def set_prompt_pages(pool, page_ids, rows):
    """Write a whole prompt a PAGE at a time into a pool plane
    [NP, h, page, d]: rows [P, h, d] at positions 0 .. P-1 (a prompt
    starts at a page's first row), page_ids [ceil(P / page)] the page of
    each `page` positions (the trash page for one wholly past the
    prompt's end). What the padding leaves behind the prompt in its
    last page lies past the slot's length until decode overwrites
    it."""
    n, page, P_ = page_ids.shape[0], pool.shape[2], rows.shape[0]
    rows = jnp.pad(rows, ((0, n * page - P_), (0, 0), (0, 0)))
    return pool.at[page_ids].set(
        rows.reshape((n, page) + rows.shape[1:]).swapaxes(1, 2)
        .astype(pool.dtype))


def gather_pages(pool, table):
    """The oracle's read of a paged pool plane: [NP, h, page, d]
    through table [B, maxp] -> the slots' contiguous [B, h, maxp*page,
    d]."""
    g = pool[table]                         # [B, maxp, h, page, d]
    B, maxp, h, page, d = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, h, maxp * page, d)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """Page-table KV cache for one layer (reference:
    paged_kv_cache.py:28). Pages are allocated lazily as sequences grow;
    the table has one row a batch row (slot), and a page holds the
    `page` positions of that slot for all of its kv heads.

    pages_k/v: [NP, Hkv, page, d]; table: [B, max_pages] int32;
    offset: valid positions. The allocator is the trivial static one —
    slot b's tile t lives at page b*max_pages + t — so `alloc` is a
    table initialization, not a runtime free-list; a serving layer can
    swap in its own table (the indirection is what the kernel needs,
    not the policy)."""

    pages_k: jax.Array
    pages_v: jax.Array
    table: jax.Array
    offset: jax.Array

    @staticmethod
    def create(batch: int, n_kv_heads: int, max_seq: int, head_dim: int,
               *, page: int = 128, dtype=jnp.bfloat16) -> "PagedKVCache":
        maxp = -(-max_seq // page)
        NP = batch * maxp
        table = jnp.arange(NP, dtype=jnp.int32).reshape(batch, maxp)
        z = jnp.zeros((NP, n_kv_heads, page, head_dim), dtype)
        return PagedKVCache(pages_k=z, pages_v=z, table=table,
                            offset=jnp.int32(0))

    @property
    def page(self) -> int:
        return self.pages_k.shape[2]

    def append(self, k_new, v_new) -> "PagedKVCache":
        """Append one position: k/v_new [B, Hkv, 1, d] -> row
        offset % page of every head of the slot's page
        table[b, offset // page]. A single-row write into a paged pool
        is a scatter (cannot be a tile-aligned DMA), so appends go
        through XLA DUS — the paged cache trades append/walk speed for
        allocation flexibility."""
        B, maxp = self.table.shape
        if not isinstance(self.offset, jax.core.Tracer):
            # eager appends (the common serving pattern) get a real
            # capacity error; a clamped OOB table read would silently
            # overwrite the last page
            if int(self.offset) >= maxp * self.page:
                raise ValueError(
                    f"PagedKVCache full: offset {int(self.offset)} at "
                    f"capacity {maxp * self.page}")
        out = self.append_slots(k_new, v_new,
                                jnp.full((B,), self.offset, jnp.int32))
        return dataclasses.replace(out, offset=self.offset + 1)

    # ------------------------------------------------------------------
    # continuous-batching slot paths (models/scheduler.py design): the
    # rows of the table are independent SLOTS at their own per-slot
    # positions; a real allocator (PageAllocator) owns the physical
    # pages, so slots of very different lengths share the pool and a
    # retired slot's pages go back on the free list.
    # ------------------------------------------------------------------

    def write_slot(self, slot: int, k, v) -> "PagedKVCache":
        """Prefill-into-slot: write a new request's whole prompt KV
        (k/v [Hkv, n, d]) through the slot's table row — positions
        0..n-1 of every head. Touches only the slot's own
        (allocator-assigned) pages, so live slots are undisturbed. The
        shared offset is NOT advanced — per-slot lengths live with the
        scheduler."""
        n = k.shape[1]
        p = jnp.arange(n)
        pidx = self.table[slot][p // self.page]            # [n]
        r = p % self.page                                  # [n]

        def scat(pages, rows):     # rows [Hkv, n, d] -> [n, Hkv, d]
            return set_page_rows(pages, pidx, r, rows.transpose(1, 0, 2))

        return dataclasses.replace(
            self, pages_k=scat(self.pages_k, k),
            pages_v=scat(self.pages_v, v))

    def append_slots(self, k_new, v_new, pos) -> "PagedKVCache":
        """Per-slot decode append: k/v_new [B, Hkv, 1, d], pos [B] —
        slot b's new row lands at ITS position pos[b] (page
        table[b, pos[b]//page], row pos[b]%page of every head). One
        scatter for the whole batch; the shared offset is untouched."""
        B = k_new.shape[0]
        pos = jnp.asarray(pos, jnp.int32)
        pidx = self.table[jnp.arange(B), pos // self.page]     # [B]
        r = pos % self.page

        def scat(pages, rows):     # rows [B, Hkv, 1, d]
            return set_page_rows(pages, pidx, r, rows[:, :, 0])

        return dataclasses.replace(
            self, pages_k=scat(self.pages_k, k_new),
            pages_v=scat(self.pages_v, v_new))

    def set_slot_table(self, slot: int, row) -> "PagedKVCache":
        """Install the allocator-assigned table row of a slot:
        row [<=max_pages] int32 physical page ids (a shorter row pads
        with its own last entry — never attended past the slot's
        length, but every entry must be a page of the pool)."""
        maxp = self.table.shape[1]
        row = jnp.asarray(row, jnp.int32)
        npg = row.shape[0]
        if npg < maxp:
            row = jnp.concatenate(
                [row, jnp.broadcast_to(row[-1:], (maxp - npg,))])
        table = jax.lax.dynamic_update_slice(self.table, row[None],
                                             (slot, 0))
        return dataclasses.replace(self, table=table)


class PageAllocator:
    """Host-side free-list over the physical page pool (the POLICY the
    trivial static table deliberately leaves out — reference:
    paged_kv_cache.py's block allocator). Slots of very different
    lengths draw from one pool; retiring a slot returns its pages for
    the next admission. Pure host bookkeeping: allocation changes the
    page TABLE (data), never the kernel (program).

    shards > 1 (sequence-parallel serving — kv_cache.PagedSlotCache SP
    SHARDING): the page-id space is partitioned in contiguous blocks —
    shard s owns ids [s*pps, (s+1)*pps), the exact mirror of the
    device-side split of the pool's leading axis — and allocation
    ROTATES across shards so a slot's consecutive logical tiles land
    on different chips (each chip then walks ~1/S of any stream's
    pages). Frees return a page to ITS OWN shard's list by id, so the
    conservation invariant holds PER SHARD:
    ``available_by_shard[s] + outstanding_by_shard[s] == pps`` after
    any sequence of operations — the per-shard zero-leak the chaos
    suite asserts. shards == 1 keeps the historical single-list
    semantics bit for bit (page 0 handed out first)."""

    def __init__(self, num_pages: int, shards: int = 1):
        if shards < 1 or num_pages % shards:
            raise ValueError(
                f"page pool of {num_pages} pages cannot split over "
                f"{shards} shards: the sp mesh size must divide the "
                f"page count (pass num_pages as a multiple of the sp "
                f"axis, or shrink the axis)")
        self.num_pages = num_pages
        self.shards = shards
        self.pages_per_shard = num_pages // shards
        pps = self.pages_per_shard
        # per-shard descending lists: pop() hands out each shard's
        # lowest id first (shard 0's first page is the reserved trash)
        self._free_by_shard = [
            list(range((s + 1) * pps - 1, s * pps - 1, -1))
            for s in range(shards)]
        self._rr = 0
        self._in_use = set()

    def shard_of(self, page: int) -> int:
        return int(page) // self.pages_per_shard

    @property
    def available(self) -> int:
        return sum(len(f) for f in self._free_by_shard)

    @property
    def available_by_shard(self):
        return [len(f) for f in self._free_by_shard]

    @property
    def outstanding(self) -> int:
        return len(self._in_use)

    @property
    def outstanding_by_shard(self):
        out = [0] * self.shards
        for p in self._in_use:
            out[p // self.pages_per_shard] += 1
        return out

    def _check(self) -> None:
        """Pool conservation invariant: every page is on the free list
        XOR outstanding — PER SHARD (a violation means the bookkeeping
        corrupted the pool; the failure mode a double-free used to
        cause silently: one physical page handed to two slots)."""
        assert self.available + len(self._in_use) == self.num_pages, (
            f"page pool corrupted: {self.available} free + "
            f"{len(self._in_use)} in use != {self.num_pages}")

    def _pick_shard(self) -> int:
        """Next shard in rotation with a free page (skip exhausted
        shards; the rotation is what spreads a slot's tiles)."""
        for k in range(self.shards):
            s = (self._rr + k) % self.shards
            if self._free_by_shard[s]:
                self._rr = (s + 1) % self.shards
                return s
        raise ValueError("page pool exhausted: no shard has a free page")

    def alloc(self, n: int):
        """Take n pages off the free lists (raises when the pool is
        exhausted — the scheduler's admission check), rotating across
        shards (the sp round-robin install; a no-op rotation at
        shards == 1)."""
        if n > self.available:
            raise ValueError(
                f"page pool exhausted: want {n}, "
                f"have {self.available}")
        out = [self._free_by_shard[self._pick_shard()].pop()
               for _ in range(n)]
        self._in_use.update(out)
        self._check()
        return out

    def free(self, pages) -> None:
        """Return pages to their own shard's free list. Rejects
        out-of-range ids and double-frees BEFORE touching the pool — a
        double-freed page would be handed to two slots, and the second
        slot's writes would silently corrupt the first's KV."""
        pages = [int(p) for p in pages]
        seen = set()
        for p in pages:
            if not 0 <= p < self.num_pages:
                raise ValueError(
                    f"free of out-of-range page {p} "
                    f"(pool has {self.num_pages})")
            if p not in self._in_use or p in seen:
                raise ValueError(f"double free of page {p}")
            seen.add(p)
        for p in pages:
            self._in_use.remove(p)
            self._free_by_shard[p // self.pages_per_shard].append(p)
        self._check()

    def alloc_slot(self, n_positions: int, page: int):
        """Pages for one slot: ceil(n_positions/page) ids, each a page
        of all the slot's kv heads. Returns the [n_pages] int32 table
        row (feed to PagedKVCache.set_slot_table); free a retired slot
        with free(row)."""
        import numpy as np
        return np.asarray(self.alloc(-(-n_positions // page)), np.int32)
