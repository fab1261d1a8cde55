"""KV cache (reference: `python/triton_dist/models/kv_cache.py`
`KV_Cache:29` — contiguous per-layer K/V buffers + a shared offset).

TPU re-design: per-layer pairs of arrays [B, Hkv, T, hd] sharded on the
KV-head axis over TP (each rank caches only its heads — same memory
split as the reference's per-rank cache), updated functionally
(`jax.lax.dynamic_update_slice`) so the decode step can donate the cache
and XLA updates it in place.

Two deliberate layout choices:
- per-layer tuple (NOT one stacked [L, ...] array): a stacked array
  would make every layer update an update-slice on the whole multi-GB
  buffer and every kernel read a materialized slice copy; separate
  buffers update in place under donation and feed Pallas directly.
- head-major [Hkv, T, hd]: each head's KV is contiguous, which is the
  read order of the flash-decode kernel (kernels/flash_attn.py) — no
  transpose on the hot path.

Slot mode (continuous batching, models/scheduler.py): each batch row
is an independent decode SLOT holding a different request. The shared
`offset` is then meaningless and stays untouched — per-slot positions
live in the scheduler's carry, rows are written by per-row scatter
(TP_Attn._attend_cached_slots) and admission replaces a whole row
(engine._write_slot_fn), so one row's request can never read another's
KV (per-row attention lengths mask the rest).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_dist_tpu.kernels.sparse_attn import index_plane_shape


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    k: Tuple[jax.Array, ...]   # L x [B, Hkv, T, hd]
    v: Tuple[jax.Array, ...]
    offset: jax.Array  # scalar int32: number of valid positions
    # int8 cache only: per-position dequant scales, L x [B, Hkv, T] f32
    # (empty tuples for the bf16 cache — a pytree-stable "absent")
    ks: Tuple[jax.Array, ...] = ()
    vs: Tuple[jax.Array, ...] = ()

    @staticmethod
    def create(num_layers: int, batch: int, max_seq: int, n_kv_heads: int,
               head_dim: int, *, mesh: Mesh, axis: str = "tp",
               dtype=jnp.bfloat16) -> "KVCache":
        """dtype=jnp.int8 stores K/V quantized with per-position scales
        — half the HBM read of the decode step's dominant traffic; the
        flash kernel dequants exactly via logit/P scaling
        (kernels/flash_attn.py)."""
        shape = (batch, n_kv_heads, max_seq, head_dim)
        sharding = NamedSharding(mesh, P(None, axis, None, None))
        k = tuple(jax.device_put(jnp.zeros(shape, dtype), sharding)
                  for _ in range(num_layers))
        v = tuple(jax.device_put(jnp.zeros(shape, dtype), sharding)
                  for _ in range(num_layers))
        ks = vs = ()
        if jnp.dtype(dtype) == jnp.int8:
            s_shd = NamedSharding(mesh, P(None, axis, None))
            mk = lambda: tuple(
                jax.device_put(jnp.zeros(shape[:3], jnp.float32), s_shd)
                for _ in range(num_layers))
            ks, vs = mk(), mk()
        # placed like what a program hands back, so the second call of
        # a program that carries the cache is not compiled again
        offset = jax.device_put(jnp.int32(0), NamedSharding(mesh, P()))
        return KVCache(k=k, v=v, offset=offset, ks=ks, vs=vs)

    @property
    def quantized(self) -> bool:
        return bool(self.ks)

    def layer(self, idx: int):
        """Per-layer cache tuple passed into TP_Attn.fwd_cached:
        (k, v) or (k, v, ks, vs) when int8."""
        if self.quantized:
            return (self.k[idx], self.v[idx], self.ks[idx], self.vs[idx])
        return self.k[idx], self.v[idx]

    def set_layer(self, idx: int, kv) -> "KVCache":
        def put(t, x):
            return t[:idx] + (x,) + t[idx + 1:]

        out = dataclasses.replace(
            self, k=put(self.k, kv[0]), v=put(self.v, kv[1]))
        if len(kv) == 4:
            out = dataclasses.replace(out, ks=put(self.ks, kv[2]),
                                      vs=put(self.vs, kv[3]))
        return out

    def advance(self, n) -> "KVCache":
        return dataclasses.replace(self, offset=self.offset + n)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedSlotCache:
    """Multi-layer paged KV cache for the continuous-batching slot path
    (models/prefix_cache.py policy over kernels/paged_kv.py mechanics).

    Per-layer physical pools pages_k/v [NP, Hkv, page, d] — one page =
    `page` contiguous positions of ONE slot for ALL of its kv heads —
    behind ONE shared page table [B, max_pages], one row a slot: a
    physical page id means the same row in EVERY layer's pool, so the
    host allocator hands out one id per logical tile and it covers all
    heads and all layers. That is what makes cross-request prefix
    sharing cheap: mapping a cached prefix into a slot is a table edit,
    not a KV copy. And it is what makes the decode walk cheap
    (kernels/paged_kv.py): one copy a page and plane moves Hkv*page*d
    elements, 32 KiB on one chip of Qwen3-1.7B, where a page of one
    (slot, head) moved 4 KiB and the walk was bound by the number of
    copies it issued (PERF.md, PR 30 and PR 35).

    Page id `trash` (row 0 by convention, reserved by the allocator) is
    the write sink for retired/dead slots: the slot scan keeps stepping
    masked-out rows, and their KV scatter must land somewhere that no
    live slot ever maps — retiring a slot points its whole table row at
    trash so its surplus writes can never corrupt a reused page. The
    same property is what makes PREEMPTION (models/scheduler.py) safe:
    a preempted slot's pages live on inside the radix tree while its
    table row points at trash, so the still-stepping masked row cannot
    scribble on KV a future re-admission will map back.

    INT8 POOL (dtype=jnp.int8 — the KV-quantization design of KIVI,
    arXiv:2402.02750, specialized to per-position symmetric scales;
    PAPERS.md): the page payload stores int8 and per-layer scale
    planes scales_k/scales_v [NP, Hkv, page] f32 ride ALONGSIDE it — a
    physical page id addresses its payload AND its scales in every
    layer, so the host allocator, the radix prefix tree, the
    copy-on-write boundary copy and the host-tier d2h/h2d extract/
    restore (models/kv_tier.py) are all layout-oblivious: whatever
    moves a page moves its scales with the same id. Quantization is
    kernels/quant.quantize_kv_int8 — the exact quantizer of the
    contiguous int8 cache — and kernels/paged_kv.flash_decode_paged
    dequants in-kernel by logit/P scaling, so paged-int8 streams are
    bitwise identical to the contiguous-int8 reference while the
    decode step's dominant HBM read halves and the same pool holds
    ~2x the resident pages.

    TP SHARDING (the multi-chip serving layout; the head-axis split of
    the contiguous KVCache carried over to the paged pool): the planes
    are sharded on their HEAD axis, NamedSharding(mesh, P(None, axis,
    None, None)) — a chip's shard [NP, Hkv/tp, page, d] holds its own
    kv heads of every page and nothing else, all of it real bytes. The
    page-id space is NOT split: the host allocator, refcounts, radix
    tree, CoW and LRU policy (models/prefix_cache.py) hand out the
    same ids whatever the mesh, and the replicated page table
    resolves a slot's tile to a page id exactly as on one chip, so
    the slot attends (layers/tp_attn.py _attend_paged_slots*) run
    under jax.shard_map with each chip walking only its local shard:
    1/tp of the decode step's KV read and attention FLOPs per chip,
    with the QKV/O projections riding the TP comm backends
    (kernels/gemm_allreduce.py et al.).

    SP SHARDING (sequence-parallel long-context serving — ROADMAP
    long-context item; the promotion of kernels/sp_flash_decode.py
    into the serving path, Ring Attention arXiv:2310.01889 /
    Infinite-LLM arXiv:2401.02669 being the deployment story): with
    `sp` > 1 the PAGE-ID SPACE is partitioned — the pools' leading
    [NP] axis shards over the sp mesh axis in contiguous blocks, chip
    s holding physical pages [s*NP/S, (s+1)*NP/S) of EVERY layer, so
    a slot's max context is bounded by the WHOLE mesh's paged HBM
    instead of one chip's. The page table, allocator free lists,
    refcounts, radix tree, CoW and host-tier bookkeeping stay
    host-side and layout-blind exactly as under the TP head split —
    the allocator (kernels/paged_kv.PageAllocator shards=) merely
    rotates fresh pages across shards so consecutive logical tiles
    interleave chips. A decode tick runs under shard_map with
    each chip walking ONLY its local pages through the split-KV
    partial kernel (kernels/paged_kv.flash_decode_paged_partial) and
    the partials merging via the cross-chip LSE combine
    (kernels/sp_flash_decode.sp_combine_partials): per-chip KV reads
    and attention FLOPs drop to ~1/S. sp composes with int8 scale
    planes (they shard alongside the payload) but not (yet) with the
    TP head split — refused capability-named at Engine construction."""

    pages_k: Tuple[jax.Array, ...]   # L x [NP, Hkv, page, d]
    pages_v: Tuple[jax.Array, ...]
    table: jax.Array                 # [B, max_pages] int32
    # int8 pool only: per-position dequant scales, L x [NP, Hkv, page]
    # f32 (empty tuples for the bf16 pool — a pytree-stable "absent")
    scales_k: Tuple[jax.Array, ...] = ()
    scales_v: Tuple[jax.Array, ...] = ()
    trash: int = dataclasses.field(default=0, metadata=dict(static=True))
    # sp mesh size the pool's page-id space is partitioned over (1 =
    # the historical single-shard pool; static so programs branch on
    # it at trace time)
    sp: int = dataclasses.field(default=1, metadata=dict(static=True))

    @staticmethod
    def create(num_layers: int, batch: int, max_seq: int, n_kv_heads: int,
               head_dim: int, *, page: int, num_pages: int, mesh: Mesh,
               axis: str = "tp", dtype=jnp.bfloat16,
               trash: int = 0,
               sp_axis: Optional[str] = None) -> "PagedSlotCache":
        maxp = -(-max_seq // page)
        G = mesh.shape[axis]
        if n_kv_heads % G:
            raise ValueError(
                f"paged pool needs n_kv_heads ({n_kv_heads}) divisible "
                f"by the TP mesh size ({G}): each chip owns whole kv "
                f"heads of the page payloads. GQA replication "
                f"(Hq > Hkv) lives on the QUERY side and does not "
                f"relax this — replicate KV heads in the checkpoint "
                f"or shrink the mesh.")
        sp = 1
        if sp_axis is not None:
            sp = mesh.shape[sp_axis]
            if sp > 1 and G > 1:
                raise ValueError(
                    "paged pool cannot shard pages over "
                    f"{sp_axis!r} AND kv heads over {axis!r} in "
                    "one pool (missing capability: sp + TP hybrid "
                    "serving) — size one of the axes to 1")
            if num_pages % sp:
                raise ValueError(
                    f"sequence-parallel pool needs num_pages "
                    f"({num_pages}) divisible by the sp mesh size "
                    f"({sp}): each chip owns a contiguous block of the "
                    f"page-id space — round num_pages up or shrink "
                    f"the axis")
        page_spec = (P(None, axis, None, None) if sp == 1
                     else P(sp_axis, axis, None, None))
        sc_spec = (P(None, axis, None) if sp == 1
                   else P(sp_axis, axis, None))
        shd = NamedSharding(mesh, page_spec)
        mk = lambda: tuple(
            jax.device_put(
                jnp.zeros((num_pages, n_kv_heads, page, head_dim), dtype),
                shd)
            for _ in range(num_layers))
        sk = sv = ()
        if jnp.dtype(dtype) == jnp.int8:
            s_shd = NamedSharding(mesh, sc_spec)
            mks = lambda: tuple(
                jax.device_put(
                    jnp.zeros((num_pages, n_kv_heads, page), jnp.float32),
                    s_shd)
                for _ in range(num_layers))
            sk, sv = mks(), mks()
        table = jax.device_put(
            jnp.full((batch, maxp), trash, jnp.int32),
            NamedSharding(mesh, P(None, None)))
        return PagedSlotCache(pages_k=mk(), pages_v=mk(), table=table,
                              scales_k=sk, scales_v=sv, trash=trash,
                              sp=sp)

    @property
    def quantized(self) -> bool:
        return bool(self.scales_k)

    @property
    def page(self) -> int:
        return self.pages_k[0].shape[2]

    @property
    def num_pages(self) -> int:
        return self.pages_k[0].shape[0]

    @property
    def kv_heads(self) -> int:
        """The kv heads a page holds of its slot (all of them: the
        planes' head axis, whole across a TP mesh)."""
        return self.pages_k[0].shape[1]

    @property
    def page_copy_bytes(self) -> int:
        """Bytes ONE copy of the decode walk moves on a chip: a page's
        K plane for the kv heads that chip holds."""
        shard = self.pages_k[0].sharding.shard_shape(
            self.pages_k[0].shape)
        return int(np.prod(shard[1:])) * self.pages_k[0].dtype.itemsize

    @property
    def pages_per_shard(self) -> int:
        """Physical pages per sp shard (== num_pages at sp == 1):
        chip s owns ids [s*pps, (s+1)*pps) — the id partition the
        allocator, the sp attends and the admit programs all share."""
        return self.pages_k[0].shape[0] // self.sp

    @property
    def capacity(self) -> int:
        """Logical positions addressable per slot (table width x page)."""
        return self.table.shape[1] * self.page

    def clear_slot(self, slot) -> "PagedSlotCache":
        """Retire hook: a slot whose whole state is its pages has
        nothing to clear (its table rows go to the trash page)."""
        return self

    def layer(self, idx: int):
        """Per-layer pool tuple for the paged attends: (pages_k,
        pages_v) — or (pages_k, pages_v, scales_k, scales_v) when
        int8 (mirrors KVCache.layer's 2-vs-4 contract)."""
        if self.quantized:
            return (self.pages_k[idx], self.pages_v[idx],
                    self.scales_k[idx], self.scales_v[idx])
        return self.pages_k[idx], self.pages_v[idx]

    def set_layer(self, idx: int, *kv) -> "PagedSlotCache":
        def put(t, x):
            return t[:idx] + (x,) + t[idx + 1:]

        out = dataclasses.replace(self, pages_k=put(self.pages_k, kv[0]),
                                  pages_v=put(self.pages_v, kv[1]))
        if len(kv) == 4:
            out = dataclasses.replace(
                out, scales_k=put(self.scales_k, kv[2]),
                scales_v=put(self.scales_v, kv[3]))
        return out


def uniform_paged_cache(model, batch: int, max_seq: int, *, page: int,
                        num_pages: int, dtype=None,
                        sp_axis: Optional[str] = None) -> PagedSlotCache:
    """The paged cache of a model whose every layer is an attention
    layer with its own K/V (DenseLLM, Qwen3MoE): one pool a layer."""
    cfg = model.config
    return PagedSlotCache.create(
        cfg.num_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim,
        page=page, num_pages=num_pages, mesh=model.mesh, axis=model.axis,
        dtype=dtype or cfg.jax_dtype, sp_axis=sp_axis)


# lanes a latent row is padded to: the chip's HBM tiles are 128 lanes
# wide, so a 576-wide row takes 640 there whether the plane says so or
# not; saying so keeps every copy, slice and dot of the walk aligned
LATENT_LANES = 128


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LatentSlotCache(PagedSlotCache):
    """The paged pool of a latent-attention model (layers/mla_attn.py;
    models/deepseek.py): ONE plane a layer, `pages_k[l]` [NP, 1, page,
    width], a position's row [c_kv (rank) | k_pe (rope) | zero pad] in
    the layer's compute dtype, and NO V plane (`pages_v` is empty): the
    decode walk reads its values out of the same rows
    (kernels/paged_kv.py, `v_cols`). Pages, the table, the allocator,
    retire-to-trash and `clear_slot` are PagedSlotCache's own; a slot's
    whole context is its pages.

    `row_bytes` is what a position costs as published (rank + rope
    values), `page_copy_bytes` what the walk's one copy a page moves
    (the padded row)."""

    rank: int = dataclasses.field(default=0, metadata=dict(static=True))
    rope: int = dataclasses.field(default=0, metadata=dict(static=True))
    # what the same positions would take as expanded per-head K and V
    # (heads x (qk + v) values): `cache_uniform_bytes`
    expanded_row_values: int = dataclasses.field(
        default=0, metadata=dict(static=True))

    @staticmethod
    def create_latent(num_layers: int, batch: int, max_seq: int, *,
                      rank: int, rope: int, expanded_row_values: int,
                      page: int, num_pages: int, mesh: Mesh,
                      dtype=jnp.bfloat16) -> "LatentSlotCache":
        width = -(-(rank + rope) // LATENT_LANES) * LATENT_LANES
        maxp = -(-max_seq // page)
        rep = NamedSharding(mesh, P())
        planes = tuple(
            jax.device_put(jnp.zeros((num_pages, 1, page, width), dtype),
                           rep) for _ in range(num_layers))
        table = jax.device_put(jnp.zeros((batch, maxp), jnp.int32), rep)
        return LatentSlotCache(pages_k=planes, pages_v=(), table=table,
                               rank=rank, rope=rope,
                               expanded_row_values=expanded_row_values)

    @property
    def row_bytes(self) -> int:
        return (self.rank + self.rope) * self.pages_k[0].dtype.itemsize

    def slot_bytes(self) -> dict:
        """Bytes a mapped page holds over all layers, as published
        (kind "latent"), beside what expanded K and V would."""
        L, item = len(self.pages_k), self.pages_k[0].dtype.itemsize
        return {"page_kind": "latent",
                "page": L * self.page * self.row_bytes,
                "uniform_page": L * self.page * self.expanded_row_values
                * item}


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class IndexedSlotCache(PagedSlotCache):
    """The slot cache of a model with learned sparse attention
    (layers/sparse_attn.py; models/qwen_moe.py with `sa_config`): TWO
    planes of a position under the page table and ONE plane a slot
    beside them.

    `pages_k[l]` [NP, 2 Hkv, page, d] holds K AND V: a page's first Hkv
    head rows are the slot's keys, its last Hkv its values, so the
    decode walk fetches both with one copy a page (the walk is bound by
    the copies it issues, PERF.md PR 35) and the append writes both
    with one scatter; `pages_v` is empty, as a latent pool's. Pages, the
    table, the allocator, retire-to-trash and `clear_slot` are
    PagedSlotCache's own.
    `pages_i[l]` [B, L / 2, lanes] is the indexer's cache, per-slot
    state beside the pages as HybridSlotCache's rings are: ONE key head
    a position, a slot's keys in one run addressed by (slot, position),
    two positions a row (kernels/sparse_attn.py has the layout; L is
    `max_seq` rounded up to the kernel's block), so that the indexer
    reads a slot's context in blocks of thousands of positions and not
    a 16-position page at a time. At `index_dim` 64 in bfloat16 a row
    is the chip's 128-lane tile and a position the published 128 B.
    The model shares no index keys between slots (prefix reuse, forks,
    the host tier and disaggregation are refused by name), so no table
    stands before the plane; a slot's rows need no clearing either: a
    later occupant's admission overwrites what its length lets it
    read."""

    pages_i: Tuple[jax.Array, ...] = ()
    index_dim: int = dataclasses.field(default=0,
                                       metadata=dict(static=True))

    @staticmethod
    def create_indexed(num_layers: int, batch: int, max_seq: int, *,
                       n_kv_heads: int, head_dim: int, index_dim: int,
                       page: int, num_pages: int, mesh: Mesh,
                       dtype=jnp.bfloat16) -> "IndexedSlotCache":
        maxp = -(-max_seq // page)
        rep = NamedSharding(mesh, P())

        def planes(shape):
            return tuple(jax.device_put(jnp.zeros(shape, dtype), rep)
                         for _ in range(num_layers))

        table = jax.device_put(jnp.zeros((batch, maxp), jnp.int32), rep)
        return IndexedSlotCache(
            pages_k=planes((num_pages, 2 * n_kv_heads, page, head_dim)),
            pages_v=(), table=table,
            pages_i=planes((batch,) + index_plane_shape(max_seq,
                                                        index_dim)),
            index_dim=index_dim)

    @property
    def kv_heads(self) -> int:
        return self.pages_k[0].shape[1] // 2

    def slot_bytes(self) -> dict:
        """Bytes a mapped page holds over all layers: its K and V rows
        (kind "pages") and, kind "index", the index keys of ITS
        positions as published (`index_dim` values a position): the
        index plane is per-slot state, but the gauge goes on counting
        the positions the live slots have mapped, not the plane's
        `max_seq` rows a slot. A uniform cache is the K and V rows
        alone."""
        L, item = len(self.pages_k), self.pages_k[0].dtype.itemsize
        kv = L * int(np.prod(self.pages_k[0].shape[1:])) * item
        return {"page": kv, "uniform_page": kv,
                "index": L * self.page * self.index_dim * item}


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HybridSlotCache(PagedSlotCache):
    """One slot cache for a model whose layers keep up to THREE kinds of
    per-slot state (models/phi4flash.py, models/afmoe.py):

    (a) pages behind the shared table, inherited from PagedSlotCache:
        `paged_layers` pools, `pages_k[i]` the i-th paged layer's.
        Phi-4-mini-flash has ONE (its full-attention layer's, which
        that layer and every cross-attention layer read, K and V in
        two planes); Trinity has one for every full-attention layer
        (`fused`: K and V in ONE plane [NP, 2 heads, page, d], a
        page's first `heads` rows the keys, its last the values, so
        the decode walk fetches both with one copy a page, as
        IndexedSlotCache; `pages_v` is then empty). Allocator, table
        install, retire-to-trash: all the paged path's own.
    (b) `win_k` / `win_v`: one RING of `window` positions per slot and
        window-attention layer, [B, heads, window, d]. Position t lives
        in row t % window, so a slot's bytes are bounded by the window
        whatever max_seq is. What a ring holds is FINAL: a model with
        positions (afmoe: rotary on the window layers) rotates a key at
        its absolute position BEFORE it writes it, a model without
        (phi4flash) writes it as projected; either way attention is
        indifferent to the order of the rows, and a decode step attends
        the first min(t + 1, window) rows of the ring as an ordinary
        cached attention (the choice between a ring and pages freed
        behind the window: the ring needs no allocator traffic per 16
        tokens and no window start in the paged walk).
    (c) `conv` [B, d_conv - 1, E] and `ssm` [B, d_state, E] float32
        planes per state-space layer: fixed size, not pageable; none
        (`state_layers` 0) in a model of attention layers only.

    `live` [B] marks the slots that hold an admitted request: a decode
    step advances (c) for live slots only and leaves every other slot's
    planes ALONE (zero since its retire, which clears them)."""

    win_k: Tuple[jax.Array, ...] = ()
    win_v: Tuple[jax.Array, ...] = ()
    conv: Tuple[jax.Array, ...] = ()
    ssm: Tuple[jax.Array, ...] = ()
    live: Optional[jax.Array] = None
    # how many attention layers read K/V in this model: what a uniform
    # cache (every one of them its own full-length pool) would multiply
    # a slot's pages by (slot_bytes)
    attn_layers: int = dataclasses.field(default=1,
                                         metadata=dict(static=True))

    @staticmethod
    def create_hybrid(batch: int, max_seq: int, *, heads: int,
                      head_dim: int, window: int, window_layers: int,
                      state_layers: int, d_inner: int, d_state: int,
                      d_conv: int, attn_layers: int, page: int,
                      num_pages: int, mesh: Mesh, axis: str = "tp",
                      dtype=jnp.bfloat16, paged_layers: int = 1,
                      fused: bool = False) -> "HybridSlotCache":
        base = PagedSlotCache.create(
            paged_layers, batch, max_seq, heads * (2 if fused else 1),
            head_dim, page=page, num_pages=num_pages, mesh=mesh,
            axis=axis, dtype=dtype)
        rep = NamedSharding(mesh, P())

        def planes(n, shape, dt):
            return tuple(jax.device_put(jnp.zeros(shape, dt), rep)
                         for _ in range(n))

        ring = (batch, heads, window, head_dim)
        return HybridSlotCache(
            pages_k=base.pages_k, pages_v=() if fused else base.pages_v,
            table=base.table, trash=base.trash,
            win_k=planes(window_layers, ring, dtype),
            win_v=planes(window_layers, ring, dtype),
            conv=planes(state_layers, (batch, d_conv - 1, d_inner),
                        jnp.float32),
            ssm=planes(state_layers, (batch, d_state, d_inner),
                       jnp.float32),
            live=jax.device_put(jnp.zeros((batch,), bool), rep),
            attn_layers=attn_layers)

    @property
    def kv_heads(self) -> int:
        return self.pages_k[0].shape[1] // (1 if self.pages_v else 2)

    def clear_slot(self, slot) -> "HybridSlotCache":
        """Retire: zero the slot's recurrent planes and mark it dead.
        (Its ring rows stay: a later occupant's lengths mask them until
        it has overwritten them.)"""
        zero = lambda t: tuple(  # noqa: E731
            jax.lax.dynamic_update_slice(
                a, jnp.zeros((1,) + a.shape[1:], a.dtype), (slot, 0, 0))
            for a in t)
        return dataclasses.replace(
            self, conv=zero(self.conv), ssm=zero(self.ssm),
            live=self.live.at[slot].set(False))

    def slot_bytes(self) -> dict:
        """Bytes ONE slot holds of each kind: a mapped page of (a) (K
        and V, every head, every paged layer), its rings (b), its
        planes (c, left out where the model has none); and what a page
        would cost in a uniform cache, where each of the `attn_layers`
        attention layers keeps its own."""
        nbytes = lambda t: sum(a[0].nbytes for a in t)  # noqa: E731
        page = nbytes(self.pages_k) + nbytes(self.pages_v)
        out = {"page": page,
               "window": nbytes(self.win_k) + nbytes(self.win_v),
               "uniform_page": page // len(self.pages_k)
               * self.attn_layers}
        if self.conv:
            out["state"] = nbytes(self.conv) + nbytes(self.ssm)
        return out
