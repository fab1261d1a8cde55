"""Flash attention for cached decode/prefill on TPU.

TPU-native re-design of the reference split-KV GQA decode kernel
(`python/triton_dist/kernels/nvidia/flash_decode.py`: split-KV
`kernel_gqa_fwd_batch_decode_split_kv:130`, combine `:308`). The
reference splits KV across CTAs and combines partials with LSE; on TPU
one core owns the whole KV, so the split-KV structure becomes a grid
walk over KV tiles with an online-softmax accumulator in VMEM — the
combine step degenerates into the running (m, l, acc) update. The
inter-rank LSE combine lives in kernels/sp_flash_decode.py.

Layout: queries fold (batch, kv-head) into ONE leading batch dimension
(Mosaic supports a single batched matmul dim), giving
    q  [B*Hkv, S*rep, d]   (rep = Hq // Hkv; GQA needs no jnp.repeat —
    k  [B*Hkv, T, d]        the group's queries share their KV head's
    v  [B*Hkv, T, d]        tile, reference flash_decode.py:130 does the
                            same with tl.dot over grouped heads)
so every QK^T is a true MXU matmul [S*rep, d] @ [d, bt] and KV is read
exactly once per step, straight from the cache, in bf16.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.runtime import interpret_mode


def _flash_decode_kernel(scale: float, rep: int, S: int, T: int,
                         partial: bool, quant: bool, per_stream: bool,
                         len_ref, q_ref, k_ref, v_ref, *rest,
                         window: int = 0):
    """Grid (X/bx, T/bt); X = B*Hkv. Online softmax over KV tiles.

    partial=False: rest = (o_ref, m_scr, l_scr, acc_scr); writes the
    normalized output. partial=True: rest = (o_ref, m_ref, l_ref,
    m_scr, l_scr, acc_scr); writes UNNORMALIZED f32 acc + (m, l) for an
    inter-chip LSE combine (reference: flash_decode.py:482).

    quant=True: k/v are int8 and rest is prefixed by per-position f32
    scale refs (ks, vs) [bx, bt]. Dequant is EXACT and costs no extra
    matmuls: K's scale multiplies the logits column-wise, V's scale
    folds into p before the PV contraction — the int8->bf16 convert
    happens in VMEM, so KV HBM traffic is halved (the decode regime is
    KV-bandwidth-bound at long context).

    per_stream=True (the continuous-batching decode path): rest is
    prefixed by a [bx, 2] int32 block of per-stream (kv length, query
    length) pairs (its BlockSpec walks the [X, 2] lens operand with the
    x grid axis) and each stream masks to its OWN lengths — slots of
    different sequence lengths share one kernel launch. q_len == 1 is
    plain decode; q_len > 1 is a PREFILL-SHAPED WINDOW — the
    speculative-verify draft (models/spec_decode.py) or a chunked-
    prefill prompt chunk (models/scheduler.py step_mixed; both ride
    the same mask): the stream's q_len query rows sit at positions
    kv_len - q_len .. kv_len - 1 and row s attends causally within
    the window (col <= kv_len - q_len + s). Padded rows
    past q_len behave like the last valid row (their outputs are
    discarded by the caller; the clamp keeps them NaN-free). Tiles past
    a stream's length are masked to a BITWISE no-op of the accumulator
    update (alpha == 1, p == 0), so a short slot's output is exactly
    what a uniform-length launch at its length produces; the grid/DMA
    walk still runs to max_len (len_ref[0]).

    window > 0 (sliding-window attention, uniform lengths only): a query
    at position p sees the `window` keys p - window + 1 .. p. Only the
    mask changes; a caller that wants the tiles before the window
    skipped hands in K/V already cut to the span its queries can see
    (models/phi4flash.py does, a query block at a time)."""
    if quant:
        ks_ref, vs_ref, *rest = rest
    else:
        ks_ref = vs_ref = None
    if per_stream:
        lens_ref, *rest = rest
    else:
        lens_ref = None
    if partial:
        o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        m_ref = l_ref = None
    t = pl.program_id(1)
    nt = pl.num_programs(1)
    bt = k_ref.shape[1]
    rows = q_ref.shape[1]          # S * rep
    kv_len = len_ref[0]
    # global position of query row 0 relative to this KV buffer's col 0;
    # a query row r sits at q_off + r//rep and sees cols <= that.
    q_off = len_ref[1]
    start = t * bt

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(start < kv_len)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        if quant:
            k = k.astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [bx, rows, bt]
        if quant:
            s = s * ks_ref[...][:, None, :]
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, bt), 0) // rep
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, bt), 1) + start
        if per_stream:
            # stream j's causal frontier: query row s (s = row, since
            # row = r // rep) sits at kv_len_j - q_len_j + s; rows past
            # q_len_j clamp to the last valid row (outputs discarded).
            # q_len == 1 degenerates to the plain col < kv_len mask.
            kvl = lens_ref[...][:, 0][:, None, None]     # [bx, 1, 1]
            ql = lens_ref[...][:, 1][:, None, None]
            frontier = kvl - ql + jnp.minimum(row[None], ql - 1)
            mask = (col[None] <= frontier) & (col[None] < T)
        else:
            # col < T guards the last block's padding when a caller
            # shifts the causal frontier past the buffer (kv_len > T,
            # e.g. the non-causal mode of sp_ring_attention)
            mask = (col <= (row + q_off)) & (col < jnp.minimum(kv_len, T))
            if window:
                mask = mask & (col > (row + q_off) - window)
            mask = mask[None]
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev,
                            jnp.max(jnp.where(mask, s, -1e30), -1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1)
        vt = v_ref[...]
        if quant:
            vt = vt.astype(q.dtype)
            sv = vs_ref[...]
            if T % bt:
                # the trailing partial block's scale pad may be NaN and
                # p is already zero there — but 0 * NaN = NaN
                scol = jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1) + start
                sv = jnp.where(scol < T, sv, 0)
            # V's per-position scale folds into p (diag(sv) V == V rows
            # scaled), so the PV dot runs on the raw int8 values. (K's
            # scale pad needs no guard: a NaN-scaled logit column is
            # masked by `mask` before it reaches p.)
            p = p * sv[:, None, :]
        if T % bt:
            # the trailing partial block is PADDED beyond T; the pad may
            # be NaN (the interpreter pads with NaN deliberately) and
            # 0 * NaN = NaN would leak through the p @ v contraction
            tcol = jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0) + start
            vt = jnp.where(tcol < T, vt, 0)
        pv = jax.lax.dot_general(
            p.astype(vt.dtype), vt, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)          # [bx, rows, d]
        acc_scr[...] = acc_scr[...] * alpha[..., None] + pv
        m_scr[...] = m_new

    @pl.when(t == nt - 1)
    def _finish():
        if partial:
            o_ref[...] = acc_scr[...]
            m_ref[...] = m_scr[...]
            l_ref[...] = l_scr[...]
        else:
            o_ref[...] = (acc_scr[...]
                          / l_scr[...][..., None]).astype(o_ref.dtype)


def _pick_bx(X: int, rows: int, d: int, bt: int, itemsize: int,
             target: int, budget: int = 12 << 20,
             kv_itemsize: Optional[int] = None,
             partial: bool = False) -> int:
    """Largest divisor of X under `target` whose pipelined VMEM footprint
    fits: double-buffered q and out blocks (weighted 2x beyond the
    double-buffering — Mosaic's real allocation at large `rows` exceeds
    the naive model, observed 17.2M vs a 10M estimate for rows=1280 at
    bx=4, a compile-time OOM on chip), double-buffered k/v blocks
    (which may be int8 — kv_itemsize), and the f32 accumulators."""
    if kv_itemsize is None:
        kv_itemsize = itemsize
    for bx in range(min(target, X), 0, -1):
        if X % bx:
            continue
        if partial and bx % 8 and bx != X:
            # partial mode writes (bx, rows) m/l blocks whose
            # second-to-minor dim is bx: Mosaic needs it 8-aligned
            # (only a FULL-dim block is exempt)
            continue
        q_out = 2 * 2 * 2 * bx * rows * d * itemsize   # q + out, dbuf, 2x
        kv = 2 * 2 * bx * bt * d * kv_itemsize         # k + v, dbuf
        scratch = bx * rows * (8 + 4 * d)
        if q_out + kv + scratch <= budget:
            return bx
    raise ValueError(
        f"flash_decode: no batch block fits VMEM (rows={rows}, d={d}, "
        f"block_t={bt}); the query block alone exceeds the budget. Chunk "
        "long prefills into shorter S segments (the engine prefill path "
        "does), or lower block_t.")


def flash_decode(q, k, v, kv_len, *, scale: Optional[float] = None,
                 block_x: Optional[int] = None,
                 block_t: Optional[int] = None,
                 k_scale=None, v_scale=None, kv_lens=None, q_lens=None,
                 window: int = 0):
    """Cached GQA attention (decode and prefill-into-cache).

    q: [B, S, Hq, d]; k, v: [B, Hkv, T, d] (T = static cache capacity);
    kv_len: traced scalar — number of valid KV positions INCLUDING the S
    query positions (query s sits at kv_len - S + s). Returns
    [B, S, Hq, d].

    k_scale/v_scale: per-position dequant scales [B, Hkv, T] f32 for an
    int8 KV cache (k/v int8); dequant folds into the logits / the P
    matrix inside the kernel (exact), halving KV HBM traffic.

    kv_lens: optional per-BATCH-ROW valid lengths [B] int32 (kv_len
    must then be their max) — the continuous-batching decode path,
    where each slot of the batch is a different request at a different
    sequence position (models/scheduler.py). Row b attends exactly its
    own kv_lens[b] positions.

    q_lens: optional per-BATCH-ROW query-window lengths [B] int32
    (requires kv_lens): slot b's first q_lens[b] query rows are a
    window at positions kv_lens[b] - q_lens[b] .. kv_lens[b] - 1,
    attending every prior position plus causally WITHIN the window —
    the speculative-verify draft (models/spec_decode.py) AND the
    chunked-prefill prompt chunk (models/scheduler.py step_mixed: a
    prefill chunk is exactly this window, which is why chunked prefill
    needed no new kernel). Rows past q_lens[b] are padding whose
    output the caller discards; q_lens[b] == 0 marks a row making no
    progress this launch (every column masked — its output is garbage
    the caller drops). Without q_lens, S must be 1 (plain per-slot
    decode).

    window: sliding-window attention — query s sees only the `window`
    keys ending at its own position (0 = full causal). Uniform lengths
    only (no kv_lens).

    Reference: flash_decode.py:130 (split-KV GQA kernel) + :308
    (combine); here split-KV partial results live in VMEM scratch and
    combine is the online-softmax update, so nothing round-trips HBM.
    """
    assert not (window and kv_lens is not None), \
        "the window mask rides the uniform-length path"
    B, S, Hq, d = q.shape
    _, Hkv, T, _ = k.shape
    rep = Hq // Hkv
    if scale is None:
        scale = d ** -0.5
    if q_lens is not None:
        assert kv_lens is not None, "q_lens rides on per-slot kv_lens"
    if kv_lens is not None:
        assert S == 1 or q_lens is not None, (
            "per-slot kv_lens with S > 1 needs q_lens (the verify path)")
        # the scalar kv_len becomes the walk bound (max over slots);
        # callers may pass anything — it is recomputed here
        kv_len = jnp.max(jnp.asarray(kv_lens, jnp.int32))
    if block_x is None or block_t is None:
        # callers that do not pin the blocks resolve explicit arg >
        # contextual profile (tools/tune.contextual_autotune) > tune
        # cache (tools/sweep) > the static defaults
        from triton_dist_tpu.tools.sweep import resolve_config
        prof = resolve_config("flash_decode", (B * Hkv, T))
        block_x = block_x if block_x is not None else prof.get("block_x",
                                                               64)
        block_t = block_t if block_t is not None else prof.get("block_t",
                                                               256)
    X = B * Hkv
    rows = S * rep
    # queries grouped by kv head: [B, S, Hkv, rep, d] -> [X, rows, d]
    qx = (q.reshape(B, S, Hkv, rep, d)
           .transpose(0, 2, 1, 3, 4)
           .reshape(X, rows, d))
    kx = k.reshape(X, T, d)
    vx = v.reshape(X, T, d)
    ks = None if k_scale is None else k_scale.reshape(X, T)
    vs = None if v_scale is None else v_scale.reshape(X, T)
    lens_x = None
    if kv_lens is not None:
        kv_x = jnp.repeat(jnp.asarray(kv_lens, jnp.int32), Hkv)
        q_x = (jnp.ones_like(kv_x) if q_lens is None
               else jnp.repeat(jnp.asarray(q_lens, jnp.int32), Hkv))
        lens_x = jnp.stack([kv_x, q_x], axis=1)          # [X, 2]
    out = _flash_call(qx, kx, vx, kv_len, kv_len - S, scale=float(scale),
                      rep=rep, S=S, T=T, partial=False, block_x=block_x,
                      block_t=block_t, ks=ks, vs=vs, lens=lens_x,
                      window=window)
    return (out.reshape(B, Hkv, S, rep, d)
               .transpose(0, 2, 1, 3, 4)
               .reshape(B, S, Hq, d))


def flash_decode_partial(q, k, v, kv_len, q_offset, *,
                         scale: Optional[float] = None,
                         block_x: Optional[int] = None,
                         block_t: Optional[int] = None):
    """Per-chip split-KV partial: unnormalized accumulator + LSE stats
    for the inter-chip combine (reference: the split-KV kernel's partial
    outputs, flash_decode.py:130, combined at :308/:482).

    q: [B, S, Hq, d]; k, v: [B, Hkv, T, d] — THIS CHIP'S KV shard.
    kv_len: valid cols in this buffer (may be 0 for an empty shard).
    q_offset: global position of query s=0 relative to this buffer's
    col 0 (query s attends cols <= q_offset + s; may be negative or
    > T). Returns (acc [B, S, Hq, d] f32 unnormalized, m [B, S, Hq],
    l [B, S, Hq]) — combine with lse_combine().
    """
    B, S, Hq, d = q.shape
    _, Hkv, T, _ = k.shape
    rep = Hq // Hkv
    if scale is None:
        scale = d ** -0.5
    if block_x is None or block_t is None:
        # same resolution order as flash_decode — the sp partial rides
        # the same "flash_decode" tuning entry (same kernel body)
        from triton_dist_tpu.tools.sweep import resolve_config
        prof = resolve_config("flash_decode", (B * Hkv, T))
        block_x = block_x if block_x is not None else prof.get("block_x",
                                                               64)
        block_t = block_t if block_t is not None else prof.get("block_t",
                                                               256)
    X = B * Hkv
    rows = S * rep
    qx = (q.reshape(B, S, Hkv, rep, d)
           .transpose(0, 2, 1, 3, 4)
           .reshape(X, rows, d))
    acc, m, l = _flash_call(qx, k.reshape(X, T, d), v.reshape(X, T, d),
                            kv_len, q_offset, scale=float(scale), rep=rep,
                            S=S, T=T, partial=True, block_x=block_x,
                            block_t=block_t)

    def unfold(a):
        tail = a.shape[2:]
        return (a.reshape(B, Hkv, S, rep, *tail)
                 .transpose(0, 2, 1, 3, *range(4, 4 + len(tail)))
                 .reshape(B, S, Hq, *tail))

    return unfold(acc), unfold(m), unfold(l)


def lse_combine(accs, ms, ls, dtype=None):
    """Merge split-KV partials across chips/chunks (reference: the
    inter-rank LSE combine, flash_decode.py:482). accs: [n, ..., d] f32
    unnormalized; ms/ls: [n, ...]. Returns normalized [..., d]."""
    m_star = jnp.max(ms, axis=0)
    scale = jnp.exp(ms - m_star[None])
    acc = jnp.sum(accs * scale[..., None], axis=0)
    l = jnp.sum(ls * scale, axis=0)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(dtype) if dtype is not None else out


def _flash_call(qx, kx, vx, kv_len, q_off, *, scale: float, rep: int,
                S: int, T: int, partial: bool, block_x: int, block_t: int,
                ks=None, vs=None, lens=None, window: int = 0):
    X, rows, d = qx.shape
    quant = ks is not None
    bt = min(block_t, T)
    bx = _pick_bx(X, rows, d, bt, jnp.dtype(qx.dtype).itemsize, block_x,
                  kv_itemsize=jnp.dtype(kx.dtype).itemsize,
                  partial=partial)
    kernel = functools.partial(_flash_decode_kernel, scale, rep, S, T,
                               partial, quant, lens is not None,
                               **({"window": int(window)} if window
                                  else {}))

    # KV-tile index map clamps t to the last block containing valid keys:
    # grid steps past kv_len re-request the same block, and the Pallas
    # pipeline ELIDES a DMA whose block index equals the previous step's
    # — so the tail of the static cache costs no HBM bandwidth (the
    # static-shape analog of the reference's dynamic split-KV grid,
    # flash_decode.py:130).
    def kv_map(x, t, len_ref):
        last = jnp.maximum((len_ref[0] + bt - 1) // bt - 1, 0)
        return (x, jnp.minimum(t, last), 0)

    def kvs_map(x, t, len_ref):
        last = jnp.maximum((len_ref[0] + bt - 1) // bt - 1, 0)
        return (x, jnp.minimum(t, last))

    def q_map(x, t, len_ref):
        return (x, 0, 0)

    in_specs = [
        pl.BlockSpec((bx, rows, d), q_map),
        pl.BlockSpec((bx, bt, d), kv_map),
        pl.BlockSpec((bx, bt, d), kv_map),
    ]
    args = [qx, kx, vx]
    if quant:
        in_specs += [pl.BlockSpec((bx, bt), kvs_map),
                     pl.BlockSpec((bx, bt), kvs_map)]
        args += [ks, vs]
    if lens is not None:
        # per-stream (kv_len, q_len) pairs ride as a [X, 2] operand
        # whose block walks the x grid axis — each bx-slab sees its own
        # lengths
        in_specs += [pl.BlockSpec((bx, 2),
                                  lambda x, t, len_ref: (x, 0))]
        args += [lens.reshape(X, 2)]

    if partial:
        out_shape = (jax.ShapeDtypeStruct((X, rows, d), jnp.float32),
                     jax.ShapeDtypeStruct((X, rows), jnp.float32),
                     jax.ShapeDtypeStruct((X, rows), jnp.float32))
        out_specs = (pl.BlockSpec((bx, rows, d), q_map),
                     pl.BlockSpec((bx, rows), lambda x, t, len_ref: (x, 0)),
                     pl.BlockSpec((bx, rows), lambda x, t, len_ref: (x, 0)))
    else:
        out_shape = jax.ShapeDtypeStruct((X, rows, d), qx.dtype)
        out_specs = pl.BlockSpec((bx, rows, d), q_map)

    scalars = jnp.stack([jnp.asarray(kv_len, jnp.int32),
                         jnp.asarray(q_off, jnp.int32)])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(X // bx, pl.cdiv(T, bt)),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((bx, rows), jnp.float32),
                pltpu.VMEM((bx, rows), jnp.float32),
                pltpu.VMEM((bx, rows, d), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret_mode(),
    )(scalars, *args)


def kv_update(cache, new, tile_pos):
    """In-place KV-cache row insert at row 8*tile_pos:
    cache[:, :, 8*tile_pos : 8*tile_pos + S, :] = new, as ONE strided
    DMA on an ALIASED buffer.

    XLA's dynamic_update_slice on a multi-GB cache carried through the
    decode scan costs ~30us per 131KB slice (sub-tile scatter +
    copy-on-write); the aliased Pallas op writes just the rows. The
    position is passed as a TILE index and multiplied by 8 inside the
    kernel — Mosaic must statically prove the sublane start is
    8-aligned, which `t8 * 8` is and a raw traced `pos` is not. S must
    be a multiple of 8 (whole sublane tiles).

    cache: [B, H, T, d] (any dtype); new: [B, H, S, d]."""
    S = new.shape[2]
    assert S % 8 == 0, f"kv_update writes whole 8-row tiles (S={S})"

    def kern(t8_ref, u_ref, c_in_ref, o_ref, sem):
        del c_in_ref   # the same buffer as o_ref (aliased)
        cp = pltpu.make_async_copy(
            u_ref, o_ref.at[:, :, pl.ds(t8_ref[0] * 8, S), :], sem)
        cp.start()
        cp.wait()

    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},
        interpret=interpret_mode(),
    )(jnp.asarray(tile_pos, jnp.int32).reshape(1), new, cache)


def attention_cached_ref(q, k, v, kv_len, *, scale: Optional[float] = None,
                         q_lens=None, window: int = 0):
    """jnp oracle for flash_decode (same layout/contract): masked f32
    softmax over the full static T — the role the torch attention plays
    for the reference's differential tests. kv_len may be a scalar
    (uniform batch) or a [B] vector (per-slot lengths, the
    continuous-batching contract of flash_decode(kv_lens=...)).
    q_lens [B] (requires vector kv_len) is the speculative-verify
    contract: slot b's first q_lens[b] query rows are its draft window
    ending at kv_len[b] - 1, causal within the window; padded rows
    clamp to the last valid row (discarded by the caller)."""
    B, S, Hq, d = q.shape
    _, Hkv, T, _ = k.shape
    rep = Hq // Hkv
    if scale is None:
        scale = d ** -0.5
    qg = q.reshape(B, S, Hkv, rep, d)
    logits = jnp.einsum("bsgrd,bgtd->bgsrt", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    si = jax.lax.broadcasted_iota(jnp.int32, (S, T), 0)
    ti = jax.lax.broadcasted_iota(jnp.int32, (S, T), 1)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    if q_lens is not None:
        ql = jnp.asarray(q_lens, jnp.int32)[:, None, None]    # [B, 1, 1]
        frontier = (kv_len[:, None, None] - ql
                    + jnp.minimum(si[None], ql - 1))
        mask = ti[None] <= frontier
    elif kv_len.ndim == 0:
        mask = (ti <= (si + (kv_len - S)))[None]              # [1, S, T]
        if window:
            mask = mask & (ti > (si + (kv_len - S)) - window)[None]
    else:
        mask = ti[None] <= (si[None] + (kv_len[:, None, None] - S))
    logits = jnp.where(mask[:, None, :, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgsrt,bgtd->bsgrd", p, v.astype(jnp.float32))
    return out.reshape(B, S, Hq, d).astype(q.dtype)
