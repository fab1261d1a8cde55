"""Learned sparse attention (the DeepSeek sparse-attention indexer, as
Keye-VL-2.0's `sa_config` sizes it): the pieces no paged walk has.

    I[t, s] = scale * sum_j w[t, j] relu(qI[t, j] . kI[s])     (float32)
    S_t     = the `k` positions s <= t with the largest I[t, s]

The index keys are no part of the paged pool: a slot's keys lie in ONE
RUN of its own plane (kv_cache.IndexedSlotCache `pages_i`, [slots, L /
2, lanes]), addressed by (slot, position), two positions a row: within
each block of T positions (`index_block`), row r holds position r in
its first half of the lanes and position r + T / 2 in its second
(`pack_index_keys`, `append_index_keys`, `unpack_index_keys`). A block of
the plane is one contiguous copy of thousands of positions (256 KiB at
T = 2,048 and 64-wide bf16 keys: the published 128 B a position), where
a 16-position page was 4 KiB and the walk was bound by the NUMBER of
its copies (PERF.md, PR 39 and PR 40).

`index_scores` computes I for every slot's query rows against its run
of keys: ONE kernel for both attends, the grid over (slots, blocks),
BlockSpec-pipelined, each slot's length scalar-prefetched. The decode
step calls it with every slot's one row over the plane; the admission
with one "slot" of 256 query rows over the prompt's fresh keys, packed
as the plane holds them (the same array is what the admission writes to
the slot's rows). The queries are padded [q | 0] and [0 | q]: a row of
two keys dotted with them gives each key's own score, and the zeros add
exact zeros. `selected_attention` is the admission's attention
under the sets: cached attention over contiguous K and V with an
ADDITIVE mask a (query, key) pair (0 where chosen, -inf where not; the
causal frontier is inside it), one mask for every head.
`select_topk` turns a row of scores into its set,
exactly: the k-th largest score is found bit by bit on an
order-preserving integer image of the floats (no sort; a pass is one
compare and one count over the row), ties at that score go to the lower
positions, as `jax.lax.top_k` breaks them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.runtime import interpret_mode

# Positions per block of an index plane, and per grid step of
# `index_scores`: 256 KiB of 64-wide bf16 keys a copy. A plane shorter
# than this is one block.
INDEX_BLOCK = 2048
_HALF_LANES = 64   # a key's half of a row, in lanes: 128-lane rows
_ROWS_STEP = 512   # keys per product of an admission's query rows
_BITS = 2          # bits of the threshold settled per pass over a row


def index_plane_shape(max_seq: int, index_dim: int):
    """(rows, lanes) of one slot's run of an index plane that holds
    `max_seq` positions: `max_seq` rounded up to the kernel's block (to
    256, one block, where it is shorter than INDEX_BLOCK), two positions
    a row, a key in half a row."""
    L = -(-max_seq // 256) * 256
    if L > INDEX_BLOCK:
        L = -(-max_seq // INDEX_BLOCK) * INDEX_BLOCK
    return L // 2, 2 * -(-index_dim // _HALF_LANES) * _HALF_LANES


def index_block(rows: int) -> int:
    """T, the positions of a block of a plane of `rows` rows a slot."""
    return min(INDEX_BLOCK, 2 * rows)


def pack_index_keys(ki, rows: int, lanes: int):
    """ki [P, d]: the keys of positions 0 .. P-1 -> [P' / 2, lanes], the
    first rows of a slot's run in a plane of `rows` rows a slot (P' = P
    rounded up to the plane's block; zeros past P and in the lanes a
    key does not fill)."""
    P_, d = ki.shape
    block = index_block(rows)
    h, w = block // 2, lanes // 2
    nb = -(-P_ // block)
    ki = jnp.pad(ki, ((0, nb * block - P_), (0, w - d)))
    return ki.reshape(nb, 2, h, w).swapaxes(1, 2).reshape(nb * h, lanes)


def append_index_keys(plane, ki, pos):
    """ki [B, d], pos [B]: slot b's key of position pos[b] into its half
    of its row of the plane [B, L / 2, lanes]. The row's other half is
    another position's: the row is read, merged and written back, the
    two leading dims indexed (the form that updates in place in the
    served scan: kernels/paged_kv.py `set_page_rows`)."""
    B, R, lanes = plane.shape
    h, w = index_block(R) // 2, lanes // 2
    off = pos % (2 * h)
    slot, row = jnp.arange(B), pos // (2 * h) * h + off % h
    new = jnp.pad(ki.astype(plane.dtype), ((0, 0), (0, w - ki.shape[1])))
    mine = (jnp.arange(lanes) // w)[None] == (off // h)[:, None]
    return plane.at[slot, row].set(
        jnp.where(mine, jnp.tile(new, 2), plane[slot, row]))


def unpack_index_keys(plane, d: int):
    """The oracle's read of a plane [..., L / 2, lanes]: the keys by
    position, [..., L, d]."""
    lead, (R, lanes) = plane.shape[:-2], plane.shape[-2:]
    h = index_block(R) // 2
    k = plane.reshape(lead + (R // h, h, 2, lanes // 2))
    return jnp.swapaxes(k, -3, -2).reshape(lead + (2 * R, lanes // 2))[
        ..., :d]


def _index_kernel(scale: float, len_ref, q_ref, w_ref, k_ref, o_ref):
    """Grid (slots, L / T). q [1, 2 Hi M, lanes]: a slot's M query rows
    of Hi heads, rows in (half, head, row) order, half 0 the queries as
    [q | 0] and half 1 as [0 | q]; w [1, Hi M, 1] f32; k [1, T / 2,
    lanes]: a block of the slot's run -> o [1, M, T] f32, the block's
    first T / 2 positions then its second. A block wholly past the
    slot's `len_ref` keys is skipped (the caller's index map asks for
    the slot's last block again, so nothing is fetched either, and its
    output is whatever was there: the caller masks by length)."""
    h = k_ref.shape[1]
    M = o_ref.shape[1]
    Hi = w_ref.shape[1] // M
    left = len_ref[pl.program_id(0)] - pl.program_id(1) * 2 * h
    dot = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    if M == 1:
        # a decode row: both halves' heads are the rows of ONE product
        @pl.when(left > 0)
        def _():
            s = jnp.maximum(dot(q_ref[0], k_ref[0]), 0.0)    # [2 Hi, h]
            for half in range(2):
                o_ref[0, :, half * h:(half + 1) * h] = jnp.sum(
                    s[half * Hi:(half + 1) * Hi] * w_ref[0], axis=0,
                    keepdims=True) * scale
        return

    # an admission's rows: a head at a time over _ROWS_STEP keys, each
    # step skipped alone once it lies past the keys that count
    step = min(h, _ROWS_STEP)
    for p0 in range(0, 2 * h, step):
        @pl.when(left > p0)
        def _(p0=p0):
            k = k_ref[0, p0 % h:p0 % h + step]
            acc = jnp.zeros((M, step), jnp.float32)
            for j in range(Hi):
                r0 = (p0 // h * Hi + j) * M
                acc = acc + jnp.maximum(
                    dot(q_ref[0, r0:r0 + M], k), 0.0
                ) * w_ref[0, j * M:(j + 1) * M]
            o_ref[0, :, p0:p0 + step] = acc * scale


def index_scores(qi, w, keys, kv_lens, *, scale: float):
    """qi [S, M, Hi, d] (the keys' dtype), w [S, M, Hi] float32, keys
    [S, L / 2, lanes]: each slot's run of an index plane (or a prompt's
    `pack_index_keys`), kv_lens [S]: the keys that count of each.
    Returns [S, M, L] float32: score[s, m, p] = scale * sum_j w[s, m, j]
    relu(qi[s, m, j] . key[s, p]) for p < kv_lens[s], anything past the
    slot's last block with keys. No causal mask: the caller's selection
    has it."""
    S, M, Hi, d = qi.shape
    _, R, lanes = keys.shape
    T = index_block(R)
    h = T // 2
    # rows (half, head, query row): [q | 0] then [0 | q]
    qt = jnp.swapaxes(qi, 1, 2).astype(keys.dtype)
    q2 = jnp.stack([jnp.pad(qt, ((0, 0),) * 3 + ((off, lanes - off - d),))
                    for off in (0, lanes // 2)], 1)
    wt = jnp.swapaxes(jnp.asarray(w, jnp.float32), 1, 2)

    def blk(s, t, n):
        # past the slot's last block with keys that block is asked for
        # again: the pipeline elides the copy, and the write-back
        return jnp.minimum(t, jnp.maximum((n[s] + T - 1) // T - 1, 0))

    return pl.pallas_call(
        functools.partial(_index_kernel, float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, R // h),
            in_specs=[
                pl.BlockSpec((1, 2 * Hi * M, lanes),
                             lambda s, t, n: (s, 0, 0)),
                pl.BlockSpec((1, Hi * M, 1), lambda s, t, n: (s, 0, 0)),
                pl.BlockSpec((1, h, lanes),
                             lambda s, t, n: (s, blk(s, t, n), 0))],
            out_specs=pl.BlockSpec((1, M, T),
                                   lambda s, t, n: (s, 0, blk(s, t, n))),
        ),
        out_shape=jax.ShapeDtypeStruct((S, M, 2 * R), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
        name="sa_index",
    )(jnp.asarray(kv_lens, jnp.int32).reshape(S),
      q2.reshape(S, 2 * Hi * M, lanes), wt.reshape(S, Hi * M, 1), keys)


def index_scores_ref(qi, w, ki, *, scale: float):
    """The oracle of both index kernels: [M, T] float32."""
    s = jnp.einsum("mhd,td->mht", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(s, 0.0)
                   * jnp.asarray(w, jnp.float32)[..., None], axis=1) * scale


def _sel_attn_kernel(scale: float, len_ref, q_ref, k_ref, v_ref, b_ref,
                     o_ref, m_scr, l_scr, acc_scr):
    """Grid (Hkv, T / bt): q [1, rep, S, d] (a kv head's `rep` query
    heads), k / v [1, bt, d], b [S, bt] (additive, 0 or -inf) -> o
    [1, rep, S, d]. Online softmax over the key tiles; a tile wholly
    past `len_ref[0]` keys is skipped. A masked pair is exp(-inf) = 0
    whatever the running maximum (which starts finite), so no compare
    or select runs on a score tile: the mask is one add."""
    t = pl.program_id(1)
    bt = k_ref.shape[1]

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -1e30, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(t * bt < len_ref[0])
    def _compute():
        k, v = k_ref[0], v_ref[0]
        b = b_ref[...].astype(jnp.float32)
        for r in range(q_ref.shape[1]):
            s = jax.lax.dot_general(
                q_ref[0, r], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale + b  # [S, bt]
            m_prev = m_scr[r]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[r] = l_scr[r] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_scr[r] = acc_scr[r] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[r] = m_new

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def selected_attention(q, k, v, sel, kv_len, *, scale: float):
    """q [S, Hq, d]; k, v [Hkv, T, d]; sel [S, T] bool: the keys query
    s attends (inside its causal frontier: the caller's selection has
    it); kv_len: traced scalar, no key at or past it is chosen by any
    row. Returns [S, Hq, d]: softmax over the chosen keys, one set for
    every head."""
    S, Hq, d = q.shape
    Hkv, T, _ = k.shape
    rep = Hq // Hkv
    bt = next(b for b in (512, 256, 128, T) if T % b == 0)
    qx = q.reshape(S, Hkv, rep, d).transpose(1, 2, 0, 3)
    bias = jnp.where(sel, 0.0, -jnp.inf).astype(jnp.bfloat16)

    def tile(h, t, n):
        # past the last tile with keys the same block is asked for
        # again, and the pipeline elides the copy
        return jnp.minimum(t, jnp.maximum((n[0] + bt - 1) // bt - 1, 0))

    out = pl.pallas_call(
        functools.partial(_sel_attn_kernel, float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Hkv, T // bt),
            in_specs=[
                pl.BlockSpec((1, rep, S, d), lambda h, t, n: (h, 0, 0, 0)),
                pl.BlockSpec((1, bt, d),
                             lambda h, t, n: (h, tile(h, t, n), 0)),
                pl.BlockSpec((1, bt, d),
                             lambda h, t, n: (h, tile(h, t, n), 0)),
                pl.BlockSpec((S, bt), lambda h, t, n: (0, tile(h, t, n)))],
            out_specs=pl.BlockSpec((1, rep, S, d),
                                   lambda h, t, n: (h, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((rep, S, 1), jnp.float32),
                            pltpu.VMEM((rep, S, 1), jnp.float32),
                            pltpu.VMEM((rep, S, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Hkv, rep, S, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
        name="sa_prefill",
    )(jnp.asarray(kv_len, jnp.int32).reshape(1), qx, k, v, bias)
    return out.transpose(2, 0, 1, 3).reshape(S, Hq, d)


def _ordered_bits(x):
    """float32 -> uint32, order-preserving (a larger float is a larger
    integer), every finite float above 0."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def select_topk(scores, valid, k: int):
    """scores [R, L] float32, valid [R, L] bool -> [R, L] bool: each
    row's k valid positions of largest score (all of them where there
    are k or fewer), ties at the k-th score to the lower positions.

    The k-th largest of a row is built from its top bit down: a
    candidate keeps its bits if at least k of the row's images are at
    or above it. `_BITS` bits a pass (2**_BITS - 1 candidates counted
    from one read of the row: two bits read 0.346 ms a [256, 16384]
    block on the chip, one 0.420, four 0.467), 32 / _BITS passes,
    whatever k and L."""
    keys = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))
    k = jnp.int32(k)
    kth = jnp.zeros(keys.shape[:1], jnp.uint32)
    # unrolled: 32 / _BITS fused passes and no loop for a trace to read
    # as one operation
    for shift in range(32 - _BITS, -1, -_BITS):
        best = kth
        for m in range(1, 1 << _BITS):
            cand = kth | jnp.uint32(m << shift)
            enough = jnp.sum(keys >= cand[:, None], axis=-1,
                             dtype=jnp.int32) >= k
            best = jnp.where(enough, cand, best)
        kth = best
    above = keys > kth[:, None]
    tie = (keys == kth[:, None]) & valid
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    # more ties at the k-th score than places left: the lower positions
    # take them. Rare (a score repeated exactly), so the running count
    # over the row is made only when some row needs it
    crowded = jnp.any(jnp.sum(tie, axis=-1, dtype=jnp.int32) > room)
    return above | jax.lax.cond(
        crowded,
        lambda: tie & (jnp.cumsum(tie.astype(jnp.int32), axis=-1)
                       <= room[:, None]),
        lambda: tie)
