"""Learned sparse attention (the DeepSeek sparse-attention indexer, as
Keye-VL-2.0's `sa_config` sizes it): the two pieces no paged walk has.

    I[t, s] = scale * sum_j w[t, j] relu(qI[t, j] . kI[s])     (float32)
    S_t     = the `k` positions s <= t with the largest I[t, s]

`index_scores` computes I for a block of query rows against CONTIGUOUS
keys (a prompt being admitted); the decode step's keys lie in the paged
pool's index plane and are scored by kernels/paged_kv.py
`index_scores_paged`. `selected_attention` is the admission's attention
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

_BT = 512          # key positions per grid step of `index_scores`
_BITS = 2          # bits of the threshold settled per pass over a row


def _index_scores_kernel(scale: float, len_ref, q_ref, w_ref, k_ref,
                         o_ref):
    """Grid (T / bt,): q [Hi, M, d], w [Hi, M, 1] f32, k [bt, d] ->
    o [M, bt] f32. A tile wholly past `len_ref[0]` keys is skipped (its
    output is whatever was there: the caller masks by length)."""
    bt = k_ref.shape[0]

    @pl.when(pl.program_id(0) * bt < len_ref[0])
    def _():
        k = k_ref[...]
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for j in range(q_ref.shape[0]):
            s = jax.lax.dot_general(
                q_ref[j], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [M, bt]
            acc = acc + jnp.maximum(s, 0.0) * w_ref[j]
        o_ref[...] = acc * scale


def index_scores(qi, w, ki, kv_len, *, scale: float):
    """qi [M, Hi, d] and ki [T, d] (one dtype), w [M, Hi] float32,
    kv_len: traced scalar, the keys that count. Returns [M, T'] float32
    (T' = T rounded up to the key tile): score[t, s] for s < kv_len,
    anything past it. No causal mask: the caller's selection has it."""
    M, Hi, d = qi.shape
    T = ki.shape[0]
    bt = min(_BT, T)
    Tp = -(-T // bt) * bt
    if Tp != T:
        ki = jnp.pad(ki, ((0, Tp - T), (0, 0)))
    return pl.pallas_call(
        functools.partial(_index_scores_kernel, float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Tp // bt,),
            in_specs=[
                pl.BlockSpec((Hi, M, d), lambda t, n: (0, 0, 0)),
                pl.BlockSpec((Hi, M, 1), lambda t, n: (0, 0, 0)),
                pl.BlockSpec((bt, d), lambda t, n: (t, 0))],
            out_specs=pl.BlockSpec((M, bt), lambda t, n: (0, t)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, Tp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret_mode(),
        name="sa_index",
    )(jnp.asarray(kv_len, jnp.int32).reshape(1),
      jnp.swapaxes(qi, 0, 1),
      jnp.swapaxes(jnp.asarray(w, jnp.float32), 0, 1)[..., None], ki)


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
