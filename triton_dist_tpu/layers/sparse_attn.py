"""Grouped-query attention with a LEARNED SPARSE SELECTION (the DeepSeek
sparse-attention indexer at Keye-VL-2.0's `sa_config` sizes): every
query attends the `topk` cached positions its indexer scores highest,
one set for all heads.

    q = RoPE(RMSNorm_d(u W_q)) [Hq, d];  k = RoPE(RMSNorm_d(u W_k)),
    v = u W_v [Hkv, d]                       (Qwen3's QK-norm; no bias)
    qI = RoPE_I(u W_qI) [Hi, di];  kI = RoPE_I(u W_kI) [di];  w = u W_w [Hi]
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) di^-0.5 Hi^-0.5  (f32)
    S_t = the topk positions s <= t of largest I[t, s] (all while t < topk)
    o[t, h] = softmax_{s in S_t}(q[t, h] . k[s, h // rep] d^-0.5) v[s, h // rep]

RoPE on q and k is multi-section (`layers/common.py rope_rows`): the d/2
frequency pairs split by `sections`, each section reading its own
component of a [3, T] position; a [T] position is a text token's. The
indexer's rotary runs over all di dims at the first component. Both
pair dim i with dim i + half (half-split).

One class, TWO attends (tests/test_keye_vl2.py ties them on the same
rows), over kv_cache.IndexedSlotCache: two planes of a position under
the page table (K and V, in one array) and one plane a SLOT beside them
(the index keys, a slot's in one run: kernels/sparse_attn.py has the
layout). Both attends score through the one kernel, `index_scores`.

- `prefill`: a whole prompt, 256 query rows at a time: the rows'
  scores against the prompt's index keys, their sets (`select_topk`),
  and attention over the prompt's own K and V under those sets
  (`selected_attention`: the mask is one add a score tile). The
  [k | v] rows go to the pool a page at a time, the packed index keys
  to the slot's rows of the index plane in one update.
- `decode`: one token a slot: its row is appended to its page and its
  index key to the slot's run, the runs of all slots are scored, the
  set is chosen, and the paged walk attends under it
  (`flash_decode_paged`, `fused`, `sel`). The walk reads every page of
  the context and masks what was not chosen: a selected position costs
  its whole page's copy either way at 2,048 of ~18,000 positions (a
  page of 16 holds a chosen position with probability 0.85), so it is
  bound by the whole context's BYTES; a copy a POSITION would be bound
  by its issue instead (PERF.md, PR 39 and PR 40).

Single chip: the mesh axis must have size 1 (the model refuses a wider
one by name).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.kernels.paged_kv import (flash_decode_paged,
                                              gather_pages, set_page_rows,
                                              set_prompt_pages)
from triton_dist_tpu.kernels.quant import qmm
from triton_dist_tpu.kernels.sparse_attn import (append_index_keys,
                                                 index_scores,
                                                 index_scores_ref,
                                                 pack_index_keys,
                                                 select_topk,
                                                 selected_attention,
                                                 unpack_index_keys)
from triton_dist_tpu.layers.common import (rms_norm, rope_rows,
                                            rotate_rows as _rope)

_PREFILL_Q = 256      # query rows per attention call of a prefill


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SA_Attn:
    w_in: jax.Array         # [D, (Hq + 2 Hkv) d + (Hi + 1) di + Hi]
    w_o: jax.Array          # [Hq d, D]
    q_norm: jax.Array       # [d]
    k_norm: jax.Array       # [d]
    n_heads: int = dataclasses.field(metadata=dict(static=True))
    n_kv_heads: int = dataclasses.field(metadata=dict(static=True))
    head_dim: int = dataclasses.field(metadata=dict(static=True))
    idx_heads: int = dataclasses.field(metadata=dict(static=True))
    idx_dim: int = dataclasses.field(metadata=dict(static=True))
    topk: int = dataclasses.field(metadata=dict(static=True))
    sections: tuple = dataclasses.field(metadata=dict(static=True))
    eps: float = dataclasses.field(metadata=dict(static=True))

    @staticmethod
    def init(w_q, w_k, w_v, w_o, q_norm, k_norm, w_qi, w_ki, w_w, *,
             n_heads: int, n_kv_heads: int, head_dim: int, idx_heads: int,
             idx_dim: int, topk: int, sections=(),
             eps: float = 1e-6) -> "SA_Attn":
        """From the seven published matrices; the six that read the
        layer's input are held side by side, one matmul a token."""
        w_in = jnp.concatenate(
            [jnp.asarray(m) for m in (w_q, w_k, w_v, w_qi, w_ki, w_w)],
            axis=1)
        return SA_Attn(
            w_in=w_in, w_o=jnp.asarray(w_o), q_norm=jnp.asarray(q_norm),
            k_norm=jnp.asarray(k_norm), n_heads=n_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim, idx_heads=idx_heads,
            idx_dim=idx_dim, topk=int(topk), sections=tuple(sections),
            eps=float(eps))

    @property
    def index_scale(self) -> float:
        return self.idx_dim ** -0.5 * self.idx_heads ** -0.5

    # -- the projections both attends share ----------------------------

    def project(self, u, rope, rope_i):
        """u [M, D]; rope / rope_i: (cos, sin) rows [M, d / 2] and
        [M, di / 2] at each row's position -> q [M, Hq, d], kv [M, 2 Hkv,
        d] (the keys, then the values: a row of the pool's K/V plane),
        qI [M, Hi, di], kI [M, di], w [M, Hi] float32."""
        M = u.shape[0]
        Hq, Hkv, d = self.n_heads, self.n_kv_heads, self.head_dim
        Hi, di = self.idx_heads, self.idx_dim
        a = qmm(u, self.w_in)
        cut = [Hq * d, Hkv * d, Hkv * d, Hi * di, di]
        q, k, v, qi, ki, w = jnp.split(a, np.cumsum(cut).tolist(), axis=1)
        q = _rope(rms_norm(q.reshape(M, Hq, d), self.q_norm, self.eps),
                  *rope)
        k = _rope(rms_norm(k.reshape(M, Hkv, d), self.k_norm, self.eps),
                  *rope)
        kv = jnp.concatenate([k, v.reshape(M, Hkv, d)], axis=1)
        return (q, kv, _rope(qi.reshape(M, Hi, di), *rope_i),
                _rope(ki, *rope_i), w.astype(jnp.float32))

    def rope_of(self, cos, sin, cos_i, sin_i, positions):
        """The four tables' rows at `positions` ([T] or [3, T])."""
        positions = jnp.asarray(positions)
        first = positions if positions.ndim == 1 else positions[0]
        return (rope_rows(cos, sin, positions, self.sections),
                (cos_i[first], sin_i[first]))

    def _out(self, o):
        return qmm(o.reshape(o.shape[0], -1).astype(self.w_o.dtype),
                   self.w_o)

    # -- prefill: one prompt --------------------------------------------

    def prefill(self, u, rope, rope_i, kv_pool, idx_pool, page_ids, slot,
                *, impl: str, return_sets: bool = False):
        """u [P, D]: a prompt at positions 0 .. P-1 (its bucket: rows
        past the prompt's end are padding); rope / rope_i as `rope_of`
        gives them; page_ids [ceil(P / page)]: the page of each 16
        positions, the trash page for a page wholly past the prompt;
        slot: traced scalar, whose run of the index plane the prompt's
        keys open. Writes the [k | v] rows a PAGE at a time and the
        index keys a BLOCK at a time (a prompt starts at a page's, and
        the run's, first row; what the padding leaves behind the prompt
        lies past the slot's length until decode overwrites it), and
        returns (attention output [P, D], kv_pool, idx_pool[, the
        selection [P, P] bool])."""
        P_ = u.shape[0]
        Hkv, d = self.n_kv_heads, self.head_dim
        q, kv, qi, ki, w = self.project(u, rope, rope_i)
        kv_pool = set_prompt_pages(kv_pool, page_ids, kv)
        # the prompt's keys as the plane holds them: what the slot's
        # run opens with, and what the blocks below score against
        kp = pack_index_keys(ki.astype(idx_pool.dtype),
                             *idx_pool.shape[1:])
        idx_pool = jax.lax.dynamic_update_slice(idx_pool, kp[None],
                                                (slot, 0, 0))
        t = jnp.arange(P_)
        if impl == "ref":
            with jax.named_scope("sa_index"):
                sc = index_scores_ref(qi, w, ki, scale=self.index_scale)
            with jax.named_scope("sa_topk"):
                sel = select_topk(sc, t[None, :] <= t[:, None], self.topk)
            with jax.named_scope("sa_prefill"):
                rep = self.n_heads // Hkv
                qg = q.reshape(P_, Hkv, rep, d).astype(jnp.float32)
                s = jnp.einsum("qhrd,khd->hrqk", qg,
                               kv[:, :Hkv].astype(jnp.float32)) * d ** -0.5
                p = jax.nn.softmax(jnp.where(sel[None, None], s, -jnp.inf),
                                   axis=-1)
                o = jnp.einsum("hrqk,khd->qhrd", p,
                               kv[:, Hkv:].astype(jnp.float32))
                o = o.reshape(P_, self.n_heads, d).astype(u.dtype)
            out = self._out(o), kv_pool, idx_pool
            return out + (sel,) if return_sets else out
        # 256 query rows at a time, one program for every block: the
        # keys of the whole (padded) prompt, of which a block reads
        # those at or before its last row
        nb = -(-P_ // _PREFILL_Q)
        Pp = nb * _PREFILL_Q
        padq = lambda a: jnp.pad(a, ((0, Pp - P_),) + ((0, 0),) * (  # noqa
            a.ndim - 1))
        kt = jnp.swapaxes(padq(kv), 0, 1)               # [2 Hkv, Pp, d]
        col = jnp.arange(Pp)

        def block(_, xs):
            qb, qib, wb, c0 = xs
            c1 = c0 + _PREFILL_Q
            causal = col[None, :] <= (c0 + jnp.arange(_PREFILL_Q))[:, None]

            def choose():
                with jax.named_scope("sa_index"):
                    sc = index_scores(qib[None], wb[None], kp[None],
                                      c1[None], scale=self.index_scale)
                with jax.named_scope("sa_topk"):
                    return select_topk(sc[0, :, :Pp], causal, self.topk)

            # a block whose rows all see topk keys or fewer attends
            # every one of them: nothing to score, nothing to choose
            sel = jax.lax.cond(c1 <= self.topk, lambda: causal, choose)
            with jax.named_scope("sa_prefill"):
                o = selected_attention(qb, kt[:Hkv], kt[Hkv:], sel, c1,
                                       scale=d ** -0.5)
            return None, ((o, sel) if return_sets else o)

        blocks = lambda a: padq(a).reshape(  # noqa: E731
            (nb, _PREFILL_Q) + a.shape[1:])
        _, ys = jax.lax.scan(
            block, None, (blocks(q), blocks(qi), blocks(w),
                          jnp.arange(nb) * _PREFILL_Q))
        o = (ys[0] if return_sets else ys).reshape(
            (Pp,) + q.shape[1:])[:P_]
        out = self._out(o), kv_pool, idx_pool
        if return_sets:
            return out + (ys[1].reshape(Pp, -1)[:P_, :P_],)
        return out

    # -- decode: one token for every slot ------------------------------

    def decode(self, u, rope, rope_i, kv_pool, idx_pool, table, pos, *,
               impl: str, return_sets: bool = False):
        """u [B, D], pos [B]: each slot's new token at its own position.
        Appends its rows, scores the slot's cached index keys, chooses,
        attends. Returns (attention output [B, D], kv_pool, idx_pool,
        positions attended [B] int32[, the selection [B, L] bool])."""
        B = u.shape[0]
        page = kv_pool.shape[2]
        Hkv, d = self.n_kv_heads, self.head_dim
        q, kv, qi, ki, w = self.project(u, rope, rope_i)
        pidx = table[jnp.arange(B), pos // page]
        kv_pool = set_page_rows(kv_pool, pidx, pos % page, kv)
        idx_pool = append_index_keys(idx_pool, ki, pos)
        lens = pos + 1
        with jax.named_scope("sa_index"):
            if impl == "ref":
                keys = unpack_index_keys(idx_pool, self.idx_dim)
                sc = jax.vmap(lambda a, b, c: index_scores_ref(
                    a[None], b[None], c, scale=self.index_scale)[0])(
                        qi, w, keys)
            else:
                sc = index_scores(qi[:, None], w[:, None], idx_pool, lens,
                                  scale=self.index_scale)[:, 0]
        with jax.named_scope("sa_topk"):
            sel = select_topk(
                sc, jnp.arange(sc.shape[1])[None] < lens[:, None],
                self.topk)
        with jax.named_scope("sa_decode"):
            if impl == "ref":
                rep = self.n_heads // Hkv
                rows = gather_pages(kv_pool, table).astype(jnp.float32)
                qg = q.reshape(B, Hkv, rep, d).astype(jnp.float32)
                s = jnp.einsum("bhrd,bhtd->bhrt", qg,
                               rows[:, :Hkv]) * d ** -0.5
                p = jax.nn.softmax(jnp.where(
                    sel[:, None, None, :s.shape[-1]], s, -jnp.inf), -1)
                o = jnp.einsum("bhrt,bhtd->bhrd", p, rows[:, Hkv:])
                o = o.reshape(B, self.n_heads, d).astype(u.dtype)
            else:
                o = flash_decode_paged(
                    q[:, None].astype(kv_pool.dtype), kv_pool, None, table,
                    jnp.max(lens), kv_lens=lens, fused=True, sel=sel)[:, 0]
        # counted from the mask the walk ran under, not from the config
        out = (self._out(o), kv_pool, idx_pool,
               jnp.sum(sel, axis=-1, dtype=jnp.int32))
        return out + (sel,) if return_sets else out
