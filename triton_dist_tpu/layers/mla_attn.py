"""Multi-head LATENT attention (MLA, DeepSeek-V2/V3): low-rank q and kv
projections with an RMSNorm between, RoPE on a 64-wide slice only, and
a cache that holds ONE latent row a position instead of per-head K and V.

    c_q = RMSNorm(u W_qa);  q = c_q W_qb -> [H, nope | rope]
    a = u W_kva;  c_kv = RMSNorm(a[:rank]);  k_pe = RoPE(a[rank:])
    [k_nope | v] = c_kv W_kvb -> [H, nope | vd]
    o = softmax(([q_nope | RoPE(q_pe)] . [k_nope | k_pe]) s) v;  out = o W_o

One class, TWO attends, two factorisations of the same attention
(tests/test_deepseek_v3.py ties them on the same rows):

- `prefill` (EXPANDED): k_nope and v are computed for the whole prompt
  and the attention runs per head through the cached-attention kernel
  (kernels/flash_attn.py `flash_decode`, which takes ONE head size):
  q and k (192 wide) and v (128) are zero-padded to 256. Exact; it costs
  (256 + 256) / (192 + 128) = 1.6x the attention's own FLOPs, which are
  ~1 % of a 1,024-token admission's (the projections are the rest). The
  rows [c_kv | k_pe | pad] go to the latent pool.
- `decode` (ABSORBED): W_kvb is held split per head as W_uk [H, nope,
  rank] and W_uv [H, rank, vd]; q_lat = q_nope W_uk, score = q_lat .
  c_kv + q_pe . k_pe, o_lat = softmax(score s) . c_kv, o = o_lat W_uv:
  every head reads the SAME latent rows, so the paged walk
  (kernels/paged_kv.py, `v_cols`) serves one stream a slot with rep =
  H query rows, values = the first `rank` columns of the key block.
  Expanding K and V in decode would be a different cache (H x (qk + vd)
  values a position, 71x the bytes at the published sizes).

RoPE pairs dim i with dim i + rope / 2 (half-split), on q_pe and k_pe
alike; the pool stores k_pe already roped. YaRN scaling: `yarn_tables`.
Single chip: the mesh axis must have size 1 (models/deepseek.py refuses
a wider one by name).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.kernels.paged_kv import gather_pages, set_page_rows
from triton_dist_tpu.kernels.quant import qmm
from triton_dist_tpu.layers.common import rms_norm

_PREFILL_Q = 256      # query rows per attention call of a prefill
_PAD_HEAD = 256       # the head size q, k and v are padded to there


def yarn_mscale(factor: float, a: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * a * math.log(factor) + 1.0


def yarn_tables(rope_dim: int, max_seq: int, theta: float, *, factor: float,
                original_max: int, beta_fast: float, beta_slow: float,
                mscale: float, mscale_all_dim: float):
    """(cos, sin) [max_seq, rope_dim / 2] float32 of YaRN-scaled RoPE:
    the frequencies below the `beta_fast` correction dim are left, those
    above the `beta_slow` one divided by `factor`, a linear ramp
    between; cos/sin scaled by m(factor, mscale) / m(factor,
    mscale_all_dim)."""
    corr = lambda r: rope_dim * math.log(  # noqa: E731
        original_max / (2 * math.pi * r)) / (2 * math.log(theta))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), rope_dim - 1)
    i = np.arange(rope_dim // 2, dtype=np.float64)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    base = theta ** (-2.0 * i / rope_dim)
    inv = base / factor * ramp + base * (1.0 - ramp)
    m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    f = np.outer(np.arange(max_seq, dtype=np.float64), inv)
    return (jnp.asarray(np.cos(f) * m, jnp.float32),
            jnp.asarray(np.sin(f) * m, jnp.float32))


def _rope(x, c, s):
    """x [M, rope] or [M, H, rope]; c, s [M, rope / 2]: the tables' rows
    at each row's own position (the caller gathers them once a forward,
    not once a layer and projection); half-split."""
    if x.ndim == 3:
        c, s = c[:, None], s[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MLA_Attn:
    w_qa: jax.Array         # [D, q_rank]
    q_norm: jax.Array       # [q_rank]
    w_qb: jax.Array         # [q_rank, H (nope + rope)]
    w_kva: jax.Array        # [D, rank + rope]
    kv_norm: jax.Array      # [rank]
    w_uk: jax.Array         # [H, nope, rank]: W_kvb's k_nope columns
    w_uv: jax.Array         # [H, rank, vd]:   W_kvb's v columns
    w_o: jax.Array          # [H vd, D]
    n_heads: int = dataclasses.field(metadata=dict(static=True))
    nope: int = dataclasses.field(metadata=dict(static=True))
    rope: int = dataclasses.field(metadata=dict(static=True))
    vd: int = dataclasses.field(metadata=dict(static=True))
    scale: float = dataclasses.field(metadata=dict(static=True))
    eps: float = dataclasses.field(metadata=dict(static=True))

    @staticmethod
    def init(w_qa, q_norm, w_qb, w_kva, kv_norm, w_kvb, w_o, *,
             n_heads: int, nope: int, rope: int, vd: int, scale: float,
             eps: float = 1e-6) -> "MLA_Attn":
        """From the published matrices: W_kvb [rank, H (nope + vd)] is
        split per head into its absorbed halves (the same numbers, no
        second copy)."""
        rank = w_kvb.shape[0]
        kvb = jnp.asarray(w_kvb).reshape(rank, n_heads, nope + vd)
        return MLA_Attn(
            w_qa=w_qa, q_norm=q_norm, w_qb=w_qb, w_kva=w_kva,
            kv_norm=kv_norm,
            w_uk=jnp.transpose(kvb[:, :, :nope], (1, 2, 0)),
            w_uv=jnp.transpose(kvb[:, :, nope:], (1, 0, 2)),
            w_o=w_o, n_heads=n_heads, nope=nope, rope=rope, vd=vd,
            scale=float(scale), eps=float(eps))

    @property
    def rank(self) -> int:
        return self.w_uk.shape[2]

    # -- the projections both attends share ----------------------------

    def _q(self, u, cos, sin):
        """u [M, D], cos / sin [M, rope / 2] (its positions' rows) ->
        (q_nope [M, H, nope], q_pe [M, H, rope] roped)."""
        c_q = rms_norm(qmm(u, self.w_qa), self.q_norm, self.eps)
        q = qmm(c_q, self.w_qb).reshape(
            u.shape[0], self.n_heads, self.nope + self.rope)
        return q[..., :self.nope], _rope(q[..., self.nope:], cos, sin)

    def latent_rows(self, u, cos, sin, width: int):
        """u [M, D] -> the pool's rows [M, width] = [c_kv | k_pe | 0]."""
        a = qmm(u, self.w_kva)
        c_kv = rms_norm(a[:, :self.rank], self.kv_norm, self.eps)
        k_pe = _rope(a[:, self.rank:], cos, sin)
        pad = jnp.zeros((u.shape[0], width - self.rank - self.rope),
                        u.dtype)
        return jnp.concatenate([c_kv, k_pe, pad], axis=-1)

    def _out(self, o):
        """o [M, H, vd] -> [M, D]."""
        return qmm(o.reshape(o.shape[0], -1).astype(self.w_o.dtype),
                   self.w_o)

    # -- prefill: expanded, one prompt ---------------------------------

    def prefill(self, u, cos, sin, pool, dest, prow, *, impl: str):
        """u [P, D]: a prompt at positions 0 .. P-1 (padded rows past
        its end have dest == the trash page); cos / sin [P, rope / 2]:
        the tables' first P rows; dest/prow [P]: the page and in-page
        row of each position. Writes the latent rows and returns
        (attention output [P, D], pool)."""
        from triton_dist_tpu.kernels.flash_attn import (
            attention_cached_ref, flash_decode)
        P_, H = u.shape[0], self.n_heads
        width = pool.shape[-1]
        rows = self.latent_rows(u, cos, sin, width)
        pool = set_page_rows(pool, dest, prow, rows[:, None, :])
        with jax.named_scope("mla_prefill"):
            q_nope, q_pe = self._q(u, cos, sin)
            c_kv = rows[:, :self.rank]
            k_pe = rows[:, self.rank:self.rank + self.rope]
            k_nope = jnp.einsum("tr,hnr->thn", c_kv, self.w_uk)
            v = jnp.einsum("tr,hrv->thv", c_kv, self.w_uv)

            def padded(*parts):
                n = sum(p.shape[-1] for p in parts)
                z = jnp.zeros((P_, H, _PAD_HEAD - n), u.dtype)
                return jnp.concatenate(
                    [p.astype(u.dtype) for p in parts] + [z], axis=-1)

            q = padded(q_nope, q_pe)
            k = padded(k_nope,
                       jnp.broadcast_to(k_pe[:, None], (P_, H, self.rope)))
            kt = jnp.swapaxes(k, 0, 1)[None]            # [1, H, P, 256]
            vt = jnp.swapaxes(padded(v), 0, 1)[None]
            if impl == "ref":
                o = attention_cached_ref(q[None], kt, vt, jnp.int32(P_),
                                         scale=self.scale)[0]
            else:
                outs = []
                for c0 in range(0, P_, _PREFILL_Q):
                    c1 = min(P_, c0 + _PREFILL_Q)
                    outs.append(flash_decode(
                        q[None, c0:c1], kt[:, :, :c1], vt[:, :, :c1],
                        jnp.int32(c1), scale=self.scale)[0])
                o = jnp.concatenate(outs, axis=0)
        return self._out(o[..., :self.vd]), pool

    # -- decode: absorbed, one token for every slot --------------------

    def decode(self, u, cos, sin, pool, table, pos, *, impl: str):
        """u [B, D], pos [B]: each slot's new token at its own
        position; cos / sin [B, rope / 2]: the tables' rows there.
        Appends its latent row and attends the slot's pages in absorbed
        form. Returns (attention output [B, D], pool)."""
        from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
        B = u.shape[0]
        page, width = pool.shape[2], pool.shape[3]
        rows = self.latent_rows(u, cos, sin, width)
        pidx = table[jnp.arange(B), pos // page]
        pool = set_page_rows(pool, pidx, pos % page, rows[:, None, :])
        q_nope, q_pe = self._q(u, cos, sin)
        with jax.named_scope("mla_absorb"):
            q_lat = jnp.einsum("bhn,hnr->bhr", q_nope, self.w_uk)
        pad = jnp.zeros((B, self.n_heads, width - self.rank - self.rope),
                        u.dtype)
        q = jnp.concatenate([q_lat.astype(u.dtype), q_pe, pad], axis=-1)
        lens = pos + 1
        with jax.named_scope("mla_decode"):
            if impl == "ref":
                kv = gather_pages(pool, table)[:, 0].astype(jnp.float32)
                s = jnp.einsum("bhd,btd->bht", q.astype(jnp.float32),
                               kv) * self.scale
                live = jnp.arange(kv.shape[1])[None, None] \
                    < lens[:, None, None]
                p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
                o_lat = jnp.einsum("bht,btr->bhr", p,
                                   kv[..., :self.rank]).astype(u.dtype)
            else:
                o_lat = flash_decode_paged(
                    q[:, None].astype(pool.dtype), pool, None, table,
                    jnp.max(lens), scale=self.scale, kv_lens=lens,
                    v_cols=self.rank)[:, 0]
        with jax.named_scope("mla_absorb"):
            o = jnp.einsum("bhr,hrv->bhv", o_lat.astype(u.dtype),
                           self.w_uv)
        return self._out(o), pool
