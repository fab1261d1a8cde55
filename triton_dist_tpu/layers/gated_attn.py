"""Grouped-query attention with QK-norm, an OUTPUT GATE and a per-layer
choice of position encoding and span (Trinity's `afmoe` layers,
models/afmoe.py):

    q = RMSNorm_d(u W_q) [Hq, d];  k = RMSNorm_d(u W_k),  v = u W_v [Hkv, d]
    g = u W_g [Hq d]                                       (no biases)
    window layer (`window` > 0): q, k <- RoPE at the token's position
        (all d dims, half-split pairing), t attends t - window < s <= t
    full layer (`window` 0): NO rotary, t attends every s <= t
    o = softmax(q k^T d^-0.5) v;  y = (o * sigmoid(g)) W_o

One class, FOUR attends, over kv_cache.HybridSlotCache:

- `decode_ring` (window layer): the new key is rotated at its absolute
  position and then written to row t % window of the slot's ring, so a
  ring holds final keys, their order does not matter, and the step
  attends the ring's first min(t + 1, window) rows with the contiguous
  `flash_decode`, as models/phi4flash.py's rings.
- `decode_paged` (full layer): the new [k | v] row goes to the slot's
  page (K and V fused in one plane, one copy a page for the walk) and
  `flash_decode_paged` walks the slot's context.
- `prefill` (both): a whole prompt, `_PREFILL_Q` query rows at a time in
  ONE scanned program, each block over the span of keys it can see (a
  window layer: the window before its first row and the block itself;
  a full layer: everything up to its last row), through the cached-
  attention kernel. The caller writes the rows it gets back to the ring
  or to pages.
- `last_query` (the stack's last layer at admission): the last prompt
  position alone over the prompt's keys.

Single chip: the model refuses a wider mesh axis by name.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.kernels.flash_attn import (attention_cached_ref,
                                                flash_decode)
from triton_dist_tpu.kernels.paged_kv import (flash_decode_paged,
                                              gather_pages, set_page_rows)
from triton_dist_tpu.kernels.quant import qmm
from triton_dist_tpu.layers.common import rms_norm, rotate_rows

_PREFILL_Q = 256      # query rows per attention call of a prefill


def prefill_attention(q, k, v, *, window: int, scale: float, impl: str,
                      scope: str):
    """Causal attention of a whole prompt: q [P, Hq, d], k / v [P, Hkv,
    d]; `window` > 0 keeps the `window` keys ending at a query's own
    position. impl "flash": one scan over blocks of `_PREFILL_Q` query
    rows, every block the same program over a slice of fixed length
    (the kernel's `kv_len` and window mask cut what a block may not
    see). Returns [P, Hq, d]."""
    P_ = q.shape[0]
    kt = jnp.swapaxes(k, 0, 1)[None]            # [1, Hkv, P, d]
    vt = jnp.swapaxes(v, 0, 1)[None]
    if impl == "ref":
        with jax.named_scope(scope):
            return attention_cached_ref(q[None], kt, vt, jnp.int32(P_),
                                        scale=scale, window=window)[0]
    Q = _PREFILL_Q
    nb = -(-P_ // Q)
    Pp = nb * Q
    span = min(Pp, window + Q) if window else Pp
    assert span % 8 == 0, (window, Q)
    pad = lambda a, ax: jnp.pad(  # noqa: E731
        a, [(0, Pp - P_) if i == ax else (0, 0) for i in range(a.ndim)])
    kt, vt = pad(kt, 2), pad(vt, 2)

    def block(_, xs):
        qb, c0 = xs
        lo = jnp.clip(c0 + Q - span, 0, Pp - span)
        ks = jax.lax.dynamic_slice_in_dim(kt, lo, span, 2)
        vs = jax.lax.dynamic_slice_in_dim(vt, lo, span, 2)
        with jax.named_scope(scope):
            o = flash_decode(qb[None], ks, vs, c0 + Q - lo, scale=scale,
                             window=window)[0]
        return None, o

    _, o = jax.lax.scan(
        block, None, (pad(q, 0).reshape((nb, Q) + q.shape[1:]),
                      jnp.arange(nb, dtype=jnp.int32) * Q))
    return o.reshape((Pp,) + q.shape[1:])[:P_]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GatedAttn:
    w_in: jax.Array         # [D, (2 Hq + 2 Hkv) d]: q | k | v | g
    w_o: jax.Array          # [Hq d, D]
    q_norm: jax.Array       # [d]
    k_norm: jax.Array       # [d]
    n_heads: int = dataclasses.field(metadata=dict(static=True))
    n_kv_heads: int = dataclasses.field(metadata=dict(static=True))
    head_dim: int = dataclasses.field(metadata=dict(static=True))
    window: int = dataclasses.field(metadata=dict(static=True))
    eps: float = dataclasses.field(metadata=dict(static=True))

    @staticmethod
    def init(w_q, w_k, w_v, w_g, w_o, q_norm, k_norm, *, n_heads: int,
             n_kv_heads: int, head_dim: int, window: int,
             eps: float = 1e-5) -> "GatedAttn":
        """From the five published matrices; the four that read the
        layer's input are held side by side, one matmul a token.
        window 0: a full layer (no rotary); > 0: a window layer."""
        w_in = jnp.concatenate(
            [jnp.asarray(m) for m in (w_q, w_k, w_v, w_g)], axis=1)
        return GatedAttn(
            w_in=w_in, w_o=jnp.asarray(w_o), q_norm=jnp.asarray(q_norm),
            k_norm=jnp.asarray(k_norm), n_heads=n_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim, window=int(window),
            eps=float(eps))

    @property
    def kind(self) -> str:
        return "swa" if self.window else "full"

    @property
    def scale(self) -> float:
        return self.head_dim ** -0.5

    # -- the projections every attend shares ---------------------------

    def project(self, u, rope=None):
        """u [M, D]; rope: (cos, sin) rows [M, d / 2] at each row's
        position, read by a window layer only -> q [M, Hq, d], k, v
        [M, Hkv, d], g [M, Hq d]."""
        M = u.shape[0]
        Hq, Hkv, d = self.n_heads, self.n_kv_heads, self.head_dim
        a = qmm(u, self.w_in)
        q, k, v, g = jnp.split(
            a, np.cumsum([Hq * d, Hkv * d, Hkv * d]).tolist(), axis=1)
        q = rms_norm(q.reshape(M, Hq, d), self.q_norm, self.eps)
        k = rms_norm(k.reshape(M, Hkv, d), self.k_norm, self.eps)
        if self.window:
            q, k = rotate_rows(q, *rope), rotate_rows(k, *rope)
        return q, k, v.reshape(M, Hkv, d), g

    def out(self, o, g):
        """o [M, Hq, d], g [M, Hq d] -> (o * sigmoid(g)) W_o [M, D]."""
        with jax.named_scope("attn_gate"):
            o = (o.reshape(g.shape).astype(jnp.float32)
                 * jax.nn.sigmoid(g.astype(jnp.float32)))
        return qmm(o.astype(self.w_o.dtype), self.w_o)

    # -- decode: one token for every slot ------------------------------

    def decode_ring(self, u, rope, ring_k, ring_v, pos, *, impl: str):
        """u [B, D], pos [B]; ring_k / ring_v [B, Hkv, window, d].
        Returns (y [B, D], ring_k, ring_v)."""
        B = u.shape[0]
        Hkv, d, W = self.n_kv_heads, self.head_dim, self.window
        q, k, v, g = self.project(u, rope)
        # the ring as [streams, rows, d] for the write: two leading
        # index dims scatter in place (models/phi4flash.py)
        X = B * Hkv
        at = (jnp.arange(X), jnp.repeat(pos, Hkv) % W)
        put = lambda ring, new: ring.reshape(X, W, d).at[at].set(  # noqa
            new.reshape(X, d).astype(ring.dtype)).reshape(ring.shape)
        ring_k, ring_v = put(ring_k, k), put(ring_v, v)
        wl = jnp.minimum(pos + 1, W)
        qq = q[:, None].astype(ring_k.dtype)
        with jax.named_scope("swa"):
            o = (flash_decode(qq, ring_k, ring_v, jnp.max(wl),
                              scale=self.scale, kv_lens=wl)
                 if impl == "flash" else
                 attention_cached_ref(qq, ring_k, ring_v, wl,
                                      scale=self.scale))[:, 0]
        return self.out(o, g), ring_k, ring_v

    def decode_paged(self, u, pool, table, pos, *, impl: str):
        """u [B, D], pos [B]; pool [NP, 2 Hkv, page, d] (a page's first
        Hkv head rows its keys, its last its values). Returns (y [B, D],
        pool)."""
        B = u.shape[0]
        Hkv, page = self.n_kv_heads, pool.shape[2]
        q, k, v, g = self.project(u)
        pool = set_page_rows(pool, table[jnp.arange(B), pos // page],
                             pos % page, jnp.concatenate([k, v], axis=1))
        lens = pos + 1
        qq = q[:, None].astype(pool.dtype)
        with jax.named_scope("full"):
            if impl == "flash":
                o = flash_decode_paged(qq, pool, None, table, jnp.max(lens),
                                       scale=self.scale, kv_lens=lens,
                                       fused=True)[:, 0]
            else:
                rows = gather_pages(pool, table)
                o = attention_cached_ref(qq, rows[:, :Hkv], rows[:, Hkv:],
                                         lens, scale=self.scale)[:, 0]
        return self.out(o, g), pool

    # -- admission: one prompt -----------------------------------------

    def prefill(self, u, rope, *, impl: str, dtype):
        """u [P, D]: a prompt at positions 0 .. P-1 (its bucket: rows
        past the prompt's end are padding, which no real row sees).
        Returns (y [P, D], k, v [P, Hkv, d] in `dtype`, as the cache
        will hold them: the keys of a window layer rotated)."""
        q, k, v, g = self.project(u, rope)
        k, v = k.astype(dtype), v.astype(dtype)
        o = prefill_attention(q.astype(dtype), k, v, window=self.window,
                              scale=self.scale, impl=impl,
                              scope=self.kind + "_prefill")
        return self.out(o, g), k, v

    def last_query(self, u, u_last, n, *, dtype):
        """The prompt's last real position (row n - 1, `u_last` [1, D])
        over the prompt's keys: u [P, D] gives K and V, one row gives
        the query and the gate. A full layer only. Returns (y [1, D],
        k, v [P, Hkv, d])."""
        assert not self.window
        Hq, Hkv, d = self.n_heads, self.n_kv_heads, self.head_dim
        # of the whole prompt the K and V columns only
        kv = qmm(u, self.w_in[:, Hq * d:(Hq + 2 * Hkv) * d]).reshape(
            u.shape[0], 2 * Hkv, d)
        k = rms_norm(kv[:, :Hkv], self.k_norm, self.eps).astype(dtype)
        v = kv[:, Hkv:].astype(dtype)
        q, _, _, g = self.project(u_last)
        with jax.named_scope("full_prefill"):
            qg = q.astype(dtype).reshape(Hkv, Hq // Hkv, d)
            s = jnp.einsum("hgd,thd->hgt", qg.astype(jnp.float32),
                           k.astype(jnp.float32)) * self.scale
            s = jnp.where((jnp.arange(k.shape[0]) < n)[None, None], s,
                          -jnp.inf)
            o = jnp.einsum("hgt,thd->hgd", jax.nn.softmax(s, axis=-1),
                           v.astype(jnp.float32))
        return self.out(o.reshape(1, Hq, d), g), k, v
