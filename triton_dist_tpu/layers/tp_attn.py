"""Tensor-parallel attention (GQA) with the reference's mode switch.

TPU-native re-design of `python/triton_dist/layers/nvidia/tp_attn.py`
(`TP_Attn:80` — QKV AG-GEMM, flash attention, O-proj GEMM-RS :213; AR and
GEMM-AR variants :251-318; RoPE :165).

Head-parallel TP: each rank owns Hq/n query heads and Hkv/n KV heads.
The QKV projection is ONE ag_gemm over a packed [q_r | k_r | v_r] weight
(every rank's output slice is self-contained), attention runs locally on
the rank's heads over the full (gathered) sequence, and the O projection
reduces+scatters back to sequence sharding.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_dist_tpu.kernels import (ag_gemm, all_reduce,
                                     create_ag_gemm_context,
                                     create_gemm_ar_context,
                                     create_gemm_rs_context, gemm_allreduce,
                                     gemm_rs)
from triton_dist_tpu.kernels.paged_kv import gather_pages, set_page_rows
from triton_dist_tpu.layers.common import (apply_rope, apply_rope_slots,
                                           rms_norm, shard_cols_packed)


def causal_attention(q, k, v, scale: float):
    """Causal GQA attention, one device's heads, full sequence.
    q: [S, Hq, d]; k, v: [T, Hkv, d] with T >= S (suffix alignment:
    query i attends to keys <= T - S + i). f32 softmax."""
    S, Hq, d = q.shape
    T, Hkv, _ = k.shape
    rep = Hq // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("shd,thd->hst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qi = jax.lax.broadcasted_iota(jnp.int32, (S, T), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (S, T), 1)
    mask = ki <= (qi + (T - S))
    logits = jnp.where(mask[None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("hst,thd->shd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TP_Attn:
    """Weights (pytree leaves) + static head/TP config.

    w_qkv: [D, (Hq + 2*Hkv) * hd] — n per-rank blocks [q_r | k_r | v_r].
    w_o:   [Hq * hd, D] — row-parallel.
    q_norm/k_norm: per-head-dim RMSNorm weights (Qwen3 QK-norm).
    """

    w_qkv: jax.Array
    w_o: jax.Array
    q_norm: Optional[jax.Array]
    k_norm: Optional[jax.Array]
    mesh: Mesh = dataclasses.field(metadata=dict(static=True))
    axis: str = dataclasses.field(metadata=dict(static=True))
    n_heads: int = dataclasses.field(metadata=dict(static=True))
    n_kv_heads: int = dataclasses.field(metadata=dict(static=True))
    head_dim: int = dataclasses.field(metadata=dict(static=True))

    @staticmethod
    def init(w_q, w_k, w_v, w_o, *, mesh: Mesh, axis: str = "tp",
             n_heads: int, n_kv_heads: int, head_dim: int,
             q_norm=None, k_norm=None):
        n = mesh.shape[axis]
        packed = shard_cols_packed([w_q, w_k, w_v], n)
        packed = jax.device_put(packed, NamedSharding(mesh, P(None, axis)))
        w_o = jax.device_put(jnp.asarray(w_o),
                             NamedSharding(mesh, P(axis, None)))
        return TP_Attn(w_qkv=packed, w_o=w_o,
                       q_norm=None if q_norm is None else jnp.asarray(q_norm),
                       k_norm=None if k_norm is None else jnp.asarray(k_norm),
                       mesh=mesh, axis=axis, n_heads=n_heads,
                       n_kv_heads=n_kv_heads, head_dim=head_dim)

    # per-rank sizes
    @property
    def _hq_loc(self):
        return self.n_heads // self.mesh.shape[self.axis]

    @property
    def _hkv_loc(self):
        return self.n_kv_heads // self.mesh.shape[self.axis]

    def _local_attn(self, qkv, cos, sin, positions, impl: str = "flash"):
        """Split a rank's packed [q|k|v] slice, QK-norm + RoPE, causal
        attention over the rank's heads (ref: tp_attn.py:165-213).

        impl="flash" runs the differentiable Pallas flash kernel
        (kernels/flash_attn_train.py) — training through the framework
        kernel, the role the reference's autograd-wrapped flash attention
        plays; impl="ref" is the jnp full-softmax oracle."""
        from triton_dist_tpu.kernels.flash_attn_train import flash_attention
        hq, hkv, hd = self._hq_loc, self._hkv_loc, self.head_dim
        scale = hd ** -0.5
        impl = self._flash_or_ref(impl, qkv.shape[0], hq // hkv, hd,
                                  qkv.dtype)

        @functools.partial(jax.shard_map, mesh=self.mesh,
                           in_specs=P(None, self.axis),
                           out_specs=P(None, self.axis), check_vma=False)
        def f(qkv_loc):
            S = qkv_loc.shape[0]
            q = qkv_loc[:, :hq * hd].reshape(S, hq, hd)
            k = qkv_loc[:, hq * hd:(hq + hkv) * hd].reshape(S, hkv, hd)
            v = qkv_loc[:, (hq + hkv) * hd:].reshape(S, hkv, hd)
            if self.q_norm is not None:
                q = rms_norm(q, self.q_norm)
            if self.k_norm is not None:
                k = rms_norm(k, self.k_norm)
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
            if impl == "flash":
                o = flash_attention(q[None], k.transpose(1, 0, 2)[None],
                                    v.transpose(1, 0, 2)[None],
                                    scale=scale)[0]
            else:
                o = causal_attention(q, k, v, scale)
            return o.reshape(S, hq * hd)

        return f(qkv)

    def fwd_xla(self, x, cos, sin, positions):
        """Pure-XLA oracle (reference: torch_fwd): jnp + XLA psum
        collective — the torch/NCCL role from the reference. QuantW
        weights dequant via qmm."""
        from triton_dist_tpu.kernels.quant import QuantW, qmm, qspec
        if isinstance(self.w_qkv, QuantW):
            @functools.partial(
                jax.shard_map, mesh=self.mesh,
                in_specs=(P(None, None),
                          qspec(self.w_qkv, P(None, self.axis),
                                P(self.axis))),
                out_specs=P(None, self.axis), check_vma=False)
            def up(x_r, w_loc):
                return qmm(x_r, w_loc)

            qkv = up(x, self.w_qkv)
        else:
            qkv = x @ self.w_qkv
        o = self._local_attn(qkv, cos, sin, positions, impl="ref")
        return self._down_psum(o)

    def _down_psum(self, o):
        """Partial O-projection + psum epilogue (the oracle down-proj;
        w_o may be int8-quantized — the flash decode path)."""
        from triton_dist_tpu.kernels.quant import qmm, qspec

        @functools.partial(jax.shard_map, mesh=self.mesh,
                           in_specs=(P(None, self.axis),
                                     qspec(self.w_o, P(self.axis, None),
                                           P(None))),
                           out_specs=P(None, None), check_vma=False)
        def down(o_loc, wo_loc):
            return jax.lax.psum(qmm(o_loc, wo_loc), self.axis)

        return down(o, self.w_o)

    @staticmethod
    def _flash_or_ref(impl: str, S: int, rep: int, hd: int, dtype) -> str:
        """Static guard: the flash forward keeps one query CHUNK
        (query_chunk rows) of a batch block resident in VMEM; fall back
        to the jnp path when even that does not fit, rather than failing
        inside pallas_call."""
        if impl != "flash":
            return impl
        from triton_dist_tpu.kernels.flash_attn import _pick_bx
        from triton_dist_tpu.kernels.flash_attn_train import (
            DEFAULT_BLOCK_R, DEFAULT_BLOCK_T, _pick_bx_bwd, query_chunk)
        try:
            _pick_bx(1, query_chunk(S, rep, DEFAULT_BLOCK_R) * rep, hd,
                     min(DEFAULT_BLOCK_T, S), jnp.dtype(dtype).itemsize, 1)
            # the backward allocates its own (larger) footprint: probe it
            # with the same default blocks so jax.grad falls back to the
            # ref path instead of raising at trace time
            _pick_bx_bwd(1, min(DEFAULT_BLOCK_R, S * rep),
                         min(DEFAULT_BLOCK_T, S), hd,
                         jnp.dtype(dtype).itemsize)
            return "flash"
        except ValueError:
            return "ref"

    def _local_attn_train(self, qkv, cos, sin, batch: int,
                          impl: str = "flash"):
        """Batched full-causal attention for training: each of `batch`
        sequences of length M//batch attends within itself.
        impl="flash" = the differentiable Pallas kernel; "ref" = the jnp
        oracle (flash_attention_ref)."""
        from triton_dist_tpu.kernels.flash_attn_train import (
            flash_attention, flash_attention_ref)
        hq, hkv, hd = self._hq_loc, self._hkv_loc, self.head_dim
        scale = hd ** -0.5
        impl = self._flash_or_ref(impl, qkv.shape[0] // batch, hq // hkv,
                                  hd, qkv.dtype)
        attend = flash_attention if impl == "flash" else flash_attention_ref
        # every trainable (or potentially updated) array must be a
        # shard_map ARGUMENT, not a closure: closures over
        # Explicit-sharded arrays are rejected, and the q/k-norm
        # cotangents must come back psum-replicated
        norms = [a for a in (self.q_norm, self.k_norm) if a is not None]

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(P(None, self.axis), P(None, None), P(None, None))
                     + (P(None),) * len(norms),
            out_specs=P(None, self.axis), check_vma=False)
        def f(qkv_loc, cos, sin, *norms):
            ni = iter(norms)
            M = qkv_loc.shape[0]
            S = M // batch
            q = qkv_loc[:, :hq * hd].reshape(batch, S, hq, hd)
            k = qkv_loc[:, hq * hd:(hq + hkv) * hd].reshape(batch, S, hkv, hd)
            v = qkv_loc[:, (hq + hkv) * hd:].reshape(batch, S, hkv, hd)
            if self.q_norm is not None:
                q = rms_norm(q, next(ni))
            if self.k_norm is not None:
                k = rms_norm(k, next(ni))
            positions = jnp.arange(S)
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
            o = attend(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                       scale=scale)
            return o.reshape(M, hq * hd)

        return f(qkv, cos, sin, *norms)

    def fwd_train(self, x, cos, sin, batch: int, impl: str = "flash"):
        """Differentiable TP attention block for training (no KV cache):
        custom-VJP AG-GEMM -> differentiable Pallas flash attention ->
        custom-VJP GEMM-RS — the whole block trains through framework
        kernels (reference analog: the autograd Function wrappers over
        the dist ops, layers/nvidia/tp_attn.py under torch.autograd).
        impl="ref" is the pure-XLA oracle (jnp matmuls + psum + jnp
        attention) for differential gradient tests.

        x: [B*S, D] row-sharded over tp (replicated for "ref");
        returns same sharding as input convention of each path."""
        from triton_dist_tpu.kernels.grad import ag_gemm_grad, gemm_rs_grad
        if impl == "flash":
            qkv = ag_gemm_grad(self.mesh, self.axis)(x, self.w_qkv)
            o = self._local_attn_train(qkv, cos, sin, batch, impl="flash")
            return gemm_rs_grad(self.mesh, self.axis)(o, self.w_o)
        qkv = x @ self.w_qkv
        o = self._local_attn_train(qkv, cos, sin, batch, impl="ref")
        return self._down_psum(o)

    def fwd_dist(self, x, cos, sin, positions):
        """AG-GEMM -> attention -> GEMM-RS (reference: dist_triton_fwd,
        tp_attn.py:213). x: [S, D] sharded on rows."""
        ag_ctx = create_ag_gemm_context(self.mesh, self.axis)
        rs_ctx = create_gemm_rs_context(self.mesh, self.axis)
        qkv = ag_gemm(x, self.w_qkv, ag_ctx)
        o = self._local_attn(qkv, cos, sin, positions)
        return gemm_rs(o, self.w_o, rs_ctx)

    def fwd_ar(self, x, cos, sin, positions):
        """Local QKV + attention + partial O-proj + AR kernel (reference:
        AR fwd, tp_attn.py:251). x replicated; returns replicated."""
        axis = self.axis
        hq, hd = self._hq_loc, self.head_dim

        from triton_dist_tpu.kernels.quant import qmm, qspec

        @functools.partial(jax.shard_map, mesh=self.mesh,
                           in_specs=(P(None, None),
                                     qspec(self.w_qkv, P(None, axis),
                                           P(axis))),
                           out_specs=P(None, axis), check_vma=False)
        def qkv_local(x_r, w_loc):
            return qmm(x_r, w_loc)

        qkv = qkv_local(x, self.w_qkv)
        o = self._local_attn(qkv, cos, sin, positions)

        @functools.partial(jax.shard_map, mesh=self.mesh,
                           in_specs=(P(None, axis),
                                     qspec(self.w_o, P(axis, None),
                                           P(None))),
                           out_specs=P(axis, None, None), check_vma=False)
        def o_partial(o_loc, wo_loc):
            return qmm(o_loc, wo_loc)[None]

        parts = o_partial(o, self.w_o)
        del hq, hd
        return all_reduce(parts, mesh=self.mesh, axis=axis)

    def fwd_gemm_ar(self, x, cos, sin, positions):
        """Fused GEMM+AR for the O projection (reference: tp_attn.py:318)."""
        axis = self.axis

        from triton_dist_tpu.kernels.quant import qmm, qspec

        @functools.partial(jax.shard_map, mesh=self.mesh,
                           in_specs=(P(None, None),
                                     qspec(self.w_qkv, P(None, axis),
                                           P(axis))),
                           out_specs=P(None, axis), check_vma=False)
        def qkv_local(x_r, w_loc):
            return qmm(x_r, w_loc)

        qkv = qkv_local(x, self.w_qkv)
        o = self._local_attn(qkv, cos, sin, positions)
        ctx = create_gemm_ar_context(self.mesh, axis)
        return gemm_allreduce(o, self.w_o, ctx)

    def __call__(self, x, cos, sin, positions, mode: str = "dist"):
        return dict(xla=self.fwd_xla, dist=self.fwd_dist, ar=self.fwd_ar,
                    gemm_ar=self.fwd_gemm_ar)[mode](x, cos, sin, positions)

    # ------------------------------------------------------------------
    # KV-cache paths (prefill fill + decode), used by models/engine
    # (reference: tp_attn.py decode with KV cache driven by
    # models/dense.py:101 + kv_cache.py:29)
    # ------------------------------------------------------------------

    def _attend_cached(self, qkv, cos, sin, batch: int, kv, kv_start,
                       impl: str = "flash"):
        """Split a rank's packed [q|k|v] slice, write K/V into this rank's
        cache shard at kv_start, attend against the cache.

        qkv: [B*S, qkv_cols] sharded P(None, tp);
        kv: (ck, cv) with ck/cv [B, Hkv, T, hd] sharded on the head axis
            — or (ck, cv, ks, vs) for an int8 cache with per-position
            f32 scales [B, Hkv, T] (kv_cache.py kv_dtype=int8; halves
            the decode step's dominant HBM read);
        kv_start: traced scalar (0 for prefill, pos for decode);
        impl: "flash" (Pallas flash-decode kernel) or "ref" (jnp oracle).
        Returns (o [B*S, hq_loc*hd] P(None, tp), updated kv).
        """
        from triton_dist_tpu.kernels.flash_attn import (attention_cached_ref,
                                                        flash_decode)
        hq, hkv, hd = self._hq_loc, self._hkv_loc, self.head_dim
        scale = hd ** -0.5
        quant = len(kv) == 4
        cache_spec = P(None, self.axis, None, None)
        scale_spec = P(None, self.axis, None)
        kv_specs = ((cache_spec, cache_spec, scale_spec, scale_spec)
                    if quant else (cache_spec, cache_spec))

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(P(None, self.axis),) + kv_specs + (P(),),
            out_specs=((P(None, self.axis),) + kv_specs),
            check_vma=False)
        def f(qkv_loc, ck_loc, cv_loc, *rest):
            *scales, kv_start = rest
            M = qkv_loc.shape[0]
            S = M // batch
            q = qkv_loc[:, :hq * hd].reshape(batch, S, hq, hd)
            k = qkv_loc[:, hq * hd:(hq + hkv) * hd].reshape(batch, S, hkv, hd)
            v = qkv_loc[:, (hq + hkv) * hd:].reshape(batch, S, hkv, hd)
            if self.q_norm is not None:
                q = rms_norm(q, self.q_norm)
            if self.k_norm is not None:
                k = rms_norm(k, self.k_norm)
            positions = kv_start + jnp.arange(S)
            # apply_rope expects [..., S, H, d]
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
            # cache layout is head-major [B, Hkv, T, hd]
            kT = k.transpose(0, 2, 1, 3)
            vT = v.transpose(0, 2, 1, 3)

            def dus(c, u, idx):
                return jax.lax.dynamic_update_slice(c, u, idx)

            def insert(c, u, pos):
                """KV-row insert. Tile-aligned whole-tile writes
                (S % 8 == 0 AND pos % 8 == 0 — e.g. prefill at offset 0)
                go through the aliased one-DMA kv_update; XLA's DUS on
                the multi-GB carried buffer costs ~30us per slice.
                pos is traced, so the alignment pick is a lax.cond —
                an unaligned multi-row write (chunked prefill at an odd
                offset) falls back to the correct DUS instead of
                silently flooring to a tile boundary."""
                from triton_dist_tpu.kernels.flash_attn import kv_update
                if u.shape[2] % 8:
                    return dus(c, u, (0, 0, pos, 0))
                return jax.lax.cond(
                    pos % 8 == 0,
                    lambda c_, u_, p: kv_update(c_, u_, p // 8),
                    lambda c_, u_, p: dus(c_, u_, (0, 0, p, 0)),
                    c, u, pos)

            if quant:
                ks_loc, vs_loc = scales

                # the repo-wide per-position KV quantizer
                # (kernels/quant.quantize_kv_int8 — shared with the
                # int8 paged pool, so the two layouts can never drift)
                from triton_dist_tpu.kernels.quant import \
                    quantize_kv_int8 as q8

                k8, k_s = q8(kT)
                v8, v_s = q8(vT)
                ck_loc = insert(ck_loc, k8, kv_start)
                cv_loc = insert(cv_loc, v8, kv_start)
                ks_loc = dus(ks_loc, k_s, (0, 0, kv_start))
                vs_loc = dus(vs_loc, v_s, (0, 0, kv_start))
                if impl == "flash":
                    # decode (S==1): one KV tile per x-block — the walk
                    # is grid-step-latency-bound at small tiles (~2.5us
                    # fixed cost/step vs ~1us of int8 KV traffic).
                    # Capped so _pick_bx's double-buffered KV term still
                    # fits VMEM for long caches (falls back to walking).
                    bt = min(ck_loc.shape[2], 2048) if S == 1 else 256
                    o = flash_decode(q.astype(jnp.bfloat16), ck_loc,
                                     cv_loc, kv_start + S, scale=scale,
                                     k_scale=ks_loc, v_scale=vs_loc,
                                     block_t=bt)
                else:
                    o = attention_cached_ref(
                        q.astype(jnp.float32),
                        ck_loc.astype(jnp.float32) * ks_loc[..., None],
                        cv_loc.astype(jnp.float32) * vs_loc[..., None],
                        kv_start + S, scale=scale)
                return (o.reshape(M, hq * hd).astype(qkv_loc.dtype),
                        ck_loc, cv_loc, ks_loc, vs_loc)

            ck_loc = insert(ck_loc, kT.astype(ck_loc.dtype), kv_start)
            cv_loc = insert(cv_loc, vT.astype(cv_loc.dtype), kv_start)
            attend = flash_decode if impl == "flash" else attention_cached_ref
            # cast the [S]-sized query side to the cache dtype — NEVER
            # the [T]-sized cache to the query dtype (a full-cache
            # convert per layer per step)
            o = attend(q.astype(ck_loc.dtype), ck_loc, cv_loc,
                       kv_start + S, scale=scale)
            return o.reshape(M, hq * hd), ck_loc, cv_loc

        out = f(qkv, *kv, jnp.asarray(kv_start, jnp.int32))
        return out[0], tuple(out[1:])

    def _attend_cached_slots(self, qkv, cos, sin, batch: int, kv, pos,
                             impl: str = "flash"):
        """Slot-variant of _attend_cached for the continuous-batching
        decode step (S == 1, per-row positions).

        qkv: [B, qkv_cols] sharded P(None, tp); pos: [B] int32 — row b
        writes its K/V at column pos[b] of ITS cache row (a per-row
        scatter; rows are independent (batch, head) streams, so a row's
        write never touches another slot's data) and attends its own
        columns [0, pos[b]] via the kernel's per-stream length mask
        (flash_decode kv_lens / attention_cached_ref vector kv_len).
        RoPE rotates row b at angle pos[b]. Returns (o, updated kv).
        """
        from triton_dist_tpu.kernels.flash_attn import (attention_cached_ref,
                                                        flash_decode)
        hq, hkv, hd = self._hq_loc, self._hkv_loc, self.head_dim
        scale = hd ** -0.5
        quant = len(kv) == 4
        cache_spec = P(None, self.axis, None, None)
        scale_spec = P(None, self.axis, None)
        kv_specs = ((cache_spec, cache_spec, scale_spec, scale_spec)
                    if quant else (cache_spec, cache_spec))

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(P(None, self.axis),) + kv_specs + (P(None),),
            out_specs=((P(None, self.axis),) + kv_specs),
            check_vma=False)
        def f(qkv_loc, ck_loc, cv_loc, *rest):
            *scales, pos = rest
            B = qkv_loc.shape[0]               # S == 1: one row per slot
            q = qkv_loc[:, :hq * hd].reshape(B, 1, hq, hd)
            k = qkv_loc[:, hq * hd:(hq + hkv) * hd].reshape(B, 1, hkv, hd)
            v = qkv_loc[:, (hq + hkv) * hd:].reshape(B, 1, hkv, hd)
            if self.q_norm is not None:
                q = rms_norm(q, self.q_norm)
            if self.k_norm is not None:
                k = rms_norm(k, self.k_norm)
            q = apply_rope_slots(q, cos, sin, pos)
            k = apply_rope_slots(k, cos, sin, pos)
            kT = k.transpose(0, 2, 1, 3)        # [B, hkv, 1, hd]
            vT = v.transpose(0, 2, 1, 3)
            rows = jnp.arange(B)
            lens = pos + 1

            def scat(c, u):
                # one row per (slot, head) stream at that slot's column
                return c.at[rows, :, pos].set(u[:, :, 0].astype(c.dtype))

            if quant:
                ks_loc, vs_loc = scales

                # the repo-wide per-position KV quantizer
                # (kernels/quant.quantize_kv_int8 — shared with the
                # int8 paged pool, so the two layouts can never drift)
                from triton_dist_tpu.kernels.quant import \
                    quantize_kv_int8 as q8

                k8, k_s = q8(kT)
                v8, v_s = q8(vT)
                ck_loc = scat(ck_loc, k8)
                cv_loc = scat(cv_loc, v8)
                ks_loc = ks_loc.at[rows, :, pos].set(k_s[:, :, 0])
                vs_loc = vs_loc.at[rows, :, pos].set(v_s[:, :, 0])
                if impl == "flash":
                    bt = min(ck_loc.shape[2], 2048)
                    o = flash_decode(q.astype(jnp.bfloat16), ck_loc,
                                     cv_loc, jnp.max(lens), scale=scale,
                                     k_scale=ks_loc, v_scale=vs_loc,
                                     block_t=bt, kv_lens=lens)
                else:
                    o = attention_cached_ref(
                        q.astype(jnp.float32),
                        ck_loc.astype(jnp.float32) * ks_loc[..., None],
                        cv_loc.astype(jnp.float32) * vs_loc[..., None],
                        lens, scale=scale)
                return (o.reshape(B, hq * hd).astype(qkv_loc.dtype),
                        ck_loc, cv_loc, ks_loc, vs_loc)

            ck_loc = scat(ck_loc, kT)
            cv_loc = scat(cv_loc, vT)
            if impl == "flash":
                o = flash_decode(q.astype(ck_loc.dtype), ck_loc, cv_loc,
                                 jnp.max(lens), scale=scale, kv_lens=lens)
            else:
                o = attention_cached_ref(q.astype(ck_loc.dtype), ck_loc,
                                         cv_loc, lens, scale=scale)
            return o.reshape(B, hq * hd), ck_loc, cv_loc

        out = f(qkv, *kv, jnp.asarray(pos, jnp.int32))
        return out[0], tuple(out[1:])

    def _attend_cached_slots_verify(self, qkv, cos, sin, batch: int, kv,
                                    pos, q_lens, impl: str = "flash"):
        """Speculative-verify variant of _attend_cached_slots
        (models/spec_decode.py): each slot feeds a variable-length
        draft window of up to S tokens in ONE forward. qkv:
        [B*S, qkv_cols] sharded P(None, tp); pos/q_lens: [B] int32 —
        slot b's q_lens[b] valid window rows sit at positions pos[b] ..
        pos[b] + q_lens[b] - 1 (RoPE-rotated there), write their K/V at
        those columns of the slot's cache row, and attend causally
        within the window (flash_decode q_lens / attention_cached_ref
        q_lens). Padded rows (s >= q_lens[b], or past the cache
        capacity) are DROPPED by the scatter (out-of-bounds update
        indices), so they can never clobber a live KV row; their
        attention outputs are computed-and-discarded. Returns
        (o [B*S, hq_loc*hd], updated kv)."""
        from triton_dist_tpu.kernels.flash_attn import (attention_cached_ref,
                                                        flash_decode)
        hq, hkv, hd = self._hq_loc, self._hkv_loc, self.head_dim
        scale = hd ** -0.5
        quant = len(kv) == 4
        cache_spec = P(None, self.axis, None, None)
        scale_spec = P(None, self.axis, None)
        kv_specs = ((cache_spec, cache_spec, scale_spec, scale_spec)
                    if quant else (cache_spec, cache_spec))

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(P(None, self.axis),) + kv_specs + (P(None), P(None)),
            out_specs=((P(None, self.axis),) + kv_specs),
            check_vma=False)
        def f(qkv_loc, ck_loc, cv_loc, *rest):
            *scales, pos, q_lens = rest
            M = qkv_loc.shape[0]
            B = batch
            S = M // B
            T = ck_loc.shape[2]
            q = qkv_loc[:, :hq * hd].reshape(B, S, hq, hd)
            k = qkv_loc[:, hq * hd:(hq + hkv) * hd].reshape(B, S, hkv, hd)
            v = qkv_loc[:, (hq + hkv) * hd:].reshape(B, S, hkv, hd)
            if self.q_norm is not None:
                q = rms_norm(q, self.q_norm)
            if self.k_norm is not None:
                k = rms_norm(k, self.k_norm)
            q = apply_rope_slots(q, cos, sin, pos)
            k = apply_rope_slots(k, cos, sin, pos)
            p = pos[:, None] + jnp.arange(S)[None]          # [B, S]
            valid = (jnp.arange(S)[None] < q_lens[:, None]) & (p < T)
            # invalid rows scatter OUT OF BOUNDS (column T) — jax drops
            # OOB scatter updates, so padding can never collide with a
            # live row's write (a clamped index could, at T - 1)
            wpos = jnp.where(valid, p, T)
            rows = jnp.arange(B)[:, None]
            lens = pos + q_lens

            def scat(c, u):   # u: [B, S, hkv, ...] matching c's cols
                return c.at[rows, :, wpos].set(u.astype(c.dtype))

            if quant:
                ks_loc, vs_loc = scales

                # the repo-wide per-position KV quantizer
                # (kernels/quant.quantize_kv_int8 — shared with the
                # int8 paged pool, so the two layouts can never drift)
                from triton_dist_tpu.kernels.quant import \
                    quantize_kv_int8 as q8

                k8, k_s = q8(k)
                v8, v_s = q8(v)
                ck_loc = scat(ck_loc, k8)
                cv_loc = scat(cv_loc, v8)
                ks_loc = ks_loc.at[rows, :, wpos].set(k_s)
                vs_loc = vs_loc.at[rows, :, wpos].set(v_s)
                if impl == "flash":
                    bt = min(T, 2048)
                    o = flash_decode(q.astype(jnp.bfloat16), ck_loc,
                                     cv_loc, jnp.max(lens), scale=scale,
                                     k_scale=ks_loc, v_scale=vs_loc,
                                     block_t=bt, kv_lens=lens,
                                     q_lens=q_lens)
                else:
                    o = attention_cached_ref(
                        q.astype(jnp.float32),
                        ck_loc.astype(jnp.float32) * ks_loc[..., None],
                        cv_loc.astype(jnp.float32) * vs_loc[..., None],
                        lens, scale=scale, q_lens=q_lens)
                return (o.reshape(M, hq * hd).astype(qkv_loc.dtype),
                        ck_loc, cv_loc, ks_loc, vs_loc)

            ck_loc = scat(ck_loc, k)
            cv_loc = scat(cv_loc, v)
            if impl == "flash":
                o = flash_decode(q.astype(ck_loc.dtype), ck_loc, cv_loc,
                                 jnp.max(lens), scale=scale, kv_lens=lens,
                                 q_lens=q_lens)
            else:
                o = attention_cached_ref(q.astype(ck_loc.dtype), ck_loc,
                                         cv_loc, lens, scale=scale,
                                         q_lens=q_lens)
            return o.reshape(M, hq * hd), ck_loc, cv_loc

        out = f(qkv, *kv, jnp.asarray(pos, jnp.int32),
                jnp.asarray(q_lens, jnp.int32))
        return out[0], tuple(out[1:])

    def fwd_cached_slots_verify(self, x, cos, sin, batch: int, kv, pos,
                                q_lens, mode: str = "dist"):
        """Speculative-verify attention block (spec decode,
        models/spec_decode.py): B slots x up to S draft-window tokens
        in ONE forward. x: [B*S, D]; pos/q_lens: [B] int32. Same mode
        dispatch as fwd_cached_slots."""
        impl = "ref" if mode == "xla" else "flash"
        qkv = self._qkv_proj(x, mode)
        o, kv = self._attend_cached_slots_verify(qkv, cos, sin, batch,
                                                 kv, pos, q_lens, impl)
        return self._o_proj(o, mode), kv

    def _paged_specs(self, quant: bool):
        """shard_map in/out specs of one layer's paged pool tuple:
        payloads [NP, Hkv, page, d] and (int8) scale planes
        [NP, Hkv, page] split on the HEAD axis (kv_cache.PagedSlotCache
        TP sharding) — each rank's shard holds its own kv heads of
        every page."""
        pool_spec = P(None, self.axis, None, None)
        sc_spec = P(None, self.axis, None)
        return ((pool_spec, pool_spec, sc_spec, sc_spec) if quant
                else (pool_spec, pool_spec))

    def _attend_paged_slots(self, qkv, cos, sin, batch: int, kv, table,
                            pos, impl: str = "flash"):
        """Paged-pool variant of _attend_cached_slots (prefix-cache
        serving, models/prefix_cache.py): row b's new K/V lands in the
        physical page its table row maps for position pos[b], and
        attention walks the pool through the table (flash_decode_paged,
        or a gather + contiguous oracle under impl="ref").

        kv: (pages_k, pages_v) [NP, Hkv, page, d] — ONE layer's pool —
        or (pages_k, pages_v, scales_k, scales_v) for the INT8 pool
        (kv_cache.PagedSlotCache with dtype=int8): the new row
        quantizes per position (kernels/quant.quantize_kv_int8 — the
        contiguous cache's exact quantizer) and its scale lands in the
        [NP, Hkv, page] scale plane at the SAME page/head/row the
        payload takes, so scales follow pages through sharing, CoW,
        eviction and the host tier for free; attention dequants
        in-kernel (flash_decode_paged k_scale/v_scale).
        table: [B, max_pages] int32, one row a slot, shared by all
        layers, replicated (the host owns it).

        TP-NATIVE (the head-sharded pool of kv_cache.PagedSlotCache):
        this attend runs under jax.shard_map exactly like the
        contiguous _attend_cached_slots — each rank scatters its OWN
        kv heads' new rows into its shard of the pool and walks only
        its local heads of every page, so a TP=N mesh reads 1/N of the
        KV and does 1/N of the attention FLOPs per chip while the page
        table, allocator and radix tree stay host-replicated and
        layout-oblivious."""
        from triton_dist_tpu.kernels.flash_attn import attention_cached_ref
        from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
        from triton_dist_tpu.kernels.quant import (dequantize_kv_int8,
                                                   quantize_kv_int8)
        hq, hkv, hd = self._hq_loc, self._hkv_loc, self.head_dim
        scale = hd ** -0.5
        quant = len(kv) == 4
        kv_specs = self._paged_specs(quant)
        B = qkv.shape[0]
        maxp = table.shape[1]

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(P(None, self.axis),) + kv_specs
                     + (P(None, None), P(None)),
            out_specs=((P(None, self.axis),) + kv_specs),
            check_vma=False)
        def f(qkv_loc, ck, cv, *rest):
            *scales, tbl, pos = rest
            page = ck.shape[2]
            q = qkv_loc[:, :hq * hd].reshape(B, 1, hq, hd)
            k = qkv_loc[:, hq * hd:(hq + hkv) * hd].reshape(B, 1, hkv, hd)
            v = qkv_loc[:, (hq + hkv) * hd:].reshape(B, 1, hkv, hd)
            if self.q_norm is not None:
                q = rms_norm(q, self.q_norm)
            if self.k_norm is not None:
                k = rms_norm(k, self.k_norm)
            q = apply_rope_slots(q, cos, sin, pos)
            k = apply_rope_slots(k, cos, sin, pos)
            pidx = tbl[jnp.arange(B), pos // page]           # [B]
            r = pos % page
            k, v = k[:, 0], v[:, 0]                          # [B, hkv, hd]
            if quant:
                sk, sv = scales
                k8, k_s = quantize_kv_int8(k)
                v8, v_s = quantize_kv_int8(v)
                ck = set_page_rows(ck, pidx, r, k8)
                cv = set_page_rows(cv, pidx, r, v8)
                sk = set_page_rows(sk, pidx, r, k_s)
                sv = set_page_rows(sv, pidx, r, v_s)
            else:
                ck = set_page_rows(ck, pidx, r, k)
                cv = set_page_rows(cv, pidx, r, v)
                sk = sv = None
            lens = pos + 1
            qd = jnp.bfloat16 if quant else ck.dtype
            if impl == "flash":
                o = flash_decode_paged(q.astype(qd), ck, cv, tbl,
                                       jnp.max(lens), scale=scale,
                                       kv_lens=lens, k_scale=sk,
                                       v_scale=sv)
            else:
                kd = dequantize_kv_int8(ck, sk) if quant else ck
                vd = dequantize_kv_int8(cv, sv) if quant else cv
                o = attention_cached_ref(q.astype(jnp.float32) if quant
                                         else q.astype(ck.dtype),
                                         gather_pages(kd, tbl),
                                         gather_pages(vd, tbl), lens,
                                         scale=scale)
            o = o.reshape(B, hq * hd)
            if quant:
                return o.astype(qkv_loc.dtype), ck, cv, sk, sv
            return o, ck, cv

        out = f(qkv, *kv, table, jnp.asarray(pos, jnp.int32))
        return out[0], tuple(out[1:])

    def _attend_paged_slots_verify(self, qkv, cos, sin, batch: int, kv,
                                   table, pos, q_lens,
                                   impl: str = "flash"):
        """Paged-pool variant of _attend_cached_slots_verify (spec
        decode over the shared-prefix pool): slot b's draft-window K/V
        lands in the physical pages its table row maps for positions
        pos[b] .. pos[b] + q_lens[b] - 1; padded rows scatter to an
        out-of-bounds page id and are dropped, so they can never touch
        a live or cached page. Attention walks the pool through the
        table with per-slot kv_lens AND q_lens (flash_decode_paged).
        An INT8 pool (kv = 4-tuple with scale planes) quantizes the
        window per position and scatters the scales to the same
        (page, row) destinations — OOB-dropped alongside the payload —
        exactly like _attend_paged_slots. Runs under jax.shard_map on
        the head-sharded pool (see _attend_paged_slots): each rank
        writes and walks only its own kv heads of every page."""
        from triton_dist_tpu.kernels.flash_attn import attention_cached_ref
        from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
        from triton_dist_tpu.kernels.quant import (dequantize_kv_int8,
                                                   quantize_kv_int8)
        hq, hkv, hd = self._hq_loc, self._hkv_loc, self.head_dim
        scale = hd ** -0.5
        quant = len(kv) == 4
        kv_specs = self._paged_specs(quant)
        B = batch
        S = qkv.shape[0] // B
        NP = kv[0].shape[0]
        maxp = table.shape[1]

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(P(None, self.axis),) + kv_specs
                     + (P(None, None), P(None), P(None)),
            out_specs=((P(None, self.axis),) + kv_specs),
            check_vma=False)
        def f(qkv_loc, ck, cv, *rest):
            *scales, tbl, pos, q_lens = rest
            page = ck.shape[2]
            M = qkv_loc.shape[0]
            q = qkv_loc[:, :hq * hd].reshape(B, S, hq, hd)
            k = qkv_loc[:, hq * hd:(hq + hkv) * hd].reshape(B, S, hkv, hd)
            v = qkv_loc[:, (hq + hkv) * hd:].reshape(B, S, hkv, hd)
            if self.q_norm is not None:
                q = rms_norm(q, self.q_norm)
            if self.k_norm is not None:
                k = rms_norm(k, self.k_norm)
            q = apply_rope_slots(q, cos, sin, pos)
            k = apply_rope_slots(k, cos, sin, pos)
            p = pos[:, None] + jnp.arange(S)[None]             # [B, S]
            valid = ((jnp.arange(S)[None] < q_lens[:, None])
                     & (p < maxp * page))
            pidx = tbl[jnp.arange(B)[:, None],
                       jnp.minimum(p // page, maxp - 1)]
            # invalid rows scatter to page NP (out of bounds -> dropped)
            dest = jnp.where(valid, pidx, NP)                  # [B, S]
            r = p % page
            if quant:
                sk, sv = scales
                k8, k_s = quantize_kv_int8(k)      # [B, S, hkv, d] / [..]
                v8, v_s = quantize_kv_int8(v)
                ck = set_page_rows(ck, dest, r, k8)
                cv = set_page_rows(cv, dest, r, v8)
                sk = set_page_rows(sk, dest, r, k_s)
                sv = set_page_rows(sv, dest, r, v_s)
            else:
                ck = set_page_rows(ck, dest, r, k)
                cv = set_page_rows(cv, dest, r, v)
                sk = sv = None
            lens = pos + q_lens
            qd = jnp.bfloat16 if quant else ck.dtype
            if impl == "flash":
                o = flash_decode_paged(q.astype(qd), ck, cv, tbl,
                                       jnp.max(lens), scale=scale,
                                       kv_lens=lens, q_lens=q_lens,
                                       k_scale=sk, v_scale=sv)
            else:
                kd = dequantize_kv_int8(ck, sk) if quant else ck
                vd = dequantize_kv_int8(cv, sv) if quant else cv
                o = attention_cached_ref(q.astype(jnp.float32) if quant
                                         else q.astype(ck.dtype),
                                         gather_pages(kd, tbl),
                                         gather_pages(vd, tbl), lens,
                                         scale=scale, q_lens=q_lens)
            o = o.reshape(M, hq * hd)
            if quant:
                return o.astype(qkv_loc.dtype), ck, cv, sk, sv
            return o, ck, cv

        out = f(qkv, *kv, table, jnp.asarray(pos, jnp.int32),
                jnp.asarray(q_lens, jnp.int32))
        return out[0], tuple(out[1:])

    def _attend_paged_slots_sp(self, qkv, cos, sin, batch: int, kv,
                               table, pos, q_lens, sp_axis: str,
                               combine: str = "xla"):
        """SEQUENCE-PARALLEL paged slot attention (long-context
        serving — the serving promotion of kernels/sp_flash_decode.py;
        Ring Attention arXiv:2310.01889 sets the blockwise cross-chip
        pattern, Infinite-LLM/DistAttention arXiv:2401.02669 the
        cluster-wide paged-KV deployment): the pool's PAGE-ID space is
        sharded over the `sp_axis` mesh axis (kv_cache.PagedSlotCache
        SP SHARDING — chip s holds physical pages [s*pps, (s+1)*pps)),
        so under jax.shard_map each chip

        - scatters the new K/V rows of the pages IT owns (other
          chips' scatters redirect out of bounds and drop — the same
          OOB-drop contract padded verify rows use; a trash-mapped
          retired row's write lands only in shard 0's local trash
          sink),
        - walks ONLY its local pages through the split-KV partial
          kernel (flash_decode_paged_partial: the replicated table is
          redirected per chip — non-owned tiles point at the last
          owned local page so their surplus DMAs elide — and a
          per-tile ownership mask makes them accumulator no-ops), and
        - merges partials via the cross-chip LSE combine
          (sp_combine_partials -> lse_combine or the one-sided Pallas
          push kernel), yielding the bitwise-softmax output replicated
          over sp.

        Per-chip KV reads and attention FLOPs drop to ~1/S and a
        slot's max context is bounded by the MESH's pooled HBM, not
        one chip's. q_lens None = the decode tick (S == 1); a [B]
        vector = the verify/chunked-prefill window (per-slot kv_lens
        AND q_lens masks, padded rows dropped) — chunked prefill over
        this attend IS the blockwise ring-style prefill: each chunk's
        window attends the distributed pages through the same
        partial+combine. Single TP group only (sp + head-group hybrid
        is refused at construction)."""
        from triton_dist_tpu.kernels.paged_kv import \
            flash_decode_paged_partial
        from triton_dist_tpu.kernels.quant import quantize_kv_int8
        from triton_dist_tpu.kernels.sp_flash_decode import \
            sp_combine_partials
        from triton_dist_tpu.runtime import next_collective_id
        hq, hkv, hd = self._hq_loc, self._hkv_loc, self.head_dim
        scale = hd ** -0.5
        quant = len(kv) == 4
        B = batch
        M = qkv.shape[0]
        S = M // B
        verify = q_lens is not None
        NP = kv[0].shape[0]
        maxp = table.shape[1]
        nsp = self.mesh.shape[sp_axis]
        pps = NP // nsp
        cid = (next_collective_id() if combine == "dist" else None)
        pool_spec = P(sp_axis, None, None, None)
        sc_spec = P(sp_axis, None, None)
        kv_specs = ((pool_spec, pool_spec, sc_spec, sc_spec) if quant
                    else (pool_spec, pool_spec))
        rep2 = P(None, None)
        in_specs = ((rep2,) + kv_specs
                    + (P(None, None), P(None))
                    + ((P(None),) if verify else ()))
        out_specs = ((rep2,) + kv_specs)

        @functools.partial(
            jax.shard_map, mesh=self.mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False)
        def f(qkv_loc, ck, cv, *rest):
            if verify:
                *scales, tbl, pos_, ql = rest
            else:
                *scales, tbl, pos_ = rest
                ql = None
            me = jax.lax.axis_index(sp_axis)
            NP_loc = ck.shape[0]                # local shard of the ids
            page = ck.shape[2]
            q = qkv_loc[:, :hq * hd].reshape(B, S, hq, hd)
            k = qkv_loc[:, hq * hd:(hq + hkv) * hd].reshape(B, S, hkv, hd)
            v = qkv_loc[:, (hq + hkv) * hd:].reshape(B, S, hkv, hd)
            if self.q_norm is not None:
                q = rms_norm(q, self.q_norm)
            if self.k_norm is not None:
                k = rms_norm(k, self.k_norm)
            q = apply_rope_slots(q, cos, sin, pos_)
            k = apply_rope_slots(k, cos, sin, pos_)
            # --- new-row scatter: only the owning chip writes ---
            if verify:
                p = pos_[:, None] + jnp.arange(S)[None]        # [B, S]
                valid = ((jnp.arange(S)[None] < ql[:, None])
                         & (p < maxp * page))
                pidx_g = tbl[jnp.arange(B)[:, None],
                             jnp.minimum(p // page, maxp - 1)]
                owned_w = valid & ((pidx_g // pps) == me)
                r = p % page
                k_rows, v_rows = k, v
            else:
                pidx_g = tbl[jnp.arange(B), pos_ // page]      # [B]
                owned_w = (pidx_g // pps) == me
                r = pos_ % page
                k_rows, v_rows = k[:, 0], v[:, 0]
            dest = jnp.where(owned_w, pidx_g - me * pps, NP_loc)
            if quant:
                sk, sv = scales
                k8, k_s = quantize_kv_int8(k_rows)
                v8, v_s = quantize_kv_int8(v_rows)
                ck = set_page_rows(ck, dest, r, k8)
                cv = set_page_rows(cv, dest, r, v8)
                sk = set_page_rows(sk, dest, r, k_s)
                sv = set_page_rows(sv, dest, r, v_s)
            else:
                ck = set_page_rows(ck, dest, r, k_rows)
                cv = set_page_rows(cv, dest, r, v_rows)
                sk = sv = None
            lens = pos_ + (ql if verify else 1)
            # --- local redirected table + per-tile ownership mask:
            # non-owned tiles repeat the last owned local page (their
            # surplus DMAs elide) and mask to accumulator no-ops ---
            owned_t = (tbl // pps) == me                   # [B, maxp]
            ti = jax.lax.broadcasted_iota(jnp.int32, (B, maxp), 1)
            lastown = jax.lax.cummax(jnp.where(owned_t, ti, -1), axis=1)
            tbl_loc = jnp.take_along_axis(
                jnp.where(owned_t, tbl - me * pps, 0),
                jnp.maximum(lastown, 0), axis=1)
            qd = jnp.bfloat16 if quant else ck.dtype
            acc, m, l = flash_decode_paged_partial(
                q.astype(qd), ck, cv, tbl_loc, kv_lens=lens,
                q_lens=ql, scale=scale,
                tile_owned=owned_t.astype(jnp.int32),
                k_scale=sk, v_scale=sv)
            o = sp_combine_partials(acc, m, l, axis=sp_axis, n=nsp,
                                    combine=combine, collective_id=cid,
                                    out_dtype=jnp.float32)
            o = o.reshape(M, hq * hd).astype(qkv_loc.dtype)
            if quant:
                return o, ck, cv, sk, sv
            return o, ck, cv

        args = (qkv,) + tuple(kv) + (table, jnp.asarray(pos, jnp.int32))
        if verify:
            args = args + (jnp.asarray(q_lens, jnp.int32),)
        out = f(*args)
        return out[0], tuple(out[1:])

    def fwd_cached_slots_paged_sp(self, x, cos, sin, batch: int, kv,
                                  table, pos, sp_axis: str,
                                  mode: str = "flash",
                                  combine: str = "xla"):
        """Slot-masked decode attention block over the SP-sharded
        paged pool (sequence-parallel long-context serving): same
        contract as fwd_cached_slots_paged, with each chip walking
        only its local pages and the partial-softmax LSE combine
        merging across the sp axis (_attend_paged_slots_sp)."""
        qkv = self._qkv_proj(x, mode)
        o, kv = self._attend_paged_slots_sp(qkv, cos, sin, batch, kv,
                                            table, pos, None, sp_axis,
                                            combine)
        return self._o_proj(o, mode), kv

    def fwd_cached_slots_paged_verify_sp(self, x, cos, sin, batch: int,
                                         kv, table, pos, q_lens,
                                         sp_axis: str,
                                         mode: str = "flash",
                                         combine: str = "xla"):
        """Speculative-verify / chunked-prefill window attention over
        the SP-sharded paged pool: fwd_cached_slots_paged_verify's
        contract through the split-KV partial + cross-chip LSE merge
        (_attend_paged_slots_sp with per-slot q_lens)."""
        qkv = self._qkv_proj(x, mode)
        o, kv = self._attend_paged_slots_sp(qkv, cos, sin, batch, kv,
                                            table, pos, q_lens, sp_axis,
                                            combine)
        return self._o_proj(o, mode), kv

    def fwd_cached_slots_paged_verify(self, x, cos, sin, batch: int, kv,
                                      table, pos, q_lens,
                                      mode: str = "flash"):
        """Speculative-verify attention block over the PAGED pool: same
        contract as fwd_cached_slots_verify with the slot's KV resolved
        through the page table (models/spec_decode.py over the
        shared-prefix serving path)."""
        impl = "ref" if mode == "xla" else "flash"
        qkv = self._qkv_proj(x, mode)
        o, kv = self._attend_paged_slots_verify(qkv, cos, sin, batch, kv,
                                                table, pos, q_lens, impl)
        return self._o_proj(o, mode), kv

    def fwd_cached_slots_paged(self, x, cos, sin, batch: int, kv, table,
                               pos, mode: str = "flash"):
        """Slot-masked decode attention block over the PAGED pool
        (shared-prefix serving): same contract as fwd_cached_slots, but
        row b's KV cache is whatever physical pages its table row maps
        — possibly pages shared read-only with other slots' prefixes.
        Decode only ever writes at pos[b] (past any shared prefix), so
        read-only sharing needs no device-side enforcement."""
        impl = "ref" if mode == "xla" else "flash"
        qkv = self._qkv_proj(x, mode)
        o, kv = self._attend_paged_slots(qkv, cos, sin, batch, kv,
                                         table, pos, impl)
        return self._o_proj(o, mode), kv

    def _qkv_proj(self, x, mode: str):
        """Mode-dispatched QKV projection (the prologue both cached
        forwards share): "dist" = AG-GEMM on row-sharded x; every other
        mode = local qmm on replicated x."""
        if mode == "dist":
            ag_ctx = create_ag_gemm_context(self.mesh, self.axis)
            return ag_gemm(x, self.w_qkv, ag_ctx)
        from triton_dist_tpu.kernels.quant import qmm, qspec

        @functools.partial(jax.shard_map, mesh=self.mesh,
                           in_specs=(P(None, None),
                                     qspec(self.w_qkv, P(None, self.axis),
                                           P(self.axis))),
                           out_specs=P(None, self.axis), check_vma=False)
        def qkv_local(x_r, w_loc):
            return qmm(x_r, w_loc)

        return qkv_local(x, self.w_qkv)

    def _o_proj(self, o, mode: str):
        """Mode-dispatched O projection epilogue (shared by both cached
        forwards): "dist" = GEMM-RS, "gemm_ar" = fused GEMM+AR, "ar" =
        partial GEMM + AR kernel, "xla"/"flash" = partial GEMM + psum."""
        axis = self.axis
        if mode == "dist":
            rs_ctx = create_gemm_rs_context(self.mesh, axis)
            return gemm_rs(o, self.w_o, rs_ctx)
        if mode == "gemm_ar":
            ctx = create_gemm_ar_context(self.mesh, axis)
            return gemm_allreduce(o, self.w_o, ctx)
        if mode == "ar":
            from triton_dist_tpu.kernels.quant import qmm, qspec

            @functools.partial(jax.shard_map, mesh=self.mesh,
                               in_specs=(P(None, axis),
                                         qspec(self.w_o, P(axis, None),
                                               P(None))),
                               out_specs=P(axis, None, None),
                               check_vma=False)
            def o_partial(o_loc, wo_loc):
                return qmm(o_loc, wo_loc)[None]

            return all_reduce(o_partial(o, self.w_o), mesh=self.mesh,
                              axis=axis)
        # "xla" oracle and "flash": psum epilogue
        return self._down_psum(o)

    def fwd_cached(self, x, cos, sin, batch: int, kv, kv_start,
                   mode: str = "dist"):
        """Full attention block with KV cache: QKV proj -> cached attend
        -> O proj, per forward mode. x: [B*S, D] (row-sharded for "dist",
        replicated otherwise). kv: the per-layer cache tuple from
        KVCache.layer() — (ck, cv) bf16 or (ck, cv, ks, vs) int8.
        Returns (y, kv).

        Modes: "xla" (jnp oracle attention + psum), "flash" (Pallas
        flash-decode attention + psum — the single-chip framework path),
        "dist"/"ar"/"gemm_ar" (overlapped comm kernels + flash-decode).
        """
        impl = "ref" if mode == "xla" else "flash"
        qkv = self._qkv_proj(x, mode)
        o, kv = self._attend_cached(qkv, cos, sin, batch, kv,
                                    kv_start, impl)
        return self._o_proj(o, mode), kv

    def fwd_cached_slots(self, x, cos, sin, batch: int, kv, pos,
                         mode: str = "dist"):
        """Slot-masked decode attention block (continuous batching,
        models/scheduler.py): one token per batch row, each row at its
        OWN sequence position. x: [B, D]; pos: [B] int32 — row b's KV
        goes to column pos[b] of its cache row and it attends columns
        [0, pos[b]]. Same mode dispatch as fwd_cached; the decode step
        stays ONE program regardless of the per-slot position mix."""
        impl = "ref" if mode == "xla" else "flash"
        qkv = self._qkv_proj(x, mode)
        o, kv = self._attend_cached_slots(qkv, cos, sin, batch, kv,
                                          pos, impl)
        return self._o_proj(o, mode), kv
