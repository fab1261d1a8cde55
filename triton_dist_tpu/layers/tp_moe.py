"""Tensor-parallel MoE layer (experts replicated, intermediate sharded).

TPU-native re-design of `python/triton_dist/layers/nvidia/tp_moe.py`
(AG-GroupGEMM front half + MoE-reduce-RS back half; kernels
`allgather_group_gemm.py:253` and `moe_reduce_rs.py:168`).

Data flow ("dist" mode, x row-sharded [M/n, D] over the TP axis):

    all_gather (Pallas ring)        <- cp-engine AG producer
    route + capacity grouping (XLA) <- sort_topk_ids_align_block_size
                                       (csrc/lib/moe_utils.cu:61)
    grouped GEMM w1 (Pallas)        <- scatter-group-GEMM consumer :536
    SwiGLU
    grouped GEMM w2 (Pallas) -> per-rank PARTIAL expert outputs
    topk-weighted scatter (XLA) + ring reduce_scatter (Pallas)
                                    <- moe_gather_rs_grouped_gemm :168

The reference fuses AG into the group-GEMM's tile waits and the weighted
gather into the RS producer; on TPU the gather/scatter planning is XLA
(it fuses with neighbors and needs dynamic indexing Pallas can't do
cheaply), while the AG, grouped-GEMM and RS stay hand-scheduled Pallas
kernels. The capacity trade (compute-then-mask padding) replaces the
reference's dynamic per-expert tile scheduling — grouped GEMM needs
static shapes on the MXU.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_dist_tpu.kernels import all_gather, grouped_gemm, reduce_scatter
from triton_dist_tpu.kernels.ep_a2a import (expert_token_counts,
                                            group_tokens_by_expert, route,
                                            scatter_weighted)
from triton_dist_tpu.kernels.swiglu import swiglu_ref
from triton_dist_tpu.layers.common import shard_cols_packed


def _pack_expert_cols(w_gate, w_up, n: int):
    """Per-expert column-parallel packing: for each expert, n per-rank
    blocks [gate_r | up_r] (the MLP packing, vmapped over experts)."""
    E = w_gate.shape[0]
    return jnp.stack([shard_cols_packed([w_gate[e], w_up[e]], n)
                      for e in range(E)])


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TP_MoE:
    """Router + per-expert SwiGLU MLPs, intermediate dim sharded over TP.

    w_router:  [D, E] replicated.
    w_gate_up: [E, D, 2I] — per expert, n per-rank [gate_r | up_r] blocks
               (column-parallel), sharded P(None, None, tp).
    w_down:    [E, I, D] row-parallel, sharded P(None, tp, None).
    """

    w_router: jax.Array
    w_gate_up: jax.Array
    w_down: jax.Array
    mesh: Mesh = dataclasses.field(metadata=dict(static=True))
    axis: str = dataclasses.field(metadata=dict(static=True))
    top_k: int = dataclasses.field(metadata=dict(static=True))
    capacity_factor: float = dataclasses.field(
        default=2.0, metadata=dict(static=True))

    @staticmethod
    def init(w_router, w_gate, w_up, w_down, *, mesh: Mesh,
             axis: str = "tp", top_k: int,
             capacity_factor: float = 2.0) -> "TP_MoE":
        n = mesh.shape[axis]
        packed = _pack_expert_cols(jnp.asarray(w_gate), jnp.asarray(w_up), n)
        packed = jax.device_put(packed,
                                NamedSharding(mesh, P(None, None, axis)))
        w_down = jax.device_put(jnp.asarray(w_down),
                                NamedSharding(mesh, P(None, axis, None)))
        return TP_MoE(w_router=jnp.asarray(w_router), w_gate_up=packed,
                      w_down=w_down, mesh=mesh, axis=axis, top_k=top_k,
                      capacity_factor=capacity_factor)

    @property
    def num_experts(self) -> int:
        return self.w_router.shape[1]

    def _cap(self, M: int) -> int:
        """Static per-expert capacity (reference analog: the max_M-sized
        symmetric workspaces). capacity_factor='dropless' uses the
        provable worst case (all routed entries on one expert) — never
        drops, at the memory price of the bound AND its work: the
        [E, C, D] grouped GEMM computes E x M x top_k rows whatever was
        routed. The dropless stage whose work follows the routed pairs
        is the ragged one (layers/ep_moe.py `expert_rows`,
        kernels/group_gemm.py `ragged_grouped_gemm`)."""
        if self.capacity_factor == "dropless":
            # rounded up to whole 8-row tiles (kernel slab slices must
            # stay sublane-aligned on real TPUs)
            return -(-M * self.top_k // 8) * 8
        E = self.num_experts
        c = int(self.capacity_factor * self.top_k * M / E) + 1
        return min(max(8, -(-c // 8) * 8), M * self.top_k)

    def _expert_mlp_sharded(self, x_e, gemm=None):
        """Per-rank grouped GEMMs over the sharded intermediate dim;
        output is this rank's PARTIAL [E, cap, D] (needs a sum over tp).
        Stacked via out_specs P(axis, ...) for the explicit RS/AR kernels.
        `gemm` swaps the grouped-GEMM callable (the train path passes
        the custom-VJP wrapper)."""
        gemm = gemm or grouped_gemm

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(P(None, None, None), P(None, None, self.axis),
                      P(None, self.axis, None)),
            out_specs=P(self.axis, None, None, None), check_vma=False)
        def f(x_e, wgu_loc, wd_loc):
            h = gemm(x_e, wgu_loc.astype(x_e.dtype))
            h = swiglu_ref(h)
            y = gemm(h, wd_loc.astype(x_e.dtype))
            return y[None]

        return f(x_e, self.w_gate_up, self.w_down)   # [n, E, cap, D]

    def _stats(self, topk_idx, inv_slot=None, cap: int = 0):
        """Serving-telemetry stats dict (return_stats=True on the
        forwards below): per-expert routed-entry counts + the capacity
        drop count (`inv_slot >= E*cap` marks entries
        group_tokens_by_expert clamped out; the dense oracle never
        drops). The dropless-or-loud contract made observable."""
        E = self.num_experts
        dropped = (jnp.sum(inv_slot >= E * cap).astype(jnp.int32)
                   if inv_slot is not None else jnp.zeros((), jnp.int32))
        return {"expert_tokens": expert_token_counts(topk_idx, E),
                "dropped": dropped}

    def fwd_xla(self, x, return_stats: bool = False):
        """Oracle: dense all-experts math with XLA psum — every token
        through every expert, topk-weighted (the torch oracle role)."""
        M, D = x.shape
        E, k = self.num_experts, self.top_k
        topk_w, topk_idx = route(x @ self.w_router, k)

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(P(None, None), P(None, None, self.axis),
                      P(None, self.axis, None)),
            out_specs=P(None, None, None), check_vma=False)
        def dense_all(x_full, wgu_loc, wd_loc):
            h = jnp.einsum("md,edf->emf", x_full, wgu_loc.astype(x_full.dtype))
            h = swiglu_ref(h)
            y = jnp.einsum("emf,efd->emd", h, wd_loc.astype(x_full.dtype))
            return jax.lax.psum(y, self.axis)        # [E, M, D]

        y_all = dense_all(x, self.w_gate_up, self.w_down)
        onehot = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)
        w_e = jnp.einsum("tk,tke->te", topk_w, onehot)
        y = jnp.einsum("te,etd->td", w_e, y_all.astype(jnp.float32))
        y = y.astype(x.dtype)
        if return_stats:
            return y, self._stats(topk_idx)
        return y

    def fwd_dist(self, x, return_stats: bool = False):
        """AG-GroupGEMM + MoE-reduce-RS (x row-sharded [M/n, D] ->
        row-sharded [M/n, D])."""
        n = self.mesh.shape[self.axis]
        xg = all_gather(x, mesh=self.mesh, axis=self.axis)  # [M, D] repl
        M = xg.shape[0]
        cap = self._cap(M)
        topk_w, topk_idx = route(xg @ self.w_router, self.top_k)
        x_e, inv_slot, token = group_tokens_by_expert(
            xg, topk_idx, self.num_experts, cap)
        y_parts = self._expert_mlp_sharded(x_e)       # [n, E, cap, D]

        # topk-weighted gather back to token order, still per-rank partial
        def _scatter(y_e):
            return scatter_weighted(y_e, inv_slot, token, topk_w, M)

        y_partial = jax.vmap(_scatter)(y_parts).astype(x.dtype)  # [n, M, D]
        y = reduce_scatter(y_partial, mesh=self.mesh, axis=self.axis)
        if return_stats:
            return y, self._stats(topk_idx, inv_slot, cap)
        return y

    def fwd_fused(self, x):
        """Fully fused path: ag_group_gemm (ring-AG of capacity chunks
        consumed by per-expert GEMMs) + moe_reduce_rs (grouped down-proj
        whose epilogue ring-reduce-scatters the slabs) — the reference's
        allgather_group_gemm.py:253 + moe_reduce_rs.py:168 pair. x
        row-sharded [M, D] -> row-sharded [M, D]; routing/grouping is
        rank-local, so rank r's capacity block r holds its own tokens
        and the RS hands each rank exactly its combine inputs back."""
        from triton_dist_tpu.kernels.ag_group_gemm import ag_group_gemm
        from triton_dist_tpu.kernels.moe_reduce_rs import moe_reduce_rs
        axis = self.axis
        n = self.mesh.shape[axis]
        M = x.shape[0]
        m_loc = M // n
        E, k = self.num_experts, self.top_k
        cap_loc = self._cap(m_loc)

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(P(axis, None), P(None, None)),
            out_specs=(P(None, axis, None), P(axis, None), P(axis, None),
                       P(axis, None, None)),
            check_vma=False)
        def prep(x_loc, w_router):
            topk_w, topk_idx = route(x_loc @ w_router, k)
            x_e, inv_slot, token = group_tokens_by_expert(
                x_loc, topk_idx, E, cap_loc)
            return (x_e, inv_slot[None], token[None], topk_w[None])

        x_e, inv_slot, token, topk_w = prep(x, self.w_router)
        h = ag_group_gemm(x_e, self.w_gate_up.astype(x.dtype),
                          mesh=self.mesh, axis=axis)

        # local slice is packed [gate_r | up_r]: swiglu splits halves
        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=P(None, None, axis), out_specs=P(None, None, axis),
            check_vma=False)
        def act(h_loc):
            return swiglu_ref(h_loc)

        h2 = act(h)
        y_e = moe_reduce_rs(h2, self.w_down.astype(x.dtype),
                            mesh=self.mesh, axis=axis)

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(P(None, axis, None), P(axis, None), P(axis, None),
                      P(axis, None, None)),
            out_specs=P(axis, None), check_vma=False)
        def combine(y_loc, inv_loc, tok_loc, w_loc):
            return scatter_weighted(y_loc, inv_loc[0], tok_loc[0],
                                    w_loc[0], m_loc).astype(x.dtype)

        return combine(y_e, inv_slot, token, topk_w)

    def fwd_fused_ar(self, x):
        """Decode path: fused grouped-GEMM + AllReduce epilogue
        (reference: moe_reduce_ar.py:323-645, the small-M latency-bound
        regime). x REPLICATED [M, D] -> replicated [M, D]: routing and
        grouping are replicated (every rank computes the same plan),
        GEMM1 consumes only local weight columns, and the down-proj's
        partial sums are combined by the one-shot push-all AR inside
        moe_reduce_ar — no separate collective, the decode analog of
        TP_MLP's gemm_ar mode."""
        from triton_dist_tpu.kernels.moe_reduce_ar import moe_reduce_ar
        E, k = self.num_experts, self.top_k
        M = x.shape[0]
        cap = self._cap(M)
        topk_w, topk_idx = route(x @ self.w_router, k)
        x_e, inv_slot, token = group_tokens_by_expert(x, topk_idx, E, cap)

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(P(None, None, None), P(None, None, self.axis)),
            out_specs=P(None, None, self.axis), check_vma=False)
        def up(x_e, wgu_loc):
            h = grouped_gemm(x_e, wgu_loc.astype(x_e.dtype))
            return swiglu_ref(h)

        h2 = up(x_e, self.w_gate_up)
        y_e = moe_reduce_ar(h2, self.w_down.astype(x.dtype),
                            mesh=self.mesh, axis=self.axis)
        return scatter_weighted(y_e, inv_slot, token, topk_w,
                                M).astype(x.dtype)

    def fwd_local(self, x, return_stats: bool = False):
        """Single-chip framework path: route + grouped-GEMM kernels with
        everything resident (the MoE analog of TP_MLP.fwd_flash)."""
        M, D = x.shape
        cap = self._cap(M)
        topk_w, topk_idx = route(x @ self.w_router, self.top_k)
        x_e, inv_slot, token = group_tokens_by_expert(
            x, topk_idx, self.num_experts, cap)
        y_parts = self._expert_mlp_sharded(x_e)       # [n, E, cap, D]
        y_sum = jnp.sum(y_parts.astype(jnp.float32), axis=0).astype(x.dtype)
        y = scatter_weighted(y_sum, inv_slot, token, topk_w,
                             M).astype(x.dtype)
        if return_stats:
            return y, self._stats(topk_idx, inv_slot, cap)
        return y

    def fwd_train(self, x):
        """Training path through framework kernels: custom-VJP
        all_gather -> route/group (XLA, differentiable) -> custom-VJP
        grouped GEMMs -> weighted scatter -> custom-VJP reduce_scatter
        (reference analog: the autograd Function over the fused MoE ops,
        function/nvidia/ep_moe_fused.py:42). x row-sharded [M/n, D] ->
        row-sharded [M/n, D]; gradients reach w_router (via the top-k
        softmax weights), w_gate_up and w_down."""
        from triton_dist_tpu.kernels.grad import (all_gather_grad,
                                                  grouped_gemm_grad,
                                                  reduce_scatter_grad)
        xg = all_gather_grad(self.mesh, self.axis)(x)
        M = xg.shape[0]
        cap = self._cap(M)
        topk_w, topk_idx = route(xg @ self.w_router, self.top_k)
        x_e, inv_slot, token = group_tokens_by_expert(
            xg, topk_idx, self.num_experts, cap)
        y_parts = self._expert_mlp_sharded(
            x_e, gemm=grouped_gemm_grad())   # [n, E, cap, D]

        # per-rank weighted combine under shard_map (Manual axes: the
        # scatter-add and its transpose stay rank-local, which
        # explicit-sharding mode cannot express for a tp-stacked vmap)
        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=(P(self.axis, None, None, None), P(None), P(None),
                      P(None, None)),
            out_specs=P(self.axis, None, None), check_vma=False)
        def scat(y_loc, inv, tok, w):
            return scatter_weighted(y_loc[0], inv, tok, w, M)[None]

        y_partial = scat(y_parts, inv_slot, token,
                         topk_w).astype(x.dtype)
        return reduce_scatter_grad(self.mesh, self.axis)(y_partial)

    def __call__(self, x, mode: str = "dist", **kw):
        """kw (`return_stats=True`) reaches the serving-reachable paths
        (xla/dist/local) — the slot-tick forwards ask for the routing
        load the telemetry gauges surface; the fused/train paths take
        no kwargs (not serving tick modes)."""
        if mode == "train":
            assert not kw, f"mode='train' takes no extra kwargs: {kw}"
            return self.fwd_train(x)
        if mode == "fused":
            assert not kw, f"mode='fused' takes no extra kwargs: {kw}"
            return self.fwd_fused(x)
        if mode == "fused_ar":
            assert not kw, f"mode='fused_ar' takes no extra kwargs: {kw}"
            return self.fwd_fused_ar(x)
        if mode in ("dist",):
            return self.fwd_dist(x, **kw)
        if mode in ("flash", "ar", "gemm_ar"):
            return self.fwd_local(x, **kw)
        return self.fwd_xla(x, **kw)
