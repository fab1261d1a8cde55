"""EP AllToAll: routed MoE token dispatch/combine over ICI.

TPU-native re-design of the reference EP kernels
(`python/triton_dist/kernels/nvidia/ep_a2a.py`: `kernel_dispatch_token:79`
per-expert putmem_nbi + signal, `kernel_combine_token:214` reverse put +
topk-weighted reduce, splits/offset exchange
`kernel_get_ag_splits_and_recv_offset:382`; intra-node variant
`ep_a2a_intra_node.py:39`; low-latency variants
`low_latency_all_to_all.py:198`, `low_latency_all_to_all_v2.py:156`).

Design differences forced (and enabled) by TPU/XLA:

- **No splits exchange.** The reference exchanges per-expert token counts
  first so receivers can compute exact recv offsets for dynamically-sized
  putmem. XLA needs static shapes, so dispatch is CAPACITY-based: every
  (src, dst) pair owns a fixed [cap, D] slot range in the recv buffer and
  a put always transfers the full slot (invalid rows are masked by the
  `valid` metadata instead of not being sent). The offsets kernel
  (ep_a2a.py:382) therefore has no analog — its job is done by the
  static layout.
- **Routing/planning is XLA, not a CUDA kernel.** Token->slot planning
  (sort by destination, capacity clamp) is the role of
  `moe_ag_scatter_align_block_size` (csrc/lib/moe_utils.cu:61); on TPU
  argsort/cumsum/scatter are efficient XLA ops and fuse with the
  surrounding math, so `plan_dispatch` is jnp. The Pallas kernel does
  what only a kernel can do: one-sided puts with semaphore signaling.
- **One slot set, no call_count double-buffering.** The reference's
  double-buffered signal slots (call_count%2, README.md:101-186) exist
  because NVSHMEM symmetric buffers persist across calls; XLA allocates
  fresh kernel buffers per call, so one set suffices.

Everything here is DEVICE-LOCAL (called inside shard_map over the ep
axis); `ep_all_to_all` is the host-level wrapper used by tests.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu import language as dl
from triton_dist_tpu.runtime import (interpret_mode, next_collective_id,
                                     shmem_compiler_params)


@dataclasses.dataclass
class EPAll2AllContext:
    """Per-op context (reference: the symmetric token buffers + signal
    arrays created per EP group, ep_a2a.py:881). Static config only —
    the buffers are the kernels' own allocations."""

    mesh: Mesh
    axis: str
    n: int
    num_experts: int
    experts_per_rank: int
    capacity: int          # max tokens per (src, dst) device pair
    collective_id: int


def create_ep_a2a_context(mesh: Mesh, axis: str = "ep", *,
                          num_experts: int, capacity: int,
                          collective_id: Optional[int] = None,
                          ) -> EPAll2AllContext:
    n = mesh.shape[axis]
    assert num_experts % n == 0, (num_experts, n)
    return EPAll2AllContext(
        mesh=mesh, axis=axis, n=n, num_experts=num_experts,
        experts_per_rank=num_experts // n, capacity=capacity,
        collective_id=(collective_id if collective_id is not None
                       else next_collective_id()))


# ----------------------------------------------------------------------
# routing + planning (XLA; csrc/moe_utils.cu analog)
# ----------------------------------------------------------------------

def route(router_logits, k: int, *, norm_topk: bool = True):
    """Softmax -> top-k -> (optionally) renormalize (Qwen3-MoE routing,
    reference models/qwen_moe.py). Returns (weights [T, k] f32,
    expert_idx [T, k] int32)."""
    with jax.named_scope("moe_route"):
        probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
        w, idx = jax.lax.top_k(probs, k)
        if norm_topk:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return w, idx.astype(jnp.int32)


def route_noaux_tc(x, w_router, e_bias, k: int, *, n_group: int,
                   topk_group: int, routed_scaling_factor: float):
    """Grouped sigmoid routing without an auxiliary loss (DeepSeek-V3's
    `noaux_tc`), in float32 whatever x's dtype, as published: scores
    sigmoid(x W_r) [T, E]; SELECTION reads the scores plus the learned
    bias `e_bias` [E]: a group's score is the sum of its two largest
    biased scores, the `topk_group` best of `n_group` groups stay in
    (the others' biased scores set to 0.0), top-k of what is left;
    WEIGHTS read the unbiased scores of the chosen experts, normalised
    to 1 and scaled. Returns (weights [T, k] f32, expert_idx [T, k]
    int32) over all E experts the router has columns for."""
    with jax.named_scope("moe_route"):
        sc = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        sb = sc + e_bias.astype(jnp.float32)
        T, E = sb.shape
        grouped = sb.reshape(T, n_group, E // n_group)
        gscore = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, gidx = jax.lax.top_k(gscore, topk_group)
        keep = jnp.any(jnp.arange(n_group) == gidx[..., None], axis=-2)
        masked = jnp.where(keep[..., None], grouped, 0.0).reshape(T, E)
        _, idx = jax.lax.top_k(masked, k)
        w = jnp.take_along_axis(sc, idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
            * routed_scaling_factor
        return w, idx.astype(jnp.int32)


@dataclasses.dataclass
class DispatchPlan:
    """Source-side record of where each (token, k) entry was placed, so
    combine can gather the returned results (the role of the reference's
    send-req index builders, ep_a2a.py:604-765)."""
    slot: jax.Array     # [T*k] slot in the [n*cap] send layout (or n*cap)
    valid: jax.Array    # [T*k] bool — False = dropped by capacity
    token: jax.Array    # [T*k] source token row

    @property
    def dropped(self) -> jax.Array:
        """Per-step count of routed entries this rank dropped by
        capacity — the loud half of dropless-or-loud. The reference
        never drops (it sizes buffers from an exact splits exchange,
        ep_a2a.py:382); the static-capacity redesign must therefore
        either COUNT its drops or be run with dropless capacities
        (EP_MoE capacity_factor='dropless')."""
        return jnp.sum(~self.valid).astype(jnp.int32)


def expert_token_counts(topk_idx, num_experts: int):
    """Routed entries per expert for ONE forward ([E] int32, from the
    router's top-k indices) — the per-expert load the serving telemetry
    surfaces (`expert_tokens{expert=...}` gauges, models/scheduler.py):
    the observable half of dropless-or-loud. Counts every routed entry
    the program computes, including capacity-dropped ones and masked
    slot rows — it measures expert COMPUTE load, not emitted tokens."""
    return jnp.bincount(topk_idx.reshape(-1),
                        length=num_experts).astype(jnp.int32)


def warn_on_drops(dropped, where: str):
    """In-program loud warning when a capacity drop occurred (traced
    scalar; prints only on the steps that actually drop)."""

    def _warn(d):
        jax.debug.print(
            "WARNING {w}: {d} routed entries dropped by expert capacity "
            "this step — raise capacity_factor or use 'dropless'",
            w=where, d=d)

    jax.lax.cond(dropped > 0, _warn, lambda d: None, dropped)


def plan_dispatch(topk_idx, n: int, experts_per_rank: int, cap: int
                  ) -> DispatchPlan:
    """Assign each routed (token, k) entry a slot in the per-destination
    capacity layout. Entries beyond a destination's capacity are dropped
    (their combine weight contribution becomes 0; plan.dropped counts
    them — callers surface it via warn_on_drops / return_stats)."""
    T, k = topk_idx.shape
    flat_e = topk_idx.reshape(-1)
    dest = flat_e // experts_per_rank                       # [T*k]
    order = jnp.argsort(dest, stable=True)
    sorted_dest = dest[order]
    # position of each sorted entry within its destination group
    start = jnp.searchsorted(sorted_dest, jnp.arange(n), side="left")
    pos = jnp.arange(T * k) - start[sorted_dest]
    valid_sorted = pos < cap
    slot_sorted = jnp.where(valid_sorted,
                            sorted_dest * cap + jnp.minimum(pos, cap - 1),
                            n * cap)
    # back to entry order
    inv = jnp.argsort(order, stable=True)
    slot = slot_sorted[inv]
    valid = valid_sorted[inv]
    token = jnp.arange(T * k) // k
    return DispatchPlan(slot=slot, valid=valid, token=token)


def plan_dispatch_valid(expert_ids, valid, n: int, experts_per_rank: int,
                        cap: int) -> "tuple[DispatchPlan, jax.Array]":
    """plan_dispatch for rows that carry their own validity mask —
    the SECOND hop of the two-tier EP path, where the 'tokens' are
    capacity slots arrived over DCN and the padding slots must not
    consume ICI capacity (reference analog: the per-node recv-offset
    recomputation of kernel_get_ag_splits_and_recv_offset,
    ep_a2a.py:382, which the inter-node dispatch runs after the
    cross-node exchange). expert_ids: [R] ids within this tier's range
    [0, n*experts_per_rank); valid: [R] bool. Invalid rows get
    slot=n*cap, valid=False."""
    R = expert_ids.shape[0]
    dest = jnp.where(valid, expert_ids // experts_per_rank, n)
    order = jnp.argsort(dest, stable=True)
    sorted_dest = dest[order]
    start = jnp.searchsorted(sorted_dest, jnp.arange(n), side="left")
    pos = jnp.arange(R) - start[jnp.minimum(sorted_dest, n - 1)]
    ok = (sorted_dest < n) & (pos < cap)
    slot_sorted = jnp.where(
        ok, sorted_dest * cap + jnp.minimum(pos, cap - 1), n * cap)
    inv = jnp.argsort(order, stable=True)
    # dropped counts only VALID rows lost to capacity (padding is not
    # a drop)
    dropped = jnp.sum((sorted_dest < n) & ~ok).astype(jnp.int32)
    plan = DispatchPlan(slot=slot_sorted[inv],
                        valid=ok[inv] & valid,
                        token=jnp.arange(R))
    # DispatchPlan.dropped would count padding rows as drops on this
    # tier; return the true (valid-only) count alongside
    return plan, dropped


def plan_dispatch_host(topk_idx, n: int, experts_per_rank: int, cap: int
                       ) -> DispatchPlan:
    """Host-side dispatch planning on the native icishmem alignment
    kernel (reference: the csrc moe_align helpers driving the eager
    dispatch path). Matches plan_dispatch on its contract — expert ids
    in [0, n*experts_per_rank) (plan_dispatch's searchsorted path has
    no defined behavior for -1, so this raises on it rather than
    diverge silently); for serving loops that plan on CPU between
    device steps instead of tracing the argsort into the program."""
    import numpy as np
    from triton_dist_tpu.runtime.native import moe_align
    topk = np.asarray(topk_idx, np.int32)
    if (topk < 0).any():
        raise ValueError("plan_dispatch_host: negative expert ids are "
                         "not part of the dispatch contract")
    T, k = topk.shape
    dest = topk.reshape(-1) // experts_per_rank
    counts, offsets, sorted_tok = moe_align(dest.reshape(-1, 1), n, 1)
    slot = np.full(T * k, n * cap, np.int32)
    valid = np.zeros(T * k, bool)
    for d in range(n):
        seg = sorted_tok[offsets[d]:offsets[d] + counts[d]]
        keep = seg[:cap]
        slot[keep] = d * cap + np.arange(len(keep))
        valid[keep] = True
    token = np.arange(T * k) // k
    import jax.numpy as _jnp
    return DispatchPlan(slot=_jnp.asarray(slot),
                        valid=_jnp.asarray(valid),
                        token=_jnp.asarray(token))


def pack_rows_int8(x):
    """[R, D] -> [R, D+4] int8: per-row symmetric int8 quantization with
    the f32 scale packed as 4 trailing int8 lanes, so ONE message
    carries payload and scale (reference: the fp8 online pack inside
    the LL dispatch kernel, low_latency_all_to_all_v2.py:55, and this
    repo's low_latency_all_to_all). Zero rows — capacity padding and
    dropped slots — quantize to zero rows, so they stay inert through
    the wire. Used by EP_MoE(payload_int8=True): the token payload of
    dispatch AND combine travels at half the bf16 bytes; on the DCN
    tier of fwd_ep_2d (where bytes hurt most) the packed rows cross
    BOTH hops without an intermediate dequant, so the only numeric loss
    is one int8 rounding per direction."""
    R, D = x.shape
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q8 = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    sc8 = jax.lax.bitcast_convert_type(scale, jnp.int8).reshape(R, 4)
    return jnp.concatenate([q8, sc8], axis=1)


def unpack_rows_int8(p, D: int, dtype):
    """Inverse of pack_rows_int8 ([R, >=D+4] int8 -> [R, D] dtype);
    trailing columns beyond D+4 (lane padding) are ignored."""
    R = p.shape[0]
    scale = jax.lax.bitcast_convert_type(
        p[:, D:D + 4].reshape(R, 1, 4), jnp.float32).reshape(R, 1)
    return (p[:, :D].astype(jnp.float32) * scale).astype(dtype)


def fill_send_buffers(x, topk_idx, plan: DispatchPlan, n: int,
                      experts_per_rank: int, cap: int):
    """Scatter tokens (+ metadata) into the [n*cap] send layout.
    Returns (send_x [n*cap, D], send_meta [n*cap, 2] int32) where
    meta[:, 0] = local expert id on the destination, meta[:, 1] = valid."""
    T, k = topk_idx.shape
    D = x.shape[1]
    dtype = x.dtype
    local_e = (topk_idx.reshape(-1) % experts_per_rank).astype(jnp.int32)
    send_x = jnp.zeros((n * cap + 1, D), dtype).at[plan.slot].set(
        x[plan.token], mode="drop")[:-1]
    meta = jnp.stack([local_e, plan.valid.astype(jnp.int32)], axis=-1)
    send_meta = jnp.zeros((n * cap + 1, 2), jnp.int32).at[plan.slot].set(
        meta, mode="drop")[:-1]
    return send_x, send_meta


def group_by_expert(recv_x, recv_meta, experts_per_rank: int,
                    expert_cap: int):
    """Arrange received tokens into capacity-padded per-expert batches
    for the grouped GEMM. Returns (x_e [E_loc, expert_cap, D],
    inv_slot [n*cap] — where each recv slot's result lives in the
    flattened [E_loc*expert_cap] expert layout, n*cap.. = dropped,
    dropped — count of VALID arrivals that exceeded expert_cap, the
    receiver-side analog of DispatchPlan.dropped)."""
    R, D = recv_x.shape
    e = jnp.where(recv_meta[:, 1] > 0, recv_meta[:, 0], experts_per_rank)
    order = jnp.argsort(e, stable=True)
    sorted_e = e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(experts_per_rank),
                             side="left")
    pos = jnp.arange(R) - start[jnp.minimum(sorted_e, experts_per_rank - 1)]
    ok = (sorted_e < experts_per_rank) & (pos < expert_cap)
    eslot_sorted = jnp.where(
        ok, sorted_e * expert_cap + jnp.minimum(pos, expert_cap - 1),
        experts_per_rank * expert_cap)
    x_e = jnp.zeros((experts_per_rank * expert_cap + 1, D),
                    recv_x.dtype).at[eslot_sorted].set(
        recv_x[order], mode="drop")[:-1].reshape(
            experts_per_rank, expert_cap, D)
    inv = jnp.argsort(order, stable=True)
    inv_slot = eslot_sorted[inv]
    dropped = jnp.sum((sorted_e < experts_per_rank) & ~ok).astype(jnp.int32)
    return x_e, inv_slot, dropped


def group_tokens_by_expert(x, topk_idx, num_experts: int, cap: int):
    """LOCAL grouping (no a2a): arrange each routed (token, k) entry into
    capacity-padded per-expert batches — the TP-MoE front half (reference:
    sort_topk_ids_align_block_size, allgather_group_gemm.py:201, backed by
    csrc/lib/moe_utils.cu:61). Returns (x_e [E, cap, D], inv_slot [T*k],
    token [T*k]) where inv_slot locates each entry's row in the flattened
    [E*cap] expert layout (E*cap = dropped by capacity)."""
    T, k = topk_idx.shape
    flat_e = topk_idx.reshape(-1)
    token = jnp.arange(T * k) // k
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(num_experts), side="left")
    pos = jnp.arange(T * k) - start[sorted_e]
    ok = pos < cap
    eslot_sorted = jnp.where(ok, sorted_e * cap + jnp.minimum(pos, cap - 1),
                             num_experts * cap)
    x_e = jnp.zeros((num_experts * cap + 1, x.shape[1]), x.dtype
                    ).at[eslot_sorted].set(
        x[token[order]], mode="drop")[:-1].reshape(num_experts, cap, -1)
    inv = jnp.argsort(order, stable=True)
    return x_e, eslot_sorted[inv], token


def scatter_weighted(y_e, inv_slot, token, topk_w, T: int):
    """Topk-weighted combine of LOCAL expert outputs back to token order
    (the weighted reduce of moe_reduce_rs's consumer, reference
    moe_reduce_rs.py:168). y_e: [E, cap, D] -> [T, D] f32."""
    E, cap, D = y_e.shape
    y_flat = y_e.reshape(E * cap, D)
    w = jnp.where(inv_slot < E * cap, topk_w.reshape(-1), 0.0)
    contrib = jnp.take(y_flat, jnp.minimum(inv_slot, E * cap - 1), axis=0)
    contrib = contrib.astype(jnp.float32) * w[:, None]
    return jax.ops.segment_sum(contrib, token, num_segments=T)


def combine_from_slots(y_back, plan: DispatchPlan, topk_w, T: int):
    """Weighted sum of each token's returned expert outputs (reference:
    the topk-weighted reduce inside kernel_combine_token, ep_a2a.py:214).
    y_back: [n*cap, D]; returns [T, D] f32."""
    D = y_back.shape[1]
    w = jnp.where(plan.valid, topk_w.reshape(-1), 0.0)
    contrib = y_back[jnp.minimum(plan.slot, y_back.shape[0] - 1)]
    contrib = contrib.astype(jnp.float32) * w[:, None]
    return jax.ops.segment_sum(contrib, plan.token, num_segments=T)


# ----------------------------------------------------------------------
# Pallas a2a kernels (the one-sided data plane)
# ----------------------------------------------------------------------

def _a2a_payload_kernel(n: int, axis: str, x_ref, m_ref, ox_ref, om_ref,
                        send_sem, recv_x_sem, recv_m_sem):
    """Dispatch a2a carrying payload + metadata in one kernel (ref:
    kernel_dispatch_token, ep_a2a.py:79 — putmem_nbi of data then
    putmem_signal of scale/meta). Chunk p of the send layout goes to
    device p's chunk `me`."""
    me = dl.my_pe(axis)
    C = x_ref.shape[0] // n
    Cm = m_ref.shape[0] // n
    dl.barrier_all(axis)
    for p in range(n):
        dl.putmem_nbi(ox_ref.at[pl.ds(me * C, C)],
                      x_ref.at[pl.ds(p * C, C)],
                      send_sem, recv_x_sem, jnp.int32(p), axis)
        dl.putmem_nbi(om_ref.at[pl.ds(me * Cm, Cm)],
                      m_ref.at[pl.ds(p * Cm, Cm)],
                      send_sem, recv_m_sem, jnp.int32(p), axis)
    dl.dma_wait(recv_x_sem, x_ref.at[pl.ds(0, C)], n)
    dl.dma_wait(recv_m_sem, m_ref.at[pl.ds(0, Cm)], n)
    dl.quiet(send_sem, x_ref.at[pl.ds(0, C)], n)
    dl.quiet(send_sem, m_ref.at[pl.ds(0, Cm)], n)


def dispatch_a2a(send_x, send_meta, *, n: int, axis: str,
                 collective_id: int):
    """Device-local (inside shard_map): exchange send buffers so device d
    ends with every peer's chunk destined for it. [n*cap, D] -> same."""
    if n == 1:
        return send_x, send_meta
    R, D = send_x.shape
    Rm, M = send_meta.shape
    kernel = functools.partial(_a2a_payload_kernel, n, axis)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((R, D), send_x.dtype),
                   jax.ShapeDtypeStruct((Rm, M), send_meta.dtype)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                        pltpu.SemaphoreType.DMA(()),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=shmem_compiler_params(collective_id, n=n),
        interpret=interpret_mode(),
    )(send_x, send_meta)


def dispatch_a2a_int8(send_p, send_meta, *, n: int, axis: str,
                      collective_id: int):
    """dispatch_a2a for pack_rows_int8 payloads: pads the packed lane
    dim to a 128-multiple (Mosaic sliced-DMA alignment) before the
    payload+meta exchange and strips it after. Row capacities must be
    32-multiples on real chips (int8 sublane tiling) — EP_MoE._caps
    rounds them when payload_int8 is on."""
    if n == 1:
        return send_p, send_meta
    R, Dp = send_p.shape
    pad = (-Dp) % 128
    if pad:
        send_p = jnp.pad(send_p, ((0, 0), (0, pad)))
    recv_p, recv_m = dispatch_a2a(send_p, send_meta, n=n, axis=axis,
                                  collective_id=collective_id)
    return recv_p[:, :Dp], recv_m


def combine_a2a(y_slots, *, n: int, axis: str, collective_id: int):
    """Device-local reverse a2a: return expert outputs to the token
    owners (ref: kernel_combine_token's put phase, ep_a2a.py:214).
    Delegates to the one-shot a2a kernel (kernels/all_to_all.py) — the
    combine traffic pattern IS an all-to-all of the slot layout."""
    if n == 1:
        return y_slots
    from triton_dist_tpu.kernels.all_to_all import _a2a_pallas
    return _a2a_pallas(y_slots, n=n, axis=axis, collective_id=collective_id)


# ----------------------------------------------------------------------
# host-level wrapper (test surface; the EP layer calls the device-local
# pieces inside its own shard_map)
# ----------------------------------------------------------------------

def ep_dispatch_combine(x, router_logits, k: int,
                        ctx: EPAll2AllContext,
                        expert_fn=None, expert_cap: Optional[int] = None):
    """Full routed dispatch -> (expert_fn on grouped tokens) -> combine.

    x: [T, D] sharded P(axis, None); router_logits: [T, E] sharded the
    same. expert_fn(x_e [E_loc, C_e, D]) -> same leading shape, applied
    to the capacity-grouped tokens on their owner device
    (identity if None). Returns y [T, D] (same sharding as x): the
    topk-weighted combination of expert outputs — differentially
    testable against a dense jnp MoE oracle.
    """
    n, axis, epr, cap = ctx.n, ctx.axis, ctx.experts_per_rank, ctx.capacity
    e_cap = expert_cap or n * cap
    cid = ctx.collective_id

    @functools.partial(
        jax.shard_map, mesh=ctx.mesh,
        in_specs=(P(axis, None), P(axis, None)),
        out_specs=P(axis, None),
        check_vma=False)
    def _f(x_loc, logits_loc):
        T = x_loc.shape[0]
        topk_w, topk_idx = route(logits_loc, k)
        plan = plan_dispatch(topk_idx, n, epr, cap)
        send_x, send_meta = fill_send_buffers(x_loc, topk_idx, plan, n,
                                              epr, cap)
        recv_x, recv_meta = dispatch_a2a(send_x, send_meta, n=n, axis=axis,
                                         collective_id=cid)
        x_e, inv_slot, r_drop = group_by_expert(recv_x, recv_meta, epr,
                                                e_cap)
        # dropless-or-loud on the public entry point too
        warn_on_drops(plan.dropped + r_drop, "ep_dispatch_combine")
        if expert_fn is not None:
            x_e = expert_fn(x_e)
        y_flat = x_e.reshape(epr * e_cap, -1)
        gathered = jnp.take(y_flat, jnp.minimum(inv_slot, epr * e_cap - 1),
                            axis=0)
        y_slots = gathered * (inv_slot < epr * e_cap)[:, None].astype(
            gathered.dtype)
        y_back = combine_a2a(y_slots, n=n, axis=axis, collective_id=cid)
        y = combine_from_slots(y_back, plan, topk_w, T)
        return y.astype(x_loc.dtype)

    return _f(x, router_logits)


def moe_oracle(x, router_logits, k: int, expert_fn_dense):
    """Dense jnp MoE reference: every token through every expert,
    topk-weighted sum (the torch oracle role from test_ep_a2a.py)."""
    T, D = x.shape
    topk_w, topk_idx = route(router_logits, k)
    y_all = expert_fn_dense(x)          # [E, T, D]
    E = y_all.shape[0]
    onehot = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)  # [T, k, E]
    w_e = jnp.einsum("tk,tke->te", topk_w, onehot)           # [T, E]
    y = jnp.einsum("te,etd->td", w_e, y_all.astype(jnp.float32))
    return y.astype(x.dtype)
