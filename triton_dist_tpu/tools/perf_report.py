"""Per-op perf report: achieved vs speed-of-light for every comm/compute
kernel family (reference analog: the perf printout of each
test/nvidia/test_*.py `--case perf` run, backed by
gemm_perf_model.py:220).

Run:  python -m triton_dist_tpu.tools.perf_report [--json PATH]

On a TPU backend the numbers are real; on the CPU interpreter substrate
they measure the simulator (still useful for relative regressions, and
flagged as such in the output).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import time

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.tools.perf_model import (chip_specs,
                                              collective_sol_us,
                                              gemm_sol_us, sol_report)


def _repeat(step, x0, k):
    """One jit program: `k` data-chained executions of `step` inside a
    fori_loop (one kernel compile regardless of k; the chain defeats
    CSE/reordering), reduced to a scalar so readback is tiny."""
    shd = getattr(x0, "sharding", None)
    if not isinstance(shd, NamedSharding):
        shd = None

    def body(i, v):
        out = step(v)
        if shd is None:
            return out
        # restore the carry's sharding (free when unchanged; a local
        # slice when the op replicated its output); jax.reshard is the
        # explicit-sharding spelling, absent on older jax — a sharding
        # constraint says the same thing there
        if hasattr(jax, "reshard"):
            return jax.reshard(out, shd)
        return jax.lax.with_sharding_constraint(out, shd)

    @jax.jit
    def prog(x):
        out = jax.lax.fori_loop(0, k, body, x)
        return jnp.sum(jax.tree.leaves(out)[0]).astype(jnp.float32)

    return functools.partial(prog, x0)


def _time(step, x0, *, k1=None, k2=None, reps=3, slopes=3):
    """Two-point amortized timing: per-op time is the slope between a
    k1-iteration and a k2-iteration loop program, cancelling the
    constant dispatch/readback overhead.
    `step(x) -> x_like` must thread a data dependence.

    A chip behind a shared host showed +-30% run-to-run noise, so take
    the MIN over `slopes` interleaved slope estimates — the best pair
    is the least-contended measurement of the same
    program. Off-chip (the interpreter smoke, where per-iteration cost
    is ~1000x and the numbers only guard against breakage) the loop
    counts shrink so the full report stays runnable."""
    if k1 is None or k2 is None:
        on_tpu = jax.default_backend() == "tpu"
        k1 = k1 if k1 is not None else (64 if on_tpu else 2)
        k2 = k2 if k2 is not None else (1024 if on_tpu else 10)
    f1, f2 = _repeat(step, x0, k1), _repeat(step, x0, k2)
    # float() forces a host readback, so the device work is finished
    # before the clock is read (as bench.py does)
    float(f1())
    float(f2())

    def best(f):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(f())
            ts.append(time.perf_counter() - t0)
        return min(ts)

    t1s, t2s = [], []
    for _ in range(slopes):
        t1s.append(best(f1))
        t2s.append(best(f2))
    # ONE slope from the pooled minima: min over per-round slope
    # DIFFERENCES would be biased low (it picks the round whose t1 was
    # contention-inflated relative to t2)
    return max((min(t2s) - min(t1s)) / (k2 - k1), 1e-9) * 1e6   # us


# below this slope the chain was elided (an op that is the identity at
# this size/ndev — e.g. any pure collective at ndev=1 — costs nothing
# inside the loop); no real TPU kernel dispatches faster
_ELIDED_US = 0.05


def chain(op):
    """Thread a serial data dependence WITHOUT changing the carry's
    sharding: fold the op's output into a negligible scalar
    perturbation of the input (f32 accumulation so the bf16 sum
    cannot overflow to inf and poison the carry). Feeding the output
    back directly would insert a cross-device reshard inside the
    timed loop for ops whose output sharding differs from their
    input's, inflating the measured per-op time. Shared with
    tools/kprof_run.py so PROFILE and PERF_OPS rows measure through
    the identical harness."""
    def step(v):
        eps = jnp.sum(op(v), dtype=jnp.float32) * 1e-30
        return v + eps.astype(v.dtype)
    return step


# registry name -> this report's row name(s); names absent here match
# on the registry name itself. Rows measure the HOST-LEVEL op, so
# several registry entries share one row (methods are row variants).
_ROW_OF = {
    "allgather_one_shot": "all_gather(one_shot)",
    "allgather_ring": "all_gather(ring)",
    "allreduce_one_shot": "all_reduce(one_shot)",
    "allreduce_two_shot": "all_reduce(two_shot)",
    "reduce_scatter_one_shot": "reduce_scatter",
    "reduce_scatter_ring": "reduce_scatter",
    "gemm_ar": "gemm_allreduce",
    "gdn_fwd": "gdn_fwd(pallas)",
}


def registry_coverage(measured_ops):
    """Cross-check this report's rows against the central kernel
    registry (kernels.kernel_registry — ISSUE 15: one enumeration for
    tdcheck, bench and the profile tools). A kernel added to the
    registry shows in `uncovered` until it gets a measured row here
    (named in _ROW_OF when the row spelling differs), so the catalogs
    cannot silently drift apart."""
    from triton_dist_tpu.kernels import kernel_registry
    measured = set(measured_ops)
    uncovered = []
    for name in kernel_registry():
        if _ROW_OF.get(name, name) not in measured:
            uncovered.append(name)
    return {"kernels_registered": len(kernel_registry()),
            "uncovered": sorted(uncovered)}


# the roofline CI gate's op subset (bench.py TDTPU_BENCH_SOLFRAC
# default): the tuned hot-path kernels, cheap enough on the CPU
# interpreter to ride inside the bench budget. "all" runs every row.
GATE_OPS = ("ag_gemm", "gemm_rs", "gemm_allreduce", "flash_decode",
            "flash_decode_paged", "ag_group_gemm", "moe_reduce_rs")


def sol_frac_rows(report):
    """Flatten a run_report() dict into bench-capture rows — one
    `{op}_sol_frac` row per measured op, unit "frac of SOL" (which
    tools/bench_compare.py treats as higher-is-better). Elided /
    degenerate rows (sol_frac None) are dropped: a clamped slope is
    not a roofline fraction."""
    env = report.get("env", {})
    rows = []
    for r in report.get("ops", []):
        frac = r.get("sol_frac")
        if frac is None:
            continue
        rows.append({
            "metric": f"{r['op']}_sol_frac",
            "value": round(float(frac), 5),
            "unit": "frac of SOL",
            "achieved_us": round(float(r["achieved_us"]), 3),
            "sol_us": round(float(r["sol_us"]), 3),
            "backend": env.get("backend", "unknown"),
            "ndev": env.get("ndev"),
            "interpreted": env.get("interpreted"),
        })
    return rows


def run_report(write_json=None, only=None):
    from triton_dist_tpu.kernels import (
        AllGatherMethod, AllReduceMethod, ag_gemm, all_gather, all_reduce,
        create_ag_gemm_context, create_gemm_ar_context,
        create_gemm_rs_context, flash_decode, gemm_allreduce, gemm_rs,
        reduce_scatter)

    # `only` restricts the report to a subset of row names (GATE_OPS
    # for the bench gate); unfiltered runs are unchanged. Sections
    # whose every row is filtered out skip their setup entirely, so a
    # gate run does not pay for PP/EP/ring machinery it will not time.
    wanted = None if only is None else frozenset(only)

    def want(name):
        return wanted is None or name in wanted

    ndev = len(jax.devices())
    on_tpu = jax.default_backend() == "tpu"
    mesh = jax.make_mesh((ndev,), ("tp",))
    spec = chip_specs()
    n = ndev
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    isz = jnp.dtype(dt).itemsize
    if on_tpu:
        # M sized so the fused kernels' whole-activation VMEM staging
        # fits a single chip's 16MB scoped vmem even at n=1 (m_loc = M)
        M, K, N = 256, 4096, 4096
    else:
        M, K, N = 64, 128, 256
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(M, K), dt)
    b = jnp.asarray(rng.randn(K, N), dt)
    x = jnp.asarray(rng.randn(M * n, N), dt)
    xs = jax.device_put(x, NamedSharding(mesh, P("tp")))
    xp = jax.device_put(jnp.broadcast_to(x[None] / n, (n,) + x.shape),
                        NamedSharding(mesh, P("tp", None, None)))
    a_cols = jax.device_put(a, NamedSharding(mesh, P(None, "tp")))
    b_rows = jax.device_put(b, NamedSharding(mesh, P("tp", None)))

    rows = []

    def add(name, step, x0, sol_us, note=""):
        if not want(name):
            return
        try:
            t = _time(step, x0)
        except Exception as e:  # noqa: BLE001
            # an op that cannot execute on this substrate (e.g. the comm
            # ring kernels on a jax without the Pallas TPU interpreter)
            # gets a degenerate row, not a dead report — the roofline
            # gate still sees every other row, and the note names the
            # failure so an on-chip crash cannot pass silently
            rows.append({"op": name, "achieved_us": None,
                         "sol_us": sol_us, "sol_frac": None,
                         "note": f"FAILED: {type(e).__name__}: {e}"[:300]})
            print(f"{name:24s}  FAILED ({type(e).__name__})")
            return
        if t < _ELIDED_US:
            # a floor-clamped slope is NOT a latency; report it as a
            # degenerate row rather than a physically impossible number
            note = (note + "; " if note else "") + (
                "DEGENERATE: loop chain elided (op is identity at "
                f"ndev={ndev}/this size); not a latency")
            rows.append({"op": name, "achieved_us": None, "sol_us": sol_us,
                         "sol_frac": None, "note": note})
            print(f"{name:24s}  elided ({note})")
            return
        rows.append({"op": name, "achieved_us": t, "sol_us": sol_us,
                     "sol_frac": sol_us / t if t else 0.0,
                     "note": note})
        print(sol_report(name, t, sol_us) + (f"  [{note}]" if note else ""))

    # AG rows feed their output back directly (the carry's reshard is
    # free); AR/RS rows use chain()'s scalar-perturbation feed — their
    # output sharding differs from the carry's on a DIFFERENT dim, and
    # a broadcast feed would produce an illegally double-sharded add at
    # ndev > 1.
    # collective_sol_us expects FULL-tensor bytes (its (n-1)/n factor is
    # the per-device share of the total payload)
    full_bytes = n * M * N * isz
    add("all_gather(one_shot)",
        lambda v: all_gather(v, mesh=mesh,
                             method=AllGatherMethod.ONE_SHOT), xs,
        collective_sol_us("ag", full_bytes, n, spec=spec))
    add("all_gather(ring)",
        lambda v: all_gather(v, mesh=mesh, method=AllGatherMethod.RING),
        xs, collective_sol_us("ag", full_bytes, n, spec=spec))
    # scalar-chained feed (chain()): the broadcast feed `v*0 + out[None]`
    # produces an illegally double-sharded add at ndev > 1 (the carry is
    # partial-sharded on dim 0, the output on dim 1)
    add("all_reduce(one_shot)",
        chain(lambda v: all_reduce(v, mesh=mesh,
                                   method=AllReduceMethod.ONE_SHOT)),
        xp, collective_sol_us("ar", n * M * N * isz, n, spec=spec))
    add("all_reduce(two_shot)",
        chain(lambda v: all_reduce(v, mesh=mesh,
                                   method=AllReduceMethod.TWO_SHOT)),
        xp, collective_sol_us("ar", n * M * N * isz, n, spec=spec))
    add("reduce_scatter",
        chain(lambda v: reduce_scatter(v, mesh=mesh)),
        xp, collective_sol_us("rs", n * M * N * isz, n, spec=spec))
    # GEMM SOL terms use PER-CHIP dims: ag_gemm computes [M, K]@[K, N/n]
    # per chip, gemm_rs/gemm_ar compute [M, K/n]@[K/n, N]
    if want("ag_gemm"):
        a_rows = jax.device_put(a, NamedSharding(mesh, P("tp", None)))
        b_cols = jax.device_put(b, NamedSharding(mesh, P(None, "tp")))
        ag_ctx = create_ag_gemm_context(mesh)
        add("ag_gemm",
            chain(lambda v: ag_gemm(v, b_cols, ag_ctx)), a_rows,
            gemm_sol_us(M, K, N // n, itemsize=isz, spec=spec)
            + collective_sol_us("ag", M * K * isz, n, spec=spec))
    if want("gemm_rs"):
        rs_ctx = create_gemm_rs_context(mesh)
        add("gemm_rs",
            chain(lambda v: gemm_rs(v, b_rows, rs_ctx)), a_cols,
            gemm_sol_us(M, K // n, N, itemsize=isz, spec=spec)
            + collective_sol_us("rs", M * N * isz, n, spec=spec))
    if want("gemm_allreduce"):
        ar_ctx = create_gemm_ar_context(mesh)
        add("gemm_allreduce",
            chain(lambda v: gemm_allreduce(v, b_rows, ar_ctx)), a_cols,
            gemm_sol_us(M, K // n, N, itemsize=isz, spec=spec)
            + collective_sol_us("ar", M * N * isz, n, spec=spec))

    # flash decode: B=8 heads=16/8 T=2048
    B, S, Hq, Hkv, T, d = (8, 1, 16, 8, 2048, 128) if on_tpu else \
                          (2, 1, 4, 2, 256, 64)
    q = jnp.asarray(rng.randn(B, S, Hq, d), dt)
    k = jnp.asarray(rng.randn(B, Hkv, T, d), dt)
    v = jnp.asarray(rng.randn(B, Hkv, T, d), dt)
    kv_bytes = 2 * B * Hkv * T * d * isz
    add("flash_decode",
        lambda u: flash_decode(u, k, v, jnp.int32(T)), q,
        kv_bytes / (spec.hbm_gbps * 1e9) * 1e6)

    # paged decode: same KV bytes through the page-table walk (W
    # slots per grid step); the row exists to keep the paged/contig
    # gap measured (target: within 15%)
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
    pg = 128 if on_tpu else 64
    maxp = T // pg

    def paged(a):       # [B, Hkv, T, d] -> pages [B*maxp, Hkv, pg, d]
        return a.reshape(B, Hkv, maxp, pg, d).transpose(
            0, 2, 1, 3, 4).reshape(B * maxp, Hkv, pg, d)

    pk, pv = paged(k), paged(v)
    ptab = jnp.arange(B * maxp, dtype=jnp.int32).reshape(B, maxp)
    add("flash_decode_paged",
        lambda u: flash_decode_paged(u, pk, pv, ptab, jnp.int32(T)), q,
        kv_bytes / (spec.hbm_gbps * 1e9) * 1e6,
        note="same bytes as flash_decode; gap = page-walk overhead")

    # MoE ring kernels (resident-B path at these sizes)
    if want("ag_group_gemm") or want("moe_reduce_rs") \
            or want("moe_reduce_ar"):
        from triton_dist_tpu.kernels.ag_group_gemm import ag_group_gemm
        from triton_dist_tpu.kernels.moe_reduce_rs import moe_reduce_rs
        E, capT, Dm, Nm = (8, 512, 1024, 1024) if on_tpu else \
                          (2, 8 * n, 64, 64 * n)
        xe = jax.device_put(jnp.asarray(rng.randn(E, capT, Dm), dt) * 0.1,
                            NamedSharding(mesh, P(None, "tp", None)))
        we = jax.device_put(jnp.asarray(rng.randn(E, Dm, Nm), dt) * 0.1,
                            NamedSharding(mesh, P(None, None, "tp")))
        add("ag_group_gemm",
            chain(lambda v: ag_group_gemm(v, we, mesh=mesh)), xe,
            gemm_sol_us(E * capT, Dm, Nm // n, itemsize=isz, spec=spec)
            + collective_sol_us("ag", E * capT * Dm * isz, n, spec=spec))
        he = jax.device_put(jnp.asarray(rng.randn(E, capT, Nm), dt) * 0.1,
                            NamedSharding(mesh, P(None, None, "tp")))
        w2 = jax.device_put(jnp.asarray(rng.randn(E, Nm, Dm), dt) * 0.1,
                            NamedSharding(mesh, P(None, "tp", None)))
        add("moe_reduce_rs",
            chain(lambda v: moe_reduce_rs(v, w2, mesh=mesh)), he,
            gemm_sol_us(E * capT, Nm // n, Dm, itemsize=isz, spec=spec)
            + collective_sol_us("rs", E * capT * Dm * isz, n, spec=spec))

        he2 = jax.device_put(jnp.asarray(rng.randn(E, capT, Nm), dt) * 0.1,
                             NamedSharding(mesh, P(None, None, "tp")))
        from triton_dist_tpu.kernels.moe_reduce_ar import moe_reduce_ar
        add("moe_reduce_ar",
            chain(lambda v: moe_reduce_ar(v, w2, mesh=mesh)), he2,
            gemm_sol_us(E * capT, Nm // n, Dm, itemsize=isz, spec=spec)
            + collective_sol_us("ar", E * capT * Dm * isz, n, spec=spec))

    # fused one-kernel EP MoE at the ep_fused docstring shape; SOL =
    # the grouped-GEMM flops over the CAPACITY rows the kernel actually
    # multiplies + the a2a payload both ways
    if want("ep_fused"):
        from triton_dist_tpu.layers.ep_moe import EP_MoE
        Ee, De, Ie = (8, 1024, 512) if on_tpu else (2 * n, 64, 32)
        Te = 1024 if on_tpu else 8 * n
        epr_rng = np.random.RandomState(7)
        moe_f = EP_MoE.init(
            jnp.asarray(epr_rng.randn(De, Ee), dt) * 0.5,
            jnp.asarray(epr_rng.randn(Ee, De, Ie), dt) * (De ** -0.5),
            jnp.asarray(epr_rng.randn(Ee, De, Ie), dt) * (De ** -0.5),
            jnp.asarray(epr_rng.randn(Ee, Ie, De), dt) * (Ie ** -0.5),
            mesh=mesh, axis="tp", top_k=2, capacity_factor=1.25)
        xe_f = jax.device_put(jnp.asarray(epr_rng.randn(Te, De), dt) * 0.3,
                              NamedSharding(mesh, P("tp", None)))
        cap_rows = Ee * moe_f._cap_e(Te // n) * n
        ep_sol = (gemm_sol_us(cap_rows, De, 2 * Ie, itemsize=isz,
                              spec=spec)
                  + gemm_sol_us(cap_rows, Ie, De, itemsize=isz, spec=spec)
                  + 2 * collective_sol_us("a2a", cap_rows * De * isz, n,
                                          spec=spec))
        add("ep_fused",
            chain(lambda v: moe_f(v, mode="ep_fused")), xe_f, ep_sol)

    # Ulysses fused QKV/O kernels (both a2a directions ride their
    # adjacent GEMMs): SOL = GEMM + a2a payload
    if want("ulysses_qkv_gemm_a2a") or want("ulysses_o_a2a_gemm"):
        from triton_dist_tpu.kernels.sp_attention import (o_a2a_gemm,
                                                          qkv_gemm_a2a)
        Bu, Su, Du, Nu = (2, 2048, 1024, 1024) if on_tpu else \
                         (1, 8 * n, 64, 64)
        xu = jax.device_put(jnp.asarray(rng.randn(Bu, Su, Du), dt) * 0.1,
                            NamedSharding(mesh, P(None, "tp", None)))
        wu_ = jnp.asarray(rng.randn(Du, Nu), dt) * 0.1
        add("ulysses_qkv_gemm_a2a",
            chain(lambda v: qkv_gemm_a2a(v, wu_, mesh=mesh, axis="tp")),
            xu,
            gemm_sol_us(Bu * Su // n, Du, Nu, itemsize=isz, spec=spec)
            + collective_sol_us("a2a", Bu * Su // n * Nu * isz, n,
                                spec=spec))
        xo = jax.device_put(jnp.asarray(rng.randn(Bu, Su, Nu), dt) * 0.1,
                            NamedSharding(mesh, P(None, None, "tp")))
        wo_ = jnp.asarray(rng.randn(Nu, Du), dt) * 0.1
        add("ulysses_o_a2a_gemm",
            chain(lambda v: o_a2a_gemm(v, wo_, mesh=mesh, axis="tp")),
            xo,
            gemm_sol_us(Bu * Su // n, Nu, Du, itemsize=isz, spec=spec)
            + collective_sol_us("a2a", Bu * Su // n * Nu * isz, n,
                                spec=spec))

    # PP: GPipe forward at pp=ndev. SOL = (M + n - 1) ticks x the
    # per-stage GEMM bound (the schedule's ideal span; the gap above it
    # is handoff + bank overhead). At ndev=1 the ring degenerates but
    # the tick loop still runs — the row then measures pure schedule
    # overhead per tick.
    if want("pp_gpipe_fwd"):
        from triton_dist_tpu.layers.pp import PPipeline
        Mp, Bp, Dp = 4 * max(n, 2), (64 if on_tpu else 8), (1024 if on_tpu
                                                            else 64)
        wp = jnp.asarray(rng.randn(n, Dp, Dp), dt) * (Dp ** -0.5)
        bp = jnp.asarray(rng.randn(n, Dp), dt) * 0.1
        pp_mesh = jax.make_mesh((n,), ("pp",))
        pipe = PPipeline.init(
            {"w": wp, "b": bp},
            lambda p, xx: jnp.tanh(xx @ p["w"] + p["b"]),
            mesh=pp_mesh, axis="pp")
        xpp = jnp.asarray(rng.randn(Mp, Bp, Dp), dt) * 0.3
        add("pp_gpipe_fwd",
            lambda v: v + 1e-30 * jnp.sum(
                pipe(v), dtype=jnp.float32).astype(v.dtype),
            xpp,
            (Mp + n - 1) * gemm_sol_us(Bp, Dp, Dp, itemsize=isz,
                                       spec=spec),
            note=f"M={Mp} microbatches, {Mp + n - 1} ticks; SOL = ideal "
                 "schedule span")

    # GDN chunkwise forward, Pallas kernel (gdn_fwd default; roofline:
    # qkv/g/beta/o traffic vs the chunk matmul FLOPs)
    if want("gdn_fwd(pallas)"):
        from triton_dist_tpu.kernels.gdn import gdn_fwd
        Bg, Hg, Tg, dk_, dv_ = (8, 16, 2048, 128, 128) if on_tpu else \
                               (2, 2, 256, 32, 32)
        C = 64
        qg = jnp.asarray(rng.randn(Bg, Hg, Tg, dk_), dt) * 0.3
        kg = jnp.asarray(rng.randn(Bg, Hg, Tg, dk_), dt) * 0.3
        vg = jnp.asarray(rng.randn(Bg, Hg, Tg, dv_), dt) * 0.3
        gg = jnp.asarray(-np.abs(rng.rand(Bg, Hg, Tg)) * 0.1, jnp.float32)
        bg = jnp.asarray(rng.rand(Bg, Hg, Tg), jnp.float32)
        gdn_bytes = Bg * Hg * Tg * (2 * dk_ + 2 * dv_) * isz
        gdn_flops = 2 * Bg * Hg * Tg * (2 * C * dk_ + 2 * C * dv_
                                        + 2 * dk_ * dv_)
        gdn_sol = max(gdn_bytes / (spec.hbm_gbps * 1e9),
                      gdn_flops / (spec.bf16_tflops * 1e12)) * 1e6
        add("gdn_fwd(pallas)",
            lambda u: gdn_fwd(u, kg, vg, gg, bg, chunk=C)[0], qg, gdn_sol)

    # SP ring attention: fused one-kernel shmem ring vs the XLA-permute
    # ring (at ndev=1 the ring degenerates to the local block — the row
    # then times the fused kernel's tile engine, comm-free)
    if want("sp_ring(ring_shmem)") or want("sp_ring(ring)"):
        from triton_dist_tpu.kernels.sp_attention import sp_ring_attention
        # rows kept small enough for BOTH modes' tilings (the XLA-permute
        # partial path needs an 8-aligned batch block)
        # d=128 in BOTH substrates: smaller d fails ring_shmem's
        # alignment gate and would silently time the XLA ring under the
        # shmem label
        Bs, Hqs, Hkvs, Ss, ds = (2, 16, 16, 256, 128) if on_tpu else \
                                (1, 2, 2, 8 * n, 128)
        qr = jnp.asarray(rng.randn(Bs, Ss, Hqs, ds), dt) * 0.3
        kr = jnp.asarray(rng.randn(Bs, Hkvs, Ss, ds), dt) * 0.3
        vr = jnp.asarray(rng.randn(Bs, Hkvs, Ss, ds), dt) * 0.3
        qr = jax.device_put(qr,
                            NamedSharding(mesh, P(None, "tp", None, None)))
        kr = jax.device_put(kr,
                            NamedSharding(mesh, P(None, None, "tp", None)))
        vr = jax.device_put(vr,
                            NamedSharding(mesh, P(None, None, "tp", None)))
        ring_flops = 2 * 2 * Bs * Hqs * Ss * Ss * ds / 2  # qk+pv, causal
        ring_sol = ring_flops / (spec.bf16_tflops * 1e12) * 1e6
        for ring_mode in ("ring_shmem", "ring"):
            add(f"sp_ring({ring_mode})",
                (lambda mm: lambda u: u + 1e-30 * jnp.sum(
                    sp_ring_attention(u, kr, vr, mesh=mesh, axis="tp",
                                      mode=mm), dtype=jnp.float32
                    ).astype(u.dtype))(ring_mode),
                qr, ring_sol,
                note="latency-bound at this size; SOL is the pure-FLOPs "
                     "bound (compare the two modes, not the fraction)")

    # provenance stamp: a perf artifact must say WHICH code it measured
    # (r4 verdict: stale rows were indistinguishable from current ones)
    import datetime
    import subprocess
    try:
        git = subprocess.run(
            ["git", "-C", _REPO, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10).stdout.strip()
        dirty = bool(subprocess.run(
            ["git", "-C", _REPO, "status", "--porcelain"],
            capture_output=True, text=True, timeout=10).stdout.strip())
    except Exception:
        git, dirty = "unknown", False
    header = {"backend": jax.default_backend(), "ndev": ndev,
              "chip": spec.name, "interpreted": not on_tpu,
              "git": git + ("+dirty" if dirty else ""),
              "date": datetime.datetime.now(
                  datetime.timezone.utc).isoformat(timespec="seconds")}
    # a filtered run would report every unfiltered kernel "uncovered";
    # record what it was filtered to instead
    out = {"env": header, "ops": rows,
           "registry": (registry_coverage([r["op"] for r in rows])
                        if wanted is None
                        else {"filtered_to": sorted(wanted)})}
    if write_json:
        with open(write_json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {write_json}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--only", default=None,
                    help="comma-separated row names (e.g. the CI gate's "
                         "subset: " + ",".join(GATE_OPS) + ")")
    args = ap.parse_args()
    only = args.only.split(",") if args.only else None
    run_report(args.json, only=only)


if __name__ == "__main__":
    main()
