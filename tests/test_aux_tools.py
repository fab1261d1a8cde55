"""Tests for the aux surface added in round 2: device-side broadcast /
fcollect helpers (reference: libshmem_device collectives), topology
probing (nv_utils analog), AOT export (compile_aot.py analog), and the
host profiler (profiler_utils.py:205 analog)."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu import language as dl
from triton_dist_tpu.runtime import (interpret_mode, next_collective_id,
                                     shmem_compiler_params)

mesh = None


def setup_module(module):
    global mesh
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("tp",))


def _run_collective(kernel, x, out_rows_factor=1):
    n = mesh.shape["tp"]
    cid = next_collective_id()
    rows, cols = x.shape[1], x.shape[2]

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=P("tp", None, None),
                       out_specs=P("tp", None, None), check_vma=False)
    def _f(x_loc):
        out = pl.pallas_call(
            functools.partial(kernel, n),
            out_shape=jax.ShapeDtypeStruct(
                (out_rows_factor * rows, cols), x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(())],
            compiler_params=shmem_compiler_params(cid, n=n),
            interpret=interpret_mode(),
        )(x_loc[0])
        return out[None]

    xs = jax.device_put(jnp.asarray(x),
                        NamedSharding(mesh, P("tp", None, None)))
    return np.asarray(jax.jit(_f)(xs))


def test_broadcastmem():
    n = mesh.shape["tp"]
    x = np.random.RandomState(0).randn(n, 8, 128).astype(np.float32)

    def kernel(n_, x_ref, o_ref, send_sem, recv_sem):
        dl.barrier_all("tp")
        dl.broadcastmem(o_ref, x_ref, jnp.int32(1), "tp", send_sem,
                        recv_sem)

    out = _run_collective(kernel, x)
    for d in range(n):
        np.testing.assert_array_equal(out[d], x[1])


def test_fcollect():
    n = mesh.shape["tp"]
    x = np.random.RandomState(1).randn(n, 4, 128).astype(np.float32)

    def kernel(n_, x_ref, o_ref, send_sem, recv_sem):
        dl.barrier_all("tp")
        dl.fcollect(o_ref, x_ref, "tp", send_sem, recv_sem)

    out = _run_collective(kernel, x, out_rows_factor=n)
    full = x.reshape(n * 4, 128)
    for d in range(n):
        np.testing.assert_array_equal(out[d], full)


def test_topology_probe_and_mesh():
    from triton_dist_tpu.runtime.topology import (Topology, probe_topology,
                                                  recommend_mesh,
                                                  ring_order)
    topo = probe_topology()
    assert topo.n_devices == len(jax.devices())
    assert topo.n_slices >= 1
    shape, names = recommend_mesh(topo)
    assert int(np.prod(shape)) == topo.n_devices
    assert len(shape) == len(names)
    # tp subdivision
    if topo.n_devices % 2 == 0 and not topo.multislice:
        shape2, names2 = recommend_mesh(topo, tp=2)
        assert shape2[-1] == 2 and names2[-1] == "tp"
    # virtual CPU devices have no coords -> ring order unavailable
    order = ring_order(topo)
    assert order is None or sorted(order) == list(range(topo.n_devices))
    # synthetic multislice topo: dcn axis goes outermost
    fake = Topology(n_devices=8, platform="tpu", device_kind="v5e",
                    coords=None, torus=None, n_slices=2,
                    devices_per_slice=4)
    shape3, names3 = recommend_mesh(fake)
    assert names3[0] == "dcn" and shape3[0] == 2


def test_aot_export_roundtrip():
    from triton_dist_tpu.tools.aot import aot_export, aot_load

    def f(x, y):
        return jnp.tanh(x) @ y

    x = jnp.asarray(np.random.RandomState(0).randn(8, 16), jnp.float32)
    y = jnp.asarray(np.random.RandomState(1).randn(16, 4), jnp.float32)
    blob = aot_export(f, (x, y))
    assert isinstance(blob, (bytes, bytearray)) and len(blob) > 100
    g = aot_load(bytes(blob))
    np.testing.assert_allclose(np.asarray(g(x, y)), np.asarray(f(x, y)),
                               atol=1e-6, rtol=1e-6)


def test_group_profile(tmp_path):
    from triton_dist_tpu.tools.profile import group_profile, named_region

    with group_profile("unit", log_dir=str(tmp_path)) as prof:
        with named_region("unit_matmul"):
            x = jnp.ones((64, 64))
            jax.block_until_ready(jax.jit(lambda v: v @ v)(x))
    assert prof["wall_s"] > 0
    assert prof["trace_dir"] == str(tmp_path)
    assert any(os.path.isfile(f) for f in prof["files"])


@pytest.mark.slow  # slow: tier-1's 870 s budget (ISSUE 15 relief) — heavy interpreted comm arm; the full suite (no -m filter) and the on-chip scripts still run it
def test_comm_trace_records_put_structure():
    """dl.comm_trace() captures the per-device SPMD comm structure at
    trace time: the ag_gemm ring must show n-1 neighbor puts of the
    local chunk's bytes, one barrier, and the final send drain — the
    raw material of MULTICHIP_OVERLAP.md. Runs isolated (fresh
    process): see _comm_trace_case.py."""
    from _isolation import run_isolated
    run_isolated("_comm_trace_case.py", "ag_gemm_trace")


def test_kprof_attribution_and_trace(tmp_path):
    """kprof: attribution = t_full - t_without (clamped at 0), residual
    covers unattributed time, Perfetto export is well-formed."""
    import json
    from triton_dist_tpu.tools.kprof import profile_phases
    rep = profile_phases(
        "toy", lambda: 100.0,
        {"mxu": lambda: 40.0,      # attribution 60
         "dma": lambda: 90.0,      # attribution 10
         "hidden": lambda: 120.0}, # slower-without (noise) -> clamp 0
        json_path=str(tmp_path / "p.json"),
        trace_path=str(tmp_path / "p.trace.json"))
    assert rep["phases"]["mxu"]["attribution_us"] == 60.0
    assert rep["phases"]["hidden"]["attribution_us"] == 0.0
    assert rep["residual_us"] == 30.0
    assert abs(rep["overlap_slack"] - 0.7) < 1e-9
    tr = json.load(open(tmp_path / "p.trace.json"))
    names = [e["name"] for e in tr["traceEvents"]]
    assert "toy (full)" in names and "mxu" in names
    assert "residual (protocol/launch)" in names


def test_kprof_ablation_variants_run(ctx8):
    """Every kprof ablation variant of every covered kernel must
    compile and run with the semaphore discipline balanced (VERDICT r4
    weak #4: coverage was one kernel) — values are garbage by design,
    only shape/termination is asserted. The full-phase run of each
    kernel is exercised by its own differential tests."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from triton_dist_tpu.kernels.ag_group_gemm import ag_group_gemm
    from triton_dist_tpu.kernels.gdn import gdn_fwd
    from triton_dist_tpu.kernels.moe_reduce_rs import moe_reduce_rs
    from triton_dist_tpu.layers.ep_moe import EP_MoE
    from triton_dist_tpu.tools.kprof_run import PHASES
    mesh = ctx8.mesh
    n = mesh.shape["tp"]
    rng = np.random.RandomState(3)
    E, capT, D, N = 2, 8 * n, 128, 128 * n
    xe = jax.device_put(jnp.asarray(rng.randn(E, capT, D), jnp.float32),
                        NamedSharding(mesh, P(None, "tp", None)))
    we = jax.device_put(jnp.asarray(rng.randn(E, D, N), jnp.float32),
                        NamedSharding(mesh, P(None, None, "tp")))
    for ph in PHASES["ag_group_gemm"]:
        y = ag_group_gemm(xe, we, mesh=mesh, ablate=frozenset([ph]))
        assert y.shape == (E, capT, N // 1), (ph, y.shape)
    he = jax.device_put(jnp.asarray(rng.randn(E, capT, N), jnp.float32),
                        NamedSharding(mesh, P(None, None, "tp")))
    w2 = jax.device_put(jnp.asarray(rng.randn(E, N, D), jnp.float32),
                        NamedSharding(mesh, P(None, "tp", None)))
    for ph in PHASES["moe_reduce_rs"]:
        y = moe_reduce_rs(he, w2, mesh=mesh, ablate=frozenset([ph]))
        assert y.shape == (E, capT, D), (ph, y.shape)
    Ee, De, Ie, T = 2 * n, 64, 32, 8 * n
    moe = EP_MoE.init(
        jnp.asarray(rng.randn(De, Ee), jnp.float32) * 0.5,
        jnp.asarray(rng.randn(Ee, De, Ie), jnp.float32) * (De ** -0.5),
        jnp.asarray(rng.randn(Ee, De, Ie), jnp.float32) * (De ** -0.5),
        jnp.asarray(rng.randn(Ee, Ie, De), jnp.float32) * (Ie ** -0.5),
        mesh=mesh, axis="tp", top_k=2, capacity_factor=float(Ee))
    xf = jax.device_put(jnp.asarray(rng.randn(T, De), jnp.float32),
                        NamedSharding(mesh, P("tp", None)))
    for ph in PHASES["ep_fused"]:
        # one program per variant: run op by op, the interpreter's
        # barrier callbacks deadlock against the next eager op
        y = jax.jit(lambda x, ph=ph: moe(
            x, mode="ep_fused", fused_ablate=frozenset([ph])))(xf)
        assert y.shape == (T, De), (ph, y.shape)
    q = jnp.asarray(rng.randn(1, 2, 128, 128), jnp.float32) * 0.3
    g = jnp.asarray(-np.abs(rng.rand(1, 2, 128)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.rand(1, 2, 128), jnp.float32)
    for ph in PHASES["gdn"]:
        o, sT = gdn_fwd(q, q, q, g, b, ablate=frozenset([ph]))
        assert o.shape == q.shape and sT.shape == (1, 2, 128, 128), ph


def test_ag_gemm_progress_trace(ctx8):
    """ag_gemm(progress_trace=True): per-rank per-ring-step semaphore
    stamps (the Mosaic-feasible slice of the reference's in-kernel
    timeline, tools/profiler/language.py:38 — see kprof.py docstring).
    Output must equal the untraced run; stamps must cover exactly the
    n-1 consumer-wait steps (>= 0) and mark the rest -1."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from triton_dist_tpu.kernels import ag_gemm, create_ag_gemm_context
    mesh = ctx8.mesh
    n = mesh.shape["tp"]
    rng = np.random.RandomState(12)
    M, K, N = 8 * n, 64, 32 * n
    a = jax.device_put(jnp.asarray(rng.randn(M, K), jnp.float32) * .1,
                       NamedSharding(mesh, P("tp", None)))
    b = jax.device_put(jnp.asarray(rng.randn(K, N), jnp.float32) * .1,
                       NamedSharding(mesh, P(None, "tp")))
    want = np.asarray(jax.jit(
        lambda x, w: ag_gemm(x, w, create_ag_gemm_context(mesh)))(a, b))
    out, trace = jax.jit(
        lambda x, w: ag_gemm(x, w, create_ag_gemm_context(mesh),
                             progress_trace=True))(a, b)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5,
                               rtol=1e-5)
    tr = np.asarray(trace)
    assert tr.shape == (n, n, 2)
    # on chip: real semaphore counts (>= 0); on the interpreter
    # (semaphore_read has no lowering): the -2 "step reached" sentinel
    assert ((tr[:, :n - 1, 0] >= 0) | (tr[:, :n - 1, 0] == -2)).all(), tr
    assert (tr[:, n - 1:, :] == -1).all(), tr  # last step: no wait
